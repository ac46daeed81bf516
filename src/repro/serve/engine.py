"""ServeEngine: continuously-batched, sharded int8 inference.

The serving twin of ``repro.train.engine``: given ``(model, ServeConfig,
mesh)``, ``make_serve_engine`` assembles everything one decode service
needs —

  * a preallocated **ring KV cache** of shape (max_batch, max_len) per
    layer with per-slot lengths (``models/transformer.init_serve_state``),
    born sharded via the same logical-axis rules the trainer uses
    (batch over ``data``, kv_heads over ``model``),
  * a jitted, donated **decode step** (one token for every slot, cache
    buffers reused in place) and a jitted **prefill** that seeds admitted
    slots' caches from pow2-bucketed prompt batches without touching live
    neighbours,
  * the **SlotScheduler** loop (``generate``) that keeps the decode batch
    full: FIFO admission into free slots, eviction on EOS / token budget /
    cache edge.

Quantized serving is the point: with ``quant_mode=int8_switchback*`` every
linear runs the same ``kernels/switchback`` forward ops as training
(``kernel_backend ∈ {xla, pallas, pallas_interpret}``) — and since
inference never needs the 16-bit wgrad "switch back", the int8 fast path
is the *whole* matmul story (DESIGN.md §8). The same backend knob routes
the attention re-attend through the fused ``kernels/flash_attention``
decode kernel (per-slot lengths, dynamic tile skip over the ring cache)
and prefill through the flash forward; RoPE cos/sin tables are hoisted to
engine constants so neither path recomputes them per layer (DESIGN.md §9).

``ServeConfig.cache_mode="paged"`` swaps the dense ring cache for the
**PagedServe** block-pool subsystem (DESIGN.md §10): KV lives in a fixed
pool of ``num_blocks`` blocks of ``block_size`` tokens, each slot carries
a host-managed block table (``serve/paged/block_pool.py``), identical
prompt prefixes adopt already-filled blocks through a radix prefix cache
(zero prefill FLOPs for the shared prefix), and the decode re-attend runs
the ``kernels/paged_attention`` block-table kernel on the Pallas
backends. Cache memory then scales with live tokens instead of
``max_batch × max_len``, and the ring path stays available as the oracle
the paged path must match token-for-token.

``ServeConfig.spec_mode="ngram"`` (paged only) adds model-free
**speculative decoding**: a pure-python prompt-lookup proposer
(``serve/spec.py``) drafts up to ``spec_k`` tokens per slot from the
request's own history, the engine verifies every slot's drafts in ONE
k-query call through the same per-slot-offset ``paged_prefill`` path
chunked prefill uses (commit-then-attend at Sq=spec_k+1), greedy
acceptance keeps the longest draft prefix matching the model's argmax
plus one free bonus token, and rejection is a host-side length
truncation + ``PagedCacheManager.rollback`` of dead tail blocks — the
append-only block discipline makes misprediction cost nothing but the
padded verify call (DESIGN.md §12). Output tokens stay identical to
``spec_mode="off"``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ParallelConfig, ServeConfig
from repro.core.precision import QuantPolicy
from repro.models import params as PRM
from repro.models import transformer as TF
from repro.models.params import default_rules, init_params, specs_to_shardings
from repro.serve.scheduler import SlotScheduler
from repro.serve.spec import NgramProposer
from repro.telemetry import MetricsRegistry, as_telemetry
from repro.train.engine import _axes_to_shardings, make_shard_ctx

#: supervisor counters surfaced in the stats row (stability_source=)
SUPERVISOR_KEYS = ("rewinds", "data_steps_skipped", "incidents",
                   "escalations", "save_failures", "save_retries")


def prefill_bucket(n: int, lo: int = 8) -> int:
    """Pad size for a prefill batch: smallest power of two >= max(n, lo).

    Bucketing bounds jit retraces to O(log max_len) prefill shapes instead
    of one compile per distinct prompt length.

    >>> prefill_bucket(1)
    8
    >>> prefill_bucket(9)
    16
    >>> prefill_bucket(16)
    16
    """
    b = max(int(lo), 1)
    while b < n:
        b *= 2
    return b


def _make_sample_fn(temperature: float):
    """(B, V) logits -> (B,) int32 tokens. The temperature is fixed per
    engine, so the greedy/categorical choice is made here at build time —
    the greedy hot path never pays the full-vocab Gumbel draw.

    ``key`` is the engine's base PRNG key (constant across the run);
    ``uids``/``steps`` are (B,) int32 per-slot request uids and
    generation-step indices. Temperature>0 folds (uid, step) into the
    key per slot, so request i's step-j draw is one fixed function of
    the seed — reproducible across batch sizes, slot assignment,
    admission order, and preemption/re-admission (the old
    split-per-engine-step key made any scheduling difference change
    every subsequent sample)."""
    if temperature > 0:
        def sample_fn(logits, key, uids, steps):
            keys = jax.vmap(lambda u, s: jax.random.fold_in(
                jax.random.fold_in(key, u), s))(uids, steps)
            return jax.vmap(lambda k, lg: jax.random.categorical(
                k, lg.astype(jnp.float32) / temperature))(
                keys, logits).astype(jnp.int32)
    else:
        def sample_fn(logits, key, uids, steps):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return sample_fn


@dataclasses.dataclass
class ServeEngine:
    """One sharded, donated decode service for a decoder-only LM.

    Build with :func:`make_serve_engine`; the fields are the assembled
    artifacts (shardings, jitted steps). The high-level entry point is
    :meth:`generate`; :meth:`prefill` / :meth:`decode` / :meth:`sample`
    are the raw jitted steps for tests and custom loops.
    """
    bundle: Any                      # ModelBundle (cfg + param specs)
    cfg: Any                         # ModelConfig
    serve_cfg: ServeConfig
    parallel: ParallelConfig
    mesh: Mesh
    policy: QuantPolicy
    rules: Dict
    specs: Dict                      # ParamSpec tree
    param_shardings: Any             # NamedShardings for params
    cache_abs: Any                   # ShapeDtypeStructs for the serve state
    cache_shardings: Any             # NamedShardings for the serve state
    jit_init_cache: Callable
    jit_prefill: Callable
    jit_decode: Callable
    jit_sample: Callable
    donate: bool
    # paged-only jitted steps (None under the ring cache): the k-query
    # speculative verify (paged_prefill at Sq=spec_k+1, argmax returned)
    # and the host->device per-slot length re-sync after a rejection
    jit_verify: Optional[Callable] = None
    jit_set_len: Optional[Callable] = None
    # paged mode (cache_mode="paged"); 0/unused under the ring cache
    num_blocks: int = 0              # physical KV blocks (excl. trash)
    blocks_per_slot: int = 0         # block-table width = cdiv(max_len, bs)
    block_bytes: int = 0             # bytes one block costs across layers
    ring_equiv_cache_bytes: int = 0  # what the dense ring cache would cost
    # observability (DESIGN.md §15): an optional Telemetry flight
    # recorder (per-request lifecycle events, wave spans, profiler
    # window) and an optional stability source — a TrainSupervisor (or
    # its report dict) whose rewind/skip counters surface in the stats
    # row as supervisor_* for finetune-while-serve deployments
    telemetry: Any = None
    stability_source: Any = None

    # -- assembly helpers ---------------------------------------------------
    def shard_ctx(self) -> PRM.ShardCtx:
        """Trace-time sharding context (activation constraints) — the same
        rule table the TrainEngine traces under."""
        return make_shard_ctx(self.mesh, self.parallel)

    def init_cache(self):
        """Fresh all-zero serve state, born on ``cache_shardings`` (no host
        round-trip). Every slot starts empty (length 0). The jitted init is
        built once in ``make_serve_engine`` so per-generate() calls hit the
        compile cache."""
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return self.jit_init_cache()

    def init_params(self, seed: int = 0):
        """Randomly initialized params already placed on the engine's
        param shardings (for synthetic serving / benchmarks; real
        deployments restore a checkpoint and ``shard_params`` it)."""
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return jax.jit(lambda k: init_params(self.specs, k),
                           out_shardings=self.param_shardings)(
                jax.random.PRNGKey(seed))

    def shard_params(self, params):
        """Place a host/replicated param tree onto the engine's shardings."""
        return jax.device_put(params, self.param_shardings)

    # -- raw jitted steps ---------------------------------------------------
    def prefill(self, params, cache, tokens, prompt_lens, admit):
        """Seed admitted slots from padded prompts.

        tokens: (max_batch, S) int32 right-padded prompts (S a pow2 bucket,
        S <= max_len); prompt_lens: (max_batch,) true lengths; admit:
        (max_batch,) bool. Returns ``(logits (B, 1, V), new_cache)`` — the
        logits row is each slot's last valid prompt position (the only one
        sampling needs; the lm head skips the other S-1 padded positions).
        The input cache's buffers are donated. Only admitted slots' cache
        rows and lengths change — live slots are byte-identical.
        """
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return self.jit_prefill(params, cache,
                                    jnp.asarray(tokens, jnp.int32),
                                    jnp.asarray(prompt_lens, jnp.int32),
                                    jnp.asarray(admit, bool))

    def decode(self, params, cache, tokens):
        """One decode step for every slot: tokens (max_batch, 1) int32 ->
        ``(logits (B, 1, V), new_cache)``. Every slot's length advances by
        one (empty slots decode garbage that admission later overwrites);
        the input cache is donated so the ring buffer updates in place.
        """
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return self.jit_decode(params, cache,
                                   jnp.asarray(tokens, jnp.int32))

    def prefill_paged(self, params, cache, tables, tokens, pref_lens,
                      prompt_lens, admit):
        """Paged prefill: seed admitted slots' block tables from prompt
        *suffixes*. tokens: (max_batch, S) right-padded suffix tokens;
        pref_lens: (max_batch,) adopted prefix lengths (block multiples);
        prompt_lens: full prompt lengths; tables: (max_batch,
        blocks_per_slot) int32. Returns ``(logits (B, 1, V), new_cache)``
        — each slot's last valid prompt position."""
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return self.jit_prefill(params, cache,
                                    jnp.asarray(tables, jnp.int32),
                                    jnp.asarray(tokens, jnp.int32),
                                    jnp.asarray(pref_lens, jnp.int32),
                                    jnp.asarray(prompt_lens, jnp.int32),
                                    jnp.asarray(admit, bool))

    def decode_paged(self, params, cache, tables, tokens):
        """One paged decode step: tokens (max_batch, 1) int32 appended
        through the block table. Same donation/lockstep-length semantics
        as :meth:`decode`."""
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return self.jit_decode(params, cache,
                                   jnp.asarray(tables, jnp.int32),
                                   jnp.asarray(tokens, jnp.int32))

    def verify_paged(self, params, cache, tables, tokens, pref_lens,
                     prompt_lens, admit):
        """Speculative verify: score ``tokens`` (max_batch, spec_k+1) —
        per slot ``[current, draft_1..draft_k]`` right-padded — at
        absolute positions ``pref_lens + [0, spec_k]`` through the paged
        prefill path (commit-then-attend: draft KVs are written
        optimistically, the accepted prefix keeps them for free).
        Returns ``(argmax (B, spec_k+1) int32, new_cache)`` — the
        model's greedy token at every verified position; the host
        compares drafts against it to find the accepted prefix."""
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return self.jit_verify(params, cache,
                                   jnp.asarray(tables, jnp.int32),
                                   jnp.asarray(tokens, jnp.int32),
                                   jnp.asarray(pref_lens, jnp.int32),
                                   jnp.asarray(prompt_lens, jnp.int32),
                                   jnp.asarray(admit, bool))

    def set_lengths(self, cache, lens):
        """Overwrite the paged cache's per-slot lengths with host truth
        (``lens`` (max_batch,) int32, cache donated) — the lazy re-sync
        after a speculative rejection left the device leaf over-counting
        (see ``transformer.set_serve_lengths``)."""
        with jax.set_mesh(self.mesh), self.shard_ctx():
            return self.jit_set_len(cache, jnp.asarray(lens, jnp.int32))

    def sample(self, logits, key, uids, steps):
        """Sample next tokens (B,) from last-position logits (B, V) with
        the engine's configured temperature (0 = greedy argmax).
        ``uids``/``steps`` (B,) int32 make temperature>0 draws a pure
        function of (seed, request uid, generation step)."""
        return self.jit_sample(logits, key,
                               jnp.asarray(uids, jnp.int32),
                               jnp.asarray(steps, jnp.int32))

    # -- the serving loop ---------------------------------------------------
    def _stats_registry(self) -> MetricsRegistry:
        """Declare the full stats-row schema as one MetricsRegistry — the
        single source of truth for :meth:`generate`'s return shape. The
        ``max_new_tokens < 1`` early return and the measured path both
        snapshot *this* registry (empty vs filled), so the two key sets
        are identical by construction — the drift the old hand-mirrored
        ``_empty_stats`` dict suffered is structurally impossible
        (pinned by tests/test_telemetry.py)."""
        scfg = self.serve_cfg
        reg = MetricsRegistry()
        for k in ("new_tokens", "prefill_tokens", "decode_steps",
                  "prefill_calls", "prefill_chunks"):
            reg.counter(k)
        for k in ("wall_s", "prefill_s", "decode_s", "tokens_per_s",
                  "decode_tokens_per_s"):
            reg.gauge(k)
        # ttft includes queueing; itl_* is decode-only (prefill stalls
        # subtracted); itl_wall_* keeps the raw wall-clock deltas and
        # prefill_stall_* isolates what admission/chunk prefills cost
        # decoding neighbours. Each renders as {name}_p50_s/_p95_s.
        for name in ("ttft", "itl", "itl_wall", "prefill_stall"):
            reg.histogram(name, percentiles=(50, 95), suffix="_s")
        # decode-batch efficiency: tokens emitted per (slot × model
        # pass). Exactly 1.0 for plain decode; speculative acceptance
        # pushes it toward spec_k + 1
        reg.gauge("tokens_per_model_pass")
        for k in SlotScheduler(scfg.max_batch, scfg.max_len).counters:
            reg.counter(f"sched_{k}")
        if scfg.cache_mode == "paged":
            for k in ("prefix_lookups", "prefix_hits",
                      "prefill_tokens_saved", "peak_blocks_in_use",
                      "peak_live_blocks", "peak_cache_bytes"):
                reg.counter(k)
            reg.gauge("prefix_hit_rate")
            reg.counter("num_blocks").set(self.num_blocks)
            reg.counter("block_bytes").set(self.block_bytes)
            reg.counter("ring_equiv_cache_bytes").set(
                self.ring_equiv_cache_bytes)
            # speculative decoding (spec_mode="ngram"): drafts proposed /
            # accepted (the free bonus token per verify is not counted
            # as accepted) and verify-call count
            for k in ("spec_drafted", "spec_accepted", "spec_verify_calls"):
                reg.counter(k)
            reg.gauge("spec_acceptance_rate")
        # supervisor counters (stability_report()["supervisor"]) for
        # finetune-while-serve: zero unless a stability_source is attached
        for k in SUPERVISOR_KEYS:
            reg.counter(f"supervisor_{k}")
        return reg

    def _fill_supervisor(self, reg: MetricsRegistry) -> None:
        """Copy the attached stability source's supervisor counters into
        the registry (accepts a TrainSupervisor, anything with a
        ``report()``/``stability_report()``, or a plain dict)."""
        src = self.stability_source
        if src is None:
            return
        if isinstance(src, dict):
            rep = src
        elif hasattr(src, "report"):
            rep = src.report()
        elif hasattr(src, "stability_report"):
            rep = src.stability_report().get("supervisor", {})
        else:
            raise TypeError(f"stability_source {type(src).__name__} has "
                            "no report()/stability_report()")
        for k in SUPERVISOR_KEYS:
            if k in rep:
                reg.counter(f"supervisor_{k}").set(rep[k])

    def _empty_stats(self) -> Dict[str, float]:
        reg = self._stats_registry()
        self._fill_supervisor(reg)
        return reg.snapshot()

    def generate(self, params, prompts: Sequence[Sequence[int]], *,
                 max_new_tokens=32, eos_id: Optional[int] = None,
                 stop: Optional[Sequence] = None,
                 seed: Optional[int] = None
                 ) -> Tuple[List[List[int]], Dict[str, float]]:
        """Continuously-batched generation for a list of prompts.

        ``max_new_tokens`` is one int for every request or a per-request
        sequence; ``stop`` is an optional per-request sequence of stop
        specs (each ``None``, one token-id sequence, or a list of them —
        see ``scheduler.normalize_stop``), matched host-side against the
        generated tail (stop tokens are kept in the output, like EOS).
        A request with a non-positive budget returns ``[]`` without
        being scheduled.

        Submits every prompt to a :class:`SlotScheduler`, then loops:
        admit queued requests into free slots, run one bucketed prefill
        call over every *prefilling* slot, decode one token for the whole
        batch, record and evict finished sequences. Returns
        ``(generations, stats)`` where ``generations[i]`` is the token
        list for ``prompts[i]`` and stats carries tokens/s, per-request
        TTFT, decode-only inter-token latency percentiles (plus the raw
        wall-clock ``itl_wall_*`` and the isolated ``prefill_stall_*``),
        and the scheduler's admission/eviction/preemption counters.

        Under ``cache_mode="paged"`` the loop additionally drives a
        :class:`~repro.serve.paged.PagedCacheManager`: admission runs the
        radix prefix-cache lookup and allocates block tables (prefilling
        only the non-shared suffix), the scheduler's ``fits`` hook lets a
        small request be admitted past a pending one whose block budget
        can't currently be met, decode grows tables one block at a time,
        and completion parks full blocks in the prefix cache for reuse.

        With ``prefill_chunk_tokens > 0`` (paged only) each engine step
        carries a fixed token budget mixing the live decode tokens with a
        bounded slice of pending prefill: a long prompt advances by
        chunks across waves (``Request.prefilled`` is the cursor) while
        decoding neighbours keep streaming — flat ITL instead of one
        monolithic stall. With ``preemption="recompute"`` admission stops
        reserving worst-case generation blocks; when decode growth finds
        the pool empty the newest occupied request is parked back to the
        radix cache and requeued (its re-prefill adopts the parked
        blocks, and greedy sampling makes the recompute exact).

        With ``spec_mode="ngram"`` (paged, temperature 0) each decode
        wave first asks the prompt-lookup proposer for up to ``spec_k``
        draft tokens per running slot; any slot with drafts upgrades the
        wave to ONE k-query verify call (``verify_paged``) whose argmax
        row both verifies the drafts and supplies the next token — the
        longest matching prefix plus one bonus token is recorded, so a
        slot can advance up to ``spec_k + 1`` tokens per model pass
        while misprediction degrades gracefully to exactly the plain
        decode's one token. Waves where no slot drafts run the ordinary
        Sq=1 decode call, so non-repetitive traffic never pays the
        padded verify shape (DESIGN.md §12).
        """
        scfg = self.serve_cfg
        B = scfg.max_batch
        paged = scfg.cache_mode == "paged"
        preempt_on = paged and scfg.preemption == "recompute"
        if isinstance(max_new_tokens, (int, np.integer)):
            budgets = [int(max_new_tokens)] * len(prompts)
        else:
            budgets = [int(m) for m in max_new_tokens]
            if len(budgets) != len(prompts):
                raise ValueError(f"{len(budgets)} max_new_tokens entries "
                                 f"for {len(prompts)} prompts")
        if stop is not None and len(stop) != len(prompts):
            raise ValueError(f"{len(stop)} stop entries for "
                             f"{len(prompts)} prompts")
        if not any(m >= 1 for m in budgets):  # prefill samples one token
            return [[] for _ in prompts], self._empty_stats()
        tele = as_telemetry(self.telemetry)
        reg = self._stats_registry()
        h_ttft, h_itl = reg.histogram("ttft"), reg.histogram("itl")
        h_itl_wall = reg.histogram("itl_wall")
        h_stall = reg.histogram("prefill_stall")
        sched = SlotScheduler(B, scfg.max_len, rollover=scfg.rollover)
        uids: List[Optional[int]] = [None] * len(prompts)
        for i, p in enumerate(prompts):
            if budgets[i] >= 1:
                uids[i] = sched.submit(
                    p, max_new_tokens=budgets[i], eos_id=eos_id,
                    stop=None if stop is None else stop[i])
                if tele.enabled:
                    tele.emit("request", uid=uids[i], event="submitted",
                              prompt_len=len(p), budget=budgets[i])
        # speculative decoding is greedy-only: acceptance compares drafts
        # against argmax, so temperature>0 engines fall back to plain
        # decode (the reproducible per-(uid, step) sampler keeps that
        # path deterministic too)
        spec_on = (paged and scfg.spec_mode == "ngram"
                   and scfg.temperature == 0)
        proposer = (NgramProposer(scfg.spec_k, scfg.spec_ngram,
                                  scfg.spec_min_ngram) if spec_on else None)
        mgr = fits = None
        if paged:
            from repro.serve.paged import NoFreeBlocks, PagedCacheManager
            mgr = PagedCacheManager(self.num_blocks, scfg.block_size, B,
                                    self.blocks_per_slot,
                                    prefix_cache=scfg.prefix_cache,
                                    preemption=preempt_on)
            # a preempted request re-prefills prompt + generated-so-far,
            # with only its remaining budget left to claim — context /
            # remaining_new collapse to prompt / max_new_tokens otherwise
            fits = lambda r: mgr.fits(len(r.context), r.remaining_new,  # noqa: E731
                                      prompt=r.context)
        cache = self.init_cache()
        cur = np.zeros((B,), np.int32)        # next input token per slot
        key = jax.random.PRNGKey(scfg.seed if seed is None else seed)
        n_new = n_prefill_tok = n_steps = n_prefills = n_chunks = 0
        n_decoded = 0                         # tokens produced by decode steps
        n_slot_passes = 0                     # live (slot, decode wave) pairs
        spec_drafted = spec_accepted = n_verify = 0
        len_dirty = False       # device length leaf over-counts after a
        # partial spec rejection; re-synced lazily before the next plain
        # decode (the only step that reads it)
        prefill_s = decode_s = 0.0
        ttft: Dict[int, float] = {}           # uid -> first-token latency
        stall: Dict[int, float] = {}          # slot -> stall since last token
        last_t: Dict[int, float] = {}         # slot -> last token timestamp
        peak_live_blocks = 0
        wave = 0                              # engine-step index (events)

        def _finish(slot, r, now):
            last_t.pop(slot, None)
            stall.pop(slot, None)
            if tele.enabled:
                tele.emit("request", uid=r.uid, event="finished",
                          reason=r.finish_reason,
                          n_generated=len(r.generated), wave=wave)
            if paged:
                # KVs written: the context plus every decoded token but
                # the last (never consumed); full blocks park for reuse
                mgr.release(slot, r.context[:-1])

        def _preempt(vslot, vr, prefilling_set):
            """Park ``vslot``'s blocks to the radix cache and requeue."""
            written = (vr.context[:vr.prefilled]
                       if vslot in prefilling_set else vr.context[:-1])
            mgr.release(vslot, written)
            sched.preempt(vslot)
            last_t.pop(vslot, None)
            stall.pop(vslot, None)
            if tele.enabled:
                tele.emit("request", uid=vr.uid, event="preempted",
                          slot=vslot, n_generated=len(vr.generated),
                          wave=wave)

        t0 = time.perf_counter()
        while sched.has_work:
            tele.maybe_profile(wave)
            if paged:
                mgr.begin_wave()
            admits = sched.admit(fits=fits)
            for slot, r in admits:
                # resident tokens: adopted prefix blocks (paged); the
                # chunk loop below prefills context[prefilled:] from here
                r.prefilled = (mgr.admit(slot, r.context, r.remaining_new)
                               if paged else 0)
                if tele.enabled:
                    ev = dict(uid=r.uid, event="admitted", slot=slot,
                              wave=wave, queue_depth=sched.pending)
                    if paged:       # radix adoption + pool pressure
                        ev.update(prefix_adopted=r.prefilled,
                                  live_blocks=mgr.live_blocks)
                    tele.emit("request", **ev)
            if paged and admits:
                peak_live_blocks = max(peak_live_blocks, mgr.live_blocks)
            prefilling = sched.prefilling
            if prefilling:
                with tele.span("serve.prefill_wave", record="prefill_wave",
                               wave=wave) as span:
                    t_pf = time.perf_counter()
                    decoding = [s for s, _ in sched.running]
                    if paged and scfg.prefill_chunk_tokens:
                        # fixed per-step token budget: live decode tokens eat
                        # into it first, the rest is split across prefills
                        budget = max(
                            scfg.prefill_chunk_tokens - len(decoding), 1)
                        slice_ = max(budget // len(prefilling), 1)
                    else:
                        slice_ = scfg.max_len          # monolithic prefill
                    chunks = {s: min(len(r.context) - r.prefilled, slice_)
                              for s, r in prefilling}
                    # clamp: the bucket may round past a non-pow2 max_len, but
                    # the scheduler guarantees every prompt fits the cache
                    S = min(prefill_bucket(max(chunks.values()),
                                           scfg.prefill_bucket), scfg.max_len)
                    span.update(slots=len(prefilling),
                                tokens=int(sum(chunks.values())), bucket=S)
                    toks = np.zeros((B, S), np.int32)
                    toks_l = np.ones((B,), np.int32)   # dummy 1 for idle slots
                    pref_l = np.zeros((B,), np.int32)
                    mask = np.zeros((B,), bool)
                    for slot, r in prefilling:
                        c = chunks[slot]
                        toks[slot, :c] = r.context[r.prefilled:r.prefilled + c]
                        toks_l[slot] = r.prefilled + c
                        pref_l[slot] = r.prefilled
                        mask[slot] = True
                    if paged:
                        logits, cache = self.prefill_paged(
                            params, cache, mgr.tables, toks, pref_l, toks_l,
                            mask)
                    else:
                        logits, cache = self.prefill(params, cache, toks,
                                                     toks_l, mask)
                    # sample here too: a max_new_tokens=1 run finishes at
                    # prefill and never reaches the decode-branch sample
                    uids_a = np.zeros((B,), np.int32)
                    steps_a = np.zeros((B,), np.int32)
                    for slot, r in prefilling:
                        uids_a[slot] = r.uid
                        steps_a[slot] = len(r.generated)
                    tok = np.asarray(self.sample(logits[:, 0], key,
                                                 uids_a, steps_a))
                    now = time.perf_counter()
                    dur = now - t_pf
                for slot, r in prefilling:
                    r.prefilled += chunks[slot]
                    if tele.enabled:
                        tele.emit("request", uid=r.uid,
                                  event="prefill_chunk", slot=slot,
                                  tokens=chunks[slot],
                                  prefilled=r.prefilled,
                                  context_len=len(r.context), wave=wave)
                    if r.prefilled >= len(r.context):
                        # prompt fully resident: first token sampled from
                        # the last position's logits; slot joins decode
                        done = sched.record(slot, tok[slot])
                        cur[slot] = tok[slot]
                        if r.uid not in ttft:
                            ttft[r.uid] = now - t0
                            tele.emit("request", uid=r.uid,
                                      event="first_token",
                                      ttft_s=ttft[r.uid], wave=wave)
                        last_t[slot] = now
                        n_new += 1
                        if done:
                            _finish(slot, r, now)
                for slot in decoding:
                    # this prefill call sat between two of the slot's
                    # decode tokens — charge it as stall, not decode ITL
                    stall[slot] = stall.get(slot, 0.0) + dur
                n_prefill_tok += int(sum(chunks.values()))
                n_chunks += len(prefilling)
                n_prefills += 1
                prefill_s += dur
                if tele.enabled:
                    tele.emit("wave", wave=wave, mode="prefill",
                              dur_s=dur, slots=len(prefilling),
                              tokens=int(sum(chunks.values())))
            running = sched.running
            if not running:
                wave += 1
                continue
            drafts: Dict[int, List[int]] = {}
            if spec_on:
                for slot, r in running:
                    # clamp so optimistic draft KVs stay inside the
                    # slot's worst-case block reservation (positions
                    # through prompt+max_new-2, i.e. remaining_new - 1
                    # drafts) and inside the table/RoPE range (max_len)
                    cap = min(scfg.spec_k, r.remaining_new - 1,
                              scfg.max_len - r.total_len)
                    if cap >= 1:
                        d = proposer.propose(r.context, cap)
                        if d:
                            drafts[slot] = d
            dead: set = set()                 # slots preempted this step
            if paged:
                pf_set = {s for s, _ in sched.prefilling}
                for slot, r in running:
                    # KV writes this step: absolute position total_len-1
                    # (the token being consumed) through total_len-1+k
                    # (the last draft, committed optimistically)
                    last = r.total_len - 1 + len(drafts.get(slot, ()))
                    while slot not in dead:
                        try:
                            for wp in range(r.total_len - 1, last + 1):
                                mgr.ensure_block(slot, wp)
                            break
                        except NoFreeBlocks:
                            if not preempt_on:
                                raise
                            # preempt-to-queue: park the newest occupied
                            # request's blocks (they become evictable ->
                            # the retry's alloc reclaims them) and requeue
                            cands = [sq for sq in sched.occupied
                                     if sq[0] not in dead]
                            vslot, vr = max(cands,
                                            key=lambda sq: sq[1].uid)
                            _preempt(vslot, vr, pf_set)
                            dead.add(vslot)
                peak_live_blocks = max(peak_live_blocks, mgr.live_blocks)
            drafts = {s: d for s, d in drafts.items() if s not in dead}
            t_dec = time.perf_counter()
            if drafts:
                # -- speculative wave: one k-query verify call ----------
                with tele.span("serve.verify_wave", record="verify_wave",
                               wave=wave):
                    S_v = scfg.spec_k + 1         # static shape: one trace
                    toks = np.zeros((B, S_v), np.int32)
                    pref = np.zeros((B,), np.int32)
                    lens = np.ones((B,), np.int32)
                    mask = np.zeros((B,), bool)
                    for slot, r in running:
                        if slot in dead:
                            continue
                        d = drafts.get(slot, ())
                        L = r.total_len - 1       # KV-resident tokens
                        toks[slot, 0] = cur[slot]
                        toks[slot, 1:1 + len(d)] = d
                        pref[slot] = L
                        lens[slot] = L + 1 + len(d)
                        mask[slot] = True
                    arg, cache = self.verify_paged(params, cache, mgr.tables,
                                                   toks, pref, lens, mask)
                    arg = np.asarray(arg)
                    now = time.perf_counter()
                for slot, r in running:
                    if slot in dead:
                        continue
                    d = drafts.get(slot, ())
                    n_in = 1 + len(d)
                    a = 0                     # accepted draft prefix
                    while a < len(d) and d[a] == int(arg[slot, a]):
                        a += 1
                    # emit the a verified drafts (== the model's argmax
                    # at their positions) plus the bonus token at the
                    # first mismatch — exactly what a + 1 sequential
                    # greedy decode steps would have produced
                    delta = now - last_t[slot]
                    stalled = stall.pop(slot, 0.0)
                    done, m = False, 0
                    for j in range(a + 1):
                        t = int(arg[slot, j])
                        done = sched.record(slot, t)
                        cur[slot] = t
                        m += 1
                        # the m tokens land together: the wave's wall
                        # gap belongs to the first, the rest are free
                        h_itl_wall.observe(delta if j == 0 else 0.0)
                        h_itl.observe(max(delta - stalled, 0.0)
                                      if j == 0 else 0.0)
                        if done:
                            break
                    if stalled:
                        h_stall.observe(stalled)
                    last_t[slot] = now
                    spec_drafted += len(d)
                    spec_accepted += min(a, m)
                    n_new += m
                    n_decoded += m
                    n_slot_passes += 1
                    if done:
                        _finish(slot, r, now)  # release frees dead tail
                    else:
                        # rejection cleanup: free whole blocks past the
                        # kept tokens; stale cells inside kept blocks
                        # are masked by kv_len until overwritten
                        mgr.rollback(slot, r.total_len - 1)
                        if m != n_in:
                            len_dirty = True
                n_steps += 1
                n_verify += 1
                if tele.enabled:
                    tele.emit("wave", wave=wave, mode="verify",
                              dur_s=now - t_dec, drafted=sum(
                                  len(d) for d in drafts.values()),
                              slots=len(drafts))
            else:
                # -- plain wave: ordinary one-token decode --------------
                with tele.span("serve.decode_wave", record="decode_wave",
                               wave=wave):
                    if paged and len_dirty:
                        # the only step that reads the device length leaf;
                        # restore host truth (prefilling slots keep their
                        # chunk cursor, idle slots write to trash anyway)
                        lens = np.zeros((B,), np.int32)
                        pf_now = {s for s, _ in sched.prefilling}
                        for slot, r in sched.occupied:
                            lens[slot] = (r.prefilled if slot in pf_now
                                          else r.total_len - 1)
                        cache = self.set_lengths(cache, lens)
                        len_dirty = False
                    if paged:
                        logits, cache = self.decode_paged(
                            params, cache, mgr.tables, cur[:, None])
                    else:
                        logits, cache = self.decode(params, cache,
                                                    cur[:, None])
                    uids_a = np.zeros((B,), np.int32)
                    steps_a = np.zeros((B,), np.int32)
                    for slot, r in running:
                        if slot not in dead:
                            uids_a[slot] = r.uid
                            steps_a[slot] = len(r.generated)
                    tok = np.asarray(self.sample(logits[:, 0], key,
                                                 uids_a, steps_a))
                    now = time.perf_counter()
                n_live = 0
                for slot, r in running:
                    if slot in dead:          # preempted mid-step: its
                        continue              # table row decoded to trash
                    done = sched.record(slot, tok[slot])
                    cur[slot] = tok[slot]
                    delta = now - last_t[slot]
                    stalled = stall.pop(slot, 0.0)
                    h_itl_wall.observe(delta)
                    h_itl.observe(max(delta - stalled, 0.0))
                    if stalled:
                        h_stall.observe(stalled)
                    last_t[slot] = now
                    n_live += 1
                    if done:
                        _finish(slot, r, now)
                n_new += n_live
                n_decoded += n_live
                n_slot_passes += n_live
                n_steps += 1
                if tele.enabled:
                    tele.emit("wave", wave=wave, mode="decode",
                              dur_s=now - t_dec, slots=n_live)
            decode_s += now - t_dec
            wave += 1
        dt = time.perf_counter() - t0

        # TTFT includes queueing time (the admission-latency signal
        # paged-vs-ring is judged on); itl_* is decode-only (prefill
        # stalls subtracted — itl_wall_* keeps the raw wall deltas the
        # client feels, prefill_stall_* isolates the difference)
        h_ttft.observe_many(ttft[u] for u in uids if u in ttft)
        reg.counter("new_tokens").set(n_new)
        reg.counter("prefill_tokens").set(n_prefill_tok)
        reg.counter("decode_steps").set(n_steps)
        reg.counter("prefill_calls").set(n_prefills)
        reg.counter("prefill_chunks").set(n_chunks)
        reg.gauge("wall_s").set(dt)
        reg.gauge("prefill_s").set(prefill_s)
        reg.gauge("decode_s").set(decode_s)
        reg.gauge("tokens_per_s").set(n_new / max(dt, 1e-9))
        reg.gauge("decode_tokens_per_s").set(n_decoded / max(decode_s, 1e-9))
        reg.gauge("tokens_per_model_pass").set(
            n_decoded / max(n_slot_passes, 1))
        reg.fill_counters(sched.counters, prefix="sched_")
        if paged:
            mstats = mgr.stats()
            reg.gauge("prefix_hit_rate").set(mstats.pop("prefix_hit_rate"))
            reg.fill_counters(mstats)
            reg.counter("peak_live_blocks").set(peak_live_blocks)
            reg.counter("peak_cache_bytes").set(
                mgr.peak_in_use * self.block_bytes)
            reg.counter("spec_drafted").set(spec_drafted)
            reg.counter("spec_accepted").set(spec_accepted)
            reg.counter("spec_verify_calls").set(n_verify)
            reg.gauge("spec_acceptance_rate").set(
                spec_accepted / max(spec_drafted, 1))
        self._fill_supervisor(reg)
        stats = reg.snapshot()
        # itl is wall-minus-stall by construction, so itl_* <= itl_wall_*
        # holds per sample; the bucketed estimator can invert the order by
        # up to one bucket width (~12%) when the series diverge, so pin
        # the definitional invariant at the row level
        for p in (50, 95):
            stats[f"itl_p{p}_s"] = min(stats[f"itl_p{p}_s"],
                                       stats[f"itl_wall_p{p}_s"])
        if tele.enabled:
            tele.emit("serve_stats", **stats)
        if tele._profiling:        # window ran off the end of the run
            tele._stop_profile(wave)
        return [[] if u is None else sched.results[u] for u in uids], stats


def make_serve_engine(model, serve_cfg: ServeConfig, mesh: Mesh, *,
                      parallel: Optional[ParallelConfig] = None,
                      policy: Optional[QuantPolicy] = None,
                      donate: bool = True) -> ServeEngine:
    """Assemble the sharded serving stack for ``model`` on ``mesh``.

    ``model`` is an arch name, a ModelConfig, or a prebuilt ModelBundle
    (decoder-only all-attention LMs; CLIP / enc-dec / ssm raise).
    ``parallel`` defaults to a no-remat ParallelConfig matching the mesh;
    ``policy`` defaults to ``serve_cfg.quant_mode``/``kernel_backend`` —
    the one knob that flips every linear between XLA and the Pallas
    SwitchBack kernels. ``donate=False`` exists for benchmarks that reuse
    a cache across timed calls.
    """
    from repro.models import build
    if isinstance(model, str):
        from repro.configs import get_config
        model = get_config(model)
    bundle = model if hasattr(model, "param_specs") else build(model)
    cfg = bundle.cfg
    if getattr(cfg, "family", "") in ("clip", "encdec"):
        raise NotImplementedError(
            "ServeEngine serves decoder-only LMs; CLIP scores pairs via "
            "models/clip.py and enc-dec decodes via models/encdec.py")

    parallel = parallel or ParallelConfig(
        mesh_shape=tuple(mesh.devices.shape),
        mesh_axes=tuple(mesh.axis_names), remat="none",
        attn_block_q=serve_cfg.attn_block_q,
        attn_block_k=serve_cfg.attn_block_k)
    assert tuple(mesh.axis_names) == tuple(parallel.mesh_axes), (
        f"mesh axes {mesh.axis_names} != ParallelConfig.mesh_axes "
        f"{parallel.mesh_axes}")
    policy = policy or QuantPolicy(serve_cfg.quant_mode,
                                   backend=serve_cfg.kernel_backend)
    rules = default_rules(parallel)
    specs = bundle.param_specs
    param_shard = specs_to_shardings(specs, mesh, rules)

    paged = serve_cfg.cache_mode == "paged"
    if serve_cfg.cache_mode not in ("ring", "paged"):
        raise ValueError(f"cache_mode {serve_cfg.cache_mode!r} not in "
                         "('ring', 'paged')")
    dtype = jnp.dtype(serve_cfg.cache_dtype)
    bs = serve_cfg.block_size
    blocks_per_slot = -(-serve_cfg.max_len // bs) if paged else 0
    # auto num_blocks = the ring cache's capacity in blocks, so the
    # default paged engine can always admit what the ring engine can;
    # size it DOWN for the memory win once the workload's live-token
    # ceiling is known (admission throttles via the scheduler fits hook)
    num_blocks = (serve_cfg.num_blocks
                  or serve_cfg.max_batch * blocks_per_slot) if paged else 0
    if serve_cfg.preemption not in ("off", "recompute"):
        raise ValueError(f"preemption {serve_cfg.preemption!r} not in "
                         "('off', 'recompute')")
    if not paged and (serve_cfg.prefill_chunk_tokens
                      or serve_cfg.preemption != "off"):
        raise NotImplementedError(
            "prefill_chunk_tokens / preemption are paged-cache features: "
            "the ring cache has no block table to chunk against or park "
            "into; set cache_mode='paged'")
    if serve_cfg.spec_mode not in ("off", "ngram"):
        raise ValueError(f"spec_mode {serve_cfg.spec_mode!r} not in "
                         "('off', 'ngram')")
    if serve_cfg.spec_mode == "ngram":
        if not paged:
            raise NotImplementedError(
                "spec_mode='ngram' verifies drafts through the paged "
                "block-table prefill path and rolls rejected KVs back "
                "by truncating the block table; the ring cache has "
                "neither — set cache_mode='paged'")
        if serve_cfg.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {serve_cfg.spec_k}")
        if not 1 <= serve_cfg.spec_min_ngram <= serve_cfg.spec_ngram:
            raise ValueError(
                f"need 1 <= spec_min_ngram <= spec_ngram, got "
                f"{serve_cfg.spec_min_ngram}..{serve_cfg.spec_ngram}")
    if paged:
        if serve_cfg.rollover:
            raise NotImplementedError(
                "cache_mode='paged' has no rollover: the block table is "
                "append-only; use the ring cache for sliding-window decode")
        cache_abs = jax.eval_shape(
            lambda: TF.init_paged_serve_state(cfg, num_blocks, bs,
                                              serve_cfg.max_batch, dtype))
        cache_shard = _axes_to_shardings(
            cache_abs, TF.paged_state_logical_axes(cfg), mesh, rules)
    else:
        cache_abs = jax.eval_shape(
            lambda: TF.init_serve_state(cfg, serve_cfg.max_batch,
                                        serve_cfg.max_len, dtype))
        cache_shard = _axes_to_shardings(
            cache_abs, TF.serve_state_logical_axes(cfg), mesh, rules)
    repl = NamedSharding(mesh, P())

    # RoPE tables hoisted to engine constants: cos/sin rows for positions
    # [0, max_len) computed once at build time instead of per layer (and,
    # for decode, per step). Gathered rows are bit-identical to the
    # on-the-fly apply_rope (models/common.rope_tables), so parity with
    # the training forward is untouched. With rollover the ring keeps
    # absolute positions past max_len — fall back to on-the-fly RoPE.
    if serve_cfg.rollover:
        rope_cos = rope_sin = None
    else:
        from repro.models.common import rope_tables
        rope_cos, rope_sin = rope_tables(cfg.hd, cfg.rope_theta,
                                         serve_cfg.max_len)

    def prefill_fn(p, st, toks, lens, admit):
        rc = (None if rope_cos is None else
              (rope_cos[:toks.shape[1]], rope_sin[:toks.shape[1]]))
        return TF.serve_prefill(p, st, toks, lens, admit, cfg, policy,
                                parallel, last_only=True, rope_cache=rc)

    def decode_fn(p, st, toks):
        if rope_cos is None:
            rc = None
        else:
            # every slot's length advances in lockstep across layers; row
            # 0 of the stacked (G, B) lengths is this step's positions.
            # Idle slots can run past max_len (their garbage is evicted
            # by admission); the gather clamps, garbage stays garbage.
            pos = next(iter(st.values())).length[0]
            rc = (rope_cos[pos][:, None], rope_sin[pos][:, None])
        return TF.decode_step(p, st, toks, cfg, policy, parallel,
                              rope_cache=rc)

    def paged_prefill_fn(p, st, tables, toks, pref_lens, lens, admit):
        if rope_cos is None:
            rc = None
        else:
            # suffix tokens sit at absolute positions pref + [0, S); the
            # per-slot gather clamps for pad rows (garbage, masked later)
            pos = pref_lens[:, None] + jnp.arange(toks.shape[1])[None, :]
            rc = (rope_cos[pos], rope_sin[pos])
        return TF.paged_prefill(p, st, tables, toks, pref_lens, lens,
                                admit, cfg, policy, parallel,
                                last_only=True, rope_cache=rc)

    def paged_decode_fn(p, st, tables, toks):
        if rope_cos is None:
            rc = None
        else:
            pos = next(iter(st.values())).length[0]
            rc = (rope_cos[pos][:, None], rope_sin[pos][:, None])
        return TF.paged_decode_step(p, st, tables, toks, cfg, policy,
                                    parallel, rope_cache=rc)

    def paged_verify_fn(p, st, tables, toks, pref_lens, lens, admit):
        # the speculative verify IS the chunked-prefill call at
        # Sq=spec_k+1 — commit-then-attend writes the draft KVs first,
        # the per-slot q_off kernel attends over resident + drafts —
        # except every position's logits come back (last_only=False)
        # reduced to their argmax, which is all greedy acceptance needs
        # (and a (B, S) int32 ship instead of (B, S, V) fp32)
        if rope_cos is None:
            rc = None
        else:
            pos = pref_lens[:, None] + jnp.arange(toks.shape[1])[None, :]
            rc = (rope_cos[pos], rope_sin[pos])
        logits, st2 = TF.paged_prefill(p, st, tables, toks, pref_lens,
                                       lens, admit, cfg, policy, parallel,
                                       last_only=False, rope_cache=rc)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), st2

    # per-mode picks: (prefill fn + its replicated-operand count, decode
    # fn + count, fresh-cache initializer); the jit wiring below is shared
    if paged:
        pf, n_pf, dc, n_dc = paged_prefill_fn, 5, paged_decode_fn, 2
        init_fn = lambda: TF.init_paged_serve_state(  # noqa: E731
            cfg, num_blocks, bs, serve_cfg.max_batch, dtype)
    else:
        pf, n_pf, dc, n_dc = prefill_fn, 3, decode_fn, 1
        init_fn = lambda: TF.init_serve_state(  # noqa: E731
            cfg, serve_cfg.max_batch, serve_cfg.max_len, dtype)

    # out_shardings pin the returned cache to the canonical layout — without
    # this GSPMD may pick a different (e.g. hd-over-model) layout for the
    # prefill output and the decode step's in_shardings would reject it.
    dn = (1,) if donate else ()
    jit_prefill = jax.jit(pf,
                          in_shardings=(param_shard, cache_shard)
                          + (repl,) * n_pf,
                          out_shardings=(None, cache_shard),
                          donate_argnums=dn)
    jit_decode = jax.jit(dc,
                         in_shardings=(param_shard, cache_shard)
                         + (repl,) * n_dc,
                         out_shardings=(None, cache_shard),
                         donate_argnums=dn)
    if paged:
        jit_verify = jax.jit(paged_verify_fn,
                             in_shardings=(param_shard, cache_shard)
                             + (repl,) * 5,
                             out_shardings=(None, cache_shard),
                             donate_argnums=dn)
        jit_set_len = jax.jit(TF.set_serve_lengths,
                              in_shardings=(cache_shard, repl),
                              out_shardings=cache_shard,
                              donate_argnums=(0,) if donate else ())
    else:
        jit_verify = jit_set_len = None
    jit_init_cache = jax.jit(init_fn, out_shardings=cache_shard)
    jit_sample = jax.jit(_make_sample_fn(serve_cfg.temperature))

    # cache-footprint accounting for the bench/stats rows: bytes one
    # physical block costs across all layers (k+v), and what the dense
    # ring cache would preallocate for the same (max_batch, max_len)
    itemsize = dtype.itemsize
    G, P_, KV, hd = TF.n_groups(cfg), TF.period(cfg), cfg.n_kv_heads, cfg.hd
    block_bytes = 2 * P_ * G * bs * KV * hd * itemsize if paged else 0
    ring_equiv = (2 * P_ * G * serve_cfg.max_batch * serve_cfg.max_len
                  * KV * hd * itemsize)

    return ServeEngine(bundle=bundle, cfg=cfg, serve_cfg=serve_cfg,
                       parallel=parallel, mesh=mesh, policy=policy,
                       rules=rules, specs=specs,
                       param_shardings=param_shard, cache_abs=cache_abs,
                       cache_shardings=cache_shard,
                       jit_init_cache=jit_init_cache,
                       jit_prefill=jit_prefill, jit_decode=jit_decode,
                       jit_sample=jit_sample, donate=donate,
                       jit_verify=jit_verify, jit_set_len=jit_set_len,
                       num_blocks=num_blocks,
                       blocks_per_slot=blocks_per_slot,
                       block_bytes=block_bytes,
                       ring_equiv_cache_bytes=ring_equiv)
