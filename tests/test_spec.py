"""Speculative decoding suite: n-gram proposer, verify-path rollback,
and spec-vs-plain token parity (DESIGN.md §12).

* NgramProposer: longest-match preference, latest-occurrence tie break,
  draft caps, min_ngram gating.
* PagedCacheManager.rollback: tail blocks return to the pool (tables ->
  trash), reservation accounting stays exact for later admissions,
  block-boundary edge cases, radix-adopted shared blocks survive an
  explicit rollback via the cache's own refcount.
* Engine level: greedy spec generation is token-for-token identical to
  spec_mode="off" — int8, xla AND pallas_interpret, plain and under
  chunked-prefill + preemption churn — while tokens_per_model_pass > 1
  on repetitive prompts (drafts actually accepted, not just proposed).
* Satellites that ride the same serve path: per-request max_new_tokens
  budgets, stop sequences, per-slot-per-step deterministic sampling
  (identical tokens across different batch widths at temperature > 0).
* Config validation: spec on the ring cache raises, bad knobs raise.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.configs import get_reduced_config
from repro.configs.base import ParallelConfig, ServeConfig
from repro.core.precision import QuantPolicy
from repro.launch.mesh import make_test_mesh
from repro.models import build
from repro.serve import (NgramProposer, PagedCacheManager, SlotScheduler,
                         make_serve_engine, normalize_stop)

ARCH = "smollm-360m"
PAR = ParallelConfig(remat="none")
INT8 = QuantPolicy("int8_switchback", compute_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# proposer
# ---------------------------------------------------------------------------

def test_proposer_prefers_longest_then_latest_match():
    p = NgramProposer(k=8, max_ngram=3, min_ngram=1)
    # trailing [7, 8] matches at position 2 (3-gram [3, 7, 8] matches
    # nothing) -> draft continues from after that occurrence
    assert p.propose([3, 7, 8, 1, 2, 7, 8], 8) == [1, 2, 7, 8]
    # two occurrences of the trailing 1-gram: the LATEST one wins
    assert p.propose([5, 1, 5, 2, 5], 8) == [2, 5]
    # a longer n-gram beats a more recent shorter one
    assert p.propose([1, 2, 9, 4, 9, 1, 2, 9], 8) == [4, 9, 1, 2, 9]


def test_proposer_caps_and_gates():
    p = NgramProposer(k=3, max_ngram=3, min_ngram=1)
    assert p.propose([1, 2, 3, 1, 2, 3, 1, 2], 8) == [3, 1, 2]   # k caps
    assert p.propose([1, 2, 3, 1, 2, 3, 1, 2], 2) == [3, 1]      # budget
    assert p.propose([1, 2, 3, 1, 2, 3, 1, 2], 0) == []
    assert p.propose([4, 5, 6, 7], 8) == []                      # no match
    assert p.propose([], 8) == []
    assert p.propose([9], 8) == []          # a 1-token history can't match
    # min_ngram=2: accidental single-token repeats don't trigger a draft
    p2 = NgramProposer(k=3, max_ngram=3, min_ngram=2)
    assert p2.propose([5, 1, 5, 2, 5], 8) == []
    assert p2.propose([1, 2, 9, 1, 2], 8) == [9, 1, 2]


def test_proposer_validates_knobs():
    with pytest.raises(ValueError):
        NgramProposer(k=0)
    with pytest.raises(ValueError):
        NgramProposer(k=4, max_ngram=2, min_ngram=3)
    with pytest.raises(ValueError):
        NgramProposer(k=4, max_ngram=3, min_ngram=0)


# ---------------------------------------------------------------------------
# rollback
# ---------------------------------------------------------------------------

def test_rollback_frees_tail_blocks_and_reservation():
    m = PagedCacheManager(num_blocks=8, block_size=4, max_batch=1,
                          blocks_per_slot=8, prefix_cache=False)
    m.admit(0, list(range(6)), max_new_tokens=11)    # 2 blocks, 2 reserved
    assert (m.pool.in_use, m._reserved[0]) == (2, 2)
    # decode at position 5 wrote into block 1; a verify with 4 drafts
    # writes positions 6..9 -> grows blocks 2 (pos 8) via ensure_block
    for wp in range(6, 10):
        m.ensure_block(0, wp)
    assert m.pool.in_use == 3 and m._reserved[0] == 1
    tail = m._slot_blocks[0][2]
    # everything rejected: keep the 6 resident cells only
    assert m.rollback(0, 6) == 1
    assert m.pool.in_use == 2 and int(m.tables[0, 2]) == m.trash
    assert m.pool.refcount(tail) == 0
    assert m._reserved[0] == 2                       # reservation restored
    # rollback inside the kept tail block is a no-op (append-only: stale
    # cells are masked by kv_len, then overwritten)
    assert m.rollback(0, 5) == 0
    assert m.pool.in_use == 2 and m._slot_blocks[0] == m._slot_blocks[0]


def test_rollback_block_boundary():
    m = PagedCacheManager(num_blocks=8, block_size=4, max_batch=1,
                          blocks_per_slot=8, prefix_cache=False)
    m.admit(0, [1, 2, 3, 4], max_new_tokens=9)       # exactly 1 full block
    for wp in range(4, 8):                           # drafts fill block 1
        m.ensure_block(0, wp)
    assert m.pool.in_use == 2
    assert m.rollback(0, 4) == 1                     # keep exactly block 0
    assert m.pool.in_use == 1
    assert m.rollback(0, 4) == 0                     # idempotent
    m.ensure_block(0, 4)                             # regrows cleanly
    assert m.pool.in_use == 2 and int(m.tables[0, 1]) != m.trash


def test_rollback_keeps_admission_accounting_exact():
    """After rollback restores the reservation, fits() must again refuse
    a request the worst case can't hold — no phantom free blocks."""
    m = PagedCacheManager(num_blocks=4, block_size=4, max_batch=2,
                          blocks_per_slot=4, prefix_cache=False)
    m.admit(0, list(range(4)), max_new_tokens=5)     # 1 block + 1 reserved
    m.begin_wave()
    assert not m.fits(8, 5)                          # 3 > 4 - 1 - 1
    for wp in range(4, 8):
        m.ensure_block(0, wp)                        # claims the reserve +1
    m.rollback(0, 4)
    m.begin_wave()
    assert not m.fits(8, 5)                          # still exactly as before
    assert m.fits(4, 4)


def test_rollback_never_frees_radix_adopted_blocks():
    """A rollback over an adopted prefix block only drops the slot's
    reference — the radix cache's own refcount keeps the shared block
    (and its cached tokens) alive for the next admission."""
    m = PagedCacheManager(num_blocks=8, block_size=4, max_batch=1,
                          blocks_per_slot=8, prefix_cache=True)
    prompt = list(range(8))
    m.admit(0, prompt, max_new_tokens=4)
    m.release(0, prompt)                             # parks 2 full blocks
    m.begin_wave()
    assert m.admit(0, prompt + [9], max_new_tokens=4) == 8   # adopts both
    shared = m._slot_blocks[0][:2]
    assert [m.pool.refcount(b) for b in shared] == [2, 2]
    for wp in range(9, 13):                          # drafts into block 3
        m.ensure_block(0, wp)
    # roll all the way back into the adopted range: slot refs drop, the
    # cache's references keep the shared blocks resident
    m.rollback(0, 4)
    assert [m.pool.refcount(b) for b in shared] == [2, 1]
    assert m.cache.match_len(prompt, max_blocks=2) == 2
    assert int(m.tables[0, 1]) == m.trash


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------

def _eng(cfg, mesh, max_batch=2, block_size=4, **kw):
    scfg = ServeConfig(max_batch=max_batch, max_len=48, cache_mode="paged",
                       block_size=block_size, quant_mode="int8_switchback",
                       **kw)
    return make_serve_engine(build(cfg), scfg, mesh, policy=INT8,
                             parallel=PAR)


def _repetitive_prompts(cfg, n=4, period=3, lo=10):
    rng = np.random.default_rng(0)
    pat = rng.integers(0, cfg.vocab_size, size=period).tolist()
    return [(pat * 8)[:lo + i] for i in range(n)]


def _copy_task_prompts(cfg, n=6, lo=10):
    """Each prompt repeats one token: greedy decode of the reduced model
    falls into self-repeating loops, the regime n-gram drafting targets."""
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, size=n)
    return [[int(t)] * (lo + i) for i, t in enumerate(toks)]


@pytest.mark.parametrize("backend,workload", [
    pytest.param("xla", "period3", id="xla"),
    pytest.param("pallas_interpret", "period3", id="pallas_interpret"),
    pytest.param("xla", "copy_task", id="xla-copy_task")])
def test_engine_spec_matches_off_int8(reduced, backend, workload):
    """Greedy spec decoding is an exact optimisation: token-for-token
    identical to plain decode, with > 1 token per model pass on
    repetitive prompts (so acceptance is real, not vacuous) and > 1.5 on
    the copy task."""
    cfg, _, _ = reduced(ARCH)
    mesh = make_test_mesh((1, 1))
    if workload == "period3":
        prompts, new, kw, spec_k, min_tpp = (
            _repetitive_prompts(cfg), 12, {}, 4, 1.0)
    else:
        prompts, new, kw, spec_k, min_tpp = (
            _copy_task_prompts(cfg), 24, dict(max_batch=4, block_size=8),
            6, 1.5)
    off = _eng(cfg, mesh, kernel_backend=backend, **kw)
    spec = _eng(cfg, mesh, kernel_backend=backend, spec_mode="ngram",
                spec_k=spec_k, spec_min_ngram=1, **kw)
    params = off.init_params(0)
    g1, s1 = off.generate(params, prompts, max_new_tokens=new)
    g2, s2 = spec.generate(params, prompts, max_new_tokens=new)
    assert g1 == g2
    assert s1["tokens_per_model_pass"] == 1.0
    assert s2["tokens_per_model_pass"] > min_tpp
    assert s2["spec_accepted"] > 0
    assert s2["spec_verify_calls"] > 0
    assert s2["new_tokens"] == s1["new_tokens"]


def test_engine_spec_matches_off_under_churn(reduced):
    """Spec + chunked prefill + preemption on a small pool: rollback,
    preempt-to-queue, and resumed prefills interleave without breaking
    parity with the uncontended plain engine."""
    cfg, _, _ = reduced(ARCH)
    mesh = make_test_mesh((1, 1))
    kw = dict(prefill_chunk_tokens=6, preemption="recompute", num_blocks=14)
    off = _eng(cfg, mesh, **kw)
    spec = _eng(cfg, mesh, spec_mode="ngram", spec_k=3, spec_min_ngram=1,
                **kw)
    params = off.init_params(0)
    prompts = _repetitive_prompts(cfg, n=5)
    g1, s1 = off.generate(params, prompts, max_new_tokens=12)
    g2, s2 = spec.generate(params, prompts, max_new_tokens=12)
    assert g1 == g2
    assert s2["spec_drafted"] > 0


def test_engine_spec_noop_on_non_repetitive_prompts(reduced):
    """min_ngram=2 on random prompts: essentially nothing drafts, every
    step takes the plain Sq=1 decode path, generations still match."""
    cfg, _, _ = reduced(ARCH)
    mesh = make_test_mesh((1, 1))
    off = _eng(cfg, mesh)
    spec = _eng(cfg, mesh, spec_mode="ngram", spec_k=4)   # min_ngram=2
    params = off.init_params(0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (9, 12, 10)]
    g1, _ = off.generate(params, prompts, max_new_tokens=8)
    g2, s2 = spec.generate(params, prompts, max_new_tokens=8)
    assert g1 == g2
    assert s2["spec_accepted"] <= s2["spec_drafted"]


def test_engine_per_request_budgets_and_stop(reduced):
    cfg, _, _ = reduced(ARCH)
    mesh = make_test_mesh((1, 1))
    e = _eng(cfg, mesh)
    params = e.init_params(0)
    prompts = _repetitive_prompts(cfg, n=3)
    gens, _ = e.generate(params, prompts, max_new_tokens=[5, 0, 2])
    assert [len(g) for g in gens] == [5, 0, 2]
    ref, _ = e.generate(params, prompts[:1], max_new_tokens=10)
    assert len(ref[0]) == 10
    # budgets don't bleed across requests: the 5-token run is a prefix
    assert ref[0][:5] == gens[0]
    stop = ref[0][2:4]
    n = len(stop)
    cut = next(j + n for j in range(len(ref[0]))
               if ref[0][j:j + n] == stop)
    got, stats = e.generate(params, prompts[:1], max_new_tokens=10,
                            stop=[stop])
    assert got[0] == ref[0][:cut]
    assert stats["sched_evicted_stop"] == 1
    assert normalize_stop([stop]) == [stop]


def test_engine_stop_applies_to_accepted_drafts(reduced):
    """A stop sequence completed mid-verify (inside an accepted draft
    run) must cut generation at the match, exactly like plain decode."""
    cfg, _, _ = reduced(ARCH)
    mesh = make_test_mesh((1, 1))
    off = _eng(cfg, mesh)
    spec = _eng(cfg, mesh, spec_mode="ngram", spec_k=4, spec_min_ngram=1)
    params = off.init_params(0)
    prompts = _repetitive_prompts(cfg, n=1)
    ref, _ = off.generate(params, prompts, max_new_tokens=12)
    stop = ref[0][5:7]
    g1, _ = off.generate(params, prompts, max_new_tokens=12, stop=[stop])
    g2, s2 = spec.generate(params, prompts, max_new_tokens=12, stop=[stop])
    assert g1 == g2


def test_engine_sampling_reproducible_across_batch_widths(reduced):
    """temperature > 0: the sample key folds (seed, request uid, step),
    so tokens don't depend on slot placement or batching — the same
    request set sampled through 1 slot and 2 slots must agree."""
    cfg, _, _ = reduced(ARCH)
    mesh = make_test_mesh((1, 1))
    cfgs = dict(max_len=48, cache_mode="paged", block_size=4,
                quant_mode="int8_switchback", temperature=0.8, seed=7)
    e1 = make_serve_engine(build(cfg), ServeConfig(max_batch=1, **cfgs),
                           mesh, policy=INT8, parallel=PAR)
    e2 = make_serve_engine(build(cfg), ServeConfig(max_batch=2, **cfgs),
                           mesh, policy=INT8, parallel=PAR)
    params = e1.init_params(0)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (10, 13, 11)]
    g1, _ = e1.generate(params, prompts, max_new_tokens=6)
    g2, _ = e2.generate(params, prompts, max_new_tokens=6)
    assert g1 == g2
    # and a different engine seed actually changes the draw
    e3 = make_serve_engine(
        build(cfg), ServeConfig(max_batch=1, **{**cfgs, "seed": 8}),
        mesh, policy=INT8, parallel=PAR)
    g3, _ = e3.generate(params, prompts, max_new_tokens=6)
    assert g3 != g1


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_spec_config_validation(reduced):
    cfg, _, _ = reduced(ARCH)
    mesh = make_test_mesh((1, 1))
    base = dict(max_batch=1, max_len=32, quant_mode="int8_switchback")
    with pytest.raises(NotImplementedError):
        make_serve_engine(build(cfg),
                          ServeConfig(spec_mode="ngram", **base),
                          mesh, policy=INT8, parallel=PAR)       # ring cache
    for bad in (dict(spec_mode="medusa"), dict(spec_k=0),
                dict(spec_min_ngram=0), dict(spec_min_ngram=5)):
        kw = {**base, "cache_mode": "paged", "block_size": 4,
              "spec_mode": "ngram", **bad}
        with pytest.raises(ValueError):
            make_serve_engine(build(cfg), ServeConfig(**kw),
                              mesh, policy=INT8, parallel=PAR)


def test_scheduler_stop_normalization_and_counter():
    assert normalize_stop(None) == []
    assert normalize_stop([5, 6]) == [[5, 6]]
    assert normalize_stop([[5], [6, 7]]) == [[5], [6, 7]]
    with pytest.raises(ValueError):
        normalize_stop([[]])
    sched = SlotScheduler(max_batch=1, max_len=32)
    sched.submit([1, 2], max_new_tokens=8, stop=[[4, 5]])
    sched.admit()
    for t in (3, 4, 5):
        done = sched.record(0, t)
    assert done
    assert sched.counters["evicted_stop"] == 1
    assert sched.results[0] == [3, 4, 5]
