"""PagedServe suite: block pool, radix prefix cache, paged kernel, and
paged-vs-ring parity (DESIGN.md §10).

* BlockPool invariants: alloc/free churn leaks nothing, double free and
  foreign-id release raise, refcount sharing semantics.
* RadixPrefixCache: full-block hit/miss, divergence, LRU eviction order,
  refcount-held nodes are not evictable, child-before-parent cascade.
* Paged decode kernel: parity vs the gather-then-dense oracle across
  shapes/GQA/ragged lengths (xla vs pallas_interpret), dead-table-entry
  safety.
* Transformer level: paged prefill/decode vs the ring path (bitwise
  prefill, per-step logit parity), prefix-adopted prefill vs full
  prefill.
* Engine level: int8 token-for-token paged-vs-ring generation across
  admission/eviction churn, shared-prefix reuse with slot churn (hit
  rate > 0 AND identical generations), peak memory < ring footprint,
  skip-ahead admission under block pressure.
* SlotScheduler: fits-hook skip-ahead + counters, preempt-to-queue FIFO.
* SLO serving (chunked prefill + preemption): per-slot-offset prefill
  kernel vs the gather-then-dense oracle (ragged offsets, chunk
  boundaries), chunked engine vs the ring oracle under slot churn,
  preempted-request token parity vs an uncontended run, optimistic
  admission accounting, incremental ``evictable`` vs the recount oracle.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.configs.base import ParallelConfig, ServeConfig
from repro.core.precision import QuantPolicy
from repro.kernels.paged_attention import ops as PA
from repro.launch.mesh import make_test_mesh
from repro.models import build
from repro.models import transformer as TF
from repro.serve import (BlockPool, NoFreeBlocks, PagedCacheManager,
                         RadixPrefixCache, SlotScheduler, make_serve_engine)

ARCH = "smollm-360m"
PAR = ParallelConfig(remat="none")
F32 = QuantPolicy("bf16", compute_dtype=jnp.float32)


def _tokens(key, batch, seq, vocab):
    return jax.random.randint(jax.random.PRNGKey(key), (batch, seq),
                              0, vocab)


# ---------------------------------------------------------------------------
# block pool
# ---------------------------------------------------------------------------

def test_block_pool_churn_no_leaks():
    pool = BlockPool(8)
    rng = np.random.default_rng(0)
    held = []
    for _ in range(200):
        if held and (rng.random() < 0.5 or pool.free == 0):
            pool.release(held.pop(rng.integers(len(held))))
        else:
            held.append(pool.alloc())
    for bid in held:
        pool.release(bid)
    assert pool.free == 8 and pool.in_use == 0
    assert sorted(pool._free) == list(range(8))      # every id came home


def test_block_pool_double_free_and_foreign_release_raise():
    pool = BlockPool(2)
    a = pool.alloc()
    pool.release(a)
    with pytest.raises(ValueError):
        pool.release(a)
    with pytest.raises(ValueError):
        pool.release(1)                              # never allocated
    with pytest.raises(ValueError):
        pool.retain(1)


def test_block_pool_refcount_sharing():
    pool = BlockPool(1)
    a = pool.alloc()
    pool.retain(a)
    pool.retain(a)
    assert pool.refcount(a) == 3
    pool.release(a)
    pool.release(a)
    assert pool.free == 0                            # one owner left
    pool.release(a)
    assert pool.free == 1
    p2 = BlockPool(1)
    p2.alloc()
    with pytest.raises(NoFreeBlocks):
        p2.alloc()


# ---------------------------------------------------------------------------
# radix prefix cache
# ---------------------------------------------------------------------------

def _cache(n_blocks=8, bs=2):
    pool = BlockPool(n_blocks)
    return pool, RadixPrefixCache(pool, bs)


def test_prefix_cache_hit_miss_divergence():
    pool, cache = _cache()
    b = [pool.alloc() for _ in range(3)]
    cache.insert([1, 2, 3, 4, 5, 6], b)             # 3 full blocks
    for bid in b:
        pool.release(bid)                           # cache is sole owner
    assert cache.match_len([1, 2, 3, 4, 5, 6], max_blocks=3) == 3
    assert cache.match_len([1, 2, 3, 4, 9, 9], max_blocks=3) == 2
    assert cache.match_len([9, 9], max_blocks=1) == 0
    got = cache.match([1, 2, 3, 4], max_blocks=2)
    assert got == b[:2]
    assert pool.refcount(b[0]) == 2                 # cache + adopter
    assert pool.refcount(b[2]) == 1                 # not matched


def test_prefix_cache_partial_blocks_never_cached():
    pool, cache = _cache(bs=4)
    a = pool.alloc()
    cache.insert([1, 2, 3], [])                     # 0 full blocks: no-op
    assert cache.n_nodes == 0
    cache.insert([1, 2, 3, 4], [a])
    assert cache.n_nodes == 1
    assert cache.match_len([1, 2, 3], max_blocks=0) == 0


def test_prefix_cache_lru_eviction_and_refcount_guard():
    pool, cache = _cache(n_blocks=4, bs=2)
    b1 = [pool.alloc(), pool.alloc()]
    b2 = [pool.alloc(), pool.alloc()]
    cache.insert([1, 2, 3, 4], b1)                  # chain A (older)
    cache.insert([5, 6, 7, 8], b2)                  # chain B (newer)
    for bid in b1 + b2:
        pool.release(bid)
    # a live adopter pins chain B's leaf
    adopted = cache.match([5, 6, 7, 8], max_blocks=2)
    assert cache.evict(1) == 1                      # LRU: chain A's leaf
    assert pool.refcount(b1[1]) == 0                # A-leaf evicted first
    assert cache.evict(10) == 1                     # A-root cascades; B held
    assert pool.free == 2
    for bid in adopted:
        pool.release(bid)
    assert cache.evict(10) == 2                     # now B evicts leaf-first
    assert pool.free == 4 and cache.n_nodes == 0


def test_prefix_cache_evictable_counts_subtrees():
    pool, cache = _cache(n_blocks=4, bs=2)
    bids = [pool.alloc() for _ in range(3)]
    cache.insert([1, 2, 3, 4, 5, 6], bids)
    for bid in bids:
        pool.release(bid)
    assert cache.evictable == 3
    got = cache.match([1, 2, 3, 4, 5, 6], max_blocks=3)   # pin the leaf
    assert cache.evictable == 0                     # parents can't go either
    for bid in got:
        pool.release(bid)
    assert cache.evictable == 3


def test_manager_reservation_blocks_overcommit():
    # 6-block pool, bs=2: one request reserving 4 blocks leaves room for
    # a 2-block one but not another 4-block one
    m = PagedCacheManager(num_blocks=6, block_size=2, max_batch=3,
                          blocks_per_slot=4, prefix_cache=False)
    m.begin_wave()
    assert m.fits(4, 5)                             # ceil((4+5-1)/2) = 4
    m.admit(0, [1, 2, 3, 4], max_new_tokens=5)      # 2 alloc'd + 2 reserved
    m.begin_wave()
    assert not m.fits(4, 5)                         # 4 > 6-2-2
    assert m.fits(2, 3)                             # 2 <= 2
    m.admit(1, [5, 6], max_new_tokens=3)
    # decode growth consumes the reservation, never over the pool
    for pos in range(4, 8):
        m.ensure_block(0, pos)
    assert m.pool.in_use <= 6
    m.release(0, [1, 2, 3, 4, 7, 8, 9, 10])
    m.release(1, [5, 6, 7, 8])
    # with the prefix cache off every block must come back
    assert m.pool.free == 6


def test_manager_wave_holds_stop_same_wave_overcommit():
    """Several fits() calls land in one admission wave BEFORE any admit()
    records reservations — earlier promises must count against later
    candidates (regression: a 3-slot wave over an 8-block pool admitted
    three 3-block requests and exhausted the pool during decode)."""
    m = PagedCacheManager(num_blocks=8, block_size=4, max_batch=3,
                          blocks_per_slot=8, prefix_cache=False)
    m.begin_wave()
    assert m.fits(6, 5)                             # need 3; hold 3
    assert m.fits(6, 5)                             # need 3; hold 6
    assert not m.fits(6, 5)                         # 3 > 8 - 6
    assert m.fits(2, 3)                             # need 1 still fits


# ---------------------------------------------------------------------------
# paged decode kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,KV,hd,bs,nb", [
    (4, 4, 16, 8, 4),       # MHA
    (8, 2, 16, 8, 4),       # GQA 4
    (4, 1, 8, 4, 3),        # MQA, non-pow2 table width
    (4, 2, 32, 16, 2),      # bigger blocks
])
def test_paged_kernel_matches_oracle(H, KV, hd, bs, nb):
    B, N = 3, 12
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (B, 1, H, hd))
    k_pool = jax.random.normal(keys[1], (N + 1, KV, bs, hd))
    v_pool = jax.random.normal(keys[2], (N + 1, KV, bs, hd))
    rng = np.random.default_rng(3)
    tables = jnp.asarray(rng.permutation(N)[:B * nb].reshape(B, nb))
    # ragged: empty-ish, mid-block, full
    kv_len = jnp.asarray([1, (nb - 1) * bs - 1, nb * bs], jnp.int32)[:B]
    a = PA.paged_decode_attention(q, k_pool, v_pool, tables, kv_len,
                                  backend="xla")
    b = PA.paged_decode_attention(q, k_pool, v_pool, tables, kv_len,
                                  backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=0, atol=1e-5)


def test_paged_kernel_ignores_dead_table_entries():
    """Blocks past a slot's live prefix must not affect the output —
    the clamp + mask make any stale/trash id harmless."""
    B, H, KV, hd, bs, nb, N = 2, 4, 2, 8, 4, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (B, 1, H, hd))
    k_pool = jax.random.normal(keys[1], (N + 1, KV, bs, hd))
    v_pool = jax.random.normal(keys[2], (N + 1, KV, bs, hd))
    kv_len = jnp.asarray([5, 3], jnp.int32)         # 2 live blocks / 1
    t1 = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    t2 = jnp.asarray([[0, 1, N, N], [4, N, N, N]], jnp.int32)  # dead->trash
    for backend in ("xla", "pallas_interpret"):
        a = PA.paged_decode_attention(q, k_pool, v_pool, t1, kv_len,
                                      backend=backend)
        b = PA.paged_decode_attention(q, k_pool, v_pool, t2, kv_len,
                                      backend=backend)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# transformer level: paged vs ring
# ---------------------------------------------------------------------------

def _paged_setup(cfg, B, max_len, bs, dtype):
    nb = max_len // bs
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    st = TF.init_paged_serve_state(cfg, B * nb, bs, B, dtype=dtype)
    return tables, st


def test_paged_prefill_matches_ring_bitwise(reduced):
    """With no adopted prefix the paged prefill is the ring dense prefill
    math-for-math: logits must match bit-for-bit in f32 compute."""
    cfg, _, params = reduced(ARCH)
    B, S, bs = 3, 8, 4
    lens = jnp.array([8, 5, 3], jnp.int32)
    tokens = _tokens(1, B, S, cfg.vocab_size)
    st = TF.init_serve_state(cfg, B, 16, dtype=jnp.float32)
    pf, st = TF.serve_prefill(params, st, tokens, lens,
                              jnp.ones((B,), bool), cfg, F32, PAR)
    tables, pst = _paged_setup(cfg, B, 16, bs, jnp.float32)
    ppf, pst = TF.paged_prefill(params, pst, tables, tokens,
                                jnp.zeros((B,), jnp.int32), lens,
                                jnp.ones((B,), bool), cfg, F32, PAR)
    for b in range(B):
        L = int(lens[b])
        np.testing.assert_array_equal(np.asarray(pf[b, :L]),
                                      np.asarray(ppf[b, :L]))
    np.testing.assert_array_equal(
        np.asarray(pst["pos0"].length),
        np.tile(np.asarray(lens), (TF.n_groups(cfg), 1)))


def test_paged_decode_matches_ring(reduced):
    """Prefill + N paged decode steps track the ring path's logits."""
    cfg, _, params = reduced(ARCH)
    B, S, bs = 3, 8, 4
    lens = jnp.array([8, 5, 3], jnp.int32)
    tokens = _tokens(1, B, S, cfg.vocab_size)
    st = TF.init_serve_state(cfg, B, 16, dtype=jnp.float32)
    _, st = TF.serve_prefill(params, st, tokens, lens,
                             jnp.ones((B,), bool), cfg, F32, PAR)
    tables, pst = _paged_setup(cfg, B, 16, bs, jnp.float32)
    _, pst = TF.paged_prefill(params, pst, tables, tokens,
                              jnp.zeros((B,), jnp.int32), lens,
                              jnp.ones((B,), bool), cfg, F32, PAR)
    cont = _tokens(2, B, 4, cfg.vocab_size)
    for t in range(4):
        lg, st = TF.decode_step(params, st, cont[:, t:t + 1], cfg, F32, PAR)
        plg, pst = TF.paged_decode_step(params, pst, tables,
                                        cont[:, t:t + 1], cfg, F32, PAR)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(plg),
                                   rtol=0, atol=1e-5)


def test_paged_prefix_adoption_matches_full_prefill(reduced):
    """Prefilling only the suffix on top of adopted prefix blocks must
    reproduce the full-prompt prefill's last-token logits and decode
    trajectory (the zero-FLOP shared prefix is exact, not approximate)."""
    cfg, _, params = reduced(ARCH)
    B, bs, max_len = 1, 4, 16
    prompt = _tokens(3, 1, 10, cfg.vocab_size)[0]    # 10 = 2 full blocks + 2
    tables, pst = _paged_setup(cfg, B, max_len, bs, jnp.float32)
    lens = jnp.array([10], jnp.int32)
    # request 1: full prefill fills blocks 0..2
    full, pst = TF.paged_prefill(params, pst, tables, prompt[None],
                                 jnp.zeros((B,), jnp.int32), lens,
                                 jnp.ones((B,), bool), cfg, F32, PAR,
                                 last_only=True)
    # request 2 (same prompt) adopts the 2 full blocks: suffix = last 2
    # tokens, pref = 8; reuse the same table/pool (blocks already filled)
    suf = prompt[8:][None]
    adopt, pst2 = TF.paged_prefill(params, pst, tables,
                                   jnp.pad(suf, ((0, 0), (0, 2))),
                                   jnp.array([8], jnp.int32), lens,
                                   jnp.ones((B,), bool), cfg, F32, PAR,
                                   last_only=True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(adopt),
                               rtol=0, atol=1e-5)
    cont = _tokens(4, B, 2, cfg.vocab_size)
    lg1, s1 = TF.paged_decode_step(params, pst, tables, cont[:, :1],
                                   cfg, F32, PAR)
    lg2, s2 = TF.paged_decode_step(params, pst2, tables, cont[:, :1],
                                   cfg, F32, PAR)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                               rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------

def _engines(max_batch, max_len=32, bs=4, **kw):
    cfg = get_reduced_config(ARCH)
    mesh = make_test_mesh((1, 1))
    common = dict(max_batch=max_batch, max_len=max_len,
                  quant_mode="int8_switchback", **kw)
    ring = make_serve_engine(build(cfg), ServeConfig(**common), mesh)
    paged = make_serve_engine(
        build(cfg), ServeConfig(cache_mode="paged", block_size=bs,
                                **common), mesh)
    return ring, paged, cfg


def test_engine_paged_matches_ring_int8_churn(reduced):
    """7 mixed-length requests through 2 slots (forces multiple
    admission/eviction waves + prefix parking/adoption) must generate
    token-for-token what the ring engine generates."""
    ring, paged, cfg = _engines(2)
    params_host = jax.device_get(ring.init_params(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (6, 9, 3, 7, 5, 12, 4)]
    g1, s1 = ring.generate(ring.shard_params(params_host), prompts,
                           max_new_tokens=5)
    g2, s2 = paged.generate(paged.shard_params(params_host), prompts,
                            max_new_tokens=5)
    assert g1 == g2
    assert s1["prefill_calls"] >= 3          # churn actually happened
    assert s2["peak_cache_bytes"] <= s2["ring_equiv_cache_bytes"]
    for k in ("ttft_p50_s", "ttft_p95_s", "itl_p50_s", "itl_p95_s"):
        assert s1[k] >= 0 and s2[k] >= 0


def test_engine_shared_prefix_reuse_across_churn(reduced):
    """Requests sharing a system prompt, churned through 2 slots: later
    waves must adopt parked prefix blocks (hit rate > 0, prefill tokens
    saved) while still matching the ring oracle token-for-token."""
    ring, paged, cfg = _engines(2, max_len=48, bs=4)
    params_host = jax.device_get(ring.init_params(0))
    rng = np.random.default_rng(1)
    sysp = rng.integers(0, cfg.vocab_size, size=16).tolist()
    prompts = [sysp + rng.integers(0, cfg.vocab_size, size=3).tolist()
               for _ in range(6)]
    g1, s1 = ring.generate(ring.shard_params(params_host), prompts,
                           max_new_tokens=4)
    g2, s2 = paged.generate(paged.shard_params(params_host), prompts,
                            max_new_tokens=4)
    assert g1 == g2
    assert s2["prefix_hits"] > 0
    assert s2["prefill_tokens_saved"] > 0
    assert s2["prefill_tokens"] < s1["prefill_tokens"]
    # shared blocks mean fewer peak blocks than 6 lone prompts would need
    assert s2["peak_blocks_in_use"] < 6 * math.ceil(19 / 4)


def test_engine_paged_no_prefix_cache_still_matches(reduced):
    """prefix_cache=False: every block frees on eviction, no adoption —
    generations still match ring and the pool drains back to empty."""
    ring, paged, cfg = _engines(2, prefix_cache=False)
    params_host = jax.device_get(ring.init_params(0))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).tolist()
               for _ in range(4)]
    g1, _ = ring.generate(ring.shard_params(params_host), prompts,
                          max_new_tokens=4)
    g2, s2 = paged.generate(paged.shard_params(params_host), prompts,
                            max_new_tokens=4)
    assert g1 == g2
    assert s2["prefix_lookups"] == 0


def test_engine_small_pool_throttles_admission(reduced):
    """A pool smaller than the ring capacity still completes every
    request — admission waits for blocks instead of crashing — and the
    peak block usage respects the pool size."""
    cfg = get_reduced_config(ARCH)
    scfg = ServeConfig(max_batch=3, max_len=32, cache_mode="paged",
                       block_size=4, num_blocks=8,      # < 3*8 ring blocks
                       quant_mode="bf16")
    eng = make_serve_engine(build(cfg), scfg, make_test_mesh((1, 1)),
                            policy=F32)
    params = eng.init_params(0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=6).tolist()
               for _ in range(4)]
    gens, stats = eng.generate(params, prompts, max_new_tokens=5)
    assert all(len(g) == 5 for g in gens)
    assert stats["peak_blocks_in_use"] <= 8


def test_engine_budget_past_cache_edge_matches_ring(reduced):
    """A token budget far past the cache edge must evict at max_len like
    the ring path — not hang admission (regression: the worst-case block
    reservation used the raw budget, so such a request never fit and
    generate() spun forever)."""
    ring, paged, cfg = _engines(2, max_len=16, bs=4)
    params_host = jax.device_get(ring.init_params(0))
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=6).tolist()
               for _ in range(3)]
    g1, _ = ring.generate(ring.shard_params(params_host), prompts,
                          max_new_tokens=99)       # evicts at the edge
    g2, _ = paged.generate(paged.shard_params(params_host), prompts,
                           max_new_tokens=99)
    assert g1 == g2
    assert all(len(g) < 99 for g in g1)


def test_manager_never_fitting_request_raises():
    """A request the pool can never hold raises loudly instead of
    returning False forever (which would spin the admission loop)."""
    m = PagedCacheManager(num_blocks=2, block_size=4, max_batch=1,
                          blocks_per_slot=8, prefix_cache=False)
    with pytest.raises(NoFreeBlocks):
        m.fits(20, 16)                             # needs 8 > 2 blocks


def test_manager_fits_discounts_adopted_blocks_from_evictable():
    """Adopting parked blocks pins them — fits() must not count the same
    block both as a prefix-hit credit and as evictable capacity
    (regression: admit() then hit NoFreeBlocks mid-wave)."""
    m = PagedCacheManager(num_blocks=5, block_size=4, max_batch=2,
                          blocks_per_slot=5)
    prompt = list(range(16))
    m.begin_wave()
    assert m.fits(16, 1)
    m.admit(0, prompt, max_new_tokens=1)
    m.release(0, prompt)                           # park all 4 full blocks
    m.begin_wave()
    assert m.fits(4, 1)                            # last free block...
    m.admit(1, [55, 66, 77, 88], max_new_tokens=1)
    assert m.pool.free == 0 and m.cache.evictable == 4
    m.begin_wave()
    # 18-token prompt whose first 16 tokens match the parked chain: needs
    # 1 fresh block but adoption pins the 4 parked ones — nothing left to
    # evict, so this must NOT fit (the old accounting said yes, then
    # admit() crashed on the empty pool)
    assert not m.fits(18, 1, prompt=prompt + [1, 2])


def test_generate_zero_budget_stats_complete(reduced):
    """max_new_tokens=0 early-returns with the full stats schema (the
    launch CLI reads ttft/itl and paged keys unconditionally)."""
    _, paged, cfg = _engines(2)
    params = paged.init_params(0)
    gens, stats = paged.generate(params, [[1, 2, 3]], max_new_tokens=0)
    assert gens == [[]]
    for k in ("ttft_p50_s", "itl_p95_s", "sched_admitted", "prefix_hits",
              "peak_cache_bytes", "ring_equiv_cache_bytes"):
        assert k in stats


def test_paged_rollover_rejected():
    cfg = get_reduced_config(ARCH)
    with pytest.raises(NotImplementedError):
        make_serve_engine(
            build(cfg), ServeConfig(cache_mode="paged", rollover=True),
            make_test_mesh((1, 1)))


def test_engine_rejects_unknown_cache_mode():
    cfg = get_reduced_config(ARCH)
    with pytest.raises(ValueError):
        make_serve_engine(build(cfg), ServeConfig(cache_mode="pagedd"),
                          make_test_mesh((1, 1)))


# ---------------------------------------------------------------------------
# scheduler skip-ahead
# ---------------------------------------------------------------------------

def test_scheduler_skip_ahead_admission():
    s = SlotScheduler(max_batch=2, max_len=64)
    s.submit([1] * 30)                       # too big for the fits below
    s.submit([2] * 4)
    s.submit([3] * 5)
    out = s.admit(fits=lambda r: len(r.prompt) <= 8)
    assert [r.prompt[0] for _, r in out] == [2, 3]   # both small ones pass
    assert s.pending == 1                    # the big one keeps its place
    assert s.counters["skipped"] == 1        # the stuck request counts
    assert s.counters["admitted"] == 2       # once per wave, not per slot
    out = s.admit(fits=lambda r: True)       # now it fits: FIFO restored
    assert len(out) == 0 or out[0][1].prompt[0] == 1


def test_scheduler_counters_track_evictions():
    s = SlotScheduler(max_batch=1, max_len=8)
    s.submit([1, 2], max_new_tokens=2)
    s.submit([1, 2], max_new_tokens=99, eos_id=7)
    s.admit()
    s.record(0, 5)
    s.record(0, 5)                           # budget eviction
    s.admit()
    s.record(0, 7)                           # EOS eviction
    assert s.counters["evicted_budget"] == 1
    assert s.counters["evicted_eos"] == 1
    assert s.counters["peak_queue_depth"] == 2


def test_scheduler_preempt_requeues_fifo():
    """preempt() must put the victim back at its FIFO arrival position
    (before later uids), keep its generated continuation, and reset the
    chunk cursor so re-prefill starts from scratch."""
    s = SlotScheduler(max_batch=2, max_len=32)
    for i in range(3):
        s.submit([10 + i] * 4, max_new_tokens=4)
    out = s.admit()
    assert [r.uid for _, r in out] == [0, 1]
    s.record(0, 5)
    s.record(1, 6)
    s.preempt(1)
    assert s.counters["preempted"] == 1
    assert s.pending == 2                    # uid 1 back in line, uid 2
    out = s.admit()
    assert len(out) == 1
    slot, r = out[0]
    assert (slot, r.uid) == (1, 1)           # ahead of uid 2 (FIFO)
    assert r.generated == [6]                # continuation kept
    assert r.prefilled == 0                  # cursor reset: full re-prefill
    assert r.context == [11, 11, 11, 11, 6]
    assert r.remaining_new == 3


# ---------------------------------------------------------------------------
# PR 6: chunked prefill, preemption, incremental evictable
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,KV,hd,bs,nb", [
    (4, 4, 16, 8, 4),       # MHA
    (8, 2, 16, 4, 6),       # GQA 4
    (4, 1, 8, 4, 5),        # MQA, odd table width
])
def test_paged_prefill_kernel_matches_oracle(H, KV, hd, bs, nb):
    """Per-slot-offset prefill tile vs the gather-then-dense oracle:
    fresh chunk (q_off=0), resumed chunk at an unaligned cursor, and a
    dry slot (kv_len=0) that must emit exact zeros on both paths."""
    B, S, N = 3, 8, 20
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (B, S, H, hd))
    k_pool = jax.random.normal(keys[1], (N + 1, KV, bs, hd))
    v_pool = jax.random.normal(keys[2], (N + 1, KV, bs, hd))
    rng = np.random.default_rng(5)
    tables = jnp.asarray(rng.permutation(N)[:B * nb].reshape(B, nb))
    q_off = jnp.asarray([0, 5, 0], jnp.int32)
    kv_len = jnp.asarray([S, 5 + S, 0], jnp.int32)
    a = PA.paged_prefill_attention(q, k_pool, v_pool, tables, q_off,
                                   kv_len, backend="xla")
    b = PA.paged_prefill_attention(q, k_pool, v_pool, tables, q_off,
                                   kv_len, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=0, atol=1e-5)
    assert np.all(np.asarray(a[2]) == 0)     # dry slot: exact zeros


def test_paged_prefill_kernel_chunked_matches_monolithic():
    """Prefilling in chunks with block-unaligned edges must reproduce the
    one-shot prefill row-for-row on both backends — the q_off plumbing is
    what makes chunk N see chunks 0..N-1 correctly."""
    B, H, KV, hd, bs, nb = 2, 4, 2, 16, 4, 6
    N, L = 12, 24
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(keys[0], (B, L, H, hd))
    k_pool = jax.random.normal(keys[1], (N + 1, KV, bs, hd))
    v_pool = jax.random.normal(keys[2], (N + 1, KV, bs, hd))
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    ones = jnp.ones((B,), jnp.int32)
    for backend in ("xla", "pallas_interpret"):
        mono = PA.paged_prefill_attention(q, k_pool, v_pool, tables,
                                          0 * ones, L * ones,
                                          backend=backend)
        parts = [PA.paged_prefill_attention(q[:, a:b], k_pool, v_pool,
                                            tables, a * ones, b * ones,
                                            backend=backend)
                 for a, b in ((0, 5), (5, 13), (13, 24))]
        np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, 1)),
                                   np.asarray(mono), rtol=0, atol=1e-5)


def test_engine_chunked_prefill_matches_ring(reduced):
    """A long prompt chunk-prefilling across waves while short requests
    stream through the other slots must generate exactly the ring
    engine's tokens (commit-then-attend stays exact across chunk
    boundaries, and decode waves interleave with resumed chunks)."""
    ring, _, cfg = _engines(3)
    chunked = make_serve_engine(
        build(cfg), ServeConfig(cache_mode="paged", block_size=4,
                                max_batch=3, max_len=32,
                                quant_mode="int8_switchback",
                                prefill_chunk_tokens=6,
                                preemption="recompute"),
        make_test_mesh((1, 1)))
    params_host = jax.device_get(ring.init_params(0))
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (20, 3, 17, 4, 9, 5)]
    g1, _ = ring.generate(ring.shard_params(params_host), prompts,
                          max_new_tokens=6)
    g2, s2 = chunked.generate(chunked.shard_params(params_host), prompts,
                              max_new_tokens=6)
    assert g1 == g2
    assert s2["prefill_chunks"] > len(prompts)   # long prompts really split
    assert s2["itl_wall_p95_s"] >= s2["itl_p95_s"] >= 0


def test_engine_preemption_token_parity(reduced):
    """Pool pressure mid-decode preempts the newest request to the queue;
    its recompute-on-resume continuation must reproduce the uncontended
    run's tokens exactly — and the tight run must actually preempt."""
    cfg = get_reduced_config(ARCH)
    mesh = make_test_mesh((1, 1))

    def eng(num_blocks):
        return make_serve_engine(
            build(cfg), ServeConfig(cache_mode="paged", block_size=4,
                                    max_batch=2, max_len=32,
                                    num_blocks=num_blocks,
                                    quant_mode="int8_switchback",
                                    preemption="recompute"), mesh)

    roomy, tight = eng(0), eng(8)            # 8 < 2*8 ring-equiv blocks
    params_host = jax.device_get(roomy.init_params(0))
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, size=10).tolist()
               for _ in range(2)]
    g1, s1 = roomy.generate(roomy.shard_params(params_host), prompts,
                            max_new_tokens=20)
    g2, s2 = tight.generate(tight.shard_params(params_host), prompts,
                            max_new_tokens=20)
    assert g1 == g2
    assert s1["sched_preempted"] == 0
    assert s2["sched_preempted"] >= 1
    assert all(len(g) == 20 for g in g2)     # preemptee still completed


def test_manager_optimistic_admission_drops_reservations():
    """preemption=True switches fits() from worst-case reservations to
    prompt-only demand (preempt-to-queue is the safety net), but a
    request whose worst case can never fit the pool still raises."""
    strict = PagedCacheManager(num_blocks=6, block_size=4, max_batch=2,
                               blocks_per_slot=8, prefix_cache=False)
    opt = PagedCacheManager(num_blocks=6, block_size=4, max_batch=2,
                            blocks_per_slot=8, prefix_cache=False,
                            preemption=True)
    for m in (strict, opt):
        m.begin_wave()
        assert m.fits(8, 16)                 # worst case exactly 6 blocks
        m.admit(0, list(range(8)), max_new_tokens=16)
        m.begin_wave()
    assert not strict.fits(8, 16)            # reservation blocks slot 1
    assert opt.fits(8, 16)                   # optimistic: prompt's 2 only
    opt.admit(1, list(range(8)), max_new_tokens=16)
    assert opt.pool.in_use == 4
    with pytest.raises(NoFreeBlocks):
        opt.fits(32, 1)                      # 8 blocks > pool, ever


def test_prefix_cache_evictable_incremental_matches_recount():
    """Churn workload: random insert/adopt/release/evict interleavings —
    the incremental evictable count must equal the O(n) recount oracle
    after every single operation."""
    pool = BlockPool(64)
    cache = RadixPrefixCache(pool, 2)
    rng = np.random.default_rng(7)
    adopted = []                             # references we hold
    for _ in range(150):
        op = rng.integers(0, 4)
        if op == 0 and pool.free >= 4:       # park a (shared-prefix) chain
            n = int(rng.integers(1, 5))
            toks = rng.integers(0, 3, size=2 * n).tolist()
            have = cache.match(toks, max_blocks=n)
            fresh = [pool.alloc() for _ in range(n - len(have))]
            cache.insert(toks, have + fresh)
            for bid in have + fresh:
                pool.release(bid)
        elif op == 1:                        # adopt and hold
            toks = rng.integers(0, 3,
                                size=2 * int(rng.integers(1, 5))).tolist()
            adopted.extend(cache.match(toks, max_blocks=4))
        elif op == 2 and adopted:            # an adopter finishes
            pool.release(adopted.pop(rng.integers(len(adopted))))
        else:
            cache.evict(int(rng.integers(1, 3)))
        assert cache.evictable == cache.recount()
    for bid in adopted:
        pool.release(bid)
    assert cache.evictable == cache.recount()
    cache.evict(64)
    assert cache.evictable == cache.recount() == 0


def test_engine_rejects_bad_slo_config():
    cfg = get_reduced_config(ARCH)
    mesh = make_test_mesh((1, 1))
    for kw in (dict(prefill_chunk_tokens=8), dict(preemption="recompute")):
        with pytest.raises(NotImplementedError):     # ring: paged-only
            make_serve_engine(build(cfg), ServeConfig(**kw), mesh)
    with pytest.raises(ValueError):
        make_serve_engine(
            build(cfg), ServeConfig(cache_mode="paged",
                                    preemption="bogus"), mesh)
