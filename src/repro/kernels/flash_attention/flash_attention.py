"""Pallas TPU kernels for fused flash attention (train fwd/bwd + decode).

TPU-native adaptation of the blockwise attention in jax.experimental
``pallas.ops.tpu.flash_attention`` / ``paged_attention``, specialised to
this repo's needs (DESIGN.md §9):

* **Forward**: online-softmax over KV tiles. Grid is (B, H, nq, nk) with
  the KV dimension innermost so the running (m, l, acc) statistics live in
  VMEM scratch across the contraction — the (Sq, Sk) score matrix is never
  materialised. Saves the per-row logsumexp for the backward.
* **GQA without expansion**: K/V keep their ``n_kv`` heads. Every kernel
  walks KV heads in its grid and loops the head's GQA query group
  in-kernel over a (1, group, …) Q block, so each fetched K/V tile is
  shared by the whole group — the ``jnp.repeat`` head expansion the XLA
  path pays for (extra HBM traffic proportional to H/KV) never happens,
  and K/V tiles are never re-streamed per query head either.
* **Causal tile skip**: KV tiles entirely above the diagonal are skipped
  with ``pl.when`` — ~2x fewer FLOPs at training shapes.
* **Backward**: recompute-style. Attention weights are rebuilt from
  (q, k, lse) — nothing quadratic is saved between fwd and bwd. One
  kernel (``flash_bwd``) walks Q tiles inside each KV tile and builds a
  tile's weights and ``ds`` once for all three gradients: dk/dv
  accumulate in scratch and land directly in KV-head layout (the
  group-sum is free), dq accumulates in an output block that holds the
  KV head's whole query group over the whole sequence in VMEM. Where that
  block does not fit, two kernels in opposite orders do the same
  arithmetic (``flash_bwd_dq``: KV tiles inner per Q tile;
  ``flash_bwd_dkv``), rebuilding the weights in each.
* **Decode**: one query per batch slot against the ring KV cache, with
  the per-slot lengths as a scalar-prefetch operand. Tiles beyond a
  slot's length are skipped dynamically on BOTH sides: the kernel body is
  predicated (no FLOPs) and the K/V index maps clamp dead tiles onto the
  last live tile so the pipeline never fetches them (no DMA) — a slot 10
  tokens in pays for 1 tile, not S_max/bk. The cache stays in its storage
  layout (B, KV, S, hd); the BlockSpec walks it directly, no transpose.

Layout rule (TPU): the last two dims of every block are either
(8, 128)-aligned or the whole array dim. So the per-row softmax stats
travel as (B, H, Sq, 1), decode queries as (B, KV, group, hd) — each KV
head's query group is one whole trailing (group, hd) slab — and every KV
cache puts its head axis ahead of the token axis.

Dots accumulate in f32 on the MXU (``preferred_element_type``) at the
MXU's default precision, which takes f32 operands in one bf16 pass — as
XLA's default does for the dense reference (measured on v5e: forcing
full f32 passes made the smollm-360m train step 75% slower). Masking uses a
finite ``-0.7·f32_max`` (never -inf: ``exp(-inf - -inf)`` NaNs). Every
kernel has a pure-jnp oracle in ``ref.py``; tests sweep shapes in
interpret mode (tests/test_attention_kernels.py).

Shape contract (enforced by ops.py, which pads): kernel-layout operands
q, k (B, H|KV, S, dqk) and v (B, KV, Sk, dv) with Sq % block_q == 0,
Sk % block_k == 0, H % KV == 0; the value and output width ``dv`` may
differ from the query/key width ``dqk`` (latent attention: 192 and 128),
neither padded to the other. ``kv_valid`` is the static true Sk before
padding (pad keys are masked in-kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# finite mask/init value: -inf would NaN via exp(-inf - (-inf)) on rows
# whose running max is still the init value
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _causal_tile_live(iq, ik, block_q, block_k):
    """True iff KV tile ik intersects the causal region of Q tile iq —
    i.e. the tile's first key position <= the tile's last query position."""
    return ik * block_k <= iq * block_q + block_q - 1


def _tile_mask(iq, ik, *, causal, kv_valid, block_q, block_k):
    """(bq, bk) mask of tile (iq, ik): keys below ``kv_valid`` (pad keys
    are out) and, when causal, not after their query."""
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < kv_valid
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = mask & (kpos <= qpos)
    return mask


def _rebuild(q, k, v, do, lse, di, mask, scale):
    """One query head's tile of the backward, rebuilt from (q, k, lse):
    the weights p = exp(s - lse) (masked -> 0) and the score gradient
    ds = p * (do·vᵀ - di), both (bq, bk) f32."""
    s = scale * jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    s = jnp.where(mask, s, MASK_VALUE)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(                                # do · vᵀ
        do, v, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p, p * (dp - di)


# ---------------------------------------------------------------------------
# forward: online softmax over KV tiles, saving logsumexp
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, kv_valid, group, n_k, block_q, block_k):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _tile():
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, dqk)
        v = v_ref[0, 0].astype(jnp.float32)                 # (bk, dv)
        mask = _tile_mask(iq, ik, causal=causal, kv_valid=kv_valid,
                          block_q=block_q, block_k=block_k)
        for g in range(group):                              # unrolled: the
            # KV tile is fetched ONCE per grid step and reused by every
            # query head in this KV head's GQA group
            q = q_ref[0, g].astype(jnp.float32) * scale     # (bq, dqk)
            s = jax.lax.dot_general(                        # (bq, bk)
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, MASK_VALUE)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_curr = jnp.max(s, axis=-1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_curr)
            p = jnp.exp(s - m_next)
            # rows with no live key yet have m_next == MASK_VALUE and
            # p == 1: harmless — the first tile with a real key corrects
            # them through alpha = exp(MASK_VALUE - m_real) == 0 (and with
            # q_offset == 0 the causal first tile always holds key 0, so
            # final rows are never dry)
            alpha = jnp.exp(m_prev - m_next)
            m_ref[g] = m_next
            l_ref[g] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_tile_live(iq, ik, block_q, block_k))(_tile)
    else:
        _tile()

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = l_ref[...]                                      # (g, bq, 1)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_safe)


def flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
              kv_valid: int, scale: float, block_q: int = 128,
              block_k: int = 128, interpret: bool = False):
    """q (B, H, Sq, dqk); k (B, KV, Sk, dqk); v (B, KV, Sk, dv). Returns
    (o (B, H, Sq, dv) in q.dtype, lse (B, H, Sq, 1) f32). ``kv_valid``
    masks pad keys.

    The grid walks KV heads, not query heads — the whole GQA group shares
    each fetched K/V tile via the in-kernel head loop, so KV HBM traffic
    is group-(H/KV)-fold lower than a query-head grid."""
    B, H, Sq, dqk = q.shape
    KV, Sk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // KV
    n_q, n_k = Sq // block_q, Sk // block_k
    grid = (B, KV, n_q, n_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, kv_valid=kv_valid,
        group=group, n_k=n_k, block_q=block_q, block_k=block_k)
    q_spec = pl.BlockSpec((1, group, block_q, dqk),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    o_spec = pl.BlockSpec((1, group, block_q, dv),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, dqk),
                          lambda b, h, iq, ik: (b, h, ik, 0))
    v_spec = pl.BlockSpec((1, 1, block_k, dv),
                          lambda b, h, iq, ik: (b, h, ik, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=[
            o_spec,
            pl.BlockSpec((1, group, block_q, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((group, block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((group, block_q, dv), jnp.float32),  # unnormed out
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward dq: for each Q tile, accumulate over KV tiles
#   p  = exp(s - lse);  ds = p * (do·vᵀ - di);  dq = scale · ds @ k
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                   acc_ref, *, scale, causal, kv_valid, group, n_k,
                   block_q, block_k):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _tile():
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(iq, ik, causal=causal, kv_valid=kv_valid,
                          block_q=block_q, block_k=block_k)
        for g in range(group):                    # K/V tile shared by the
            q = q_ref[0, g].astype(jnp.float32)   # KV head's query group
            do = do_ref[0, g].astype(jnp.float32)
            _, ds = _rebuild(q, k, v, do, lse_ref[0, g], di_ref[0, g], mask,
                             scale)
            acc_ref[g] += jax.lax.dot(ds, k,
                                      preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_tile_live(iq, ik, block_q, block_k))(_tile)
    else:
        _tile()

    @pl.when(ik == n_k - 1)
    def _finalize():
        dq_ref[0] = (scale * acc_ref[...]).astype(dq_ref.dtype)


def flash_bwd_dq(q, k, v, do, lse, di, *, causal: bool, kv_valid: int,
                 scale: float, block_q: int = 128, block_k: int = 128,
                 interpret: bool = False):
    """Returns dq (B, H, Sq, dqk) f32; ``do`` is (B, H, Sq, dv) and lse/di
    are (B, H, Sq, 1) f32. Same KV-head grid + in-kernel group loop as
    flash_fwd."""
    B, H, Sq, dqk = q.shape
    KV, Sk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // KV
    n_q, n_k = Sq // block_q, Sk // block_k
    kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, kv_valid=kv_valid,
        group=group, n_k=n_k, block_q=block_q, block_k=block_k)
    q_spec = pl.BlockSpec((1, group, block_q, dqk),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    do_spec = pl.BlockSpec((1, group, block_q, dv),
                           lambda b, h, iq, ik: (b, h, iq, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, dqk),
                          lambda b, h, iq, ik: (b, h, ik, 0))
    v_spec = pl.BlockSpec((1, 1, block_k, dv),
                          lambda b, h, iq, ik: (b, h, ik, 0))
    stat_spec = pl.BlockSpec((1, group, block_q, 1),
                             lambda b, h, iq, ik: (b, h, iq, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, KV, n_q, n_k),
        in_specs=[q_spec, k_spec, v_spec, do_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dqk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((group, block_q, dqk), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# backward dk/dv: for each KV tile, accumulate over Q tiles AND the GQA
# group's query heads (so dk/dv come out in (B, KV, Sk, hd) directly)
#   dv = pᵀ @ do;  dk = scale · dsᵀ @ q
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    kv_valid, group, n_q, block_q, block_k):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _tile():
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, dqk)
        v = v_ref[0, 0].astype(jnp.float32)                 # (bk, dv)
        mask = _tile_mask(iq, ik, causal=causal, kv_valid=kv_valid,
                          block_q=block_q, block_k=block_k)
        for g in range(group):                              # unrolled
            q = q_ref[0, g].astype(jnp.float32)             # (bq, dqk)
            do = do_ref[0, g].astype(jnp.float32)
            p, ds = _rebuild(q, k, v, do, lse_ref[0, g], di_ref[0, g], mask,
                             scale)
            dv_acc[...] += jax.lax.dot_general(              # pᵀ @ do
                p, do, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[...] += jax.lax.dot_general(              # dsᵀ @ q
                ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_tile_live(iq, ik, block_q, block_k))(_tile)
    else:
        _tile()

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[0, 0] = (scale * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _kv_major_specs(group, block_q, block_k, dqk, dv):
    """Blocks of q, k, v, do, lse and di on the (B, KV, nk, nq) grid of the
    backward kernels that walk Q tiles inside each KV tile. A q, do or
    stat block holds `group` query heads: block index h covers KV head
    h's whole query group."""
    def q_map(b, h, ik, iq):
        return b, h, iq, 0

    def kv_map(b, h, ik, iq):
        return b, h, ik, 0

    stat_spec = pl.BlockSpec((1, group, block_q, 1), q_map)
    return [pl.BlockSpec((1, group, block_q, dqk), q_map),
            pl.BlockSpec((1, 1, block_k, dqk), kv_map),
            pl.BlockSpec((1, 1, block_k, dv), kv_map),
            pl.BlockSpec((1, group, block_q, dv), q_map),
            stat_spec, stat_spec]


def flash_bwd_dkv(q, k, v, do, lse, di, *, causal: bool, kv_valid: int,
                  scale: float, block_q: int = 128, block_k: int = 128,
                  interpret: bool = False):
    """Returns dk (B, KV, Sk, dqk) and dv (B, KV, Sk, dv), both f32 and
    already summed over each KV head's GQA group (the in-kernel head
    loop)."""
    B, H, Sq, dqk = q.shape
    KV, Sk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // KV
    n_q, n_k = Sq // block_q, Sk // block_k
    kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, kv_valid=kv_valid,
        group=group, n_q=n_q, block_q=block_q, block_k=block_k)
    in_specs = _kv_major_specs(group, block_q, block_k, dqk, dv)
    return pl.pallas_call(
        kernel,
        grid=(B, KV, n_k, n_q),
        in_specs=in_specs,
        out_specs=in_specs[1:3],                            # dk, dv
        out_shape=[jax.ShapeDtypeStruct((B, KV, Sk, dqk), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, Sk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, dqk), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# backward, one pass: the dkv kernel's order (Q tiles inner per KV tile),
# each tile's p and ds built once and feeding all three gradients
#   dv = pᵀ @ do;  dk = scale · dsᵀ @ q;  dq[Q tile] += ds @ k
# dq lives in one output block per (batch, KV head) that stays in VMEM
# across the KV and Q tiles; for a fixed Q tile it sums the KV tiles in
# the split dq kernel's order, so all three come out bit-identical to it
# ---------------------------------------------------------------------------

# Scoped VMEM of the one-pass kernel beyond its resident dq: the split
# kernels' blocks and the (bq, bk) f32 score temporaries fit the default
# 16 MiB of a v5e; twice that leaves room for holding both kernels' live
# values at once
BWD_VMEM_HEADROOM = 32 * 1024 * 1024


def resident_dq_bytes(group: int, Sq: int, dqk: int) -> int:
    """Scoped VMEM of the one-pass kernel's dq block: a KV head's query
    group over the whole padded query axis in f32, its width padded to the
    128 lanes, double-buffered by the pipeline."""
    return 2 * group * Sq * (-(-dqk // 128) * 128) * 4


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                kv_valid, group, n_q, n_k, block_q, block_k):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when((ik == 0) & (iq == 0))
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _tile():
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, dqk)
        v = v_ref[0, 0].astype(jnp.float32)                 # (bk, dv)
        mask = _tile_mask(iq, ik, causal=causal, kv_valid=kv_valid,
                          block_q=block_q, block_k=block_k)
        rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
        for g in range(group):                              # unrolled
            q = q_ref[0, g].astype(jnp.float32)             # (bq, dqk)
            do = do_ref[0, g].astype(jnp.float32)
            p, ds = _rebuild(q, k, v, do, lse_ref[0, g], di_ref[0, g], mask,
                             scale)
            dv_acc[...] += jax.lax.dot_general(              # pᵀ @ do
                p, do, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[...] += jax.lax.dot_general(              # dsᵀ @ q
                ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_ref[0, g, rows, :] += jax.lax.dot(            # ds @ k
                ds, k, preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_tile_live(iq, ik, block_q, block_k))(_tile)
    else:
        _tile()

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[0, 0] = (scale * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((ik == n_k - 1) & (iq == n_q - 1))
    def _finalize_dq():
        dq_ref[...] = scale * dq_ref[...]


def flash_bwd(q, k, v, do, lse, di, *, causal: bool, kv_valid: int,
              scale: float, block_q: int = 128, block_k: int = 128,
              interpret: bool = False):
    """Returns (dq (B, H, Sq, dqk), dk (B, KV, Sk, dqk), dv (B, KV, Sk,
    dv)), all f32, from one pass over the tiles: what ``flash_bwd_dq`` and
    ``flash_bwd_dkv`` return, bit for bit. Its scoped VMEM grows with
    :func:`resident_dq_bytes`; the caller checks that it fits."""
    B, H, Sq, dqk = q.shape
    KV, Sk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // KV
    n_q, n_k = Sq // block_q, Sk // block_k
    kernel = functools.partial(
        _bwd_kernel, scale=scale, causal=causal, kv_valid=kv_valid,
        group=group, n_q=n_q, n_k=n_k, block_q=block_q, block_k=block_k)
    in_specs = _kv_major_specs(group, block_q, block_k, dqk, dv)
    dq_spec = pl.BlockSpec((1, group, Sq, dqk),
                           lambda b, h, ik, iq: (b, h, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, KV, n_k, n_q),
        in_specs=in_specs,
        out_specs=[dq_spec] + in_specs[1:3],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, dqk), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, Sk, dqk), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, Sk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, dqk), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=(resident_dq_bytes(group, Sq, dqk)
                              + BWD_VMEM_HEADROOM)),
        name="flash_bwd",
        interpret=interpret,
    )(q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# decode: one query per slot against the ring cache, per-slot lengths
# ---------------------------------------------------------------------------

def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale, n_k, block_k):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    kv_len = lens_ref[b]                                     # per-slot valid

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dynamic tile skip: a slot `L` tokens in touches cdiv(L, bk) tiles,
    # not S_max/bk — this is the win over the dense full-window re-attend.
    # The guard kills the compute; the DMA is killed by the K/V index maps
    # in `decode_fwd`, which clamp dead tiles to the last live tile (an
    # unchanged block index means the pipeline skips the fetch).
    @pl.when(ik * block_k < kv_len)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (group, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(                             # (group, bk)
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, MASK_VALUE)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        p = jnp.exp(s - m_next)
        alpha = jnp.exp(m_prev - m_next)
        m_ref[...] = m_next
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def decode_fwd(q: jax.Array, k: jax.Array, v: jax.Array, kv_len: jax.Array,
               *, scale: float, block_k: int = 128,
               interpret: bool = False):
    """Single-query ring-cache attention.

    q (B, KV, group, hd) — query head ``h * group + g`` at ``[:, h, g]``;
    k, v (B, KV, S, hd) — the cache's own storage layout, no transpose;
    kv_len (B,) int32 valid-cell counts (callers pass
    ``min(length + 1, S_max)``, which with ring writes at ``length % S``
    makes wrapped slots attend over the whole window). S % block_k == 0.
    Returns o (B, KV, group, hd) in q.dtype.

    ``kv_len`` rides in as a scalar-prefetch operand so the K/V BlockSpec
    index maps can see it: tiles past a slot's last live tile are clamped
    onto that tile, which leaves the block index unchanged and makes the
    Pallas pipeline skip their HBM fetch entirely — the dynamic skip
    saves the DMA, not just the FLOPs.
    """
    B, KV, group, hd = q.shape
    S = k.shape[2]
    n_k = S // block_k
    kernel = functools.partial(_decode_kernel, scale=scale, n_k=n_k,
                               block_k=block_k)

    def kv_map(b, h, ik, lens):
        last = jnp.maximum((lens[b] + block_k - 1) // block_k - 1, 0)
        return (b, h, jnp.minimum(ik, last), 0)

    q_spec = pl.BlockSpec((1, 1, group, hd),
                          lambda b, h, ik, lens: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="flash_decode",
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q, k, v)
