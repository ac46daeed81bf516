"""Pallas TPU kernels for paged attention (block-pool KV cache): the
single-query decode kernel and the per-slot-offset chunked-prefill
kernel.

The paged twin of ``kernels/flash_attention``'s ring-cache decode kernel
(DESIGN.md §10): K/V live in a fixed pool of physical blocks of shape
(num_blocks + 1, KV, block_size, hd) — the last block is the engine's
trash block; the head axis sits ahead of the token axis so one head's
block is a whole trailing (block_size, hd) slab, as the TPU's block
layout rule asks — and each batch slot owns a *block table* row mapping its
logical block j to a physical block id. The kernel walks logical blocks;
the **block table rides in as a scalar-prefetch operand** so the K/V
BlockSpec index maps can translate logical tile → physical block before
the pipeline issues the fetch:

* grid is (B, KV, n_blocks_per_slot); the KV axis walks KV heads and the
  in-kernel loop covers the head's whole GQA query group from one fetched
  K/V block (same discipline as the ring kernel — no ``jnp.repeat``).
* per-slot valid lengths are the second scalar-prefetch operand. Tiles at
  or past a slot's last live block are *clamped onto the last live block*
  by the index map — an unchanged physical block id means the Pallas
  pipeline skips the HBM fetch — and the kernel body is predicated with
  ``pl.when`` so the FLOPs are skipped too: a slot L tokens in pays for
  cdiv(L, block_size) block fetches, not n_blocks_per_slot.
* dead table entries (freed blocks, idle slots parked on the trash block)
  are never dereferenced beyond the clamp, so a stale id costs nothing.

The prefill kernel is deliberately shape-generic in S: the serve engine
reuses it at S = spec_k + 1 as the speculative-decoding verify pass
(q_off = resident length, one Q tile covering the current token plus the
n-gram draft), so the same per-slot-offset streaming that amortises
chunked prefill also scores k draft positions for one weight pass.

Same numerics discipline as every kernel in this repo: f32 on the MXU via
``preferred_element_type``, finite ``MASK_VALUE`` masking (never -inf),
online softmax with (m, l, acc) VMEM scratch. The pure-jnp oracle is
``ref.py``; ``ops.py`` dispatches backends and gathers-then-attends on
``xla``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.flash_attention import MASK_VALUE


def _paged_decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale, n_b, block_size):
    b = pl.program_id(0)
    ib = pl.program_id(2)
    kv_len = lens_ref[b]                                 # valid cells, slot b

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dynamic block skip: the guard kills the FLOPs for logical blocks past
    # the slot's live prefix; the DMA for those blocks is killed by the
    # index maps in `paged_decode_fwd`, which clamp them onto the last
    # live physical block (unchanged block index => no fetch).
    @pl.when(ib * block_size < kv_len)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (group, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bs, hd)
        s = jax.lax.dot_general(                         # (group, bs)
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        kpos = ib * block_size + jax.lax.broadcasted_iota(
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

    @pl.when(ib == n_b - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _paged_prefill_kernel(tables_ref, qoff_ref, lens_ref, q_ref, k_ref,
                          v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale,
                          n_b, block_size, block_q, group):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ib = pl.program_id(3)
    kv_len = lens_ref[b]                     # valid pool cells, slot b
    q_off = qoff_ref[b]                      # abs position of query row 0

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dynamic skip on BOTH the live-length side (blocks past the slot's
    # resident cells) and the causal side (blocks entirely after this Q
    # tile's last absolute position, per-slot via q_off); the DMA for the
    # same blocks is killed by `kv_map` in `paged_prefill_fwd`.
    @pl.when((ib * block_size < kv_len)
             & (ib * block_size <= q_off + (iq + 1) * block_q - 1))
    def _tile():
        k = k_ref[0, 0].astype(jnp.float32)                  # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        kpos = ib * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_size), 1)
        qpos = q_off + iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_size), 0)
        mask = (kpos <= qpos) & (kpos < kv_len)
        for g in range(group):               # unrolled: one fetched K/V
            # block serves the KV head's whole GQA query group
            q = q_ref[0, g].astype(jnp.float32) * scale      # (bq, hd)
            s = jax.lax.dot_general(                         # (bq, bs)
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, MASK_VALUE)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_curr = jnp.max(s, axis=-1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_curr)
            p = jnp.exp(s - m_next)
            alpha = jnp.exp(m_prev - m_next)
            m_ref[g] = m_next
            l_ref[g] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)

    @pl.when(ib == n_b - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)     # dry rows (kv_len == 0,
        # e.g. a non-admitted slot) emit exact zeros, like the oracle
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_prefill_fwd(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      tables: jax.Array, q_off: jax.Array,
                      kv_len: jax.Array, *, scale: float,
                      block_q: int = 128, interpret: bool = False):
    """Chunked-prefill attention through a block table with per-slot
    query offsets.

    q (B, H, Sq, hd) kernel layout with ``Sq % block_q == 0`` — the
    current chunk's queries, row r of slot b at absolute position
    ``q_off[b] + r``; k_pool/v_pool (N+1, KV, block_size, hd) with the
    chunk's own K/V **already committed** (commit-then-attend); tables
    (B, n_blocks_per_slot) int32; kv_len (B,) int32 valid cells per slot
    (adopted prefix + every committed chunk including this one). Each Q
    tile streams the slot's pool blocks with an online softmax, masked
    per-element by ``kpos <= q_off[b] + row`` — chunk N attends to the
    committed blocks of chunks 0..N-1 plus its own causal prefix without
    ever materialising the gather-then-concat dense cache.

    All three host arrays are scalar-prefetch operands: the K/V index
    maps clamp blocks past the slot's live prefix *or* past the Q tile's
    per-slot causal horizon onto the last useful block (unchanged block
    index ⇒ the pipeline skips the fetch), and the kernel body predicates
    the FLOPs the same way.
    """
    B, H, Sq, hd = q.shape
    KV, bs = k_pool.shape[1], k_pool.shape[2]
    group = H // KV
    n_b = tables.shape[1]
    assert Sq % block_q == 0, (Sq, block_q)
    n_q = Sq // block_q
    kernel = functools.partial(_paged_prefill_kernel, scale=scale, n_b=n_b,
                               block_size=bs, block_q=block_q, group=group)

    def q_map(b, h, iq, ib, tables, q_off, lens):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, ib, tables, q_off, lens):
        last_kv = jnp.maximum((lens[b] + bs - 1) // bs - 1, 0)
        last_causal = (q_off[b] + (iq + 1) * block_q - 1) // bs
        phys = tables[b, jnp.minimum(ib, jnp.minimum(last_kv, last_causal))]
        return (phys, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, n_q, n_b),
        in_specs=[
            pl.BlockSpec((1, group, block_q, hd), q_map),
            pl.BlockSpec((1, 1, bs, hd), kv_map),
            pl.BlockSpec((1, 1, bs, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, group, block_q, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((group, block_q, 1), jnp.float32),
            pltpu.VMEM((group, block_q, 1), jnp.float32),
            pltpu.VMEM((group, block_q, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        name="paged_prefill",
        interpret=interpret,
    )(tables.astype(jnp.int32), q_off.astype(jnp.int32),
      kv_len.astype(jnp.int32), q, k_pool, v_pool)


def paged_decode_fwd(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     tables: jax.Array, kv_len: jax.Array, *, scale: float,
                     interpret: bool = False):
    """Single-query attention through a block table.

    q (B, KV, group, hd) — query head ``h * group + g`` at ``[:, h, g]``;
    k_pool, v_pool (N+1, KV, block_size, hd) — the physical block pools in
    storage layout (last block = trash, never attended); tables
    (B, n_blocks_per_slot) int32 logical→physical block ids; kv_len (B,)
    int32 valid cells per slot. Returns o (B, KV, group, hd) q.dtype.

    ``tables`` and ``kv_len`` are scalar-prefetch operands: the K/V index
    maps read them to aim each grid step's DMA at the right physical
    block, and to clamp logical blocks past ``cdiv(kv_len, bs)`` onto the
    last live one so the pipeline never fetches dead blocks.
    """
    B, KV, group, hd = q.shape
    bs = k_pool.shape[2]
    n_b = tables.shape[1]
    kernel = functools.partial(_paged_decode_kernel, scale=scale, n_b=n_b,
                               block_size=bs)

    def kv_map(b, h, ib, tables, lens):
        last = jnp.maximum((lens[b] + bs - 1) // bs - 1, 0)
        phys = tables[b, jnp.minimum(ib, last)]
        return (phys, h, 0, 0)

    q_spec = pl.BlockSpec((1, 1, group, hd),
                          lambda b, h, ib, tables, lens: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_b),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, 1, bs, hd), kv_map),
            pl.BlockSpec((1, 1, bs, hd), kv_map),
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
        name="paged_decode",
        interpret=interpret,
    )(tables.astype(jnp.int32), kv_len.astype(jnp.int32), q, k_pool, v_pool)
