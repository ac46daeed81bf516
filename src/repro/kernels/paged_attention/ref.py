"""Pure-jnp oracles for paged attention (decode + chunked prefill).

Gathers each slot's logical blocks into a dense (B, n_blocks·bs, KV, hd)
cache through the block table, then runs the masked softmax dense —
materialising exactly what the paged kernels stream block by block.
These are both the ``backend="xla"`` implementations behind ``ops.py``
and the parity oracles the interpret-mode tests compare the kernels
against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ref as _flash_ref
from repro.kernels.flash_attention.flash_attention import MASK_VALUE


def gather_blocks(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """(N+1, KV, bs, hd) pool + (B, nb) int32 table -> (B, nb·bs, KV, hd)
    dense cache in logical order (cell j·bs+o of slot b is the pool cell
    (tables[b, j], :, o)). Out-of-range ids clamp (jax gather semantics)."""
    B, nb = tables.shape
    _, KV, bs, hd = pool.shape
    g = jnp.swapaxes(pool[tables], 2, 3)             # (B, nb, bs, KV, hd)
    return g.reshape(B, nb * bs, KV, hd)


def paged_decode_fwd(q, k_pool, v_pool, tables, kv_len, *, scale: float):
    """q (B, H, hd); pools (N+1, KV, bs, hd); tables (B, nb) int32;
    kv_len (B,) int32. Returns o (B, H, hd) q.dtype — the gather-then-
    dense re-attend the paged kernel replaces."""
    k = jnp.swapaxes(gather_blocks(k_pool, tables), 1, 2)   # (B, KV, L, hd)
    v = jnp.swapaxes(gather_blocks(v_pool, tables), 1, 2)
    return _flash_ref.decode_fwd(q, k, v, kv_len.reshape(-1, 1),
                                 scale=scale)


def paged_prefill_fwd(q, k_pool, v_pool, tables, q_off, kv_len, *,
                      scale: float):
    """Chunked-prefill oracle with per-slot query offsets.

    q (B, Sq, H, hd) *model* layout — chunk queries, row r of slot b at
    absolute position ``q_off[b] + r``; pools (N+1, KV, bs, hd) with the
    chunk's K/V already committed; tables (B, nb) int32; kv_len (B,)
    int32 valid cells. Returns (B, Sq, H, hd) q.dtype. Rows with no live
    key (``kv_len == 0`` — non-admitted slots) emit exact zeros, matching
    the kernel's dry-row convention.
    """
    B, Sq, H, hd = q.shape
    KV = k_pool.shape[1]
    k = gather_blocks(k_pool, tables)                # (B, L, KV, hd)
    v = gather_blocks(v_pool, tables)
    kx = jnp.repeat(k, H // KV, axis=2)
    vx = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   kx.astype(jnp.float32))
    L = k.shape[1]
    qpos = q_off[:, None] + jnp.arange(Sq)[None, :]          # (B, Sq)
    kpos = jnp.arange(L)[None, None, :]                      # (1, 1, L)
    live = (kpos <= qpos[..., None]) & (kpos < kv_len[:, None, None])
    s = jnp.where(live[:, None], s, MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(live[:, None], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    a = p / jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, vx.astype(jnp.float32))
    return o.astype(q.dtype)
