"""Jit'd public wrappers + backend dispatch for paged attention.

Model-layout contract (what models/attention.py speaks): decode q
(B, 1, H, hd), prefill q (B, S, H, hd); k_pool/v_pool (N+1, KV,
block_size, hd) physical block pools; tables (B, n_blocks_per_slot) int32;
kv_len (B,) valid cells per slot; prefill additionally takes q_off (B,)
per-slot absolute offsets of query row 0 (the chunk cursor). On ``xla``
the path is gather-then-dense (``ref``); on ``pallas``/
``pallas_interpret`` the fused kernels stream K/V blocks through the
block-table scalar-prefetch index maps — same one-knob dispatch
discipline as kernels/flash_attention/ops.py.
"""
from __future__ import annotations

import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import paged_attention as _k
from repro.kernels.paged_attention import ref as _ref

Backend = Literal["xla", "pallas", "pallas_interpret"]
BACKENDS: tuple[str, ...] = ("xla", "pallas", "pallas_interpret")


@functools.partial(jax.jit, static_argnames=("backend",))
def paged_decode_attention(q, k_pool, v_pool, tables, kv_len, *,
                           backend: Backend = "xla"):
    """Single-query attention over the paged KV cache.

    q (B, 1, H, hd); k_pool/v_pool (N+1, KV, block_size, hd) in the pool's
    storage layout (block N is the engine's trash block); tables (B, nb)
    int32 logical→physical block ids; kv_len (B,) int32 — valid cells per
    slot. Returns (B, 1, H, hd). On the pallas backends, blocks past a
    slot's live prefix are skipped dynamically (FLOPs *and* DMA).
    """
    B, one, H, hd = q.shape
    assert one == 1, q.shape
    KV = k_pool.shape[1]
    assert H % KV == 0, (H, KV)
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    scale = 1.0 / math.sqrt(hd)
    q3 = q[:, 0]                                         # (B, H, hd)
    tables = tables.astype(jnp.int32)
    kv_len = kv_len.astype(jnp.int32)
    if backend == "xla":
        return _ref.paged_decode_fwd(q3, k_pool, v_pool, tables, kv_len,
                                     scale=scale)[:, None]
    o = _k.paged_decode_fwd(q3.reshape(B, KV, H // KV, hd), k_pool, v_pool,
                            tables, kv_len, scale=scale,
                            interpret=(backend == "pallas_interpret"))
    return o.reshape(B, 1, H, hd)


@functools.partial(jax.jit, static_argnames=("backend",))
def paged_prefill_attention(q, k_pool, v_pool, tables, q_off, kv_len, *,
                            backend: Backend = "xla"):
    """Chunked-prefill attention over the paged KV cache.

    q (B, S, H, hd) — the current chunk's queries, row r of slot b at
    absolute position ``q_off[b] + r``, with the chunk's own K/V already
    committed to the pools (commit-then-attend); tables (B, nb) int32;
    q_off/kv_len (B,) int32. Returns (B, S, H, hd). On the pallas
    backends the kernel streams each slot's live blocks once per Q tile
    (per-slot causal + length skip on FLOPs *and* DMA); on ``xla`` it is
    the gather-then-dense oracle.

    Besides chunked prefill this is also the speculative-decoding verify
    primitive: the engine scores k drafted tokens + 1 in one call with
    S = spec_k + 1 and ``q_off`` = the slot's resident length, reading
    all S logit rows instead of the last. Row r then reproduces exactly
    what a plain decode at absolute position ``q_off + r`` would compute
    (same committed pool cells, same causal window), which is what makes
    greedy accept/reject exact rather than approximate.
    """
    B, S, H, hd = q.shape
    KV = k_pool.shape[1]
    assert H % KV == 0, (H, KV)
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    scale = 1.0 / math.sqrt(hd)
    tables = tables.astype(jnp.int32)
    q_off = q_off.astype(jnp.int32)
    kv_len = kv_len.astype(jnp.int32)
    if backend == "xla":
        return _ref.paged_prefill_fwd(q, k_pool, v_pool, tables, q_off,
                                      kv_len, scale=scale)
    # kernel layout + Q-tile padding (pad rows compute garbage that the
    # slice below drops; they can't NaN — key 0 is live whenever kv_len>0)
    block_q = min(128, max(8, 1 << (S - 1).bit_length()))
    S_pad = math.ceil(S / block_q) * block_q
    qk = jnp.moveaxis(q, 1, 2)                       # (B, H, S, hd)
    if S_pad != S:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
    o = _k.paged_prefill_fwd(qk, k_pool, v_pool, tables, q_off, kv_len,
                             scale=scale, block_q=block_q,
                             interpret=(backend == "pallas_interpret"))
    return jnp.moveaxis(o[:, :, :S], 2, 1)
