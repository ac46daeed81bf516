"""Jit'd public wrappers for the flash-attention kernels.

Handles: backend dispatch (pallas TPU / pallas interpret / pure-XLA ref),
model→kernel layout moves, shape padding to tile multiples (pad keys are
masked in-kernel via the static ``kv_valid``; pad queries are sliced off),
the static tile choice from the call's sequence lengths, and the
``custom_vjp`` that wires the recompute-style backward in: the one-pass
kernel where its resident dq fits VMEM, else the split pair (DESIGN.md
§9).

Model-layout contract (what models/attention.py speaks): q (B, Sq, H, dqk),
k (B, Sk, KV, dqk), v (B, Sk, KV, dv) with H a multiple of KV (GQA); the
output is (B, Sq, H, dv) in q's dtype. The value width ``dv`` may differ
from the query/key width ``dqk`` (latent attention: 192 and 128); the
softmax scale is 1/sqrt(dqk).
"""
from __future__ import annotations

import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _k
from repro.kernels.flash_attention import ref as _ref

Backend = Literal["xla", "pallas", "pallas_interpret"]
BACKENDS: tuple[str, ...] = ("xla", "pallas", "pallas_interpret")

# Largest tiles of the train kernels (fwd, bwd) per sequence axis.
# Every grid step costs a fixed ~0.35 µs on a v5e whatever its tile, so
# tiles are as large as the default scoped VMEM allows; among those the
# chip sweep of PERF.md §5 found 512 query by 1024 key rows fastest at
# smollm-360m's widths (a 1024-row Q tile overflows VMEM in the split
# backward's flash_bwd_dq).
TILE_CAP_Q = 512
TILE_CAP_K = 1024
# The one-pass backward (flash_bwd) holds a KV head's dq over the whole
# query axis in VMEM; it runs where that block, double-buffered, fits this
# budget (smollm-360m's 3 x 4096 rows of 64: 12 MiB; Moonlight's 8192 rows
# of 192: 16 MiB; CLIP's: under 0.2 MiB), else the split flash_bwd_dq +
# flash_bwd_dkv pair runs (a 3-head group of 64 at 32k tokens: 96 MiB).
FUSED_BWD_DQ_BYTES = 32 * 1024 * 1024
# A grid step holds its KV head's whole query group: the q, do and o
# blocks and the f32 accumulators scale with group x block_q x hd (hd
# padded to the 128 lanes). The Q tile shrinks to keep that product at
# what 512 rows of smollm-360m's 3 heads of 64 hold.
Q_GROUP_ELEMS = TILE_CAP_Q * 3 * 128
# Latent attention (Moonlight-16B-A3B: B 2, S 8192, 16 heads, q/k 192 and
# v 128 wide, no GQA) takes 512 x 1024 on a v5e: 192 lanes pad to 256, and
# one head's 512 x 256 Q block is under the group cap, so the Q tile stays
# at TILE_CAP_Q; the grid is 2 x 16 x 16 x 8 steps per kernel call
# (tests/test_tpu_compile.py compiles it).

# Decode tile over the ring cache (choose_decode_block)
DECODE_BLOCK = 128


def seq_tile(S: int, cap: int) -> int:
    """Rows per tile of a sequence axis of ``S`` rows: ``n = ceil(S/cap)``
    equal tiles, each rounded up to the 8 sublanes — one tile with under
    8 pad rows up to ``cap`` (129 -> 136, 77 -> 80), else ``n`` tiles
    (4096 at cap 512 -> 8 x 512)."""
    n = max(1, -(-S // cap))
    rows = -(-S // n)
    return -(-rows // 8) * 8


def choose_attn_blocks(Sq: int, Sk: int, block_q: int = 0,
                       block_k: int = 0, *, group: int = 1,
                       hd: int = 128) -> tuple[int, int]:
    """Static (block_q, block_k) of the train kernels: the configured size
    when given (>0), else :func:`seq_tile` of each axis, the Q cap cut to
    what a GQA ``group`` of query heads of width ``hd`` fits in VMEM."""
    lanes = -(-hd // 128) * 128
    cap_q = min(TILE_CAP_Q, max(8, Q_GROUP_ELEMS // (group * lanes) // 8 * 8))
    return block_q or seq_tile(Sq, cap_q), block_k or seq_tile(Sk, TILE_CAP_K)


def choose_decode_block(S: int, block_k: int = 0) -> int:
    """KV tile of the decode kernel over a ring cache of ``S`` cells. It
    must divide S (padding the cache would copy it every step) and be a
    multiple of 8 sublanes unless it is all of S: the configured size (or
    DECODE_BLOCK) when it qualifies, else the largest qualifying divisor
    below it — S=96, block_k=128 -> 96; S=200 -> 40."""
    bk = min(block_k or DECODE_BLOCK, S)
    while S % bk or (bk % 8 and bk != S):
        bk -= 1
        if bk == 0:
            bk = S
    return bk


def _blocks(q, k, v, block_q: int, block_k: int) -> tuple[int, int]:
    """choose_attn_blocks for model-layout operands, sized by the wider of
    the query/key and value widths."""
    return choose_attn_blocks(q.shape[1], k.shape[1], block_q, block_k,
                              group=q.shape[2] // k.shape[2],
                              hd=max(q.shape[3], v.shape[3]))


def _pad_seq(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _to_kernel(x: jax.Array) -> jax.Array:
    """(B, S, H, hd) -> (B, H, S, hd)."""
    return jnp.transpose(x, (0, 2, 1, 3))


def _check(q, k, v):
    """Shapes of one call: k and v share batch, keys and heads; q and k
    share batch and width (dqk); v's width (dv) is its own. Returns the
    softmax scale 1/sqrt(dqk)."""
    B, Sq, H, dqk = q.shape
    KV = k.shape[2]
    if (k.shape[:3] != v.shape[:3] or k.shape[0] != B
            or k.shape[3] != dqk):
        raise ValueError(f"flash attention shapes q {q.shape}, k {k.shape}, "
                         f"v {v.shape}: k and v must share batch, keys and "
                         f"heads, q and k batch and width")
    if H % KV:
        raise ValueError(f"GQA needs H % KV == 0, got {H} % {KV}")
    return 1.0 / math.sqrt(dqk)


@functools.partial(jax.jit,
                   static_argnames=("causal", "backend", "block_q", "block_k"))
def flash_fwd_lse(q, k, v, *, causal: bool, backend: Backend = "xla",
                  block_q: int = 0, block_k: int = 0):
    """Raw forward: (o (B, Sq, H, dv) in q.dtype, lse (B, H, Sq) f32) for
    q (B, Sq, H, dqk), k (B, Sk, KV, dqk), v (B, Sk, KV, dv).

    The non-differentiable entry point (tests, benchmarks, inference
    paths); training goes through :func:`flash_attention`.
    """
    scale = _check(q, k, v)
    Sq, Sk = q.shape[1], k.shape[1]
    if backend == "xla":
        o, lse = _ref.mha_fwd(_to_kernel(q), _to_kernel(k), _to_kernel(v),
                              causal=causal, kv_valid=Sk, scale=scale)
        return _to_kernel(o), lse
    bq, bk = _blocks(q, k, v, block_q, block_k)
    qk = _pad_seq(_to_kernel(q), 2, bq)
    kk = _pad_seq(_to_kernel(k), 2, bk)
    vk = _pad_seq(_to_kernel(v), 2, bk)
    o, lse = _k.flash_fwd(qk, kk, vk, causal=causal, kv_valid=Sk,
                          scale=scale, block_q=bq, block_k=bk,
                          interpret=(backend == "pallas_interpret"))
    return _to_kernel(o[:, :, :Sq]), lse[:, :, :Sq, 0]


def fused_bwd(group: int, Sq: int, dqk: int) -> bool:
    """Whether the backward of a call with a GQA ``group``, ``Sq`` padded
    query rows and query/key width ``dqk`` runs the one-pass kernel: its
    resident dq fits FUSED_BWD_DQ_BYTES."""
    return _k.resident_dq_bytes(group, Sq, dqk) <= FUSED_BWD_DQ_BYTES


def _bwd_impl(causal, backend, block_q, block_k, res, do):
    q, k, v, o, lse = res
    scale = _check(q, k, v)
    Sq, Sk = q.shape[1], k.shape[1]
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)                                    # (B, Sq, H)
    di = jnp.transpose(di, (0, 2, 1))                        # (B, H, Sq)
    if backend == "xla":
        dq, dk, dv = _ref.mha_bwd(
            _to_kernel(q), _to_kernel(k), _to_kernel(v), _to_kernel(o),
            lse, _to_kernel(do), causal=causal, kv_valid=Sk, scale=scale)
    else:
        bq, bk = _blocks(q, k, v, block_q, block_k)
        interp = backend == "pallas_interpret"
        qp = _pad_seq(_to_kernel(q), 2, bq)
        kp = _pad_seq(_to_kernel(k), 2, bk)
        vp = _pad_seq(_to_kernel(v), 2, bk)
        # pad queries carry zero `do`, so their (finite) rebuilt weights
        # contribute exactly zero to dk/dv; pad lse/di of 0 keep exp finite
        dop = _pad_seq(_to_kernel(do), 2, bq)
        lsep = _pad_seq(lse, 2, bq)[..., None]
        dip = _pad_seq(di, 2, bq)[..., None]
        args = (qp, kp, vp, dop, lsep, dip)
        kw = dict(causal=causal, kv_valid=Sk, scale=scale, block_q=bq,
                  block_k=bk, interpret=interp)
        if fused_bwd(qp.shape[1] // kp.shape[1], qp.shape[2], qp.shape[3]):
            dq, dk, dv = _k.flash_bwd(*args, **kw)
        else:
            dq = _k.flash_bwd_dq(*args, **kw)
            dk, dv = _k.flash_bwd_dkv(*args, **kw)
        dq, dk, dv = dq[:, :, :Sq], dk[:, :, :Sk], dv[:, :, :Sk]
    return (_to_kernel(dq).astype(q.dtype), _to_kernel(dk).astype(k.dtype),
            _to_kernel(dv).astype(v.dtype))


@functools.lru_cache(maxsize=None)
def make_flash_attention(causal: bool, backend: Backend = "xla",
                         block_q: int = 0, block_k: int = 0):
    """One differentiable flash-attention function per static config —
    lru-cached so jit tracing sees stable function identities (the same
    discipline as core/switchback.make_switchback_matmul)."""
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")

    @jax.custom_vjp
    def attn(q, k, v):
        o, _ = flash_fwd_lse(q, k, v, causal=causal, backend=backend,
                             block_q=block_q, block_k=block_k)
        return o

    def fwd(q, k, v):
        o, lse = flash_fwd_lse(q, k, v, causal=causal, backend=backend,
                               block_q=block_q, block_k=block_k)
        return o, (q, k, v, o, lse)

    attn.defvjp(fwd, functools.partial(_bwd_impl, causal, backend,
                                       block_q, block_k))
    return attn


def flash_attention(q, k, v, *, causal: bool, backend: Backend = "xla",
                    block_q: int = 0, block_k: int = 0):
    """Differentiable fused attention, model layout.

    q (B, Sq, H, dqk); k (B, Sk, KV, dqk); v (B, Sk, KV, dv) — KV heads
    stay folded (the kernel maps query head h onto KV head h // group; no
    jnp.repeat). Returns (B, Sq, H, dv). Gradients flow to q, k, v via the
    recompute-style backward kernels.
    """
    return make_flash_attention(causal, backend, block_q, block_k)(q, k, v)


@functools.partial(jax.jit, static_argnames=("backend", "block_k"))
def decode_attention(q, k, v, kv_len, *, backend: Backend = "xla",
                     block_k: int = 0):
    """Single-query attention over the (ring) KV cache.

    q (B, 1, H, hd); k, v (B, KV, S_max, hd) in the cache's storage layout;
    kv_len (B,) int32 — valid cells per slot (``min(length + 1, S_max)``,
    so ring-wrapped slots attend over the whole window). Returns
    (B, 1, H, hd). One width ``hd`` serves queries, keys and values here:
    the decode kernel does not take ``dv != dqk`` (no latent cache is
    served yet). Tiles beyond a slot's length are skipped dynamically on
    the pallas backends.
    """
    B, one, H, hd = q.shape
    assert one == 1, q.shape
    KV, S = v.shape[1], v.shape[2]
    assert H % KV == 0, (H, KV)
    scale = 1.0 / math.sqrt(hd)
    q3 = q[:, 0]                                             # (B, H, hd)
    kv_len = kv_len.reshape(B, 1).astype(jnp.int32)
    if backend == "xla":
        return _ref.decode_fwd(q3, k, v, kv_len, scale=scale)[:, None]
    bk = choose_decode_block(S, block_k)
    o = _k.decode_fwd(q3.reshape(B, KV, H // KV, hd), k, v, kv_len[:, 0],
                      scale=scale, block_k=bk,
                      interpret=(backend == "pallas_interpret"))
    return o.reshape(B, 1, H, hd)
