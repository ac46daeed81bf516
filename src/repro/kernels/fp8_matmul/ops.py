"""Jit'd public wrappers for the fp8 matmul kernels.

Backend dispatch follows kernels/switchback: ``xla`` runs the pure-jnp
oracle in ``ref.py``, ``pallas``/``pallas_interpret`` run the tiled kernels
with shape padding to block multiples.

Bit-parity contract: f32 accumulation is order-sensitive, so the SAME
``block_k`` (chosen once here) is handed to both the kernel and the oracle —
the oracle replays the kernel's k-blocked accumulation, making
``pallas_interpret`` bit-identical to ``xla`` (tests/test_fp8_backends.py).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels.fp8_matmul import fp8_matmul as _k
from repro.kernels.fp8_matmul import ref as _ref
from repro.kernels.switchback.ops import (  # same VMEM heuristics: int8 and
    _pad_to, choose_blocks, quant_block_rows)  # fp8 operands are 1 byte

Backend = Literal["xla", "pallas", "pallas_interpret"]
BACKENDS: tuple[str, ...] = ("xla", "pallas", "pallas_interpret")

FORMATS = _ref.FORMATS
FMT_DTYPE = _ref.FMT_DTYPE


@functools.partial(jax.jit, static_argnames=("fmt", "backend"))
def row_quantize(x: jax.Array, *, fmt: str = "e4m3",
                 backend: Backend = "xla"):
    """x (B, K) -> (q fp8 (B, K), state f32 (B, 1))."""
    if backend == "xla":
        return _ref.row_quantize(x, fmt=fmt)
    interp = backend == "pallas_interpret"
    B = x.shape[0]
    bb = 256 if B >= 256 else B
    xp = _pad_to(x, (bb, 1))   # zero rows: scale floors at 1e-12, sliced off
    q, s = _k.row_quantize(xp, fmt=fmt, block_b=bb, interpret=interp)
    return q[:B], s[:B]


@functools.partial(jax.jit, static_argnames=("fmt", "backend"))
def tensor_quantize(x: jax.Array, *, fmt: str = "e4m3",
                    backend: Backend = "xla"):
    """x (R, C) -> (q fp8 (R, C), state f32 (1, 1))."""
    if backend == "xla":
        return _ref.tensor_quantize(x, fmt=fmt)
    interp = backend == "pallas_interpret"
    R = x.shape[0]
    # the cast's f32 grid rounding needs ~10x its input block in VMEM
    br = quant_block_rows(x, block_bytes=1024 * 1024)
    xp = _pad_to(x, (br, 1))   # zero rows don't change the absmax
    q, s = _k.tensor_quantize(xp, fmt=fmt, block_rows=br, interpret=interp)
    return q[:R], s


@functools.partial(jax.jit,
                   static_argnames=("fmt", "block_rows", "block_cols",
                                    "backend"))
def block_quantize(x: jax.Array, *, fmt: str = "e4m3",
                   block_rows: int = 128, block_cols: int = 128,
                   backend: Backend = "xla"):
    """Blockwise fp8 quantization: one scale per (block_rows × block_cols)
    tile. x (R, C) -> (q fp8 (R, C), state f32 (⌈R/br⌉, ⌈C/bc⌉))."""
    if backend == "xla":
        return _ref.block_quantize(x, fmt=fmt, block_rows=block_rows,
                                   block_cols=block_cols)
    interp = backend == "pallas_interpret"
    R, C = x.shape
    br = min(block_rows, R)
    bc = min(block_cols, C)
    xp = _pad_to(x, (br, bc))  # zero pads don't change a block's absmax
    q, s = _k.block_quantize(xp, fmt=fmt, block_rows=br, block_cols=bc,
                             interpret=interp)
    return q[:R, :C], s


def fallback_mask(state: jax.Array, ratio: float) -> jax.Array:
    """Outlier-block mask: 1.0 where a block's absmax exceeds ``ratio`` ×
    the median block absmax. Plain jnp on the tiny (nbr, nbc) state —
    backend-free by construction (single shared implementation)."""
    return _ref.fallback_mask(state, ratio)


@functools.partial(jax.jit, static_argnames=("transpose_w", "out_dtype",
                                             "backend"))
def fp8_matmul_dequant(x_q, w_q, row_scale, *, transpose_w: bool = False,
                       out_dtype=jnp.bfloat16, backend: Backend = "xla"):
    """y = row_scale ⊙ (x_q · w_q[ᵀ]) with f32 accumulation.

    ``row_scale`` is (B, 1) f32 and already folds the weight scale
    (s_x · s_w), so the epilogue is one broadcast multiply.
    """
    B, K = x_q.shape
    M = w_q.shape[0] if transpose_w else w_q.shape[1]
    bb, bk, bm = choose_blocks(B, K, M)
    bk = min(bk, K)            # identical tiling on both paths: XLA's gemm
    if backend == "xla":       # is only shape-reproducible, so the oracle
        return _ref.fp8_matmul_dequant(  # replays the full (i, j, k) tiles
            x_q, w_q, row_scale, transpose_w=transpose_w,
            out_dtype=out_dtype, block_b=bb, block_m=bm, block_k=bk)
    interp = backend == "pallas_interpret"
    xp = _pad_to(x_q, (bb, bk))
    wp = _pad_to(w_q, (bm, bk) if transpose_w else (bk, bm))
    sp = _pad_to(row_scale, (bb, 1))
    y = _k.fp8_matmul_dequant(
        xp, wp, sp, transpose_w=transpose_w, out_dtype=out_dtype,
        block_b=bb, block_m=bm, block_k=bk, interpret=interp)
    return y[:B, :M]


@functools.partial(jax.jit,
                   static_argnames=("fmt", "block_rows", "block_cols",
                                    "transpose_w", "out_dtype", "backend"))
def fp8_mixed_matmul(x, w_q, s_w, *, fmt: str = "e4m3",
                     block_rows: int = 128, block_cols: int = 128,
                     fallback_ratio: float = 8.0,
                     transpose_w: bool = False, out_dtype=jnp.bfloat16,
                     backend: Backend = "xla"):
    """Fused blockwise-quantize → mixed fp8/bf16 matmul with dynamic
    fallback: x is quantized in (block_rows × block_cols) tiles, tiles whose
    absmax exceeds ``fallback_ratio`` × the median run as bf16 dots against
    the dequantized weight, the rest as scaled fp8 dots.

    x: (B, K) high precision. w_q: (K, M) fp8 ((M, K) if transpose_w) with
    tensor scale s_w (1, 1). The quantization tiles ARE the matmul (i, k)
    tiles, so the mask costs one (1, 1) operand per grid step.
    """
    B, K = x.shape
    M = w_q.shape[0] if transpose_w else w_q.shape[1]
    br = min(block_rows, B)
    bk = min(block_cols, K)
    bm = min(256, M)
    if backend == "xla":
        x_q, s_blk = _ref.block_quantize(x, fmt=fmt, block_rows=br,
                                         block_cols=bk)
        fb = _ref.fallback_mask(s_blk, fallback_ratio)
        return _ref.fp8_mixed_matmul_blocks(
            x, x_q, s_blk, fb, w_q, s_w, transpose_w=transpose_w,
            out_dtype=out_dtype, block_rows=br, block_m=bm, block_k=bk)
    interp = backend == "pallas_interpret"
    xp = _pad_to(x, (br, bk))
    xq, s_blk = _k.block_quantize(xp, fmt=fmt, block_rows=br, block_cols=bk,
                                  interpret=interp)
    fb = _ref.fallback_mask(s_blk, fallback_ratio)
    wp = _pad_to(w_q, (bm, bk) if transpose_w else (bk, bm))
    y = _k.fp8_mixed_matmul(
        xp, xq, s_blk, fb, wp, s_w.reshape(1, 1), transpose_w=transpose_w,
        out_dtype=out_dtype, block_b=br, block_m=bm, block_k=bk,
        interpret=interp)
    return y[:B, :M]
