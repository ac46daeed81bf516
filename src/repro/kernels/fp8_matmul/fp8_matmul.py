"""Pallas TPU kernels for real fp8 training matmuls (E4M3 fwd / E5M2 dgrad).

Layout mirrors kernels/switchback (DESIGN.md §3): HBM→VMEM staging via
`pallas_call` grid + BlockSpec, grid order (i, j, k) with K innermost so the
f32 VMEM scratch accumulator lives across the contraction, dequantize fused
into the matmul epilogue. Differences from the int8 kernels:

* Quantized storage is a native fp8 dtype (`float8_e4m3fn` / `float8_e5m2`),
  rounded by `core.quantization.fp8_grid_round` — bit ops on the f32
  representation only, so it lowers through Mosaic and is bit-identical to
  the `core/fp8.py` frexp oracle (pinned by tests).
* Scales are explicit Scalify-style: q = fp8(x / s), so dequant is a single
  f32 multiply by s_x · s_w (no 127² folding).
* Accumulation is f32 (fp8 operands widen before the dot). f32 adds are
  order-sensitive, so `ref.py` replays the identical k-blocking — the ops
  layer hands both paths the same `block_k`.
* The mixed kernel carries a per-(i, k)-tile scale and fallback bit as
  (1, 1) BlockSpec operands: fallback tiles run a bf16 dot against the
  dequantized fp8 weight (`pl.when` on the bit — the skipped dot costs
  nothing on hardware), clean tiles run the fp8 dot. This is the dynamic
  block-level fallback contraction (DESIGN.md §13).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fp8_matmul import ref as _ref

FMT_DTYPE = _ref.FMT_DTYPE


# ---------------------------------------------------------------------------
# row-wise quantize: x (B, K) -> q (B, K) fp8, state (B, 1) f32
# ---------------------------------------------------------------------------

def _row_quantize_kernel(x_ref, q_ref, s_ref, *, fmt: str):
    q, am = _ref.rowwise_fp8_math(x_ref[...], fmt)
    q_ref[...] = q
    s_ref[...] = am


def row_quantize(x: jax.Array, *, fmt: str = "e4m3", block_b: int = 256,
                 interpret: bool = False):
    """Row-wise fp8 quantization: each grid step owns `block_b` full rows so
    the row absmax reduction is local to one VMEM block."""
    B, K = x.shape
    block_b = min(block_b, B)
    grid = (pl.cdiv(B, block_b),)
    return pl.pallas_call(
        functools.partial(_row_quantize_kernel, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, K), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_b, K), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K), FMT_DTYPE[fmt]),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        name="fp8_row_quantize",
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# tensor-wise quantize (two-pass absmax then cast, as in switchback)
# ---------------------------------------------------------------------------

def _absmax_kernel(x_ref, o_ref):
    # the running max is a (1, 1) vector, never a scalar: Mosaic cannot
    # store scalars to VMEM
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros((1, 1), jnp.float32)
    m = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)), keepdims=True)
    o_ref[...] = jnp.maximum(o_ref[...], m)


def _cast_kernel(x_ref, s_ref, q_ref, *, fmt: str):
    q_ref[...] = _ref.cast_fp8_math(x_ref[...], s_ref[0, 0], fmt)


def tensor_quantize(x: jax.Array, *, fmt: str = "e4m3",
                    block_rows: int = 512, interpret: bool = False):
    """Tensor-wise fp8 quantization: grid-sequential absmax into a (1, 1)
    state, then a cast pass. The eps clamp lands between the passes so the
    returned state matches the oracle's clamped absmax bit-for-bit."""
    R, C = x.shape
    block_rows = min(block_rows, R)
    grid = (pl.cdiv(R, block_rows),)
    absmax = pl.pallas_call(
        _absmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        name="fp8_tensor_quantize",
        interpret=interpret,
    )(x)
    absmax = jnp.maximum(absmax, 1e-12)
    q = pl.pallas_call(
        functools.partial(_cast_kernel, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), FMT_DTYPE[fmt]),
        name="fp8_tensor_quantize",
        interpret=interpret,
    )(x, absmax)
    return q, absmax


# ---------------------------------------------------------------------------
# block-wise quantize: x (R, C) -> q (R, C) fp8, state (nbr, nbc) f32
# (quantization blocks == matmul tiles, so the mixed kernel reads one scale
#  and one fallback bit per grid step)
# ---------------------------------------------------------------------------

def _block_quantize_kernel(x_ref, q_ref, s_ref, *, fmt: str):
    x = x_ref[...].astype(jnp.float32)
    am = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    s_ref[0, 0] = am
    q_ref[...] = _ref.cast_fp8_math(x, am, fmt)


def block_quantize(x: jax.Array, *, fmt: str = "e4m3",
                   block_rows: int = 128, block_cols: int = 128,
                   interpret: bool = False):
    """Blockwise fp8 quantization: one scale per (block_rows × block_cols)
    tile; each grid step owns exactly one tile."""
    R, C = x.shape
    br = min(block_rows, R)
    bc = min(block_cols, C)
    grid = (pl.cdiv(R, br), pl.cdiv(C, bc))
    return pl.pallas_call(
        functools.partial(_block_quantize_kernel, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), FMT_DTYPE[fmt]),
            jax.ShapeDtypeStruct((grid[0], grid[1]), jnp.float32),
        ],
        name="fp8_block_quantize",
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# fp8 matmul + fused dequant epilogue
#   y[b, m] = row_scale[b] * sum_k x_q[b, k] * w_q[k, m]   (f32 accumulate)
# ---------------------------------------------------------------------------

def _fp8_matmul_dequant_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                               n_k: int, transpose_w: bool, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        dimension_numbers=dims, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(out_dtype)


def fp8_matmul_dequant(x_q: jax.Array, w_q: jax.Array, row_scale: jax.Array,
                       *, transpose_w: bool = False, out_dtype=jnp.bfloat16,
                       block_b: int = 256, block_m: int = 256,
                       block_k: int = 512, interpret: bool = False):
    """Tiled fp8×fp8→f32 matmul with fused dequant epilogue.

    x_q: (B, K) fp8. w_q: (K, M) fp8, or (M, K) if transpose_w (dgrad — the
    second dim of both operands contracts; no transpose materialized).
    row_scale: (B, 1) f32 — the prefolded s_x · s_w.
    """
    B, K = x_q.shape
    M = w_q.shape[0] if transpose_w else w_q.shape[1]
    block_b = min(block_b, B)
    block_m = min(block_m, M)
    block_k = min(block_k, K)
    n_k = pl.cdiv(K, block_k)
    grid = (pl.cdiv(B, block_b), pl.cdiv(M, block_m), n_k)

    if transpose_w:
        w_spec = pl.BlockSpec((block_m, block_k), lambda i, j, k: (j, k))
    else:
        w_spec = pl.BlockSpec((block_k, block_m), lambda i, j, k: (k, j))

    kernel = functools.partial(_fp8_matmul_dequant_kernel, n_k=n_k,
                               transpose_w=transpose_w, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            w_spec,
            pl.BlockSpec((block_b, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_m), jnp.float32)],
        name="fp8_matmul_dequant",
        interpret=interpret,
    )(x_q, w_q, row_scale)


# ---------------------------------------------------------------------------
# mixed-precision blocked matmul with dynamic bf16 fallback
#   clean (i, k) tiles: fp8 dot × per-tile scale; outlier tiles: bf16 dot
#   against the dequantized fp8 weight
# ---------------------------------------------------------------------------

def _fp8_mixed_matmul_kernel(x16_ref, xq_ref, s_ref, fb_ref, w_ref, sw_ref,
                             o_ref, acc_ref, *, n_k: int, transpose_w: bool,
                             out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
    fb = fb_ref[0, 0]

    @pl.when(fb == 0)
    def _fp8_tile():
        # dequantize into the LHS operand, NOT the dot output: a post-dot
        # multiply feeding the accumulator add invites FMA contraction,
        # whose skipped rounding breaks oracle bit-parity
        xs = xq_ref[...].astype(jnp.float32) * (s_ref[0, 0] * sw_ref[0, 0])
        acc_ref[...] += jax.lax.dot_general(
            xs, w_ref[...].astype(jnp.float32),
            dimension_numbers=dims, preferred_element_type=jnp.float32)

    @pl.when(fb != 0)
    def _bf16_tile():
        # one weight representation everywhere: dequantized fp8, not a
        # full-precision shadow copy
        w16 = (w_ref[...].astype(jnp.float32) * sw_ref[0, 0]).astype(jnp.bfloat16)
        acc_ref[...] += jax.lax.dot_general(
            x16_ref[...], w16, dimension_numbers=dims,
            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def fp8_mixed_matmul(x16: jax.Array, x_q: jax.Array, s_blk: jax.Array,
                     fb_blk: jax.Array, w_q: jax.Array, s_w: jax.Array, *,
                     transpose_w: bool = False, out_dtype=jnp.bfloat16,
                     block_b: int = 128, block_m: int = 256,
                     block_k: int = 128, interpret: bool = False):
    """Mixed fp8/bf16 matmul: the quantization blocks of `x_q` ARE the
    (block_b × block_k) matmul tiles, so each grid step reads its tile's
    scale and fallback bit as (1, 1) operands indexed (i, k).

    x16: (B, K) bf16 originals (only read on fallback tiles).
    x_q: (B, K) fp8, s_blk/fb_blk: (B/block_b, K/block_k) f32.
    w_q: (K, M) fp8 ((M, K) if transpose_w) with tensor scale s_w (1, 1).
    Shapes must already be padded to exact block multiples (ops.py does).
    """
    B, K = x_q.shape
    M = w_q.shape[0] if transpose_w else w_q.shape[1]
    assert B % block_b == 0 and K % block_k == 0, (B, K, block_b, block_k)
    n_k = K // block_k
    block_m = min(block_m, M)
    grid = (B // block_b, pl.cdiv(M, block_m), n_k)

    if transpose_w:
        w_spec = pl.BlockSpec((block_m, block_k), lambda i, j, k: (j, k))
    else:
        w_spec = pl.BlockSpec((block_k, block_m), lambda i, j, k: (k, j))

    kernel = functools.partial(_fp8_mixed_matmul_kernel, n_k=n_k,
                               transpose_w=transpose_w, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (i, k)),
            w_spec,
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_m), jnp.float32)],
        name="fp8_mixed_matmul",
        interpret=interpret,
    )(x16.astype(jnp.bfloat16), x_q, s_blk, fb_blk, w_q, s_w)
