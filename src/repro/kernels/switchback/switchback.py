"""Pallas TPU kernels for SwitchBack int8 training matmuls.

These are the TPU-native adaptation of the paper's Triton kernels
(bitsandbytes `triton_based_modules.py`). Design notes (DESIGN.md §3):

* HBM→VMEM staging via `pallas_call` grid + BlockSpec replaces Triton's
  DRAM→SRAM `tl.load` tiling.
* The dequantize epilogue is fused into the matmul kernel (the paper fuses
  dequant into its int8 matmul the same way); scales ride in VMEM blocks.
* No transposes are ever materialized: the dgrad kernel contracts the
  *second* dim of both operands via `dot_general` dimension numbers. The
  paper's `tensor-wise_quantize_transpose` exists only because cuBLAS int8
  is ABᵀ-only — a constraint the MXU does not have.
* int8 blocks want (32, 128)-aligned tiles (int8 sublane packing ×4);
  accumulation is int32 in a VMEM scratch accumulator across the K grid dim.
* Grid iteration order is (i, j, k) with K innermost so the accumulator
  lives across the contraction steps ("revisiting" output blocks).

Every kernel here has a pure-jnp oracle in `ref.py`; tests sweep shapes and
dtypes and assert allclose in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# row-wise quantize kernel: x (B, K) -> q (B, K) int8, state (B, 1) f32
# ---------------------------------------------------------------------------

def _row_quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    absmax = jnp.maximum(absmax, 1e-12)
    q_ref[...] = jnp.round(x * (127.0 / absmax)).astype(jnp.int8)
    s_ref[...] = absmax


def row_quantize(x: jax.Array, *, block_b: int = 256,
                 interpret: bool = False):
    """Row-wise int8 quantization (paper Eq. 1) as a Pallas kernel.

    Each grid step owns `block_b` full rows so the row absmax reduction is
    local to one VMEM block (K must fit VMEM: K*block_b + K*block_b bytes).
    """
    B, K = x.shape
    block_b = min(block_b, B)
    grid = (pl.cdiv(B, block_b),)
    return pl.pallas_call(
        _row_quantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, K), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_b, K), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K), jnp.int8),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        name="row_quantize",
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# column-wise quantize kernel: x (R, C) -> q (R, C) int8, state (1, C) f32
# (per-output-unit W scales of SwitchBackQ / LLM.int8, paper Eq. 4)
# ---------------------------------------------------------------------------

def _col_quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=0, keepdims=True)
    absmax = jnp.maximum(absmax, 1e-12)
    q_ref[...] = jnp.round(x * (127.0 / absmax)).astype(jnp.int8)
    s_ref[...] = absmax


def col_quantize(x: jax.Array, *, block_c: int = 256,
                 interpret: bool = False):
    """Column-wise int8 quantization: one scale per column. Each grid step
    owns `block_c` full columns so the column absmax reduction is local to
    one VMEM block (R must fit VMEM, like K in row_quantize)."""
    R, C = x.shape
    block_c = min(block_c, C)
    grid = (pl.cdiv(C, block_c),)
    return pl.pallas_call(
        _col_quantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((R, block_c), lambda j: (0, j))],
        out_specs=[
            pl.BlockSpec((R, block_c), lambda j: (0, j)),
            pl.BlockSpec((1, block_c), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), jnp.int8),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
        ],
        name="col_quantize",
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# tensor-wise quantize kernel (two-pass absmax then cast)
# ---------------------------------------------------------------------------

def _absmax_kernel(x_ref, o_ref):
    # the running max is a (1, 1) vector, never a scalar: Mosaic cannot
    # store scalars to VMEM
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros((1, 1), jnp.float32)
    m = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)), keepdims=True)
    o_ref[...] = jnp.maximum(o_ref[...], m)


def _cast_tensorwise_kernel(x_ref, s_ref, q_ref):
    scale = 127.0 / jnp.maximum(s_ref[0, 0], 1e-12)
    q_ref[...] = jnp.round(x_ref[...].astype(jnp.float32) * scale).astype(jnp.int8)


def tensor_quantize(x: jax.Array, *, block_rows: int = 512,
                    interpret: bool = False):
    """Tensor-wise int8 quantization (paper Eq. 2): grid-sequential absmax
    reduction into a (1,1) output, then a cast pass."""
    R, C = x.shape
    block_rows = min(block_rows, R)
    grid = (pl.cdiv(R, block_rows),)
    absmax = pl.pallas_call(
        _absmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        name="tensor_quantize",
        interpret=interpret,
    )(x)
    q = pl.pallas_call(
        _cast_tensorwise_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.int8),
        name="tensor_quantize",
        interpret=interpret,
    )(x, absmax)
    return q, absmax


# ---------------------------------------------------------------------------
# int8 matmul + fused dequant epilogue
#   y[b, m] = row_scale[b] * sum_k x_q[b, k] * w_q[k, m]
# `transpose_w=True` contracts w's second dim (dgrad: w is (M_out, K_contr))
# ---------------------------------------------------------------------------

def _int8_matmul_dequant_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                                n_k: int, transpose_w: bool, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], dimension_numbers=dims,
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        # fused dequantize: one f32 multiply per output element, in VREGs
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * s_ref[...]).astype(out_dtype)


def _int8_matmul_dequant_colscale_kernel(x_ref, w_ref, s_ref, c_ref, o_ref,
                                         acc_ref, *, n_k: int,
                                         transpose_w: bool, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], dimension_numbers=dims,
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        # rank-1 dequantize: per-row AND per-output-column scales (Eq. 4)
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * (s_ref[...] * c_ref[...])).astype(out_dtype)


def int8_matmul_dequant(x_q: jax.Array, w_q: jax.Array, row_scale: jax.Array,
                        *, col_scale: jax.Array | None = None,
                        transpose_w: bool = False,
                        out_dtype=jnp.bfloat16,
                        block_b: int = 256, block_m: int = 256,
                        block_k: int = 512, interpret: bool = False):
    """Tiled int8×int8→int32 matmul with fused dequant epilogue.

    x_q: (B, K) int8. w_q: (K, M) int8, or (M, K) if transpose_w (dgrad).
    row_scale: (B, 1) f32 — the combined scale s_x * s_w / 127² (tensor-wise
    weight scale pre-folded by the caller, so the epilogue is one broadcast
    multiply).
    col_scale: optional (1, M) f32 for column-wise weight states (SwitchBackQ
    / LLM.int8, paper Eq. 4) — the epilogue becomes a rank-1 scale
    row_scale ⊗ col_scale; the weight scale then rides here instead of being
    folded into row_scale.
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

    in_specs = [
        pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
        w_spec,
        pl.BlockSpec((block_b, 1), lambda i, j, k: (i, 0)),
    ]
    operands = [x_q, w_q, row_scale]
    if col_scale is None:
        kernel = functools.partial(
            _int8_matmul_dequant_kernel, n_k=n_k, transpose_w=transpose_w,
            out_dtype=out_dtype)
    else:
        kernel = functools.partial(
            _int8_matmul_dequant_colscale_kernel, n_k=n_k,
            transpose_w=transpose_w, out_dtype=out_dtype)
        in_specs.append(pl.BlockSpec((1, block_m), lambda i, j, k: (0, j)))
        operands.append(col_scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_m), jnp.int32)],
        name="int8_matmul_dequant",
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# fused row-quantize + int8 matmul (K fits one VMEM block)
# ---------------------------------------------------------------------------

def _fused_switchback_fwd_kernel(x_ref, w_ref, sw_ref, o_ref, *, out_dtype):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12)
    x_q = jnp.round(x * (127.0 / absmax)).astype(jnp.int8)
    acc = jax.lax.dot_general(
        x_q, w_ref[...], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    scale = absmax * (sw_ref[0, 0] / (127.0 * 127.0))
    o_ref[...] = (acc.astype(jnp.float32) * scale).astype(out_dtype)


def fused_switchback_fwd(x: jax.Array, w_q: jax.Array, s_w: jax.Array, *,
                         out_dtype=jnp.bfloat16, block_b: int = 256,
                         block_m: int = 512, interpret: bool = False):
    """Forward SwitchBack with the X-quantize fused into the matmul kernel —
    one HBM read of X total (quantize in VREGs, int8 MXU dot, dequant
    epilogue). Requires the full contraction dim K in one block; used when
    K ≤ ~2048 (attention projections, small-d MLPs)."""
    B, K = x.shape
    M = w_q.shape[1]
    block_b = min(block_b, B)
    block_m = min(block_m, M)
    grid = (pl.cdiv(B, block_b), pl.cdiv(M, block_m))
    kernel = functools.partial(_fused_switchback_fwd_kernel, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, block_m), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M), out_dtype),
        name="fused_switchback_fwd",
        interpret=interpret,
    )(x, w_q, s_w.reshape(1, 1))


# ---------------------------------------------------------------------------
# fused row-quantize + int8 dgrad matmul (M fits one VMEM block)
#   dx[b, n] = s_g[b] * s_w/127² * sum_m q_row(g)[b, m] * w_q[n, m]
# ---------------------------------------------------------------------------

def _fused_switchback_dgrad_kernel(g_ref, w_ref, sw_ref, o_ref, *, out_dtype):
    g = g_ref[...].astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(g), axis=-1, keepdims=True), 1e-12)
    g_q = jnp.round(g * (127.0 / absmax)).astype(jnp.int8)
    # contract over m = dim 1 of BOTH operands (w_q stays (n, m) exactly as
    # the forward quantized it — no transpose is ever materialized; the MXU
    # contracts arbitrary dimension pairs, unlike cuBLAS int8's ABᵀ)
    acc = jax.lax.dot_general(
        g_q, w_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    scale = absmax * (sw_ref[0, 0] / (127.0 * 127.0))
    o_ref[...] = (acc.astype(jnp.float32) * scale).astype(out_dtype)


def fused_switchback_dgrad(g: jax.Array, w_q: jax.Array, s_w: jax.Array, *,
                           out_dtype=jnp.bfloat16, block_b: int = 256,
                           block_n: int = 512, interpret: bool = False):
    """Input-grad SwitchBack with the Ẏ row-quantize fused into the matmul
    kernel — one HBM read of Ẏ total, reusing the forward's int8 W and
    tensor-wise scale. Requires the full contraction dim M (the layer's
    output width) in one block; used when M ≤ ~2048."""
    B, M = g.shape
    N = w_q.shape[0]
    block_b = min(block_b, B)
    block_n = min(block_n, N)
    grid = (pl.cdiv(B, block_b), pl.cdiv(N, block_n))
    kernel = functools.partial(_fused_switchback_dgrad_kernel,
                               out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, M), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, M), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), out_dtype),
        name="fused_switchback_dgrad",
        interpret=interpret,
    )(g, w_q, s_w.reshape(1, 1))


# ---------------------------------------------------------------------------
# 16-bit weight-grad matmul: dw[k, m] = sum_b x[b, k] * g[b, m]
# (the "switch back" — bf16 inputs, f32 accumulate on the MXU)
# ---------------------------------------------------------------------------

def _wgrad_bf16_kernel(x_ref, g_ref, o_ref, acc_ref, *, n_b: int):
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], g_ref[...], dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(b == n_b - 1)
    def _write():
        o_ref[...] = acc_ref[...]


def wgrad_bf16(x: jax.Array, g: jax.Array, *, block_k: int = 256,
               block_m: int = 256, block_b: int = 512,
               interpret: bool = False):
    """Ẇ = Xᵀ Ẏ with bf16 inputs and f32 accumulation. The inner dim is
    b = batch×seq (huge); this is the matmul SwitchBack keeps in 16-bit."""
    B, K = x.shape
    M = g.shape[1]
    block_k = min(block_k, K)
    block_m = min(block_m, M)
    block_b = min(block_b, B)
    n_b = pl.cdiv(B, block_b)
    grid = (pl.cdiv(K, block_k), pl.cdiv(M, block_m), n_b)
    kernel = functools.partial(_wgrad_bf16_kernel, n_b=n_b)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, b: (b, i)),
            pl.BlockSpec((block_b, block_m), lambda i, j, b: (b, j)),
        ],
        out_specs=pl.BlockSpec((block_k, block_m), lambda i, j, b: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, M), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_k, block_m), jnp.float32)],
        name="wgrad_bf16",
        interpret=interpret,
    )(x.astype(jnp.bfloat16), g.astype(jnp.bfloat16))
