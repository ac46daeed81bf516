"""Pallas TPU kernels for the grouped SwitchBack matmuls of a dropless
mixture-of-experts layer (DESIGN.md §3).

The rows are the layer's routed slots, sorted by expert and padded per
expert to a whole number of ``block_m``-row tiles, so that every row tile
belongs to one expert: ``tile_expert[i]`` is tile ``i``'s expert and
``n_live`` the number of tiles that hold slots. Both ride in by scalar
prefetch, so the BlockSpec index maps pick each tile's expert weight and
clamp the tiles past ``n_live`` onto the last live one: the pipeline then
neither fetches nor writes for them, and their output rows are left as
they were (the caller masks them). The grid is static (the slot buffer's
bound), the work follows the slots actually routed.

* ``moe_gmm_fwd``   y = Q_row(x) · Q_tensor(W_e): rows quantized per row in
                    the kernel (the whole contraction in one block), each
                    expert's int8 weight with its own tensor-wise scale,
                    int32 accumulation and a fused dequant epilogue — the
                    forward of ``kernels/switchback``'s fused kernel, per
                    tile's expert.
* ``moe_gmm_dgrad`` dx = Q_row(g) · Q_tensor(W_e)ᵀ, contracting W's second
                    dim (never transposed).
* ``moe_gmm_wgrad`` dW_e = Σ_{rows of e} xᵀ g in bf16 with f32 accumulation
                    (the 16-bit "switch back"), accumulated in the output
                    block across the expert's consecutive tiles. Every
                    expert owns at least one tile (an empty one is a tile
                    of zero rows), so every expert's block is written.

Grids put the row tiles innermost: an output block's index then stays put
over the dead tiles at the end, so nothing is written back for them.
Every kernel has a pure-jnp oracle in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_I2 = 127.0 * 127.0

# The fused kernels hold one tile's whole contraction and one expert's
# weight block; at Moonlight's widths (rows of 2048, a 2048 x 1408 int8
# weight) that is past the default scoped VMEM, well inside a v5e's
# 128 MiB.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _live(i, n_live_ref):
    """Tile ``i`` or, past the live tiles, the last live one."""
    return jnp.minimum(i, n_live_ref[0] - 1)


def _quantize_rows(x):
    xf = x.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), 1e-12)
    return jnp.round(xf * (127.0 / absmax)).astype(jnp.int8), absmax


def _params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


# ---------------------------------------------------------------------------
# forward: y[rows of tile i] = Q_row(x) · W_q[e_i], e_i = tile_expert[i]
# ---------------------------------------------------------------------------

def _fwd_kernel(te_ref, nl_ref, x_ref, w_ref, s_ref, o_ref, *, out_dtype):
    @pl.when(pl.program_id(1) < nl_ref[0])
    def _tile():
        x_q, absmax = _quantize_rows(x_ref[...])
        acc = jax.lax.dot_general(
            x_q, w_ref[...], dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        scale = absmax * (s_ref[0, 0] / _I2)
        o_ref[...] = (acc.astype(jnp.float32) * scale).astype(out_dtype)


def gmm_fwd(x, w_q, s_w, tile_expert, n_live, *, block_m: int,
            block_n: int, out_dtype=jnp.bfloat16, interpret: bool = False):
    """x (R, K) with R = len(tile_expert) * block_m; w_q (E, K, N) int8;
    s_w (E, 1, 1) f32 absmax per expert; tile_expert (n_tiles,) int32;
    n_live (1,) int32. Returns y (R, N); rows of dead tiles unwritten."""
    R, K = x.shape
    N = w_q.shape[2]
    n_tiles = R // block_m
    x_map = lambda j, i, te, nl: (_live(i, nl), 0)
    w_map = lambda j, i, te, nl: (te[_live(i, nl)], 0, j)
    s_map = lambda j, i, te, nl: (te[_live(i, nl)], 0, 0)
    o_map = lambda j, i, te, nl: (_live(i, nl), j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // block_n, n_tiles),
        in_specs=[pl.BlockSpec((block_m, K), x_map),
                  pl.BlockSpec((None, K, block_n), w_map),
                  pl.BlockSpec((None, 1, 1), s_map)],
        out_specs=pl.BlockSpec((block_m, block_n), o_map))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, N), out_dtype),
        compiler_params=_params(),
        name="moe_gmm_fwd",
        interpret=interpret,
    )(tile_expert, n_live, x, w_q, s_w)


# ---------------------------------------------------------------------------
# input gradient: dx[rows of tile i] = Q_row(g) · W_q[e_i]ᵀ
# ---------------------------------------------------------------------------

def _dgrad_kernel(te_ref, nl_ref, g_ref, w_ref, s_ref, o_ref, *, out_dtype):
    @pl.when(pl.program_id(1) < nl_ref[0])
    def _tile():
        g_q, absmax = _quantize_rows(g_ref[...])
        acc = jax.lax.dot_general(
            g_q, w_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        scale = absmax * (s_ref[0, 0] / _I2)
        o_ref[...] = (acc.astype(jnp.float32) * scale).astype(out_dtype)


def gmm_dgrad(g, w_q, s_w, tile_expert, n_live, *, block_m: int,
              block_k: int, out_dtype=jnp.bfloat16, interpret: bool = False):
    """g (R, N); w_q (E, K, N) int8 as the forward quantized it. Returns
    dx (R, K); rows of dead tiles unwritten."""
    R, N = g.shape
    K = w_q.shape[1]
    n_tiles = R // block_m
    g_map = lambda j, i, te, nl: (_live(i, nl), 0)
    w_map = lambda j, i, te, nl: (te[_live(i, nl)], j, 0)
    s_map = lambda j, i, te, nl: (te[_live(i, nl)], 0, 0)
    o_map = lambda j, i, te, nl: (_live(i, nl), j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K // block_k, n_tiles),
        in_specs=[pl.BlockSpec((block_m, N), g_map),
                  pl.BlockSpec((None, block_k, N), w_map),
                  pl.BlockSpec((None, 1, 1), s_map)],
        out_specs=pl.BlockSpec((block_m, block_k), o_map))
    return pl.pallas_call(
        functools.partial(_dgrad_kernel, out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, K), out_dtype),
        compiler_params=_params(),
        name="moe_gmm_dgrad",
        interpret=interpret,
    )(tile_expert, n_live, g, w_q, s_w)


# ---------------------------------------------------------------------------
# weight gradient: dW[e] = Σ_{tiles of e} xᵀ g, bf16 in, f32 out
# ---------------------------------------------------------------------------

def _wgrad_kernel(te_ref, nl_ref, x_ref, g_ref, o_ref):
    i = pl.program_id(2)

    @pl.when(i < nl_ref[0])
    def _tile():
        part = jax.lax.dot_general(
            x_ref[...], g_ref[...], dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        first = (i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

        @pl.when(first)
        def _set():
            o_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _add():
            o_ref[...] += part


def gmm_wgrad(x, g, tile_expert, n_live, *, n_experts: int, block_m: int,
              block_k: int, block_n: int, interpret: bool = False):
    """x (R, K), g (R, N), both bf16. Returns dW (E, K, N) f32."""
    R, K = x.shape
    N = g.shape[1]
    n_tiles = R // block_m
    x_map = lambda a, b, i, te, nl: (_live(i, nl), a)
    g_map = lambda a, b, i, te, nl: (_live(i, nl), b)
    o_map = lambda a, b, i, te, nl: (te[_live(i, nl)], a, b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K // block_k, N // block_n, n_tiles),
        in_specs=[pl.BlockSpec((block_m, block_k), x_map),
                  pl.BlockSpec((block_m, block_n), g_map)],
        out_specs=pl.BlockSpec((None, block_k, block_n), o_map))
    return pl.pallas_call(
        _wgrad_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts, K, N), jnp.float32),
        compiler_params=_params(),
        name="moe_gmm_wgrad",
        interpret=interpret,
    )(tile_expert, n_live, x.astype(jnp.bfloat16), g.astype(jnp.bfloat16))
