"""Pure-jnp oracles for the SwitchBack Pallas kernels (allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def row_quantize(x: jax.Array):
    xf = x.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), 1e-12)
    q = jnp.round(xf * (127.0 / absmax)).astype(jnp.int8)
    return q, absmax


def col_quantize(x: jax.Array):
    xf = x.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(xf), axis=0, keepdims=True), 1e-12)
    q = jnp.round(xf * (127.0 / absmax)).astype(jnp.int8)
    return q, absmax


def tensor_quantize(x: jax.Array):
    xf = x.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12).reshape(1, 1)
    q = jnp.round(xf * (127.0 / absmax)).astype(jnp.int8)
    return q, absmax


def int8_matmul_dequant(x_q, w_q, row_scale, *, col_scale=None,
                        transpose_w=False, out_dtype=jnp.bfloat16):
    dims = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(x_q, w_q, dimension_numbers=dims,
                              preferred_element_type=jnp.int32)
    scale = row_scale if col_scale is None else row_scale * col_scale
    return (acc.astype(jnp.float32) * scale).astype(out_dtype)


def fused_switchback_fwd(x, w_q, s_w, *, out_dtype=jnp.bfloat16):
    x_q, s_x = row_quantize(x)
    scale = s_x * (s_w.reshape(()) / (127.0 * 127.0))
    return int8_matmul_dequant(x_q, w_q, scale, out_dtype=out_dtype)


def fused_switchback_dgrad(g, w_q, s_w, *, out_dtype=jnp.bfloat16):
    g_q, s_g = row_quantize(g)
    scale = s_g * (s_w.reshape(()) / (127.0 * 127.0))
    return int8_matmul_dequant(g_q, w_q, scale, transpose_w=True,
                               out_dtype=out_dtype)


def wgrad_bf16(x, g):
    return jax.lax.dot_general(
        x.astype(jnp.bfloat16), g.astype(jnp.bfloat16),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# grouped (mixture-of-experts) oracles: rows in tiles of ``block_m``, tile i
# of expert tile_expert[i]; tiles from n_live on hold no slots (zeros here)

def _row_expert(tile_expert, n_live, block_m, n_rows):
    tile = jnp.arange(n_rows) // block_m
    live = tile < n_live.reshape(())
    return tile_expert[tile], live


def _group_sizes(tile_expert, n_live, block_m, n_experts):
    live = jnp.arange(tile_expert.shape[0]) < n_live.reshape(())
    return jnp.zeros((n_experts + 1,), jnp.int32).at[
        jnp.where(live, tile_expert, n_experts)].add(block_m)[:n_experts]


def moe_gmm_fwd(x, w_q, s_w, tile_expert, n_live, *, block_m,
                out_dtype=jnp.bfloat16):
    x_q, s_x = row_quantize(x)
    sizes = _group_sizes(tile_expert, n_live, block_m, w_q.shape[0])
    acc = jax.lax.ragged_dot(x_q, w_q, sizes,
                             preferred_element_type=jnp.int32)
    e, live = _row_expert(tile_expert, n_live, block_m, x.shape[0])
    scale = s_x * (s_w.reshape(-1)[e][:, None] / (127.0 * 127.0))
    return jnp.where(live[:, None], acc.astype(jnp.float32) * scale,
                     0.0).astype(out_dtype)


def moe_gmm_dgrad(g, w_q, s_w, tile_expert, n_live, *, block_m,
                  out_dtype=jnp.bfloat16):
    return moe_gmm_fwd(g, jnp.swapaxes(w_q, 1, 2), s_w, tile_expert, n_live,
                       block_m=block_m, out_dtype=out_dtype)


def moe_gmm_wgrad(x, g, tile_expert, n_live, *, block_m, n_experts):
    sizes = _group_sizes(tile_expert, n_live, block_m, n_experts)
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    w0 = jnp.zeros((n_experts, x.shape[1], g.shape[1]), jnp.float32)
    _, vjp = jax.vjp(lambda w: jax.lax.ragged_dot(
        bf(x), w, sizes, precision=jax.lax.Precision.HIGHEST), w0)
    return vjp(bf(g))[0]
