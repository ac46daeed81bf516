"""Pallas kernel: tensor-wise fp8 quantize (scale into [-1,1] + exact-value
rounding), the hot op of the paper's simulated-fp8 path (§2.2.1).

On real fp8 hardware this kernel disappears into the matmul; for the
simulation it is a bandwidth-bound elementwise pass, tiled (rows, cols)
blocks through VMEM. Rounding uses the native float8 dtypes (exact values),
cross-checked against the bit-level oracle in ref.py / core/fp8.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_FMT_DTYPE = {"e4m3": jnp.float8_e4m3fn, "e5m2": jnp.float8_e5m2}
_FMT_MAX = {"e4m3": 448.0, "e5m2": 57344.0}


def _fp8_cast_kernel(x_ref, s_ref, o_ref, *, fmt: str):
    # the shared f32 grid-round (bit ops only — Mosaic-lowerable) makes the
    # dtype cast exact; see core/quantization.fp8_grid_round
    from repro.core.quantization import fp8_grid_round
    dt = _FMT_DTYPE[fmt]
    x = x_ref[...].astype(jnp.float32)
    inv = 1.0 / jnp.maximum(s_ref[0, 0], 1e-12)
    scaled = jnp.clip(x * inv, -_FMT_MAX[fmt], _FMT_MAX[fmt])
    o_ref[...] = fp8_grid_round(scaled, fmt).astype(dt).astype(jnp.float32)


def fp8_cast_tensorwise(x: jax.Array, absmax: jax.Array, *, fmt: str = "e4m3",
                        block_rows: int = 512, interpret: bool = False):
    """q = fp8cast(x / absmax) with exact fp8 values widened to f32."""
    R, C = x.shape
    block_rows = min(block_rows, R)
    grid = (pl.cdiv(R, block_rows),)
    kernel = functools.partial(_fp8_cast_kernel, fmt=fmt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        name="fp8_cast_tensorwise",
        interpret=interpret,
    )(x, absmax.reshape(1, 1))
