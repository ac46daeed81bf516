"""Mamba selective SSM block (Gu & Dao 2023), for the Jamba hybrid.

    x -> in_proj -> (x_ssm, z gate)
    x_ssm -> causal conv1d -> silu -> selective scan -> ·silu(z) -> out_proj

Selective scan per channel c with state dim N:
    h_t = exp(Δ_t A) h_{t-1} + Δ_t B_t x_t        (A diagonal, (d_inner, N))
    y_t = C_t · h_t + D x_t

The recurrence is a lax.scan in f32 (elementwise/small-N — not a GEMM, so
SwitchBack does not apply; in/out projections do route through
quant_linear). Decode keeps (conv window, h) as the recurrent state, giving
O(1) per-token cost — this is why Jamba runs the long_500k shape.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.precision import QuantPolicy, quant_linear

Array = jax.Array


class MambaState(NamedTuple):
    conv: Array     # (B, d_conv-1, d_inner) last inputs for the causal conv
    h: Array        # (B, d_inner, N) SSM state


def _conv1d_causal(x: Array, kernel: Array, bias: Array,
                   prefix: Array | None = None) -> Array:
    """Depthwise causal conv. x: (B, S, C); kernel: (K, C)."""
    K = kernel.shape[0]
    if prefix is None:
        prefix = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prefix, x], axis=1)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(K):
        out = out + xp[:, i:i + x.shape[1], :].astype(jnp.float32) * \
            kernel[i].astype(jnp.float32)
    return (out + bias.astype(jnp.float32)).astype(x.dtype)


def selective_scan(u: Array, delta: Array, A: Array, B: Array, C: Array,
                   D: Array, h0: Array | None = None):
    """u, delta: (B, S, d); A: (d, N); B, C: (B, S, N); D: (d,).
    Returns (y (B,S,d), h_final (B,d,N))."""
    Bsz, S, d = u.shape
    N = A.shape[1]
    uf = u.astype(jnp.float32)
    df = delta.astype(jnp.float32)
    dA = jnp.exp(df[..., None] * A[None, None])            # (B,S,d,N)
    dBu = df[..., None] * B[:, :, None, :].astype(jnp.float32) * uf[..., None]

    def step(h, inp):
        dA_t, dBu_t, C_t = inp
        h = dA_t * h + dBu_t                                # (B,d,N)
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    init = h0 if h0 is not None else jnp.zeros((Bsz, d, N), jnp.float32)
    xs = (jnp.moveaxis(dA, 1, 0), jnp.moveaxis(dBu, 1, 0),
          jnp.moveaxis(C.astype(jnp.float32), 1, 0))
    h_final, ys = jax.lax.scan(step, init, xs)
    y = jnp.moveaxis(ys, 0, 1) + uf * D.astype(jnp.float32)[None, None]
    return y.astype(u.dtype), h_final


def mamba_block(x: Array, p: dict, cfg, policy: QuantPolicy, *,
                state: MambaState | None = None):
    """x: (B, S, D) -> (out (B, S, D), new_state)."""
    mc = cfg.mamba
    B, S, D = x.shape
    d_inner = mc.expand * D
    N = mc.d_state
    dt_rank = mc.dt_rank or -(-D // 16)

    cd = policy.compute_dtype
    xz = quant_linear(x, p["w_in"].astype(cd),
                      policy=policy)          # (B,S,2*d_inner)
    xs_raw, z = jnp.split(xz, 2, axis=-1)
    prefix = (state.conv.astype(x.dtype) if state is not None else
              jnp.zeros((B, mc.d_conv - 1, d_inner), x.dtype))
    xs_ = _conv1d_causal(xs_raw, p["conv_w"], p["conv_b"], prefix)
    # conv state = last (d_conv-1) *raw* inputs (pre-conv, post-split)
    hist = jnp.concatenate([prefix, xs_raw.astype(prefix.dtype)], axis=1)
    new_conv = hist[:, hist.shape[1] - (mc.d_conv - 1):, :]
    xs_ = jax.nn.silu(xs_.astype(jnp.float32)).astype(x.dtype)

    # data-dependent Δ, B, C
    dbc = quant_linear(xs_, p["w_x_proj"].astype(cd),
                       policy=policy)   # (B,S,dt_rank+2N)
    dt, Bm, Cm = jnp.split(dbc, [dt_rank, dt_rank + N], axis=-1)
    delta = jax.nn.softplus(
        (dt.astype(jnp.float32) @ p["w_dt"].astype(jnp.float32))
        + p["dt_bias"].astype(jnp.float32)).astype(x.dtype)  # (B,S,d_inner)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))             # (d_inner, N)

    h0 = state.h if state is not None else None
    y, h_final = selective_scan(xs_, delta, A, Bm, Cm, p["D"], h0)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = quant_linear(y, p["w_out"].astype(cd), policy=policy)
    return out, MambaState(new_conv, h_final)
