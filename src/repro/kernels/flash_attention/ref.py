"""Pure-jnp oracles for the flash-attention kernels.

Same kernel-layout contract as flash_attention.py — q (B, H, Sq, dqk),
k (B, KV, Sk, dqk), v (B, KV, Sk, dv) — but materialising the full (Sq, Sk) score matrix.
These are the ``backend="xla"`` implementations behind ops.py AND the
parity oracles the interpret-mode tests compare against; the backward is
written out explicitly (the same p/ds algebra the kernels use) rather
than via jax.grad so a sign error can't cancel between paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import MASK_VALUE


def _expand_heads(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, KV, S, hd) -> (B, H, S, hd) repeating each KV head."""
    rep = n_heads // k.shape[1]
    return jnp.repeat(k, rep, axis=1) if rep > 1 else k


def _scores(q, k, *, scale, causal, kv_valid):
    """Masked f32 scores (B, H, Sq, Sk); k already head-expanded."""
    s = scale * jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                           k.astype(jnp.float32))
    Sq, Sk = q.shape[2], k.shape[2]
    kpos = jnp.arange(Sk)[None, :]
    mask = kpos < kv_valid
    if causal:
        mask = mask & (kpos <= jnp.arange(Sq)[:, None])
    return jnp.where(mask[None, None], s, MASK_VALUE)


def mha_fwd(q, k, v, *, causal: bool, kv_valid: int, scale: float):
    """Returns (o (B, H, Sq, hd) q.dtype, lse (B, H, Sq) f32)."""
    s = _scores(q, _expand_heads(k, q.shape[1]), scale=scale, causal=causal,
                kv_valid=kv_valid)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqk,bhkd->bhqd", p / l_safe,
                   _expand_heads(v, q.shape[1]).astype(jnp.float32))
    lse = (m + jnp.log(l_safe))[..., 0]
    return o.astype(q.dtype), lse


def mha_bwd(q, k, v, o, lse, do, *, causal: bool, kv_valid: int,
            scale: float):
    """Returns (dq (B,H,Sq,hd), dk, dv (B,KV,Sk,hd)) — all f32. Same
    recompute-from-lse algebra as the kernels: p = exp(s - lse),
    ds = p ⊙ (do·vᵀ - di), dq = scale·ds@k, dk = scale·dsᵀ@q, dv = pᵀ@do,
    with the GQA group summed into each KV head."""
    H, KV = q.shape[1], k.shape[1]
    kx = _expand_heads(k, H)
    vx = _expand_heads(v, H).astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = _scores(q, kx, scale=scale, causal=causal, kv_valid=kv_valid)
    p = jnp.exp(s - lse[..., None])
    di = jnp.sum(o.astype(jnp.float32) * dof, axis=-1)      # (B, H, Sq)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    ds = p * (jnp.einsum("bhqd,bhkd->bhqk", dof, vx) - di[..., None])
    dq = scale * jnp.einsum("bhqk,bhkd->bhqd", ds,
                            kx.astype(jnp.float32))
    dk = scale * jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    group = H // KV
    if group > 1:                                           # GQA group-sum
        B, _, Sk, _ = dk.shape
        dk = dk.reshape(B, KV, group, Sk, dk.shape[-1]).sum(axis=2)
        dv = dv.reshape(B, KV, group, Sk, dv.shape[-1]).sum(axis=2)
    return dq, dk, dv


def decode_fwd(q, k, v, kv_len, *, scale: float):
    """q (B, H, hd); k, v (B, KV, S, hd); kv_len (B, 1) int32 valid cells.
    Returns o (B, H, hd) q.dtype — the dense full-window re-attend the
    decode kernel replaces."""
    H = q.shape[1]
    kx = _expand_heads(k, H).astype(jnp.float32)
    vx = _expand_heads(v, H).astype(jnp.float32)
    s = scale * jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32), kx)
    mask = jnp.arange(k.shape[2])[None, None, :] < kv_len[:, :, None]
    s = jnp.where(mask, s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p, vx).astype(q.dtype)
