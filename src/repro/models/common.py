"""Shared model components: norms, RoPE, activations, embeddings."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import QuantPolicy, quant_linear

Array = jax.Array


def rms_norm(x: Array, gain: Array, eps: float = 1e-5) -> Array:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps)
        return (out * gain.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: Array, gain: Array, bias: Array | None = None,
               eps: float = 1e-5) -> Array:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out.astype(x.dtype)


def apply_norm(x: Array, p: dict, kind: str, eps: float) -> Array:
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"], eps)
    return layer_norm(x, p["scale"], p.get("bias"), eps)


def group_norm_heads(x: Array, gain: Array, n_heads: int, eps: float = 64e-5
                     ) -> Array:
    """Per-head group norm (RWKV 'ln_x'). x: (..., n_heads*hd)."""
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(shape[:-1] + (n_heads, -1))
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out.reshape(shape) * gain.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def rope_tables(head_dim: int, theta: float, max_pos: int
                ) -> tuple[Array, Array]:
    """Precomputed (cos, sin) tables, each (max_pos, head_dim/2) f32.

    Row ``p`` holds exactly the values ``apply_rope`` computes for position
    ``p`` (same f32 multiply then cos/sin), so gathering rows and applying
    :func:`apply_rope_cached` is bit-identical to the on-the-fly path —
    the serve engine hoists these out of the per-layer (and, for decode,
    per-step) hot path as jit-time constants.
    """
    ang = (jnp.arange(max_pos, dtype=jnp.float32)[:, None]
           * rope_freqs(head_dim, theta))
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope_cached(x: Array, cos: Array, sin: Array) -> Array:
    """x: (B, S, H, hd); cos/sin: (S, hd/2) or (B, S, hd/2) gathered rows
    of :func:`rope_tables`. Same rotation (and op order) as apply_rope."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def activation(h: Array, gate: Array | None, act: str) -> Array:
    if act == "swiglu":
        assert gate is not None
        return jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * h
    if act == "gelu":
        return jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    raise ValueError(act)


def embed_tokens(emb: Array, tokens: Array, dtype) -> Array:
    # one-hot-free gather; scaled in models that need it
    return jnp.asarray(emb, dtype)[tokens]


def cross_entropy_loss(logits: Array, labels: Array,
                       softcap: float = 0.0) -> Array:
    """Mean token cross-entropy, f32 log-softmax (stable under bf16 logits)."""
    with jax.named_scope("head"):
        lf = logits.astype(jnp.float32)
        if softcap:
            lf = softcap * jnp.tanh(lf / softcap)
        lse = jax.nn.logsumexp(lf, axis=-1)
        picked = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)
