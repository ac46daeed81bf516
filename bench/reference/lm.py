"""Plain float32 reference of a Llama-style decoder (SmolLM): RMSNorm,
rotary positions on split halves, grouped-query causal attention, SwiGLU,
tied input and output embeddings, mean next-token cross-entropy.

It reads the configuration's published keys (``hidden_size``,
``num_hidden_layers`` ...) and the weights the benchmark made, laid out as
``abstract`` says, and imports nothing of the program. Layers run one at a
time under a scan and are recomputed for the backward pass; attention runs
in blocks of queries, each over the keys it can see, and the output head
in blocks of positions, so that
the whole forward and backward pass fits on one chip beside nothing else.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from bench.reference.common import HIGHEST, dot, log_softmax_ce

Q_BLOCK = 512          # queries per attention block
HEAD_BLOCK = 512       # positions per output-head block


def dims(c: Dict):
    D, H = c["hidden_size"], c["num_attention_heads"]
    return D, H, c["num_key_value_heads"], D // H


def abstract(c: Dict):
    """The weight tree (names and shapes, float32) the reference reads."""
    D, H, KV, hd = dims(c)
    L, F, V = c["num_hidden_layers"], c["intermediate_size"], c["vocab_size"]
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    return {
        "embed": s(V, D),
        "final_norm": {"scale": s(D)},
        "blocks": {"pos0": {
            "norm1": {"scale": s(L, D)}, "norm2": {"scale": s(L, D)},
            "attn": {"wq": s(L, D, H * hd), "wk": s(L, D, KV * hd),
                     "wv": s(L, D, KV * hd), "wo": s(L, H * hd, D)},
            "mlp": {"w_gate": s(L, D, F), "w_up": s(L, D, F),
                    "w_down": s(L, F, D)}}},
    }


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x (B, S, heads, hd): rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v):
    """q (B, S, H, hd); k, v (B, S, KV, hd). Blocks of queries, each over
    the keys up to its last position and recomputed in the backward
    pass."""
    H = q.shape[2]
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)
    S = q.shape[1]
    return jnp.concatenate(
        [_attend(q[:, lo:lo + Q_BLOCK], k[:, :lo + Q_BLOCK],
                 v[:, :lo + Q_BLOCK], lo) for lo in range(0, S, Q_BLOCK)],
        axis=1)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _attend(q, k, v, lo: int):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=HIGHEST) / math.sqrt(q.shape[-1])
    qpos = lo + jnp.arange(q.shape[1])
    s = jnp.where(jnp.arange(k.shape[1])[None, :] > qpos[:, None], -jnp.inf,
                  s)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                      precision=HIGHEST)


def layer(x, lp, c: Dict, linear: Callable):
    D, H, KV, hd = dims(c)
    B, S, _ = x.shape
    eps = c["rms_norm_eps"]
    h = rms_norm(x, lp["norm1"]["scale"], eps)
    a = lp["attn"]
    q = rope(linear(h, a["wq"]).reshape(B, S, H, hd), c["rope_theta"])
    k = rope(linear(h, a["wk"]).reshape(B, S, KV, hd), c["rope_theta"])
    v = linear(h, a["wv"]).reshape(B, S, KV, hd)
    x = x + linear(causal_attention(q, k, v).reshape(B, S, H * hd), a["wo"])
    h = rms_norm(x, lp["norm2"]["scale"], eps)
    m = lp["mlp"]
    return x + linear(jax.nn.silu(linear(h, m["w_gate"]))
                      * linear(h, m["w_up"]), m["w_down"])


def hidden(params, tokens, c: Dict, linear: Callable = dot):
    """The final normed hidden states (B, S, D) of ``tokens`` (B, S)."""
    x = params["embed"][tokens]
    body = jax.checkpoint(lambda xx, lp: (layer(xx, lp, c, linear), None))
    x, _ = jax.lax.scan(body, x, params["blocks"]["pos0"])
    return rms_norm(x, params["final_norm"]["scale"], c["rms_norm_eps"])


def loss(params, batch, key, c: Dict, linear: Callable = dot,
         rows: slice = slice(None)):
    """Mean next-token cross-entropy over ``batch["tokens"][rows]``.
    ``key`` is unused (no dropout)."""
    del key
    labels = batch["labels"][rows]
    x = hidden(params, batch["tokens"][rows], c, linear)
    B, S, D = x.shape
    nb = -(-S // HEAD_BLOCK)

    @jax.checkpoint
    def head(args):
        xi, li = args
        logits = jnp.einsum("bsd,vd->bsv", xi, params["embed"],
                            precision=HIGHEST)
        return jnp.sum(log_softmax_ce(logits, li))

    xs = x.reshape(B, nb, S // nb, D).swapaxes(0, 1)
    ls = labels.reshape(B, nb, S // nb).swapaxes(0, 1)
    return jnp.sum(jax.lax.map(head, (xs, ls))) / (B * S)


def logits_at(params, tokens, positions, c: Dict, linear: Callable = dot):
    """Logits (B, P, V) of ``tokens`` (B, S) at ``positions`` (B, P): the
    next-token scores after each of those positions."""
    x = hidden(params, tokens, c, linear)
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    return jnp.einsum("bpd,vd->bpv", x, params["embed"], precision=HIGHEST)
