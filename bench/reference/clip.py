"""Plain float32 reference of OpenCLIP's two-tower CLIP (ViT image tower,
causal text tower) with the symmetric contrastive loss, as the paper
trains it: patch embedding as a linear over flattened 14x14 patches,
patch dropout keeping a random half of the patches (one permutation per
step for the whole batch, drawn from the step's key), class token, a
LayerNorm after the patch embedding (paper section 3.2), pre-norm blocks,
the text tower pooled at its last position, and the logit scale clipped
at ln(100).

Departure from OpenCLIP, shared with the configuration it checks: GELU is
the tanh approximation. Nothing of the program is imported; layers run
one at a time under a scan, recomputed for the backward pass.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from bench.reference.common import HIGHEST, dot, gelu_tanh

EPS = 1e-5


def dims(c: Dict):
    v, t = c["vision_cfg"], c["text_cfg"]
    return v, t, v["width"] * v["mlp_ratio"], t["width"] * t["mlp_ratio"]


def abstract(c: Dict):
    v, t, vff, tff = dims(c)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def blocks(L, W, F):
        ln = lambda: {"scale": s(L, W), "bias": s(L, W)}
        return {"norm1": ln(), "norm2": ln(),
                "attn": {"wq": s(L, W, W), "wk": s(L, W, W),
                         "wv": s(L, W, W), "wo": s(L, W, W),
                         "bq": s(L, W), "bk": s(L, W), "bv": s(L, W),
                         "bo": s(L, W)},
                "mlp": {"w_up": s(L, W, F), "b_up": s(L, F),
                        "w_down": s(L, F, W), "b_down": s(L, W)}}

    VW, TW, E = v["width"], t["width"], c["embed_dim"]
    n_patches = (v["image_size"] // v["patch_size"]) ** 2
    ln = lambda W: {"scale": s(W), "bias": s(W)}
    return {
        "visual": {
            "patch_embed": s(3 * v["patch_size"] ** 2, VW),
            "cls_token": s(1, 1, VW), "pos_embed": s(1, n_patches + 1, VW),
            "post_embed_norm": ln(VW),
            "blocks": blocks(v["layers"], VW, vff),
            "final_norm": ln(VW), "proj": s(VW, E)},
        "text": {
            "embed": s(t["vocab_size"], TW),
            "pos_embed": s(1, t["context_length"], TW),
            "blocks": blocks(t["layers"], TW, tff),
            "final_norm": ln(TW), "proj": s(TW, E)},
        "logit_scale": s(),
    }


def layer_norm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def block(x, lp, heads: int, causal: bool, linear: Callable):
    B, S, W = x.shape
    hd = W // heads
    a = lp["attn"]
    h = layer_norm(x, lp["norm1"])
    q, k, v = ((linear(h, a[w]) + a[b]).reshape(B, S, heads, hd)
               for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    if causal:
        s = jnp.where(jnp.triu(jnp.ones((S, S), bool), 1), -jnp.inf, s)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST).reshape(B, S, W)
    x = x + linear(o, a["wo"]) + a["bo"]
    m = lp["mlp"]
    h = layer_norm(x, lp["norm2"])
    h = gelu_tanh(linear(h, m["w_up"]) + m["b_up"])
    return x + linear(h, m["w_down"]) + m["b_down"]


def tower(x, blocks, heads, causal, linear):
    body = jax.checkpoint(
        lambda xx, lp: (block(xx, lp, heads, causal, linear), None))
    return jax.lax.scan(body, x, blocks)[0]


def patches(images, p: int):
    B, H, W, C = images.shape
    x = images.reshape(B, H // p, p, W // p, p, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, (H // p) * (W // p), p * p * C)


def loss(params, batch, key, c: Dict, linear: Callable = dot,
         rows: slice = slice(None)):
    """Symmetric InfoNCE over the pairs ``rows`` of the batch; ``key`` is
    the step's patch-dropout key."""
    v, t, _, _ = dims(c)
    pv, pt = params["visual"], params["text"]
    x = linear(patches(batch["images"][rows], v["patch_size"]),
               pv["patch_embed"])
    B, N, W = x.shape
    x = x + pv["pos_embed"][:, 1:N + 1]
    keep = max(1, int(N * (1 - c["patch_dropout"])))
    x = jnp.take(x, jax.random.permutation(key, N)[:keep], axis=1)
    cls = jnp.broadcast_to(pv["cls_token"] + pv["pos_embed"][:, :1], (B, 1, W))
    x = layer_norm(jnp.concatenate([cls, x], 1), pv["post_embed_norm"])
    x = tower(x, pv["blocks"], v["width"] // v["head_width"], False, linear)
    img = dot(layer_norm(x, pv["final_norm"])[:, 0], pv["proj"])

    tok = batch["texts"][rows]
    y = pt["embed"][tok] + pt["pos_embed"][:, :tok.shape[1]]
    y = tower(y, pt["blocks"], t["heads"], True, linear)
    txt = dot(layer_norm(y, pt["final_norm"])[:, -1], pt["proj"])

    img = img / jnp.linalg.norm(img, axis=-1, keepdims=True)
    txt = txt / jnp.linalg.norm(txt, axis=-1, keepdims=True)
    cap = c["logit_scale_max"]
    logits = jnp.exp(jnp.clip(params["logit_scale"], -cap, cap)) * jnp.einsum(
        "ie,te->it", img, txt, precision=HIGHEST)
    diag = jnp.arange(logits.shape[0])
    l_i = -jnp.mean(jax.nn.log_softmax(logits, -1)[diag, diag])
    l_t = -jnp.mean(jax.nn.log_softmax(logits.T, -1)[diag, diag])
    return 0.5 * (l_i + l_t)
