"""Plain float32 reference of a DeepSeek-V3-style decoder (Moonlight-16B-A3B)
on one chip's share of an expert-parallel deployment: multi-head latent
attention, leading dense layers, then mixture-of-experts layers with
sigmoid routing over every expert, the held experts computed, two shared
experts, an untied output head over a slice of the vocabulary, and mean
next-token cross-entropy plus the sequence-wise balance loss.

It reads the configuration's published keys (``hidden_size``,
``kv_lora_rank``, ``n_routed_experts`` ...) and its ``share`` (the router's
full expert count and the first held id), and the weights the benchmark
made, laid out as ``abstract`` says; it imports nothing of the program.
Every matmul of a layer goes through the ``linear`` it is given (the
low-precision control swaps it); the router, attention scores and the
head are float32 at full precision. Layers run one at a time under a scan
and are recomputed for the backward pass; attention runs in blocks of
queries; each held expert runs over every token under a scan, weighted by
its gate (zero where the token did not choose it); the head runs in blocks
of positions.

Departures from the published description (HF ``deepseek_v3``):

* RoPE rotates the two halves of each rope part, where HF first permutes
  the rope columns to interleaved pairs; the two are equal under a fixed
  permutation of ``W_q``'s and ``W_kv_a``'s rope columns, which random
  weights do not see.
* The chip's share (``share``): the router scores all experts, only the
  held ones (``n_routed_experts`` of them from ``held_first``) are
  computed, and what the others would add is left out; the vocabulary is
  the slice ``vocab_size``.
* The selection bias is a weight that stays at zero (it is updated by a
  training rule outside the layer, ``assumed`` in the configuration), and
  the balance loss weight ``seq_aux_alpha`` is assumed (``seq_aux: true``
  gives none).
* Random weights from the seed, not the released checkpoint.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from bench.reference.common import HIGHEST, dot, log_softmax_ce
from bench.reference.lm import causal_attention, rms_norm, rope

HEAD_BLOCK = 512       # positions per output-head block


def dims(c: Dict):
    return (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])


def abstract(c: Dict):
    """The weight tree (names and shapes, float32) the reference reads."""
    D, H, r, dn, dr, dv = dims(c)
    V = c["vocab_size"]
    K0 = c["first_k_dense_replace"]
    Lm = c["num_hidden_layers"] - K0
    E, Eh = c["share"]["router_experts"], c["n_routed_experts"]
    F, Fd = c["moe_intermediate_size"], c["intermediate_size"]
    Fs = c["n_shared_experts"] * F
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)

    def attn(n):
        return {"wq": s(n, D, H * (dn + dr)), "wkv_a": s(n, D, r + dr),
                "kv_norm": {"scale": s(n, r)},
                "wkv_b": s(n, r, H * (dn + dv)), "wo": s(n, H * dv, D)}

    def mlp(*lead, f):
        return {"w_gate": s(*lead, D, f), "w_up": s(*lead, D, f),
                "w_down": s(*lead, f, D)}

    def norms(n):
        return {"norm1": {"scale": s(n, D)}, "norm2": {"scale": s(n, D)}}

    return {
        "embed": s(V, D),
        "head": s(D, V),
        "final_norm": {"scale": s(D)},
        "dense_blocks": {"pos0": {**norms(K0), "attn": attn(K0),
                                  "mlp": mlp(K0, f=Fd)}},
        "blocks": {"pos0": {**norms(Lm), "attn": attn(Lm), "moe": {
            "w_router": s(Lm, D, E), "bias": s(Lm, E),
            **mlp(Lm, Eh, f=F), "shared": mlp(Lm, f=Fs)}}},
    }


def swiglu(x, m, linear: Callable):
    return linear(jax.nn.silu(linear(x, m["w_gate"])) * linear(x, m["w_up"]),
                  m["w_down"])


def mla(x, a, c: Dict, linear: Callable):
    """Latent attention: q from x; k and v from the RMS-normed latent; one
    rope key part shared by the heads; causal, scale 1/sqrt(dn + dr)."""
    D, H, r, dn, dr, dv = dims(c)
    B, S, _ = x.shape
    theta = c["rope_theta"]
    q = linear(x, a["wq"]).reshape(B, S, H, dn + dr)
    kv_a = linear(x, a["wkv_a"])
    latent = rms_norm(kv_a[..., :r], a["kv_norm"]["scale"], c["rms_norm_eps"])
    k_rope = rope(kv_a[..., None, r:], theta)
    kv = linear(latent, a["wkv_b"]).reshape(B, S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (B, S, H, dr))], -1)
    o = causal_attention(q, k, kv[..., dn:])
    return linear(o.reshape(B, S, H * dv), a["wo"])


def moe(x, m, c: Dict, linear: Callable):
    """(routed + shared) (B, S, D) and the sequence-wise balance loss."""
    B, S, D = x.shape
    E, K = c["share"]["router_experts"], c["num_experts_per_tok"]
    first, Eh = c["share"]["held_first"], c["n_routed_experts"]
    x2 = x.reshape(B * S, D)
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x2, m["w_router"],
                                       precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores + m["bias"], K)
    gates = jnp.take_along_axis(scores, chosen, -1)
    if c["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    gates = gates * c["routed_scaling_factor"]
    held_ids = first + jnp.arange(Eh)
    weight = jnp.sum(jnp.where(chosen[:, :, None] == held_ids,
                               gates[:, :, None], 0.0), axis=1)   # (T, Eh)

    @jax.checkpoint
    def expert(acc, args):
        w_e, ep = args
        return acc + w_e[:, None] * swiglu(x2, ep, linear), None

    eps = {k: m[k] for k in ("w_gate", "w_up", "w_down")}
    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x2), (weight.T, eps))
    out = routed + swiglu(x2, m["shared"], linear)
    hits = jax.nn.one_hot(chosen.reshape(B, S * K), E).sum(1) * (E / (K * S))
    norm = (scores / jnp.sum(scores, -1, keepdims=True)).reshape(B, S, E)
    aux = jnp.mean(jnp.sum(hits * norm.mean(1), -1))
    return out.reshape(B, S, D), c["seq_aux_alpha"] * aux


def layer(x, lp, c: Dict, linear: Callable, dense: bool):
    eps = c["rms_norm_eps"]
    x = x + mla(rms_norm(x, lp["norm1"]["scale"], eps), lp["attn"], c, linear)
    h = rms_norm(x, lp["norm2"]["scale"], eps)
    if dense:
        return x + swiglu(h, lp["mlp"], linear), jnp.zeros((), jnp.float32)
    m, aux = moe(h, lp["moe"], c, linear)
    return x + m, aux


def hidden(params, tokens, c: Dict, linear: Callable = dot):
    """The final normed hidden states (B, S, D) of ``tokens`` (B, S) and
    the summed balance loss of the MoE layers."""
    x = params["embed"][tokens]

    def stack(x, blocks, dense):
        body = jax.checkpoint(functools.partial(
            lambda xx, lp, d: layer(xx, lp, c, linear, d), d=dense))
        return jax.lax.scan(body, x, blocks)

    x, _ = stack(x, params["dense_blocks"]["pos0"], True)
    x, aux = stack(x, params["blocks"]["pos0"], False)
    return rms_norm(x, params["final_norm"]["scale"],
                    c["rms_norm_eps"]), jnp.sum(aux)


def loss(params, batch, key, c: Dict, linear: Callable = dot,
         rows: slice = slice(None)):
    """Mean next-token cross-entropy over ``batch["tokens"][rows]`` plus
    the balance loss. ``key`` is unused (no dropout)."""
    del key
    labels = batch["labels"][rows]
    x, aux = hidden(params, batch["tokens"][rows], c, linear)
    B, S, D = x.shape
    nb = -(-S // HEAD_BLOCK)

    @jax.checkpoint
    def head(args):
        xi, li = args
        logits = jnp.einsum("bsd,dv->bsv", xi, params["head"],
                            precision=HIGHEST)
        return jnp.sum(log_softmax_ce(logits, li))

    xs = x.reshape(B, nb, S // nb, D).swapaxes(0, 1)
    ls = labels.reshape(B, nb, S // nb).swapaxes(0, 1)
    return jnp.sum(jax.lax.map(head, (xs, ls))) / (B * S) + aux
