"""Two-tower CLIP (the paper's own model) with contrastive loss.

Image tower: ViT (vit.py); text tower: pre-norm causal transformer, pooled
at the final token. The InfoNCE loss gathers features across the data axis
— in pjit the sharded (B, E) @ (E, B) similarity einsum makes GSPMD emit
the all-gather that dominates CLIP's communication (the signature
collective noted in DESIGN.md §5). logit_scale is learned and clipped at
ln(100) (paper §3.2).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import CLIPConfig, ParallelConfig
from repro.core.precision import QuantPolicy
from repro.models import params as PRM
from repro.models.params import ParamSpec
from repro.models.common import layer_norm
from repro.models.vit import (_block_specs, _ln_spec, vision_param_specs,
                              vision_forward, vit_block)

Array = jax.Array


def param_specs(cfg: CLIPConfig) -> Dict[str, Any]:
    from repro.models.transformer import _stack_specs
    W = cfg.text_width
    return {
        "visual": vision_param_specs(cfg),
        "text": {
            "embed": ParamSpec((cfg.text_vocab, W), ("vocab", "embed"),
                               "normal", 0.02),
            "pos_embed": ParamSpec((1, cfg.text_ctx, W),
                                   (None, "seq", "embed"), "normal", 0.01),
            "blocks": _stack_specs(
                _block_specs(W, cfg.text_heads, cfg.text_ff,
                             cfg.layer_scale_init), cfg.text_layers),
            "final_norm": _ln_spec(W),
            "proj": ParamSpec((W, cfg.embed_dim), ("embed", "heads"),
                              "fan_in", 1.0),
        },
        "logit_scale": ParamSpec((), (), "constant", cfg.logit_scale_init),
    }


def text_forward(params, tokens: Array, cfg: CLIPConfig,
                 policy: QuantPolicy, parallel: ParallelConfig):
    tp = params["text"]
    with jax.named_scope("embed"):
        x = jnp.asarray(tp["embed"], policy.compute_dtype)[tokens]
        x = x + tp["pos_embed"][:, :x.shape[1]].astype(x.dtype)
        x = PRM.constrain(x, ("batch", "seq", "embed"))

    def body(xx, lp):
        xx, _ = vit_block(xx, lp, cfg.text_heads, policy, causal=True,
                          impl=parallel.attn_impl,
                          block_q=parallel.attn_block_q,
                          block_k=parallel.attn_block_k)
        return xx, None

    blk = (jax.checkpoint(lambda c, lw: body(c, lw))
           if parallel.remat != "none" else body)
    if parallel.scan_layers:
        x, _ = jax.lax.scan(blk, x, tp["blocks"])
    else:
        for i in range(cfg.text_layers):
            x, _ = blk(x, jax.tree.map(lambda p: p[i], tp["blocks"]))
    with jax.named_scope("head"):
        x = layer_norm(x, tp["final_norm"]["scale"],
                       tp["final_norm"]["bias"])
        pooled = x[:, -1]   # last token (EOT)
        return jnp.einsum("bd,de->be", pooled,
                          jnp.asarray(tp["proj"], pooled.dtype))


def clip_forward(params, batch: Dict[str, Array], cfg: CLIPConfig,
                 policy: QuantPolicy, parallel: ParallelConfig, *,
                 patch_drop_rng: Optional[Array] = None,
                 collect_stats: bool = False):
    img_emb, stats = vision_forward(
        params["visual"], batch["images"], cfg, policy, parallel,
        patch_drop_rng=patch_drop_rng, collect_stats=collect_stats)
    txt_emb = text_forward(params, batch["texts"], cfg, policy, parallel)
    with jax.named_scope("head"):
        img_emb = img_emb / jnp.linalg.norm(
            img_emb.astype(jnp.float32), axis=-1, keepdims=True)
        txt_emb = txt_emb / jnp.linalg.norm(
            txt_emb.astype(jnp.float32), axis=-1, keepdims=True)
        return (img_emb.astype(jnp.float32), txt_emb.astype(jnp.float32),
                stats)


def clip_loss(params, batch, cfg: CLIPConfig, policy: QuantPolicy,
              parallel: ParallelConfig, *, patch_drop_rng=None,
              collect_stats: bool = False):
    """Symmetric InfoNCE. Returns (loss, metrics)."""
    img, txt, stats = clip_forward(params, batch, cfg, policy, parallel,
                                   patch_drop_rng=patch_drop_rng,
                                   collect_stats=collect_stats)
    with jax.named_scope("head"):
        # paper §3.2: clip the logit_scale parameter (ln 100 cap)
        scale = jnp.exp(jnp.clip(params["logit_scale"].astype(jnp.float32),
                                 -cfg.logit_scale_max, cfg.logit_scale_max))
        # (B, E) x (B, E) -> (B, B): GSPMD all-gathers the data-sharded
        # features
        logits = scale * (img @ txt.T)
        labels = jnp.arange(logits.shape[0])
        l_i = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), labels[:, None], -1))
        l_t = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits.T, axis=-1), labels[:, None], -1))
        loss = 0.5 * (l_i + l_t)
        acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, {"contrastive_acc": acc, "logit_scale": scale,
                  "feature_stats": stats}


def zero_shot_accuracy(img_embs: Array, class_embs: Array,
                       labels: Array) -> Array:
    """Zero-shot classification: cosine sim against class prototype
    embeddings (the 80-prompt-template average in the paper's eval)."""
    sims = img_embs @ class_embs.T
    return jnp.mean(jnp.argmax(sims, -1) == labels)
