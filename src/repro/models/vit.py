"""Vision Transformer tower (the paper's experimental substrate).

Matches the OpenCLIP ViT used in the paper: conv patch embedding
(expressed as a linear over flattened patches — identical math, and the
layer whose out-of-date second moment causes the loss spikes, §3.4), class
token, learned positional embedding, a LayerNorm after the patch embedding
(paper §3.2), pre-norm blocks with optional zero-init layer-scale (§2.3),
and patch dropout (§2.2.2, Li et al. 2022).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import CLIPConfig, ParallelConfig
from repro.core.layer_scale import apply_layer_scale
from repro.core.precision import QuantPolicy, quant_linear
from repro.models import params as PRM
from repro.models.params import ParamSpec
from repro.models.common import layer_norm

Array = jax.Array


def _ln_spec(width):
    return {"scale": ParamSpec((width,), ("embed",), "ones"),
            "bias": ParamSpec((width,), ("embed",), "zeros")}


def _block_specs(width, heads, ff, layer_scale_init):
    hd = width // heads
    s = {
        "norm1": _ln_spec(width),
        "attn": {
            "wq": ParamSpec((width, width), ("embed", "heads"), "fan_in", 1.0),
            "wk": ParamSpec((width, width), ("embed", "heads"), "fan_in", 1.0),
            "wv": ParamSpec((width, width), ("embed", "heads"), "fan_in", 1.0),
            "wo": ParamSpec((width, width), ("heads", "embed"), "fan_in", 1.0),
            "bq": ParamSpec((width,), ("heads",), "zeros"),
            "bk": ParamSpec((width,), ("heads",), "zeros"),
            "bv": ParamSpec((width,), ("heads",), "zeros"),
            "bo": ParamSpec((width,), ("embed",), "zeros"),
        },
        "norm2": _ln_spec(width),
        "mlp": {
            "w_up": ParamSpec((width, ff), ("embed", "mlp"), "fan_in", 1.0),
            "b_up": ParamSpec((ff,), ("mlp",), "zeros"),
            "w_down": ParamSpec((ff, width), ("mlp", "embed"), "fan_in", 1.0),
            "b_down": ParamSpec((width,), ("embed",), "zeros"),
        },
    }
    if layer_scale_init is not None:
        init = "zeros" if layer_scale_init == 0.0 else "constant"
        s["gamma1"] = ParamSpec((width,), ("embed",), init, layer_scale_init)
        s["gamma2"] = ParamSpec((width,), ("embed",), init, layer_scale_init)
    return s


def vision_param_specs(cfg: CLIPConfig) -> Dict[str, Any]:
    from repro.models.transformer import _stack_specs
    W = cfg.vision_width
    patch_dim = 3 * cfg.patch_size * cfg.patch_size
    return {
        # conv1 expressed as linear over flattened patches — this is
        # `visual.conv1.weight`, the paper's loss-spike layer
        "patch_embed": ParamSpec((patch_dim, W), ("embed", "heads"),
                                 "fan_in", 1.0),
        "cls_token": ParamSpec((1, 1, W), (None, None, "embed"),
                               "normal", 0.02),
        "pos_embed": ParamSpec((1, cfg.n_patches + 1, W),
                               (None, "seq", "embed"), "normal", 0.02),
        "post_embed_norm": _ln_spec(W),
        "blocks": _stack_specs(
            _block_specs(W, cfg.vision_heads, cfg.vision_ff,
                         cfg.layer_scale_init), cfg.vision_layers),
        "final_norm": _ln_spec(W),
        "proj": ParamSpec((W, cfg.embed_dim), ("embed", "heads"),
                          "fan_in", 1.0),
    }


def _attn(x, p, heads, policy, causal, impl="flash_scan", block_q=0,
          block_k=0):
    B, S, W = x.shape
    hd = W // heads
    cd = policy.compute_dtype
    q = quant_linear(x, p["wq"].astype(cd), p["bq"],
                     policy=policy).reshape(B, S, heads, hd)
    k = quant_linear(x, p["wk"].astype(cd), p["bk"],
                     policy=policy).reshape(B, S, heads, hd)
    v = quant_linear(x, p["wv"].astype(cd), p["bv"],
                     policy=policy).reshape(B, S, heads, hd)
    # same backend rule as the LM towers: the policy's kernel backend
    # flips both towers of the paper's CLIP onto the fused flash kernels
    from repro.models.attention import _core_attention
    o = _core_attention(q, k, v, causal=causal, policy=policy, impl=impl,
                        block_q=block_q, block_k=block_k).reshape(B, S, W)
    return quant_linear(o, p["wo"].astype(cd), p["bo"], policy=policy)


def _mlp(x, p, policy):
    cd = policy.compute_dtype
    h = quant_linear(x, p["w_up"].astype(cd), p["b_up"], policy=policy)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return quant_linear(h, p["w_down"].astype(cd), p["b_down"],
                        policy=policy)


def vit_block(x, lp, heads: int, policy: QuantPolicy, causal: bool = False,
              collect_stats: bool = False, impl: str = "flash_scan",
              block_q: int = 0, block_k: int = 0):
    h = layer_norm(x, lp["norm1"]["scale"], lp["norm1"]["bias"])
    a = _attn(h, lp["attn"], heads, policy, causal, impl, block_q, block_k)
    x = x + apply_layer_scale(lp.get("gamma1"), a)
    h = layer_norm(x, lp["norm2"]["scale"], lp["norm2"]["bias"])
    m = _mlp(h, lp["mlp"], policy)
    x = x + apply_layer_scale(lp.get("gamma2"), m)
    x = PRM.constrain(x, ("batch", "seq", "embed"))
    stat = (jnp.mean(jnp.abs(x.astype(jnp.float32)))
            if collect_stats else jnp.zeros((), jnp.float32))
    return x, stat


def patchify(images: Array, patch: int) -> Array:
    """(B, H, W, 3) -> (B, N, 3·p·p)."""
    B, H, W, C = images.shape
    x = images.reshape(B, H // patch, patch, W // patch, patch, C)
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(B, (H // patch) * (W // patch), patch * patch * C)


def vision_forward(params, images_or_patches: Array, cfg: CLIPConfig,
                   policy: QuantPolicy, parallel: ParallelConfig, *,
                   patch_drop_rng: Optional[Array] = None,
                   collect_stats: bool = False):
    """Returns (pooled embedding (B, embed_dim), per-block |x| stats).

    ``images_or_patches``: (B, H, W, 3) images or (B, N, 3p²) pre-patchified.
    """
    with jax.named_scope("embed"):
        if images_or_patches.ndim == 4:
            patches = patchify(images_or_patches, cfg.patch_size)
        else:
            patches = images_or_patches
        B, N, _ = patches.shape
        cd = policy.compute_dtype
        x = quant_linear(patches.astype(cd), params["patch_embed"].astype(cd),
                         policy=policy)
        x = x + params["pos_embed"][:, 1:N + 1].astype(x.dtype)

        # patch dropout (paper §2.2.2: 0.5) — keep a random half at train
        # time
        if patch_drop_rng is not None and cfg.patch_dropout > 0:
            n_keep = max(1, int(N * (1 - cfg.patch_dropout)))
            idx = jax.random.permutation(patch_drop_rng, N)[:n_keep]
            x = jnp.take(x, idx, axis=1)

        cls = (params["cls_token"].astype(x.dtype)
               + params["pos_embed"][:, :1].astype(x.dtype))
        x = jnp.concatenate([jnp.broadcast_to(cls, (B, 1, x.shape[-1])), x],
                            axis=1)
        if cfg.post_embed_norm:   # paper §3.2: LN after patch embed
            x = layer_norm(x, params["post_embed_norm"]["scale"],
                           params["post_embed_norm"]["bias"])
        x = PRM.constrain(x, ("batch", "seq", "embed"))

    def body(carry, lp):
        xx = carry
        xx, stat = vit_block(xx, lp, cfg.vision_heads, policy,
                             collect_stats=collect_stats,
                             impl=parallel.attn_impl,
                             block_q=parallel.attn_block_q,
                             block_k=parallel.attn_block_k)
        return xx, stat

    blk = (jax.checkpoint(body) if parallel.remat != "none" else body)
    if parallel.scan_layers:
        x, stats = jax.lax.scan(blk, x, params["blocks"])
    else:
        stats = []
        for i in range(cfg.vision_layers):
            x, s = blk(x, jax.tree.map(lambda p: p[i], params["blocks"]))
            stats.append(s)
        stats = jnp.stack(stats)
    with jax.named_scope("head"):
        x = layer_norm(x, params["final_norm"]["scale"],
                       params["final_norm"]["bias"])
        pooled = x[:, 0]    # CLS
        emb = jnp.einsum("bd,de->be", pooled,
                         jnp.asarray(params["proj"], pooled.dtype))
    return emb, stats
