"""Encoder-decoder transformer (seamless-m4t-large-v2 backbone).

The audio frontend is a STUB per the assignment: `input_specs()` provides
precomputed frame embeddings (B, S_src, d_model); a learned projection maps
them into the encoder. Encoder: bidirectional self-attn + MLP. Decoder:
causal self-attn + cross-attn + MLP. All linears route through the
precision policy (SwitchBack applies to enc, dec and cross projections).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.layer_scale import apply_layer_scale
from repro.core.precision import QuantPolicy, quant_linear
from repro.models import params as PRM
from repro.models.params import ParamSpec
from repro.models import attention as ATT
from repro.models import transformer as TF
from repro.models.common import apply_norm, cross_entropy_loss
from repro.models.mlp import mlp_block

Array = jax.Array


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    ec = cfg.encdec
    enc_layer = {"norm1": TF._norm_spec(cfg), "attn": TF._attn_specs(cfg),
                 "norm2": TF._norm_spec(cfg), "mlp": TF._mlp_specs(cfg)}
    dec_layer = {"norm1": TF._norm_spec(cfg), "attn": TF._attn_specs(cfg),
                 "norm_x": TF._norm_spec(cfg), "xattn": TF._attn_specs(cfg),
                 "norm2": TF._norm_spec(cfg), "mlp": TF._mlp_specs(cfg)}
    if cfg.layer_scale_init is not None:
        init = "zeros" if cfg.layer_scale_init == 0.0 else "constant"
        for d in (enc_layer, dec_layer):
            d["gamma1"] = ParamSpec((cfg.d_model,), ("embed",), init,
                                    cfg.layer_scale_init)
            d["gamma2"] = ParamSpec((cfg.d_model,), ("embed",), init,
                                    cfg.layer_scale_init)
        dec_layer["gamma_x"] = ParamSpec((cfg.d_model,), ("embed",), init,
                                         cfg.layer_scale_init)
    return {
        "frontend_proj": ParamSpec((cfg.d_model, cfg.d_model),
                                   ("embed", "mlp"), "fan_in", 1.0),
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                           "normal", 0.02),
        "enc_blocks": TF._stack_specs(enc_layer, ec.n_encoder_layers),
        "dec_blocks": TF._stack_specs(dec_layer, cfg.n_layers),
        "enc_norm": TF._norm_spec(cfg),
        "final_norm": TF._norm_spec(cfg),
        "head": ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                          "fan_in", 1.0),
    }


def _enc_layer(x, lp, cfg, policy, parallel, positions):
    h = apply_norm(x, lp["norm1"], cfg.norm, cfg.norm_eps)
    a = ATT.attention_block(h, lp["attn"], cfg, policy, positions=positions,
                            causal=False, impl=parallel.attn_impl)
    x = x + apply_layer_scale(lp.get("gamma1"), a)
    h = apply_norm(x, lp["norm2"], cfg.norm, cfg.norm_eps)
    m = mlp_block(h, lp["mlp"], cfg, policy)
    x = x + apply_layer_scale(lp.get("gamma2"), m)
    return PRM.constrain(x, ("batch", "seq", "embed"))


def _dec_layer(x, lp, cfg, policy, parallel, positions, enc_out,
               self_cache=None):
    h = apply_norm(x, lp["norm1"], cfg.norm, cfg.norm_eps)
    new_cache = self_cache
    if self_cache is None:
        a = ATT.attention_block(h, lp["attn"], cfg, policy,
                                positions=positions, causal=True,
                                impl=parallel.attn_impl)
    else:
        a, new_cache = ATT.attention_decode_step(h, self_cache, lp["attn"],
                                                 cfg, policy,
                                                 impl=parallel.attn_impl)
    x = x + apply_layer_scale(lp.get("gamma1"), a)
    h = apply_norm(x, lp["norm_x"], cfg.norm, cfg.norm_eps)
    enc_kv = ATT.encode_cross_kv(enc_out, lp["xattn"], cfg, policy)
    c = ATT.cross_attention(h, enc_kv, lp["xattn"], cfg, policy,
                            impl=parallel.attn_impl)
    x = x + apply_layer_scale(lp.get("gamma_x"), c)
    h = apply_norm(x, lp["norm2"], cfg.norm, cfg.norm_eps)
    m = mlp_block(h, lp["mlp"], cfg, policy)
    x = x + apply_layer_scale(lp.get("gamma2"), m)
    return PRM.constrain(x, ("batch", "seq", "embed")), new_cache


def encode(params, frames: Array, cfg: ModelConfig, policy: QuantPolicy,
           parallel: ParallelConfig) -> Array:
    """frames: (B, S_src, d_model) stub features -> encoder output."""
    x = quant_linear(frames.astype(policy.compute_dtype),
                     params["frontend_proj"], policy=policy)
    x = PRM.constrain(x, ("batch", "seq", "embed"))
    positions = jnp.arange(x.shape[1])
    body = functools.partial(_enc_layer, cfg=cfg, policy=policy,
                             parallel=parallel, positions=positions)
    blk = TF._maybe_remat(body, parallel)
    if parallel.scan_layers:
        x, _ = jax.lax.scan(lambda c, lw: (blk(c, lw), None), x,
                            params["enc_blocks"])
    else:
        for i in range(cfg.encdec.n_encoder_layers):
            x = blk(x, jax.tree.map(lambda p: p[i], params["enc_blocks"]))
    return apply_norm(x, params["enc_norm"], cfg.norm, cfg.norm_eps)


def forward(params, batch: Dict[str, Array], cfg: ModelConfig,
            policy: QuantPolicy, parallel: ParallelConfig):
    """Training forward: encode frames, decode target tokens. Returns logits."""
    enc_out = encode(params, batch["frames"], cfg, policy, parallel)
    x = jnp.asarray(params["embed"], policy.compute_dtype)[batch["tokens"]]
    x = PRM.constrain(x, ("batch", "seq", "embed"))
    positions = jnp.arange(x.shape[1])
    body = functools.partial(_dec_layer, cfg=cfg, policy=policy,
                             parallel=parallel, positions=positions,
                             enc_out=enc_out)
    blk = TF._maybe_remat(lambda xx, pp: body(xx, pp)[0], parallel)
    if parallel.scan_layers:
        x, _ = jax.lax.scan(lambda c, lw: (blk(c, lw), None), x,
                            params["dec_blocks"])
    else:
        for i in range(cfg.n_layers):
            x = blk(x, jax.tree.map(lambda p: p[i], params["dec_blocks"]))
    x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x,
                        jnp.asarray(params["head"], policy.compute_dtype))
    return PRM.constrain(logits, ("batch", "seq", "vocab"))


def loss_fn(params, batch, cfg, policy, parallel):
    logits = forward(params, batch, cfg, policy, parallel)
    ce = cross_entropy_loss(logits, batch["labels"], cfg.logit_softcap)
    return ce, {"ce": ce}


class EncDecDecodeState(NamedTuple):
    self_caches: Any          # stacked KVCache over decoder layers
    enc_out: Array            # (B, S_src, D) encoder output (fixed)


def init_decode_state(params, frames, cfg, policy, parallel, batch: int,
                      max_len: int, dtype=jnp.bfloat16):
    enc_out = encode(params, frames, cfg, policy, parallel)
    L = cfg.n_layers
    caches = ATT.KVCache(
        jnp.zeros((L, batch, cfg.n_kv_heads, max_len, cfg.hd), dtype),
        jnp.zeros((L, batch, cfg.n_kv_heads, max_len, cfg.hd), dtype),
        jnp.zeros((L,), jnp.int32))
    return EncDecDecodeState(caches, enc_out)


def decode_step(params, state: EncDecDecodeState, tokens: Array,
                cfg: ModelConfig, policy: QuantPolicy,
                parallel: ParallelConfig):
    x = jnp.asarray(params["embed"], policy.compute_dtype)[tokens]
    positions = jnp.arange(1)
    body = functools.partial(_dec_layer, cfg=cfg, policy=policy,
                             parallel=parallel, positions=positions,
                             enc_out=state.enc_out)

    def scan_body(x, inp):
        lp, cache = inp
        x2, nc = body(x, lp, self_cache=cache)
        return x2, nc

    if parallel.scan_layers:
        x, new_caches = jax.lax.scan(scan_body, x,
                                     (params["dec_blocks"],
                                      state.self_caches))
    else:
        outs = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda p: p[i], params["dec_blocks"])
            cache = jax.tree.map(lambda c: c[i], state.self_caches)
            x, nc = scan_body(x, (lp, cache))
            outs.append(nc)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x,
                        jnp.asarray(params["head"], policy.compute_dtype))
    return logits, EncDecDecodeState(new_caches, state.enc_out)
