"""Decoder-only LM assembly for all assigned architecture families.

Layer heterogeneity (Jamba's 1-attn-per-8 + MoE-every-2, Qwen3's all-MoE,
RWKV's attention-free stack) is handled with a *period group*: the layer
pattern repeats with period P = lcm(attention period, MoE period); the model
scans over L/P groups, unrolling the P heterogeneous layers inside the group
body. This keeps HLO size O(P) instead of O(L) (probe: 186s unrolled vs 2.5s
scanned compile at 20B scale) while supporting mixed layer kinds.

The same `group_apply` body is reused by the dry-run cost probes
(launch/dryrun.py) so per-layer FLOPs/bytes/collectives are measured from
exactly the compiled computation and multiplied by the group count.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig
from repro.core.layer_scale import apply_layer_scale
from repro.core.precision import QuantPolicy, quant_linear
from repro.models import params as PRM
from repro.models.params import ParamSpec
from repro.models import attention as ATT
from repro.models.common import apply_norm, cross_entropy_loss
from repro.models.mlp import mlp_block
from repro.models.moe import moe_block
from repro.models.ssm.mamba import mamba_block, MambaState
from repro.models.ssm.rwkv6 import rwkv6_block, rwkv_channel_mix, RWKVState

Array = jax.Array


# ---------------------------------------------------------------------------
# period structure
# ---------------------------------------------------------------------------

def period(cfg: ModelConfig) -> int:
    p = 1
    if cfg.attn_layer_period:
        p = math.lcm(p, cfg.attn_layer_period)
    if cfg.moe is not None and cfg.moe.every_n_layers > 1:
        p = math.lcm(p, cfg.moe.every_n_layers)
    assert (cfg.n_layers - cfg.first_k_dense) % p == 0, (cfg.n_layers, p)
    return p


def n_groups(cfg: ModelConfig) -> int:
    """Scanned period groups, after the ``first_k_dense`` leading layers."""
    return (cfg.n_layers - cfg.first_k_dense) // period(cfg)


# ---------------------------------------------------------------------------
# per-layer parameter specs
# ---------------------------------------------------------------------------

def _norm_spec(cfg) -> Dict[str, ParamSpec]:
    d = {"scale": ParamSpec((cfg.d_model,), ("embed",), "ones")}
    if cfg.norm == "layernorm":
        d["bias"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
    return d


def _attn_specs(cfg) -> Dict[str, ParamSpec]:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "wq": ParamSpec((D, H * m.qk_dim), ("embed", "heads"), "fan_in",
                            1.0),
            "wkv_a": ParamSpec((D, m.kv_lora_rank + m.qk_rope_dim),
                               ("embed", None), "fan_in", 1.0),
            "kv_norm": {"scale": ParamSpec((m.kv_lora_rank,), (None,),
                                           "ones")},
            "wkv_b": ParamSpec((m.kv_lora_rank,
                                H * (m.qk_nope_dim + m.v_dim)),
                               (None, "heads"), "fan_in", 1.0),
            "wo": ParamSpec((H * m.v_dim, D), ("heads", "embed"), "fan_in",
                            1.0),
        }
    return {
        "wq": ParamSpec((D, H * hd), ("embed", "heads"), "fan_in", 1.0),
        "wk": ParamSpec((D, KV * hd), ("embed", "kv_heads"), "fan_in", 1.0),
        "wv": ParamSpec((D, KV * hd), ("embed", "kv_heads"), "fan_in", 1.0),
        "wo": ParamSpec((H * hd, D), ("heads", "embed"), "fan_in", 1.0),
    }


def _mlp_specs(cfg, d_ff=None) -> Dict[str, ParamSpec]:
    D, FF = cfg.d_model, d_ff or cfg.d_ff
    s = {
        "w_up": ParamSpec((D, FF), ("embed", "mlp"), "fan_in", 1.0),
        "w_down": ParamSpec((FF, D), ("mlp", "embed"), "fan_in", 1.0),
    }
    if cfg.act == "swiglu":
        s["w_gate"] = ParamSpec((D, FF), ("embed", "mlp"), "fan_in", 1.0)
    return s


def _moe_specs(cfg) -> Dict[str, ParamSpec]:
    """Router over all ``moe.n_experts``; weights of the held experts only;
    the selection bias (sigmoid scoring; zero, not trained: no gradient
    reaches it) and the shared experts when the config has them."""
    moe = cfg.moe
    D, FF, E = cfg.d_model, moe.expert_ff or cfg.d_ff, moe.n_experts
    Eh = cfg.held[1]
    s = {
        "w_router": ParamSpec((D, E), ("embed", None), "fan_in", 1.0),
        "w_up": ParamSpec((Eh, D, FF), ("experts", "embed", "mlp"), "fan_in", 1.0),
        "w_down": ParamSpec((Eh, FF, D), ("experts", "mlp", "embed"), "fan_in", 1.0),
    }
    if cfg.act == "swiglu":
        s["w_gate"] = ParamSpec((Eh, D, FF), ("experts", "embed", "mlp"),
                                "fan_in", 1.0)
    if moe.scoring == "sigmoid":
        s["bias"] = ParamSpec((E,), (None,), "zeros")
    if moe.n_shared:
        s["shared"] = _mlp_specs(cfg, moe.n_shared * FF)
    return s


def _mamba_specs(cfg) -> Dict[str, ParamSpec]:
    mc = cfg.mamba
    D = cfg.d_model
    d_in = mc.expand * D
    dt_rank = mc.dt_rank or -(-D // 16)
    N = mc.d_state
    return {
        "w_in": ParamSpec((D, 2 * d_in), ("embed", "mlp"), "fan_in", 1.0),
        "conv_w": ParamSpec((mc.d_conv, d_in), ("conv", "mlp"), "normal", 0.02),
        "conv_b": ParamSpec((d_in,), ("mlp",), "zeros"),
        "w_x_proj": ParamSpec((d_in, dt_rank + 2 * N), ("mlp", None),
                              "fan_in", 1.0),
        "w_dt": ParamSpec((dt_rank, d_in), ("lora", "mlp"), "fan_in", 1.0),
        "dt_bias": ParamSpec((d_in,), ("mlp",), "zeros"),
        "A_log": ParamSpec((d_in, N), ("mlp", "state"), "constant", 0.0),
        "D": ParamSpec((d_in,), ("mlp",), "ones"),
        "w_out": ParamSpec((d_in, D), ("mlp", "embed"), "fan_in", 1.0),
    }


def _rwkv_specs(cfg) -> Dict[str, ParamSpec]:
    rc = cfg.rwkv
    D = cfg.d_model
    H = D // rc.head_dim
    lr = rc.mix_lora
    dr = rc.decay_lora
    mixes = {}
    for nm in ("r", "k", "v", "w", "g", "ck", "cr"):
        mixes[f"mu_{nm}"] = ParamSpec((D,), ("embed",), "constant", 0.5)
        mixes[f"mix_lora_b_{nm}"] = ParamSpec((lr, D), ("lora", "embed"),
                                              "zeros")
    return {
        **mixes,
        "mix_lora_a": ParamSpec((D, lr), ("embed", "lora"), "fan_in", 1.0),
        "w0": ParamSpec((D,), ("embed",), "constant", -6.0),
        "w_lora_a": ParamSpec((D, dr), ("embed", "lora"), "fan_in", 1.0),
        "w_lora_b": ParamSpec((dr, D), ("lora", "embed"), "zeros"),
        "u": ParamSpec((D,), ("embed",), "normal", 0.5),
        "wr": ParamSpec((D, D), ("embed", "heads"), "fan_in", 1.0),
        "wk": ParamSpec((D, D), ("embed", "heads"), "fan_in", 1.0),
        "wv": ParamSpec((D, D), ("embed", "heads"), "fan_in", 1.0),
        "wg": ParamSpec((D, D), ("embed", "heads"), "fan_in", 1.0),
        "wo": ParamSpec((D, D), ("heads", "embed"), "fan_in", 1.0),
        "ln_x": ParamSpec((D,), ("embed",), "ones"),
        # channel mix
        "w_key": ParamSpec((D, cfg.d_ff), ("embed", "mlp"), "fan_in", 1.0),
        "w_value": ParamSpec((cfg.d_ff, D), ("mlp", "embed"), "fan_in", 1.0),
        "w_receptance": ParamSpec((D, D), ("embed", "heads"), "fan_in", 1.0),
    }


def layer_specs(cfg: ModelConfig, layer_idx: int,
                dense: bool = False) -> Dict[str, Any]:
    """Layer ``layer_idx`` of a period group; ``dense``: a leading dense
    layer (attention and a ``d_ff`` MLP)."""
    kind = "attn" if dense else cfg.layer_kind(layer_idx)
    specs: Dict[str, Any] = {"norm1": _norm_spec(cfg), "norm2": _norm_spec(cfg)}
    if kind == "attn":
        specs["attn"] = _attn_specs(cfg)
    elif kind == "mamba":
        specs["mamba"] = _mamba_specs(cfg)
    elif kind == "rwkv":
        specs["rwkv"] = _rwkv_specs(cfg)
    if kind != "rwkv":   # rwkv channel-mix params live in the rwkv dict
        if not dense and cfg.layer_is_moe(layer_idx):
            specs["moe"] = _moe_specs(cfg)
            if cfg.moe.dense_residual:
                specs["dense_mlp"] = _mlp_specs(cfg, cfg.moe.dense_residual_ff)
        else:
            specs["mlp"] = _mlp_specs(cfg)
    if cfg.layer_scale_init is not None:
        init = ("zeros" if cfg.layer_scale_init == 0.0 else "constant")
        specs["gamma1"] = ParamSpec((cfg.d_model,), ("embed",), init,
                                    cfg.layer_scale_init)
        specs["gamma2"] = ParamSpec((cfg.d_model,), ("embed",), init,
                                    cfg.layer_scale_init)
    return specs


def _stack_specs(specs, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.logical,
                            s.init, s.scale, s.dtype),
        specs, is_leaf=PRM.is_spec)


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    P = period(cfg)
    G = n_groups(cfg)
    blocks = {f"pos{i}": _stack_specs(layer_specs(cfg, i), G)
              for i in range(P)}
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                           "normal", 0.02),
        "blocks": blocks,
        "final_norm": _norm_spec(cfg),
    }
    if cfg.first_k_dense:
        specs["dense_blocks"] = {"pos0": _stack_specs(
            layer_specs(cfg, 0, dense=True), cfg.first_k_dense)}
    if not cfg.tie_embeddings:
        specs["head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                  ("embed", "vocab"), "fan_in", 1.0)
    if cfg.frontend is not None:
        # learned projection from the stub frontend features into d_model
        specs["frontend_proj"] = ParamSpec(
            (cfg.d_model, cfg.d_model), ("embed", "embed"), "fan_in", 1.0)
    return specs


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _layer_apply(x: Array, lp: Dict, cfg: ModelConfig, policy: QuantPolicy,
                 parallel: ParallelConfig, layer_idx: int, *,
                 positions: Array, state=None, prefill=None,
                 rope_cache=None, paged=None, dense: bool = False):
    """One transformer layer. Returns (x, new_state, aux_loss, stats):
    ``stats`` the MoE layer's routing counters ({} without one).
    ``dense``: a leading dense layer (``first_k_dense``).

    ``prefill=(admit, prompt_lens)`` is the serving admission mode: the
    attention sub-block runs ``attention_prefill`` (the exact training
    forward plus an admit-masked cache write into ``state``) and admitted
    slots' lengths reset to their prompt length; everything after the
    sequence mixer is the shared layer body, so serve prefill can't drift
    from the training forward. ``rope_cache=(cos, sin)`` — pre-gathered
    RoPE table rows for this call's positions, hoisted once per step by
    the serve engine instead of recomputed per layer.

    ``paged=(tables, pref_lens)`` switches the serving modes onto the
    block-pool cache (``state`` is then a PagedKVCache): prefill runs the
    chunked ``attention_paged_prefill`` (suffix only, adopted prefix read
    through the table) and decode appends through the table/trash-block
    discipline. ``pref_lens`` is only read in prefill mode."""
    kind = "attn" if dense else cfg.layer_kind(layer_idx)
    aux = jnp.zeros((), jnp.float32)
    stats = {}
    g1 = lp.get("gamma1")
    g2 = lp.get("gamma2")
    bq, bk = parallel.attn_block_q, parallel.attn_block_k

    h = apply_norm(x, lp["norm1"], cfg.norm, cfg.norm_eps)
    new_state = state
    if kind == "attn":
        if prefill is not None:
            admit, prompt_lens = prefill
            if paged is not None:
                tables, pref_lens = paged
                a, new_state = ATT.attention_paged_prefill(
                    h, state, tables, lp["attn"], cfg, policy, admit=admit,
                    pref_lens=pref_lens, prompt_lens=prompt_lens,
                    rope_cache=rope_cache, impl=parallel.attn_impl)
            else:
                a, new_state = ATT.attention_prefill(
                    h, state, lp["attn"], cfg, policy, admit=admit,
                    rope_cache=rope_cache, impl=parallel.attn_impl,
                    block_q=bq, block_k=bk)
            new_state = new_state._replace(
                length=jnp.where(admit, prompt_lens, new_state.length))
        elif state is None and cfg.mla is not None:
            a = ATT.mla_block(h, lp["attn"], cfg, policy, positions=positions,
                              impl=parallel.attn_impl, block_q=bq,
                              block_k=bk)
        elif state is None:
            a = ATT.attention_block(h, lp["attn"], cfg, policy,
                                    positions=positions,
                                    impl=parallel.attn_impl,
                                    block_q=bq, block_k=bk)
        elif paged is not None:
            a, new_state = ATT.attention_paged_decode_step(
                h, state, paged[0], lp["attn"], cfg, policy,
                rope_cache=rope_cache, impl=parallel.attn_impl)
        else:
            a, new_state = ATT.attention_decode_step(h, state, lp["attn"],
                                                     cfg, policy,
                                                     rope_cache=rope_cache,
                                                     impl=parallel.attn_impl,
                                                     block_k=bk)
    elif kind == "mamba":
        a, new_state = mamba_block(h, lp["mamba"], cfg, policy, state=state)
    else:  # rwkv
        a, new_state = rwkv6_block(h, lp["rwkv"], cfg, policy, state=state)
    x = x + apply_layer_scale(g1, a)
    x = PRM.constrain(x, ("batch", "seq", "embed"))

    h2 = apply_norm(x, lp["norm2"], cfg.norm, cfg.norm_eps)
    if kind == "rwkv":
        cm_prev = state.cm_x_prev if state is not None else None
        m, cm_last = rwkv_channel_mix(h2, lp["rwkv"], cfg, policy,
                                      x_prev=cm_prev)
        if state is not None:
            new_state = new_state._replace(cm_x_prev=cm_last)
    elif not dense and cfg.layer_is_moe(layer_idx):
        m, aux, stats = moe_block(h2, lp["moe"], cfg, policy)
        if cfg.moe.dense_residual:
            m = m + mlp_block(h2, lp["dense_mlp"], cfg, policy)
    else:
        m = mlp_block(h2, lp["mlp"], cfg, policy)
    x = x + apply_layer_scale(g2, m)
    x = PRM.constrain(x, ("batch", "seq", "embed"))
    return x, new_state, aux, stats


def group_apply(x: Array, gp: Dict[str, Dict], cfg: ModelConfig,
                policy: QuantPolicy, parallel: ParallelConfig, *,
                positions: Array, states: Optional[Dict] = None,
                rope_cache=None, paged=None):
    """Apply one period-group (P heterogeneous layers unrolled).
    gp: {"pos{i}": layer params (unstacked)}. Returns (x, new_states, aux,
    stats): ``stats`` the group's MoE routing counters by layer."""
    P = period(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_states, stats = {}, {}
    for i in range(P):
        st = states.get(f"pos{i}") if states is not None else None
        x, ns, aux, st_i = _layer_apply(x, gp[f"pos{i}"], cfg, policy,
                                        parallel, i, positions=positions,
                                        state=st, rope_cache=rope_cache,
                                        paged=paged)
        aux_total = aux_total + aux
        if st_i:
            stats[f"pos{i}"] = st_i
        if states is not None:
            new_states[f"pos{i}"] = ns
    return x, (new_states if states is not None else None), aux_total, stats


def _maybe_remat(fn, parallel: ParallelConfig):
    if parallel.remat == "none":
        return fn
    if parallel.remat == "save_dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)   # "block": save only group inputs


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def embed_input(params, tokens: Array, cfg: ModelConfig,
                policy: QuantPolicy, extra_embeds: Optional[Array] = None):
    with jax.named_scope("embed"):
        x = jnp.asarray(params["embed"], policy.compute_dtype)[tokens]
        if extra_embeds is not None:
            fe = quant_linear(extra_embeds.astype(policy.compute_dtype),
                              PRM.use_weight(params["frontend_proj"],
                                             ("embed", "embed"),
                                             policy.compute_dtype),
                              policy=policy)
            x = jnp.concatenate([fe, x], axis=1)
        return PRM.constrain(x, ("batch", "seq", "embed"))


def lm_head(params, x: Array, cfg: ModelConfig, policy: QuantPolicy):
    with jax.named_scope("head"):
        x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        if cfg.tie_embeddings:
            w = jnp.swapaxes(
                jnp.asarray(params["embed"], policy.compute_dtype), 0, 1)
            logits = jnp.einsum("btd,dv->btv", x, w)
        else:
            # head stays un-quantized: the paper quantizes transformer
            # linears, not the (huge-vocab) classifier; also numerically
            # sensitive.
            logits = jnp.einsum(
                "btd,dv->btv", x.astype(policy.compute_dtype),
                PRM.use_weight(params["head"], ("embed", "vocab"),
                               policy.compute_dtype))
        # vocab gets the model axis (takes precedence over seq under SP)
        return PRM.constrain(logits, ("batch", None, "vocab"))


def _route_stats(per_layer) -> Dict[str, Array]:
    """The step's routing counters from the MoE layers' (stacked by group,
    keyed by position in the group): held slots as the mean per layer,
    load ratios as the worst layer's, balance loss summed."""
    flat: Dict[str, list] = {}
    for st in per_layer.values():
        for k, v in st.items():
            flat.setdefault(k, []).append(jnp.ravel(v))
    out = {}
    for k, vs in flat.items():
        v = jnp.concatenate(vs)
        out[k] = (jnp.mean(v) if k == "moe/held_slots" else
                  jnp.sum(v) if k == "moe/balance_loss" else jnp.max(v))
    return out


def forward(params, tokens: Array, cfg: ModelConfig, policy: QuantPolicy,
            parallel: ParallelConfig, extra_embeds: Optional[Array] = None,
            with_stats: bool = False):
    """Training/prefill forward: the ``first_k_dense`` leading layers, then
    the scanned period groups. Returns (logits, aux_loss), and with
    ``with_stats`` the MoE routing counters third ({} without MoE)."""
    x = embed_input(params, tokens, cfg, policy, extra_embeds)
    positions = jnp.arange(x.shape[1])
    body = functools.partial(group_apply, cfg=cfg, policy=policy,
                             parallel=parallel, positions=positions)

    def group_fwd(xx, pp):
        out, _, a, st = body(xx, pp)
        return out, (a, st)

    def dense_fwd(xx, lp):
        out, _, _, _ = _layer_apply(xx, lp, cfg, policy, parallel, 0,
                                    positions=positions, dense=True)
        return out

    dense_blk = _maybe_remat(dense_fwd, parallel)
    for i in range(cfg.first_k_dense):
        x = dense_blk(x, jax.tree.map(lambda p: p[i],
                                      params["dense_blocks"]["pos0"]))

    blk = _maybe_remat(group_fwd, parallel)
    if parallel.scan_layers and n_groups(cfg) > 1:
        x, (auxs, stats) = jax.lax.scan(blk, x, params["blocks"])
    else:
        outs = []
        for g in range(n_groups(cfg)):
            x, o = blk(x, jax.tree.map(lambda p: p[g], params["blocks"]))
            outs.append(o)
        auxs, stats = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    logits = lm_head(params, x, cfg, policy)
    if with_stats:
        return logits, jnp.sum(auxs), _route_stats(stats)
    return logits, jnp.sum(auxs)


def loss_fn(params, batch: Dict[str, Array], cfg: ModelConfig,
            policy: QuantPolicy, parallel: ParallelConfig):
    """Mean next-token cross-entropy plus the MoE layers' balance loss
    (each already times its ``balance_alpha``); the metrics carry both and
    the routing counters."""
    logits, aux, stats = forward(params, batch["tokens"], cfg, policy,
                                 parallel,
                                 extra_embeds=batch.get("extra_embeds"),
                                 with_stats=True)
    # frontend tokens (prepended) carry no next-token target
    n_front = logits.shape[1] - batch["labels"].shape[1]
    if n_front:
        logits = logits[:, n_front:]
    ce = cross_entropy_loss(logits, batch["labels"], cfg.logit_softcap)
    return ce + aux, {"ce": ce, "moe_aux": aux, **stats}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16):
    """Stacked-over-groups recurrent state for every position-in-period."""
    _require_cache_layout(cfg, "init_decode_state")
    P = period(cfg)
    G = n_groups(cfg)

    def one(i):
        kind = cfg.layer_kind(i)
        if kind == "attn":
            return ATT.KVCache(
                jnp.zeros((G, batch, cfg.n_kv_heads, max_len, cfg.hd), dtype),
                jnp.zeros((G, batch, cfg.n_kv_heads, max_len, cfg.hd), dtype),
                jnp.zeros((G,), jnp.int32))
        if kind == "mamba":
            d_in = cfg.mamba.expand * cfg.d_model
            return MambaState(
                jnp.zeros((G, batch, cfg.mamba.d_conv - 1, d_in), dtype),
                jnp.zeros((G, batch, d_in, cfg.mamba.d_state), jnp.float32))
        H = cfg.d_model // cfg.rwkv.head_dim
        return RWKVState(
            jnp.zeros((G, batch, H, cfg.rwkv.head_dim, cfg.rwkv.head_dim),
                      jnp.float32),
            jnp.zeros((G, batch, cfg.d_model), dtype),
            jnp.zeros((G, batch, cfg.d_model), dtype))

    return {f"pos{i}": one(i) for i in range(P)}


def decode_state_logical_axes(cfg: ModelConfig):
    """Logical axes for the decode state (for sharding assignment)."""
    P = period(cfg)

    def one(i):
        kind = cfg.layer_kind(i)
        if kind == "attn":
            ax = ("layers", "batch", "kv_heads", "cache_seq", None)
            return ATT.KVCache(ax, ax, ("layers",))
        if kind == "mamba":
            return MambaState(("layers", "batch", None, "mlp"),
                              ("layers", "batch", "mlp", None))
        return RWKVState(("layers", "batch", "heads", None, None),
                         ("layers", "batch", "embed"),
                         ("layers", "batch", "embed"))

    return {f"pos{i}": one(i) for i in range(P)}


def decode_step(params, states, tokens: Array, cfg: ModelConfig,
                policy: QuantPolicy, parallel: ParallelConfig, *,
                rope_cache=None):
    """One-token decode. tokens: (B, 1). Returns (logits (B,1,V), states).

    ``rope_cache=(cos, sin)`` — this step's pre-gathered RoPE rows (shape
    (B, 1, hd/2)); the serve engine gathers them once per step from its
    hoisted tables so layers skip the cos/sin recompute."""
    x = embed_input(params, tokens, cfg, policy)
    positions = jnp.arange(1)   # RoPE position comes from cache length inside
    body = functools.partial(group_apply, cfg=cfg, policy=policy,
                             parallel=parallel, positions=positions,
                             rope_cache=rope_cache)

    def scan_body(x, inp):
        gp, st = inp
        x2, ns, _, _ = body(x, gp, states=st)
        return x2, ns

    if parallel.scan_layers and n_groups(cfg) > 1:
        x, new_states = jax.lax.scan(scan_body, x,
                                     (params["blocks"], states))
    else:
        G = n_groups(cfg)
        outs = []
        for g in range(G):
            gp = jax.tree.map(lambda p: p[g], params["blocks"])
            st = jax.tree.map(lambda s: s[g], states)
            x, ns = scan_body(x, (gp, st))
            outs.append(ns)
        new_states = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    logits = lm_head(params, x, cfg, policy)
    return logits, new_states


# ---------------------------------------------------------------------------
# serving (continuous batching: per-slot KV caches)
# ---------------------------------------------------------------------------

def _require_cache_layout(cfg: ModelConfig, what: str):
    """Decode and serving caches hold per-head keys and values of one
    width: latent attention (a latent cache) and leading dense layers are
    not served yet."""
    if cfg.mla is not None or cfg.first_k_dense:
        raise NotImplementedError(
            f"{what}: {cfg.name} has latent attention or leading dense "
            "layers, which train but do not decode yet")


def _require_all_attention(cfg: ModelConfig, what: str):
    _require_cache_layout(cfg, what)
    P = period(cfg)
    kinds = {cfg.layer_kind(i) for i in range(P)}
    if kinds != {"attn"}:
        raise NotImplementedError(
            f"{what} supports all-attention stacks only (got layer kinds "
            f"{sorted(kinds)} for {cfg.name}); ssm/hybrid archs decode "
            "through decode_step one token at a time")
    if cfg.frontend is not None:
        raise NotImplementedError(f"{what}: multimodal frontends are a "
                                  "training-path feature")


def init_serve_state(cfg: ModelConfig, max_batch: int, max_len: int,
                     dtype=jnp.bfloat16):
    """Per-slot (continuous-batching) KV caches, stacked over groups.

    Layout per position-in-period: ``KVCache`` with k/v of shape
    (G, max_batch, n_kv_heads, max_len, hd) and per-slot lengths (G, B).
    Unlike ``init_decode_state`` every batch slot tracks its own length, so
    slots can hold sequences at different positions (admit/evict freely).
    """
    _require_all_attention(cfg, "init_serve_state")
    P = period(cfg)
    G = n_groups(cfg)
    shape = (G, max_batch, cfg.n_kv_heads, max_len, cfg.hd)
    return {f"pos{i}": ATT.KVCache(jnp.zeros(shape, dtype),
                                   jnp.zeros(shape, dtype),
                                   jnp.zeros((G, max_batch), jnp.int32))
            for i in range(P)}


def serve_state_logical_axes(cfg: ModelConfig):
    """Logical axes for the serve state — cache leaves shard like the
    decode state (batch over data, kv_heads over model); lengths shard
    over batch with the slots they describe."""
    P = period(cfg)
    ax = ("layers", "batch", "kv_heads", "cache_seq", None)
    return {f"pos{i}": ATT.KVCache(ax, ax, ("layers", "batch"))
            for i in range(P)}


def init_paged_serve_state(cfg: ModelConfig, num_blocks: int,
                           block_size: int, max_batch: int,
                           dtype=jnp.bfloat16):
    """Block-pool KV caches, stacked over groups (DESIGN.md §10).

    Layout per position-in-period: ``PagedKVCache`` with k/v pools of
    shape (G, num_blocks + 1, n_kv_heads, block_size, hd) — one extra
    *trash* block at index ``num_blocks`` absorbs masked writes — and
    per-slot absolute lengths (G, max_batch). Unlike
    :func:`init_serve_state` the cache footprint scales with
    ``num_blocks`` (live tokens), not ``max_batch × max_len``; which slot
    owns which block is the host-side block table
    (serve/paged/block_pool.py), passed to every jitted step.
    """
    _require_all_attention(cfg, "init_paged_serve_state")
    P = period(cfg)
    G = n_groups(cfg)
    shape = (G, num_blocks + 1, cfg.n_kv_heads, block_size, cfg.hd)
    return {f"pos{i}": ATT.PagedKVCache(jnp.zeros(shape, dtype),
                                        jnp.zeros(shape, dtype),
                                        jnp.zeros((G, max_batch), jnp.int32))
            for i in range(P)}


def set_serve_lengths(states, lens: Array):
    """Overwrite every group's per-slot lengths with ``lens`` (B,) int32.

    The host scheduler is the source of truth for how many KV cells per
    slot are *valid*; the device leaf normally tracks it for free (+1
    per decode step, ``prompt_lens`` on prefill), but a speculative
    verify call commits draft KVs optimistically and a partial rejection
    leaves the leaf over-counting. The engine re-syncs from host truth
    with this (one tiny jitted update, cache donated) lazily — only
    before a plain decode step actually reads the leaf again
    (DESIGN.md §12).
    """
    out = {}
    for key, st in states.items():
        G = st.length.shape[0]
        new = jnp.broadcast_to(lens.astype(jnp.int32)[None, :],
                               (G, lens.shape[0]))
        out[key] = st._replace(length=new)
    return out


def paged_state_logical_axes(cfg: ModelConfig):
    """Logical axes for the paged serve state. Blocks are shared across
    batch slots, so the pool cannot shard over ``data`` the way the ring
    cache's batch dim does — it replicates there and shards kv_heads over
    ``model``; lengths shard over batch with the slots they describe."""
    P = period(cfg)
    ax = ("layers", None, "kv_heads", None, None)
    return {f"pos{i}": ATT.PagedKVCache(ax, ax, ("layers", "batch"))
            for i in range(P)}


def paged_prefill(params, states, tables: Array, tokens: Array,
                  pref_lens: Array, prompt_lens: Array, admit: Array,
                  cfg: ModelConfig, policy: QuantPolicy,
                  parallel: ParallelConfig, *, last_only: bool = False,
                  rope_cache=None):
    """Seed admitted slots' block-table caches from their prompt
    *suffixes* (the part the prefix cache didn't already hold).

    tokens: (B, S) suffix tokens right-padded to a common S;
    pref_lens: (B,) adopted prefix lengths (block multiples, 0 = no
    sharing); prompt_lens: (B,) full prompt lengths; admit: (B,) bool;
    tables: (B, n_blocks_per_slot) int32. Returns (logits, new states) —
    logits (B, S, V), or (B, 1, V) with ``last_only`` (each slot's last
    valid prompt position, the only row sampling needs). With
    ``pref_lens == 0`` this is math-for-math the ring ``serve_prefill``
    dense path, which the paged-vs-ring parity tests pin.
    """
    _require_all_attention(cfg, "paged_prefill")
    x = embed_input(params, tokens, cfg, policy)
    positions = jnp.arange(tokens.shape[1])
    paged = (tables, pref_lens)

    def body(xx, inp):
        gp, st = inp
        new_st = {}
        for i in range(period(cfg)):
            xx, new_st[f"pos{i}"], _, _ = _layer_apply(
                xx, gp[f"pos{i}"], cfg, policy, parallel, i,
                positions=positions, state=st[f"pos{i}"],
                prefill=(admit, prompt_lens), rope_cache=rope_cache,
                paged=paged)
        return xx, new_st

    if parallel.scan_layers and n_groups(cfg) > 1:
        x, new_states = jax.lax.scan(body, x, (params["blocks"], states))
    else:
        outs = []
        for g in range(n_groups(cfg)):
            gp = jax.tree.map(lambda p: p[g], params["blocks"])
            st = jax.tree.map(lambda s: s[g], states)
            x, ns = body(x, (gp, st))
            outs.append(ns)
        new_states = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    if last_only:
        idx = jnp.clip(prompt_lens - pref_lens - 1, 0, x.shape[1] - 1)
        x = x[jnp.arange(x.shape[0]), idx][:, None]
    logits = lm_head(params, x, cfg, policy)
    return logits, new_states


def paged_decode_step(params, states, tables: Array, tokens: Array,
                      cfg: ModelConfig, policy: QuantPolicy,
                      parallel: ParallelConfig, *, rope_cache=None):
    """One-token decode over the block-pool cache. tokens: (B, 1);
    tables: (B, n_blocks_per_slot) int32. Returns (logits (B, 1, V),
    states). Same lockstep-length discipline as :func:`decode_step`;
    the per-slot write lands in the table's block for ``length[b]``
    (the engine guarantees it exists for live slots)."""
    _require_all_attention(cfg, "paged_decode_step")
    x = embed_input(params, tokens, cfg, policy)
    positions = jnp.arange(1)   # RoPE position comes from cache length inside
    body = functools.partial(group_apply, cfg=cfg, policy=policy,
                             parallel=parallel, positions=positions,
                             rope_cache=rope_cache, paged=(tables, None))

    def scan_body(x, inp):
        gp, st = inp
        x2, ns, _, _ = body(x, gp, states=st)
        return x2, ns

    if parallel.scan_layers and n_groups(cfg) > 1:
        x, new_states = jax.lax.scan(scan_body, x,
                                     (params["blocks"], states))
    else:
        G = n_groups(cfg)
        outs = []
        for g in range(G):
            gp = jax.tree.map(lambda p: p[g], params["blocks"])
            st = jax.tree.map(lambda s: s[g], states)
            x, ns = scan_body(x, (gp, st))
            outs.append(ns)
        new_states = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    logits = lm_head(params, x, cfg, policy)
    return logits, new_states


def serve_prefill(params, states, tokens: Array, prompt_lens: Array,
                  admit: Array, cfg: ModelConfig, policy: QuantPolicy,
                  parallel: ParallelConfig, *, last_only: bool = False,
                  rope_cache=None):
    """Seed admitted slots' caches from their (padded) prompts.

    tokens: (B, S) prompts right-padded to a common S <= max_len;
    prompt_lens: (B,) true lengths; admit: (B,) bool — which slots are
    being (re)filled. Returns (logits (B, S, V), new states). Logits at
    positions >= prompt_lens[b] (and for non-admitted slots) are garbage;
    callers read position ``prompt_lens[b] - 1``. Because the attention is
    the exact dense training forward, prefill logits match ``forward`` on
    the same tokens — the parity tests in tests/test_serve.py pin this.

    ``last_only=True`` gathers each slot's last valid hidden state before
    the lm head and returns logits of shape (B, 1, V) — the serving loop
    only samples from that row, and the (S, vocab) projection is by far
    the largest prefill matmul. Norm + head are positionwise, so the
    gathered row equals ``logits[b, prompt_lens[b]-1]`` of the full call.
    """
    _require_all_attention(cfg, "serve_prefill")
    x = embed_input(params, tokens, cfg, policy)
    positions = jnp.arange(tokens.shape[1])

    def body(xx, inp):
        gp, st = inp
        new_st = {}
        for i in range(period(cfg)):
            xx, new_st[f"pos{i}"], _, _ = _layer_apply(
                xx, gp[f"pos{i}"], cfg, policy, parallel, i,
                positions=positions, state=st[f"pos{i}"],
                prefill=(admit, prompt_lens), rope_cache=rope_cache)
        return xx, new_st

    if parallel.scan_layers and n_groups(cfg) > 1:
        x, new_states = jax.lax.scan(body, x, (params["blocks"], states))
    else:
        outs = []
        for g in range(n_groups(cfg)):
            gp = jax.tree.map(lambda p: p[g], params["blocks"])
            st = jax.tree.map(lambda s: s[g], states)
            x, ns = body(x, (gp, st))
            outs.append(ns)
        new_states = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    if last_only:
        x = x[jnp.arange(x.shape[0]), jnp.maximum(prompt_lens - 1, 0)][:, None]
    logits = lm_head(params, x, cfg, policy)
    return logits, new_states
