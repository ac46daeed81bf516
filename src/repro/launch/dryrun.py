"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) cell with ShapeDtypeStruct inputs (zero allocation), record
memory_analysis / cost_analysis / collective schedule, and emit the
roofline terms.

Cost assembly: XLA cost_analysis counts a scan body ONCE (probe-verified:
scan reports 1/L of unrolled FLOPs), so per-cell costs are assembled from
per-component compiles:

    train:   total = full + (n_micro-1)·micro + n_micro·(n_groups-1)·group
    prefill: total = full + (n_groups-1)·group_fwd
    decode:  total = full + (n_groups-1)·group_dec
    encdec:  + (n_enc_layers-1)·enc_group  etc.
    clip:    total = full + (L_vis-1)·vis_block + (L_txt-1)·txt_block

where `full` compiles the real scanned program (the compile-proof +
memory_analysis deliverable) and each probe compiles exactly the scanned
body at identical shapes/shardings.

Usage:
    python -m repro.launch.dryrun --arch smollm-360m --shape train_4k \
        --mesh single --out results/dryrun
    python -m repro.launch.dryrun --all --mesh both
"""
if __name__ == "__main__":
    # run as a script: 512 fake host devices (REPRO_DRYRUN_DEVICES=N
    # overrides), appended before jax's first backend init; importing the
    # module leaves XLA_FLAGS alone
    from repro.host_devices import force_host_device_count
    force_host_device_count(default=512)

import argparse
import functools
import json
import os
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ALL_ARCHS, PAPER_ARCH, get_config, shapes_for)
from repro.configs.base import (CLIPConfig, ParallelConfig, ShapeConfig,
                                SHAPES, TrainConfig)
from repro.core.precision import QuantPolicy
from repro.distributed.hlo_analysis import (collective_summary,
                                            count_dot_flops_by_dtype)
from repro.distributed.roofline import RooflineCell, model_flops
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.models import params as PRM
from repro.models import transformer as TF
from repro.models import encdec as ED
from repro.models.params import (ParamSpec, abstract_params, default_rules,
                                 logical_to_pspec, specs_to_shardings,
                                 _divisible)
from repro.train.engine import batch_shardings, make_engine, make_shard_ctx

# ---------------------------------------------------------------------------
# per-arch parallel runbook (what makes each model FIT; see DESIGN.md §6)
# ---------------------------------------------------------------------------

RUNBOOK: Dict[str, Dict] = {
    "smollm-360m":           dict(fsdp=False, n_micro=1),
    "starcoder2-3b":         dict(fsdp=False, n_micro=2),
    "granite-20b":           dict(fsdp=True,  n_micro=4),
    "minitron-8b":           dict(fsdp=True,  n_micro=2),
    "qwen3-moe-30b-a3b":     dict(fsdp=True,  n_micro=4),
    "arctic-480b":           dict(fsdp=True,  n_micro=8),
    "internvl2-76b":         dict(fsdp=True,  n_micro=8),
    "jamba-v0.1-52b":        dict(fsdp=True,  n_micro=4),
    "rwkv6-1.6b":            dict(fsdp=False, n_micro=1),
    "seamless-m4t-large-v2": dict(fsdp=False, n_micro=1),
    "clip-vit-huge":         dict(fsdp=True,  n_micro=1),
}

# §Perf winners per arch (hypothesis->measure log in EXPERIMENTS.md §Perf).
# Applied on top of RUNBOOK via --optimized. Per-arch rationale:
#   * ZeRO-3 weight gathers (int8 wire) win when per-layer weights are
#     SMALL vs per-microbatch activations (dense archs, qwen's 768-wide
#     experts); they LOSE for arctic/jamba's multi-GB expert tensors, so
#     those keep GSPMD's activation-reduce choice.
#   * clip (1B params) needs no TP at all: pure-DP over all 256 chips.
#   * kv-head replication (run_cell default for train/prefill) helped qwen
#     (kv=4) but hurt internvl2 (kv=8) — internvl pins shard_kv_heads=True.
OPTIMIZED: Dict[str, Dict] = {
    "granite-20b":       dict(fsdp_gather_weights=True, gather_wire="int8",
                              shard_kv_heads=False),
    "minitron-8b":       dict(fsdp_gather_weights=True, gather_wire="int8",
                              shard_kv_heads=False),
    "qwen3-moe-30b-a3b": dict(fsdp_gather_weights=True, gather_wire="int8",
                              n_micro=2, shard_kv_heads=False),
    "jamba-v0.1-52b":    dict(shard_kv_heads=False),
    "internvl2-76b":     dict(fsdp_gather_weights=True, gather_wire="int8",
                              n_micro=4),
    "clip-vit-huge":     dict(fsdp_gather_weights=True, pure_dp=True),
}


def parallel_for(arch: str, multi_pod: bool, overrides: Optional[Dict] = None
                 ) -> ParallelConfig:
    rb = dict(RUNBOOK.get(arch, {}))
    rb.update(overrides or {})
    n_micro = rb.pop("n_micro", 1)
    mesh_shape = (2, 16, 16) if multi_pod else (16, 16)
    mesh_axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    par = ParallelConfig(mesh_shape=mesh_shape, mesh_axes=mesh_axes,
                         scan_layers=True, remat=rb.pop("remat", "block"),
                         fsdp=rb.pop("fsdp", False),
                         fsdp_gather_weights=rb.pop("fsdp_gather_weights",
                                                    False),
                         gather_wire=rb.pop("gather_wire", "bf16"),
                         pure_dp=rb.pop("pure_dp", False),
                         sequence_parallel=rb.pop("sequence_parallel", False),
                         shard_kv_heads=rb.pop("shard_kv_heads", True),
                         attn_impl=rb.pop("attn_impl", "flash_scan"))
    return par, n_micro


# ---------------------------------------------------------------------------
# metrics extraction
# ---------------------------------------------------------------------------

def metrics_of(compiled, n_devices: int) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = collective_summary(hlo, n_devices)
    dots = count_dot_flops_by_dtype(hlo)
    ma = compiled.memory_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "dot_flops_int8": dots["int8"],
        "dot_flops_other": dots["other"],
        "wire_bytes": colls["wire_bytes_per_device"],
        "coll_ops": colls["n_ops"],
        "coll_bytes_by_kind": {k: colls[k] for k in
                               ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute")},
        "temp_bytes": int(ma.temp_size_in_bytes),
        "arg_bytes": int(ma.argument_size_in_bytes),
        "out_bytes": int(ma.output_size_in_bytes),
    }


def combine(parts) -> Dict[str, float]:
    """total = Σ count·metrics; memory fields come from the 'full' part."""
    tot = {"flops": 0.0, "bytes_accessed": 0.0, "dot_flops_int8": 0.0,
           "dot_flops_other": 0.0, "wire_bytes": 0.0}
    mem = {}
    for name, count, m in parts:
        for k in tot:
            tot[k] += count * m[k]
        if name == "full":
            mem = {k: m[k] for k in ("temp_bytes", "arg_bytes", "out_bytes")}
    tot.update(mem)
    return tot


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(arch: str, shape: ShapeConfig, cfg) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    if isinstance(cfg, CLIPConfig):
        # paper shape: global batch 16384 (CLIP's own training recipe);
        # assignment train_4k batch is token-denominated — we keep CLIP's
        # native batch and note it in EXPERIMENTS.md.
        B = 16384
        return {"images": sds((B, cfg.image_size, cfg.image_size, 3),
                              jnp.bfloat16),
                "texts": sds((B, cfg.text_ctx), jnp.int32)}
    if cfg.family == "encdec":
        if shape.kind == "train":
            return {"frames": sds((B, S, cfg.d_model), jnp.bfloat16),
                    "tokens": sds((B, S), jnp.int32),
                    "labels": sds((B, S), jnp.int32)}
        if shape.kind == "prefill":
            return {"frames": sds((B, S, cfg.d_model), jnp.bfloat16),
                    "tokens": sds((B, 1), jnp.int32)}
        return {"tokens": sds((B, 1), jnp.int32)}   # decode
    out = {"tokens": sds((B, S if shape.kind != "decode" else 1), jnp.int32)}
    if shape.kind == "train":
        out["labels"] = sds((B, S), jnp.int32)
        if cfg.frontend:
            out["extra_embeds"] = sds((B, cfg.frontend_tokens, cfg.d_model),
                                      jnp.bfloat16)
    if shape.kind == "prefill" and cfg.frontend:
        out["extra_embeds"] = sds((B, cfg.frontend_tokens, cfg.d_model),
                                  jnp.bfloat16)
    return out


# ---------------------------------------------------------------------------
# cell runners
# ---------------------------------------------------------------------------

def run_train_cell(arch, cfg, shape, mesh, par, n_micro, policy, probes=True):
    """Thin wrapper over the TrainEngine: the engine owns state assembly
    (param/opt/scaler shardings, donation, the jitted step); this path
    lowers it abstractly and harvests compile metrics + cost probes."""
    tc = TrainConfig(microbatch_steps=n_micro, quant_mode=policy.mode,
                     kernel_backend=policy.backend)
    inputs = input_specs(arch, shape, cfg)
    eng = make_engine(cfg, tc, par, mesh, inputs, policy=policy)

    t0 = time.time()
    compiled = eng.lower().compile()
    compile_s = time.time() - t0
    print(f"  [full] compiled in {compile_s:.1f}s")
    print("  memory:", compiled.memory_analysis())
    ca = compiled.cost_analysis() or {}
    print("  cost: flops/dev=%.3e bytes/dev=%.3e" % (
        ca.get("flops", 0), ca.get("bytes accessed", 0)))
    parts = [("full", 1, metrics_of(compiled, mesh.size))]

    if probes:
        parts += train_probes(arch, cfg, shape, mesh, par, n_micro,
                              policy, eng.rules, eng.specs,
                              eng.param_shardings)
    return parts, compile_s


def _group_abs_and_shard(cfg, mesh, rules, which="blocks"):
    """Abstract one scanned group's params + shardings (drop layer axis)."""
    if isinstance(cfg, CLIPConfig):
        raise ValueError("use clip-specific probes")
    specs = (TF.param_specs(cfg) if cfg.family != "encdec"
             else ED.param_specs(cfg))
    sub = specs[which]
    one = jax.tree.map(
        lambda s: ParamSpec(s.shape[1:], s.logical[1:], s.init, s.scale,
                            s.dtype), sub, is_leaf=PRM.is_spec)
    return (abstract_params(one), specs_to_shardings(one, mesh, rules))


def train_probes(arch, cfg, shape, mesh, par, n_micro, policy, rules,
                 specs, params_shard):
    """Per-component cost probes for the scan bodies.

    Assembly identity: the full train step counts the microbatch-scan body
    once (which itself counts the group-scan body once). Each additional
    microbatch contributes one `micro` probe (embed + head + loss + grads,
    group-scan counted once), and each additional group contributes one
    `group` probe — so   total = full + (n_micro−1)·micro
                                 + n_micro·(n_groups−1)·group.
    """
    parts = []
    B, S = shape.global_batch, shape.seq_len
    B_mb = B // max(n_micro, 1)

    # ---- micro probe: one microbatch's loss+grad (embed/head/loss ×count)
    if n_micro > 1 and not isinstance(cfg, CLIPConfig):
        bundle = build(cfg)
        mb_inputs = jax.tree.map(
            lambda v: sds((v.shape[0] // n_micro,) + v.shape[1:], v.dtype),
            input_specs(arch, shape, cfg))
        mb_shard = batch_shardings(mb_inputs, mesh, rules)
        params_abs = abstract_params(specs)

        def micro(params, mb):
            return jax.grad(lambda p: bundle.loss_fn(
                p, mb, policy, par)[0])(params)

        with jax.set_mesh(mesh), make_shard_ctx(mesh, par):
            c = jax.jit(micro, in_shardings=(params_shard, mb_shard)) \
                .lower(params_abs, mb_inputs).compile()
        parts.append(("micro", n_micro - 1, metrics_of(c, mesh.size)))
    act_sh = NamedSharding(mesh, _divisible(
        (B_mb, S, cfg.d_model) if not isinstance(cfg, CLIPConfig) else (1,),
        logical_to_pspec(("batch", "seq", "embed"), rules), mesh))

    if isinstance(cfg, CLIPConfig):
        return clip_probes(cfg, mesh, par, policy, rules)

    if cfg.family == "encdec":
        S_eff = S
        # decoder group probe
        for which, count, seqlen in (
                ("dec_blocks", cfg.n_layers - 1, S),
                ("enc_blocks", cfg.encdec.n_encoder_layers - 1, S)):
            gp_abs, gp_shard = _group_abs_and_shard(cfg, mesh, rules, which)
            x_abs = sds((B_mb, seqlen, cfg.d_model), policy.compute_dtype)
            positions = jnp.arange(seqlen)
            if which == "dec_blocks":
                enc_abs = sds((B_mb, seqlen, cfg.d_model),
                              policy.compute_dtype)

                def probe(gp, x, enc):
                    def f(gp, x, enc):
                        out, _ = ED._dec_layer(x, gp, cfg, policy, par,
                                               positions, enc)
                        return jnp.sum(out.astype(jnp.float32))
                    f = TF._maybe_remat(f, par)
                    return jax.grad(f, argnums=(0, 1, 2))(gp, x, enc)
                args, shards = (gp_abs, x_abs, enc_abs), \
                    (gp_shard, act_sh, act_sh)
            else:
                def probe(gp, x):
                    def f(gp, x):
                        return jnp.sum(ED._enc_layer(
                            x, gp, cfg, policy, par, positions)
                            .astype(jnp.float32))
                    f = TF._maybe_remat(f, par)
                    return jax.grad(f, argnums=(0, 1))(gp, x)
                args, shards = (gp_abs, x_abs), (gp_shard, act_sh)
            with jax.set_mesh(mesh), make_shard_ctx(mesh, par):
                c = jax.jit(probe, in_shardings=shards).lower(*args).compile()
            parts.append((which, count * max(n_micro, 1),
                          metrics_of(c, mesh.size)))
        return parts

    # LM family: one probe per period-group
    S_eff = S + (cfg.frontend_tokens if cfg.frontend else 0)
    G = TF.n_groups(cfg)
    if G > 1:
        gp_abs, gp_shard = _group_abs_and_shard(cfg, mesh, rules)
        x_abs = sds((B_mb, S_eff, cfg.d_model), policy.compute_dtype)
        positions = jnp.arange(S_eff)

        def probe(gp, x):
            def f(gp, x):
                out, _, aux, _ = TF.group_apply(x, gp, cfg, policy, par,
                                                positions=positions)
                return jnp.sum(out.astype(jnp.float32)) + aux
            f = TF._maybe_remat(f, par)
            return jax.grad(f, argnums=(0, 1))(gp, x)

        with jax.set_mesh(mesh), make_shard_ctx(mesh, par):
            c = jax.jit(probe, in_shardings=(gp_shard, act_sh)) \
                .lower(gp_abs, x_abs).compile()
        parts.append(("group", (G - 1) * max(n_micro, 1),
                      metrics_of(c, mesh.size)))
    return parts


def clip_probes(cfg: CLIPConfig, mesh, par, policy, rules):
    from repro.models.vit import vit_block, _block_specs
    parts = []
    B = 16384
    n_keep = max(1, int(cfg.n_patches * (1 - cfg.patch_dropout))) + 1
    for name, width, heads, ff, L, S in (
            ("vis_block", cfg.vision_width, cfg.vision_heads, cfg.vision_ff,
             cfg.vision_layers, n_keep),
            ("txt_block", cfg.text_width, cfg.text_heads, cfg.text_ff,
             cfg.text_layers, cfg.text_ctx)):
        bs = _block_specs(width, heads, ff, cfg.layer_scale_init)
        gp_abs = abstract_params(bs)
        gp_shard = specs_to_shardings(bs, mesh, rules)
        x_abs = sds((B, S, width), policy.compute_dtype)
        x_sh = NamedSharding(mesh, _divisible(
            (B, S, width), logical_to_pspec(("batch", "seq", "embed"), rules),
            mesh))

        def probe(gp, x, heads=heads):
            def f(gp, x):
                out, _ = vit_block(x, gp, heads, policy)
                return jnp.sum(out.astype(jnp.float32))
            f = TF._maybe_remat(f, par)
            return jax.grad(f, argnums=(0, 1))(gp, x)

        with jax.set_mesh(mesh), make_shard_ctx(mesh, par):
            c = jax.jit(probe, in_shardings=(gp_shard, x_sh)) \
                .lower(gp_abs, x_abs).compile()
        parts.append((name, L - 1, metrics_of(c, mesh.size)))
    return parts


def run_serve_cell(arch, cfg, shape, mesh, par, policy, probes=True):
    """prefill / decode compile."""
    rules = default_rules(par)
    bundle = build(cfg)
    specs = bundle.param_specs
    params_abs = abstract_params(specs)
    params_shard = specs_to_shardings(specs, mesh, rules)
    inputs = input_specs(arch, shape, cfg)
    in_shard = batch_shardings(inputs, mesh, rules)
    B, S = shape.global_batch, shape.seq_len
    parts = []

    with jax.set_mesh(mesh), make_shard_ctx(mesh, par):
        if shape.kind == "prefill":
            if cfg.family == "encdec":
                def prefill(params, batch):
                    enc = ED.encode(params, batch["frames"], cfg, policy, par)
                    return enc
            else:
                def prefill(params, batch):
                    logits, _ = TF.forward(
                        params, batch["tokens"], cfg, policy, par,
                        extra_embeds=batch.get("extra_embeds"))
                    return logits[:, -1:]
            f = jax.jit(prefill, in_shardings=(params_shard, in_shard))
            t0 = time.time()
            compiled = f.lower(params_abs, inputs).compile()
            print(f"  [full prefill] compiled in {time.time()-t0:.1f}s")
            print("  memory:", compiled.memory_analysis())
            parts.append(("full", 1, metrics_of(compiled, mesh.size)))
            if probes and cfg.family != "encdec" and TF.n_groups(cfg) > 1:
                parts += serve_group_probe(cfg, shape, mesh, par, policy,
                                           rules, decode=False)
            elif probes and cfg.family == "encdec":
                gp_abs, gp_shard = _group_abs_and_shard(cfg, mesh, rules,
                                                        "enc_blocks")
                x_abs = sds((B, S, cfg.d_model), policy.compute_dtype)
                x_sh = NamedSharding(mesh, _divisible(
                    (B, S, cfg.d_model),
                    logical_to_pspec(("batch", "seq", "embed"), rules), mesh))
                positions = jnp.arange(S)

                def probe(gp, x):
                    return ED._enc_layer(x, gp, cfg, policy, par, positions)
                c = jax.jit(probe, in_shardings=(gp_shard, x_sh)) \
                    .lower(gp_abs, x_abs).compile()
                parts.append(("enc_group",
                              cfg.encdec.n_encoder_layers - 1,
                              metrics_of(c, mesh.size)))
        else:   # decode
            if cfg.family == "encdec":
                src_len = 4096     # fixed cross-attention source length
                state_abs = jax.eval_shape(functools.partial(
                    ED.init_decode_state, cfg=cfg, policy=policy,
                    parallel=par, batch=B, max_len=S),
                    params_abs, sds((B, src_len, cfg.d_model), jnp.bfloat16))
                cache_sh = NamedSharding(mesh, _divisible(
                    (cfg.n_layers, B, cfg.n_kv_heads, S, cfg.hd),
                    logical_to_pspec(
                        ("layers", "batch", "kv_heads", "cache_seq", None),
                        rules), mesh))
                state_shard = ED.EncDecDecodeState(
                    type(state_abs.self_caches)(
                        cache_sh, cache_sh, NamedSharding(mesh, P())),
                    NamedSharding(mesh, _divisible(
                        (B, src_len, cfg.d_model),
                        logical_to_pspec(("batch", "seq", "embed"), rules),
                        mesh)))

                def step(params, st, batch):
                    return ED.decode_step(params, st, batch["tokens"], cfg,
                                          policy, par)
            else:
                state_abs = jax.eval_shape(
                    functools.partial(TF.init_decode_state, cfg, B, S))
                log_ax = TF.decode_state_logical_axes(cfg)
                state_shard = jax.tree.map(
                    lambda a, ax: NamedSharding(mesh, _divisible(
                        a.shape, logical_to_pspec(ax, rules), mesh)),
                    state_abs, log_ax,
                    is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

                def step(params, st, batch):
                    return TF.decode_step(params, st, batch["tokens"], cfg,
                                          policy, par)

            f = jax.jit(step, in_shardings=(params_shard, state_shard,
                                            in_shard),
                        donate_argnums=(1,))
            t0 = time.time()
            compiled = f.lower(params_abs, state_abs, inputs).compile()
            print(f"  [full decode] compiled in {time.time()-t0:.1f}s")
            print("  memory:", compiled.memory_analysis())
            parts.append(("full", 1, metrics_of(compiled, mesh.size)))
            if probes and cfg.family != "encdec" and TF.n_groups(cfg) > 1:
                parts += serve_group_probe(cfg, shape, mesh, par, policy,
                                           rules, decode=True)
    return parts


def serve_group_probe(cfg, shape, mesh, par, policy, rules, *, decode):
    B, S = shape.global_batch, shape.seq_len
    G = TF.n_groups(cfg)
    gp_abs, gp_shard = _group_abs_and_shard(cfg, mesh, rules)
    if decode:
        state_abs_full = jax.eval_shape(
            functools.partial(TF.init_decode_state, cfg, B, S))
        log_ax = TF.decode_state_logical_axes(cfg)
        st_abs = jax.tree.map(lambda a: sds(a.shape[1:], a.dtype),
                              state_abs_full,
                              is_leaf=lambda x: isinstance(
                                  x, jax.ShapeDtypeStruct))
        st_shard = jax.tree.map(
            lambda a, ax: NamedSharding(mesh, _divisible(
                a.shape[1:], logical_to_pspec(ax[1:], rules), mesh)),
            state_abs_full, log_ax,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        x_abs = sds((B, 1, cfg.d_model), policy.compute_dtype)
        x_sh = NamedSharding(mesh, _divisible(
            (B, 1, cfg.d_model),
            logical_to_pspec(("batch", None, "embed"), rules), mesh))

        def probe(gp, st, x):
            out, ns, _, _ = TF.group_apply(x, gp, cfg, policy, par,
                                           positions=jnp.arange(1),
                                           states=st)
            return out, ns
        with jax.set_mesh(mesh), make_shard_ctx(mesh, par):
            c = jax.jit(probe, in_shardings=(gp_shard, st_shard, x_sh),
                        donate_argnums=(1,)) \
                .lower(gp_abs, st_abs, x_abs).compile()
    else:
        S_eff = S + (cfg.frontend_tokens if cfg.frontend else 0)
        x_abs = sds((B, S_eff, cfg.d_model), policy.compute_dtype)
        x_sh = NamedSharding(mesh, _divisible(
            (B, S_eff, cfg.d_model),
            logical_to_pspec(("batch", "seq", "embed"), rules), mesh))
        positions = jnp.arange(S_eff)

        def probe(gp, x):
            out, _, _, _ = TF.group_apply(x, gp, cfg, policy, par,
                                          positions=positions)
            return out
        with jax.set_mesh(mesh), make_shard_ctx(mesh, par):
            c = jax.jit(probe, in_shardings=(gp_shard, x_sh)) \
                .lower(gp_abs, x_abs).compile()
    return [("group", G - 1, metrics_of(c, mesh.size))]


# ---------------------------------------------------------------------------
# model-FLOPs accounting (6·N·D with N_active for MoE)
# ---------------------------------------------------------------------------

def count_params(specs, active_only_cfg=None) -> float:
    total = 0.0
    for leaf in jax.tree.leaves(specs, is_leaf=PRM.is_spec):
        n = 1.0
        for d in leaf.shape:
            n *= d
        total += n
    return total


def active_params(cfg, specs) -> float:
    """N_active: expert params scaled by top_k/n_experts."""
    total = 0.0
    flat = jax.tree_util.tree_leaves_with_path(specs, is_leaf=PRM.is_spec)
    moe = getattr(cfg, "moe", None)
    for path, leaf in flat:
        n = 1.0
        for d in leaf.shape:
            n *= d
        path_s = jax.tree_util.keystr(path)
        if moe is not None and "moe" in path_s and "w_router" not in path_s:
            n *= moe.top_k / moe.n_experts
        if "embed" in path_s.split("'")[-2:] or path_s.endswith("embed']"):
            pass
        total += n
    return total


def cell_model_flops(arch, cfg, shape) -> float:
    bundle = build(cfg)
    if isinstance(cfg, CLIPConfig):
        n = count_params(bundle.param_specs)
        n_keep = max(1, int(cfg.n_patches * (1 - cfg.patch_dropout))) + 1
        tokens = 16384 * (n_keep + cfg.text_ctx)
        return model_flops(n, tokens, "train")
    n_act = active_params(cfg, bundle.param_specs)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return model_flops(n_act, tokens, "train")
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return model_flops(n_act, tokens, "infer")
    tokens = shape.global_batch * 1          # decode: one token per seq
    return model_flops(n_act, tokens, "infer")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             quant_mode: str = "bf16", kernel_backend: str = "xla",
             probes: bool = True,
             overrides: Optional[Dict] = None, optimized: bool = False) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    base_over = dict(OPTIMIZED.get(arch, {})) if optimized else {}
    base_over.update(overrides or {})
    overrides = base_over
    if shape.kind == "decode":
        # decode always shards KV projections (the cache shards over model)
        # and never gathers weights per token step (weights >> activations
        # at decode batch sizes — measured 0.6x regression otherwise)
        overrides["shard_kv_heads"] = True
        overrides["fsdp_gather_weights"] = False
    par, n_micro = parallel_for(arch, multi_pod, overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = QuantPolicy(quant_mode, backend=kernel_backend)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    print(f"=== {arch} × {shape_name} × {mesh_name} "
          f"(quant={quant_mode}, fsdp={par.fsdp}, n_micro={n_micro}) ===")
    t0 = time.time()
    if shape.kind == "train":
        parts, _ = run_train_cell(arch, cfg, shape, mesh, par, n_micro,
                                  policy, probes)
    else:
        parts = run_serve_cell(arch, cfg, shape, mesh, par, policy, probes)
    total = combine(parts)
    mf = cell_model_flops(arch, cfg, shape)
    cell = RooflineCell(
        arch=arch, shape=shape_name, mesh=mesh_name, n_devices=mesh.size,
        flops_int8=total["dot_flops_int8"],
        flops_other=max(total["flops"] - total["dot_flops_int8"], 0.0),
        bytes_accessed=total["bytes_accessed"],
        wire_bytes=total["wire_bytes"],
        model_flops_global=mf,
        notes=f"quant={quant_mode}")
    row = cell.row()
    row.update({"parts": [(n, c, m) for n, c, m in parts],
                "temp_bytes": total.get("temp_bytes"),
                "arg_bytes": total.get("arg_bytes"),
                "wall_s": time.time() - t0,
                "n_micro": n_micro, "fsdp": par.fsdp,
                "quant_mode": quant_mode})
    print(f"  -> T_comp={cell.t_compute:.4f}s T_mem={cell.t_memory:.4f}s "
          f"T_coll={cell.t_collective:.4f}s bottleneck={cell.bottleneck} "
          f"frac={cell.roofline_fraction:.3f}")
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--quant-mode", default="bf16")
    ap.add_argument("--kernel-backend", default="xla",
                    choices=("xla", "pallas", "pallas_interpret"))
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--fsdp", default=None, choices=[None, "on", "off"])
    ap.add_argument("--fsdp-gather", default=None, choices=[None, "on", "off"])
    ap.add_argument("--gather-wire", default=None, choices=[None, "bf16", "int8"])
    ap.add_argument("--pure-dp", default=None, choices=[None, "on", "off"])
    ap.add_argument("--seq-parallel", default=None, choices=[None, "on", "off"])
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--tag", default="", help="suffix for result filenames")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf per-arch winning overrides")
    args = ap.parse_args()

    overrides = {}
    if args.fsdp is not None:
        overrides["fsdp"] = args.fsdp == "on"
    if args.fsdp_gather is not None:
        overrides["fsdp_gather_weights"] = args.fsdp_gather == "on"
    if args.gather_wire is not None:
        overrides["gather_wire"] = args.gather_wire
    if args.pure_dp is not None:
        overrides["pure_dp"] = args.pure_dp == "on"
    if args.seq_parallel is not None:
        overrides["sequence_parallel"] = args.seq_parallel == "on"
    if args.n_micro is not None:
        overrides["n_micro"] = args.n_micro
    if args.remat is not None:
        overrides["remat"] = args.remat
    if args.attn_impl is not None:
        overrides["attn_impl"] = args.attn_impl

    archs = ALL_ARCHS if args.all or args.arch is None else (args.arch,)
    meshes = {"single": (False,), "multi": (True,),
              "both": (False, True)}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        shapes = shapes_for(arch)
        if args.shape:
            shapes = [s for s in shapes if s.name == args.shape]
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape.name}_{'multi' if mp else 'single'}" \
                      + (f"_{args.quant_mode}" if args.quant_mode != "bf16"
                         else "") + (f"_{args.tag}" if args.tag else "")
                try:
                    row = run_cell(arch, shape.name, mp,
                                   quant_mode=args.quant_mode,
                                   kernel_backend=args.kernel_backend,
                                   probes=not args.no_probes and not mp,
                                   overrides=overrides or None,
                                   optimized=args.optimized)
                    with open(os.path.join(args.out, tag + ".json"),
                              "w") as f:
                        json.dump(row, f, indent=1, default=str)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    traceback.print_exc()
    if failures:
        print("FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("all requested cells compiled OK")


if __name__ == "__main__":
    main()
