"""Training launcher CLI — every mode runs the same sharded TrainEngine.

* ``--arch NAME`` trains the published config of NAME; ``NAME-reduced``
  (the default, ``smollm-360m-reduced``) is its shrunken CPU-sized twin.
  Either trains on synthetic data end-to-end through the engine on a mesh
  over the local devices — the same jitted, donated, sharded step the
  benchmark times.
* ``--mesh test`` with forced host devices exercises real partitioning:

    PYTHONPATH=src REPRO_DRYRUN_DEVICES=8 python -m repro.launch.train \
        --arch smollm-360m-reduced --steps 20 --mesh test
"""
from __future__ import annotations

import argparse

from repro.host_devices import force_host_device_count

# must run before the jax import below: REPRO_DRYRUN_DEVICES / --devices N
force_host_device_count()

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import ALL_ARCHS, REDUCED_ARCHS, get_config
from repro.configs.base import (CLIPConfig, ParallelConfig, SupervisorConfig,
                                TelemetryConfig, TrainConfig)
from repro.core.precision import QuantPolicy
from repro.data import BigramLM, SyntheticCLIP, SyntheticSeq2Seq
from repro.launch.mesh import make_cli_mesh
from repro.models import build
from repro.telemetry import Telemetry, parse_profile_steps
from repro.train import FaultPlan, Trainer, make_engine


def make_data(cfg, batch: int, seq: int):
    if isinstance(cfg, CLIPConfig):
        d = SyntheticCLIP(cfg.image_size, cfg.text_ctx, cfg.text_vocab,
                          n_classes=32)
        return lambda i: {k: jnp.asarray(v) for k, v in d.batch(batch).items()
                          if k != "class_ids"}
    if cfg.family == "encdec":
        d = SyntheticSeq2Seq(cfg.d_model, cfg.vocab_size)
        return lambda i: {k: jnp.asarray(v) for k, v in
                          d.batch(batch, cfg.frontend_tokens, seq).items()}
    d = BigramLM(cfg.vocab_size, temperature=0.2)

    def fn(i):
        b = {k: jnp.asarray(v) for k, v in d.batch(batch, seq).items()}
        if cfg.frontend:
            b["extra_embeds"] = jax.random.normal(
                jax.random.PRNGKey(i), (batch, cfg.frontend_tokens,
                                        cfg.d_model), jnp.bfloat16)
        return b
    return fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m-reduced",
                    choices=ALL_ARCHS + REDUCED_ARCHS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--quant-mode", default="bf16")
    ap.add_argument("--kernel-backend", default="xla",
                    choices=("xla", "pallas", "pallas_interpret"))
    ap.add_argument("--fp8-block", type=int, nargs=2, default=(128, 128),
                    metavar=("ROWS", "COLS"),
                    help="fp8_mixed blockwise-quantization tile shape")
    ap.add_argument("--fp8-fallback-ratio", type=float, default=8.0,
                    help="fp8_mixed: tile absmax > ratio x median falls "
                         "back to bf16 (lower = more conservative)")
    ap.add_argument("--attn-impl", default="flash_scan",
                    choices=("flash_scan", "dense"),
                    help="XLA attention path (pallas backends use the "
                         "fused flash kernels unless 'dense')")
    ap.add_argument("--attn-block-q", type=int, default=0,
                    help="flash-attention Q tile rows (0 = auto)")
    ap.add_argument("--attn-block-k", type=int, default=0,
                    help="flash-attention KV tile rows (0 = auto)")
    ap.add_argument("--optimizer", default="stable_adamw")
    ap.add_argument("--beta2", type=float, default=0.95)
    ap.add_argument("--loss-scaler", default="none")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--supervise", action="store_true",
                    help="run under the self-healing TrainSupervisor "
                         "(anomaly detection -> verified-checkpoint rewind "
                         "-> deterministic data skip); needs --ckpt-dir")
    ap.add_argument("--fault-plan", default=None,
                    help="inject faults: JSON list (inline or a file path) "
                         'of {"step", "kind", ...} specs — see '
                         "repro/train/faults.py for kinds")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="supervisor: rewinds per incident before abort")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--mesh", default="auto",
                    choices=("auto", "test"))
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host CPU devices (read pre-jax-import)")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard params/moments over data too (ZeRO-3)")
    ap.add_argument("--pure-dp", action="store_true",
                    help="fold the model axis into data parallelism")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write flight-recorder JSONL events here (read "
                         "with python -m repro.telemetry.report)")
    ap.add_argument("--profile-steps", default=None, metavar="A:B",
                    help="wrap steps A..B (inclusive) in a jax.profiler "
                         "trace (written under --profile-dir)")
    ap.add_argument("--profile-dir", default="/tmp/repro-profile")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    bundle = build(cfg)
    mesh = make_cli_mesh(args.mesh)
    par = ParallelConfig(mesh_shape=tuple(mesh.devices.shape),
                         mesh_axes=tuple(mesh.axis_names),
                         fsdp=args.fsdp, pure_dp=args.pure_dp,
                         remat="block", attn_impl=args.attn_impl,
                         attn_block_q=args.attn_block_q,
                         attn_block_k=args.attn_block_k)
    tc = TrainConfig(optimizer=args.optimizer, learning_rate=args.lr,
                     warmup_steps=max(args.steps // 10, 1),
                     total_steps=args.steps, beta2=args.beta2,
                     loss_scaler=args.loss_scaler,
                     quant_mode=args.quant_mode,
                     kernel_backend=args.kernel_backend,
                     fp8_block_rows=args.fp8_block[0],
                     fp8_block_cols=args.fp8_block[1],
                     fp8_fallback_ratio=args.fp8_fallback_ratio,
                     microbatch_steps=args.microbatch)
    policy = QuantPolicy.from_train_config(tc)
    data_fn = make_data(cfg, args.batch, args.seq)

    engine = make_engine(bundle, tc, par, mesh, data_fn(0), policy=policy)
    state = engine.init_state(seed=0)
    n_sharded = sum(not l.sharding.is_fully_replicated
                    for l in jax.tree.leaves(state.params))
    print(f"[train] mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"fsdp={par.fsdp} pure_dp={par.pure_dp} — "
          f"{n_sharded}/{len(jax.tree.leaves(state.params))} param tensors "
          f"partitioned, step donated")

    plan = FaultPlan.from_json(args.fault_plan) if args.fault_plan else None
    ckpt_every = max(args.steps // 3, 10) if args.ckpt_dir else 0
    tele = Telemetry.from_config(
        TelemetryConfig(path=args.telemetry,
                        profile_steps=parse_profile_steps(args.profile_steps),
                        profile_dir=args.profile_dir),
        program="train",
        meta={"arch": args.arch, "quant_mode": args.quant_mode,
              "kernel_backend": args.kernel_backend,
              "optimizer": args.optimizer, "steps": args.steps,
              "supervised": bool(args.supervise)})
    try:
        if args.supervise:
            if not args.ckpt_dir:
                ap.error("--supervise needs --ckpt-dir (rewind is the "
                         "recovery primitive)")
            sup = engine.make_supervisor(
                state, data_fn, checkpoint_dir=args.ckpt_dir,
                config=SupervisorConfig(checkpoint_every=ckpt_every,
                                        max_retries=args.max_retries),
                fault_plan=plan, telemetry=tele)
            start = sup.maybe_resume()
            sup.run(args.steps - start)
            trainer = sup.trainer
        else:
            trainer = Trainer(engine.step, state,
                              checkpoint_dir=args.ckpt_dir,
                              checkpoint_every=ckpt_every, log_every=10,
                              state_shardings=engine.state_shardings,
                              fault_plan=plan, telemetry=tele)
            start = trainer.maybe_resume()
            trainer.run(lambda i: engine.shard_batch(data_fn(i)),
                        args.steps - start)
            sup = None
    finally:
        tele.close()
    if trainer.history:
        print("final loss:", trainer.history[-1]["loss"])
        print("stability:", (sup or trainer).stability_report())
    else:
        print(f"nothing to do: resumed at step {start} >= --steps "
              f"{args.steps}")
    if args.telemetry:
        print(f"[telemetry] events written to {args.telemetry} — summarize "
              f"with: python -m repro.telemetry.report {args.telemetry}")


if __name__ == "__main__":
    main()
