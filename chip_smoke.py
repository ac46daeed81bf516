"""Chip smoke: drive the main paths once on a TPU, at published widths,
through the entry points a user calls, and check what comes out.

    python chip_smoke.py             # one chip: train + serve smollm-360m
    python chip_smoke.py --chips 4   # four chips: CLIP ViT-Huge, fsdp

One process, no subprocesses. It prints the devices first and exits
non-zero, printing no result, when JAX finds no TPU: a CPU run says
nothing about the chip. No phase's failure is caught. The last line of
stdout is ``{"ok": true, "device": {...}}``.

* train: smollm-360m (32 layers, d_model 960) wired as
  ``repro.launch.train`` wires it — ``make_engine`` + ``Trainer``, int8
  SwitchBack through the Pallas kernels, StableAdamW, 4096 tokens per step,
  5 steps. Every loss must be finite, step 1's loss must match the same
  seed and batch on ``kernel_backend="xla"``, and the compiled step must
  hold Mosaic kernels (``tpu_custom_call``). The Trainer runs the step's
  ahead-of-time compiled executable, so the HLO checked is the program
  that ran and nothing is compiled twice.
* serve: smollm-360m through ``make_serve_engine`` at the launcher's
  policy (bf16) with the paged cache, chunked prefill and the Pallas
  kernels; 8 greedy requests with 64-512 token prompts and 32 new tokens
  each. The prefill log-softmax must match the ``xla`` engine's within
  ``SERVE_LOGSM_ATOL``, and each request's first token must equal the
  ``xla`` engine's or lie within that tolerance of xla's best token.
* ``--chips 4`` (only this phase): CLIP ViT-Huge, int8 SwitchBack +
  Pallas, fsdp over a (4, 1) mesh for 3 steps. The same program cut to
  4 vision + 4 text layers runs on one chip and on four; their step-1
  losses must agree, and the parameters must sit on 4 distinct devices.

The times and memory it prints are a smoke, not benchmark numbers: one
cold run, compile included, no warm window.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
TRAIN_STEPS = 5
TRAIN_SEQ = 4096
# Step-1 loss, pallas vs xla: the same int8 quantization on both paths, so
# they differ only where a few activations round across an int8 or bf16
# step after the attention kernels' different f32 summation order. Averaged
# over 4096 tokens of a ~10.8-nat cross-entropy that is far below 2e-3
# relative; a wrong kernel moves the loss by O(1).
TRAIN_LOSS_RTOL = 2e-3
SERVE_REQUESTS = 8
SERVE_PROMPT_LENS = (64, 512)
SERVE_NEW_TOKENS = 32
SERVE_CHUNK = 256
SERVE_MAX_LEN = 1024
# Serving runs at the launcher's policy: bf16 activations over a bf16 KV
# cache, so the last-position logits are bf16 and two sound paths that
# sum in different orders land whole bf16 steps apart. On a v5e the
# pallas-vs-xla reading was 0.053; with the paged prefill kernel's kv_len
# planted one short it read 0.48, and with its KV heads reversed 2.4.
# 0.1 sits between. A first token may differ from xla's only where xla
# ranks it within this limit of its best.
SERVE_LOGSM_ATOL = 0.1
CLIP_STEPS = 3
CLIP_BATCH = 256
CLIP_CUT = (4, 4)                     # vision, text layers of the cut
# Step-1 loss of the cut, 1 chip vs 4: the same kernels on the same rows;
# only the cross-device reductions of the contrastive logits and the
# psummed weight gradients change order — 1e-3 of a ~5.5-nat loss.
CLIP_LOSS_RTOL = 1e-3


def log(*a):
    print(*a, flush=True)


def peak_bytes(dev) -> int:
    return (dev.memory_stats() or {}).get("peak_bytes_in_use", -1)


def train_engine(cfg, backend: str, mesh, batch, *, steps: int,
                 fsdp: bool = False):
    """The engine ``repro.launch.train`` builds for ``cfg`` on ``mesh``."""
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.core.precision import QuantPolicy
    from repro.models import build
    from repro.train import make_engine
    par = ParallelConfig(mesh_shape=tuple(mesh.devices.shape),
                         mesh_axes=tuple(mesh.axis_names), fsdp=fsdp,
                         remat="block")
    tc = TrainConfig(optimizer="stable_adamw", learning_rate=1e-3,
                     warmup_steps=max(steps // 10, 1), total_steps=steps,
                     beta2=0.95, quant_mode="int8_switchback",
                     kernel_backend=backend)
    return make_engine(build(cfg), tc, par, mesh, batch,
                       policy=QuantPolicy.from_train_config(tc))


def train_run(cfg, backend: str, mesh, batches, *, fsdp: bool = False,
              label: str):
    """Compile the engine's step ahead of time, then train
    ``len(batches)`` steps through the Trainer on that executable.
    Returns (losses, its HLO text, final state)."""
    import jax

    from repro.train import Trainer
    eng = train_engine(cfg, backend, mesh, batches[0], steps=len(batches),
                       fsdp=fsdp)
    state = eng.init_state(seed=SEED)
    t0 = time.perf_counter()
    step = eng.lower().compile()
    compile_s = time.perf_counter() - t0
    hlo = step.as_text()
    trainer = Trainer(step, state, log_every=1,
                      state_shardings=eng.state_shardings)
    hist = trainer.run(lambda i: eng.shard_batch(batches[i]), len(batches))
    losses = [h["loss"] for h in hist]
    steady = sorted(h["dt"] for h in hist[1:])
    timing = (f"step {steady[len(steady) // 2] * 1e3:.1f}ms (median of "
              f"steps 2-{len(hist)})" if steady else "one step")
    log(f"[smoke:{label}] compile {compile_s:.1f}s, first step "
        f"{hist[0]['dt']:.1f}s, {timing}, peak device memory "
        f"{peak_bytes(jax.devices()[0]) / 2**30:.2f} GiB so far, "
        f"{hlo.count('tpu_custom_call')} tpu_custom_call in the step")
    log(f"[smoke:{label}] losses {losses}")
    return losses, hlo, trainer.state


def check(ok: bool, what):
    """A failed check raises (unlike ``assert``, also under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def check_losses(losses, label: str):
    check(all(math.isfinite(v) for v in losses), (label, losses))


def check_close(got: float, ref: float, rtol: float, what: str):
    rel = abs(got - ref) / abs(ref)
    log(f"[smoke] {what}: {got!r} vs {ref!r}, rel diff {rel:.3e} "
        f"(limit {rtol:g})")
    check(rel <= rtol, (what, got, ref, rel))


def phase_train():
    from repro.configs import get_config
    from repro.launch.mesh import make_cli_mesh
    from repro.launch.train import make_data
    cfg = get_config("smollm-360m")
    mesh = make_cli_mesh("auto")
    data = make_data(cfg, 1, TRAIN_SEQ)
    batches = [data(i) for i in range(TRAIN_STEPS)]
    ref, _, state = train_run(cfg, "xla", mesh, batches[:1],
                              label="train xla")
    del state
    losses, hlo, _ = train_run(cfg, "pallas", mesh, batches,
                               label="train pallas")
    check_losses(losses, "train")
    check_close(losses[0], ref[0], TRAIN_LOSS_RTOL,
                "train step-1 loss pallas vs xla")
    check("tpu_custom_call" in hlo, "train step holds no Mosaic kernel")


def serve_engine(cfg, backend: str, mesh):
    """The engine ``repro.launch.serve`` builds, policy included."""
    from repro.configs.base import ServeConfig
    from repro.models import build
    from repro.serve import make_serve_engine
    scfg = ServeConfig(max_batch=SERVE_REQUESTS, max_len=SERVE_MAX_LEN,
                       kernel_backend=backend, cache_mode="paged",
                       prefill_chunk_tokens=SERVE_CHUNK, seed=SEED)
    return make_serve_engine(build(cfg), scfg, mesh)


def prefill_logsoftmax(engine, params, prompts):
    """Monolithic paged prefill of every prompt, one per slot. Returns the
    last prompt position's log-softmax (B, V) and the compiled prefill and
    decode programs' HLO text."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    B, nb = len(prompts), engine.blocks_per_slot
    S = 1 << (max(map(len, prompts)) - 1).bit_length()
    toks = np.zeros((B, S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    args = (jnp.asarray(np.arange(B * nb, dtype=np.int32).reshape(B, nb)),
            jnp.asarray(toks), jnp.zeros((B,), jnp.int32),
            jnp.asarray([len(p) for p in prompts], jnp.int32),
            jnp.ones((B,), bool))
    with jax.set_mesh(engine.mesh), engine.shard_ctx():
        hlo = engine.jit_prefill.lower(
            params, engine.init_cache(), *args).compile().as_text()
        hlo += engine.jit_decode.lower(
            params, engine.init_cache(), args[0],
            jnp.zeros((B, 1), jnp.int32)).compile().as_text()
    logits, _ = engine.prefill_paged(params, engine.init_cache(), *args)
    return np.asarray(jax.nn.log_softmax(
        logits[:, 0].astype(jnp.float32), axis=-1)), hlo


def phase_serve():
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.mesh import make_cli_mesh
    cfg = get_config("smollm-360m")
    mesh = make_cli_mesh("auto")
    rng = np.random.default_rng(SEED)
    lens = rng.integers(SERVE_PROMPT_LENS[0], SERVE_PROMPT_LENS[1] + 1,
                        size=SERVE_REQUESTS)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
               for n in lens]

    engine = serve_engine(cfg, "pallas", mesh)
    params = engine.init_params(SEED)
    t0 = time.perf_counter()
    engine.generate(params, prompts, max_new_tokens=2)   # compiles buckets
    warm_s = time.perf_counter() - t0
    gens, stats = engine.generate(params, prompts,
                                  max_new_tokens=SERVE_NEW_TOKENS)
    log(f"[smoke:serve pallas] warmup (compile) {warm_s:.1f}s; "
        f"{stats['new_tokens']} new tokens in {stats['wall_s']:.2f}s "
        f"({stats['wall_s'] / SERVE_REQUESTS * 1e3:.1f}ms per request), "
        f"ttft p50 {stats['ttft_p50_s'] * 1e3:.1f}ms, itl p50 "
        f"{stats['itl_p50_s'] * 1e3:.2f}ms, {stats['prefill_chunks']} "
        f"prefill chunks; peak device memory "
        f"{peak_bytes(jax.devices()[0]) / 2**30:.2f} GiB so far")
    check(all(len(g) == SERVE_NEW_TOKENS for g in gens),
          [len(g) for g in gens])
    logsm, hlo = prefill_logsoftmax(engine, params, prompts)
    log(f"[smoke:serve pallas] {hlo.count('tpu_custom_call')} "
        "tpu_custom_call in the prefill + decode programs")
    check("tpu_custom_call" in hlo, "serve steps hold no Mosaic kernel")

    ref_engine = serve_engine(cfg, "xla", mesh)
    ref_gens, _ = ref_engine.generate(params, prompts, max_new_tokens=1)
    ref_logsm, _ = prefill_logsoftmax(ref_engine, params, prompts)
    firsts = [g[0] for g in gens]
    ref_firsts = [g[0] for g in ref_gens]
    # how far below xla's best token xla ranks the token pallas chose
    gaps = (ref_logsm.max(axis=-1)
            - ref_logsm[np.arange(len(firsts)), firsts]).tolist()
    err = np.abs(logsm - ref_logsm)
    diff = float(err.max())
    log(f"[smoke:serve] first tokens pallas {firsts} xla {ref_firsts}; "
        f"xla log-prob of pallas's token below xla's best {gaps}")
    log(f"[smoke:serve] prefill log-softmax |pallas - xla| max {diff!r} "
        f"(limit {SERVE_LOGSM_ATOL:g}), mean {float(err.mean())!r}")
    check(diff <= SERVE_LOGSM_ATOL, ("prefill log-softmax", diff))
    check(all(f == r or g <= SERVE_LOGSM_ATOL
              for f, r, g in zip(firsts, ref_firsts, gaps)),
          ("first tokens", firsts, ref_firsts, gaps))


def clip_mesh(n: int):
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((n, 1), ("data", "model"),
                         devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * 2)


def phase_clip4():
    import jax

    from repro.configs import get_config
    from repro.launch.train import make_data
    cfg = get_config("clip-vit-huge")
    data = make_data(cfg, CLIP_BATCH, 0)
    batches = [data(i) for i in range(CLIP_STEPS)]
    cut = dataclasses.replace(cfg, vision_layers=CLIP_CUT[0],
                              text_layers=CLIP_CUT[1])
    one, _, _ = train_run(cut, "pallas", clip_mesh(1), batches[:1],
                          label="clip cut 1 chip")
    four, _, _ = train_run(cut, "pallas", clip_mesh(4), batches[:1],
                           fsdp=True, label="clip cut 4 chips fsdp")
    check_losses(one + four, "clip cut")
    check_close(four[0], one[0], CLIP_LOSS_RTOL,
                "clip cut step-1 loss 4 chips vs 1")
    losses, hlo, state = train_run(cfg, "pallas", clip_mesh(4), batches,
                                   fsdp=True, label="clip full 4 chips fsdp")
    check_losses(losses, "clip full")
    check("tpu_custom_call" in hlo, "clip step holds no Mosaic kernel")
    leaves = jax.tree.leaves(state.params)
    devs = {s.device for leaf in leaves for s in leaf.addressable_shards}
    n_split = sum(not leaf.sharding.is_fully_replicated for leaf in leaves)
    log(f"[smoke:clip] parameter shards on devices "
        f"{sorted(d.id for d in devs)}; {n_split}/{len(leaves)} tensors "
        "partitioned")
    check(len(devs) == 4 and n_split > 0, ("param placement", devs, n_split))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip CLIP fsdp phase")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"[smoke] jax.devices(): {devs}")
    log(f"[smoke] platform {dev.platform}, device_kind {dev.device_kind}, "
        f"count {len(devs)}")
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX found {dev.platform!r}; nothing to "
              "check here", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"[smoke] --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    log(f"[smoke] compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        phase_clip4()
    else:
        phase_train()
        phase_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
