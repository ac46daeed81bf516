"""Training cells: the engine's ahead-of-time compiled step, driven through
the ``Trainer`` as ``repro.launch.train`` drives it.

Set-up builds one engine and one state (weights made on the device from
the seed by ``bench/weights.py``), compiles the step (from the persistent
cache after a cell's first run), and drives that same trainer through its
first steps (the configuration's ``check_steps``) on distinct batches:
they warm every shape and give the readings that ``correct`` compares
with the plain reference. The
window then runs whole steps for about ``--seconds`` and ends on the last
step's state. After the window the state is freed and the reference
follows the first steps from the same weights and batches.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import math
import time
import types
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench import common, trace, weights
from bench.reference import common as refc

def program_config(config):
    from repro.configs import get_config
    prog = config["program"]
    return dataclasses.replace(get_config(prog["arch"]), **prog["replace"])


def make_engine(config, devices, batch, *, quant_mode=None):
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.core.precision import QuantPolicy
    from repro.models import build
    from repro.train import make_engine as program_engine
    from jax.sharding import AxisType
    n = len(devices)
    mesh = jax.make_mesh((n, 1), ("data", "model"), devices=devices,
                         axis_types=(AxisType.Auto,) * 2)
    hp = config["train"]
    par = ParallelConfig(mesh_shape=(n, 1), mesh_axes=("data", "model"),
                         fsdp=n > 1, remat=hp["remat"])
    tc = TrainConfig(optimizer=hp["optimizer"],
                     learning_rate=hp["learning_rate"],
                     warmup_steps=hp["warmup_steps"],
                     total_steps=hp["total_steps"], beta1=hp["beta1"],
                     beta2=hp["beta2"], weight_decay=hp["weight_decay"],
                     quant_mode=quant_mode or config["path"]["quant_mode"],
                     kernel_backend=config["path"]["kernel_backend"])
    return program_engine(build(program_config(config)), tc, par, mesh,
                          batch, policy=QuantPolicy.from_train_config(tc))


def shapes(tree):
    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def init_state(engine, abstract, seed: int):
    """The TrainState from the seed, in one jitted call on the device:
    weights by ``bench/weights.py``, the program's own optimizer and
    scaler initial state, step 0, the seed's key for dropout."""
    from repro.train.train_step import TrainState
    wkey, dkey = jax.random.split(common.raw_key(seed))

    def init(wk, dk):
        p = weights.make(abstract, wk)
        return TrainState(p, engine.opt.init(p), engine.scaler.init(),
                          jnp.zeros((), jnp.int32), dk)

    with jax.set_mesh(engine.mesh), engine.shard_ctx():
        state = jax.jit(init, out_shardings=engine.state_shardings)(
            wkey, dkey)
    return state, wkey, dkey


def dropout_keys(dkey, n: int):
    """The key the program's step ``t`` hands the loss: it splits the
    state's key and passes on the second half."""
    out = []
    for _ in range(n):
        dkey, sub = jax.random.split(dkey)
        out.append(sub)
    return out


def program_readings(trainer, feed, make_w, wkey,
                     steps: int) -> Dict[str, Any]:
    """Drive the trainer through its first ``steps`` steps and read what
    the reference is compared on. After step 1 the optimizer's first
    moment is the first gradient (its debiased beta1 is 0 at step 1)."""
    norms = jax.jit(refc.leaf_norms)
    trainer.run(feed, 1)
    grad1 = refc.flat_norms(norms(trainer.state.opt_state.exp_avg))
    trainer.run(feed, steps - 1)
    delta = jax.jit(lambda p, w: refc.leaf_norms(
        jax.tree.map(jnp.subtract, p, w)))(trainer.state.params, make_w(wkey))
    return {"losses": [h["loss"] for h in trainer.history],
            "grad1": grad1, "delta": refc.flat_norms(delta)}


def reference_readings(cell, make_w, ring, wkey, dkey, *, bits=None,
                       rows=slice(None)):
    """The plain reference over the same weights and batches; ``bits`` and
    ``rows`` give the control (lower precision) and the half-batch fault."""
    loss = functools.partial(cell.reference.loss, c=cell.config,
                             linear=refc.make_linear(bits), rows=rows)
    steps = cell.config["train"]["check_steps"]
    batches = [jax.tree.map(jnp.asarray, b) for b in ring[:steps]]
    return refc.train_readings(loss, lambda: make_w(wkey), batches,
                               dropout_keys(dkey, steps),
                               cell.config["train"], steps)


def run(cell, seed: int, seconds: float, do_trace: bool, devices,
        t_start: float, *, limits: Dict[str, float]) -> str:
    from repro.train import Trainer
    ring = cell.kind.make(cell.traffic, cell.config, seed)
    engine = make_engine(cell.config, devices, ring[0])
    abstract = cell.reference.abstract(cell.config)
    if shapes(engine.state_abs.params) != shapes(abstract):
        raise RuntimeError("the program's parameter tree differs from the "
                           "reference's layout")
    state, wkey, dkey = init_state(engine, abstract, seed)
    make_w = jax.jit(lambda k: weights.make(abstract, k))
    step = engine.lower().compile()

    def feed(i):
        with jax.profiler.TraceAnnotation("bench.feed"):
            return engine.shard_batch(ring[i % len(ring)])

    def dispatch(s, b):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return step(s, b)

    trainer = Trainer(dispatch, state, log_every=10 ** 9,
                      state_shardings=engine.state_shardings)
    del state
    prog = program_readings(trainer, feed, make_w, wkey,
                            cell.config["train"]["check_steps"])
    est = trainer.history[-1]["dt"]
    n = max(2, round(seconds / est))
    setup_s = time.perf_counter() - t_start
    common.log(f"[bench] set-up {setup_s:.1f}s; step estimate "
               f"{est * 1e3:.1f}ms; window of {n} steps")

    tdir = trace.start() if do_trace else None
    h0 = len(trainer.history)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        t0 = time.perf_counter()
        trainer.run(feed, n)
        jax.block_until_ready(trainer.state)
        window_s = time.perf_counter() - t0
    if do_trace:
        trace.stop()
    losses = [h["loss"] for h in trainer.history[h0:]]
    failed = sum(not math.isfinite(x) for x in losses)
    device = common.device_info(devices)
    step_s = window_s / n
    common.log(f"[bench] window {window_s:.3f}s, {n} steps, "
               f"{step_s * 1e3:.2f} ms per step, losses {losses[0]:.4f} .. "
               f"{losses[-1]:.4f}")
    del trainer, step, engine
    gc.collect()

    t_ref = time.perf_counter()
    refr = reference_readings(cell, make_w, ring, wkey, dkey)
    gaps = refc.compare_train(prog, refr)
    common.log(f"[bench] reference {time.perf_counter() - t_ref:.1f}s; "
               f"losses program {prog['losses']} reference "
               f"{refr['losses']}")
    checks = [common.Check(k, v, limits[k]) for k, v in gaps.items()]

    if do_trace:
        red = trace.reduce_dir(tdir)
        trace.cleanup(tdir)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        work = common.load_module(common.BENCH / "work"
                                  / f"{cell.config['family']}.py")
        mctx = types.SimpleNamespace(
            cell=cell, red=red, step_s=step_s, window_s=window_s, steps=n,
            device=device, work=work.train_step(cell.config, cell.traffic))
        metrics = read_per_layer(cell, mctx)
        return common.result_line(checks=checks, attempted=n, failed=failed,
                                  metrics=metrics, device=device,
                                  breakdown=red.breakdown())
    metrics = {"setup_s": {"value": setup_s, "unit": "s"},
               "train_step_ms": {"value": step_s * 1e3, "unit": "ms"}}
    return common.result_line(checks=checks, attempted=n, failed=failed,
                              metrics=metrics, device=device)


def read_per_layer(cell, ctx) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        mod = common.load_module(common.BENCH / "metrics" / f"{m['name']}.py")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
