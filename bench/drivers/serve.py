"""Serving cells: ``ServeEngine.generate`` as ``repro.launch.serve`` drives
it, on the configuration's paged cache, chunked prefill and kernels.

Set-up builds the engine, makes the weights on the device from the seed
(``bench/weights.py``, placed on the engine's shardings), and warms every
shape the traffic can use: the chunked prefill at each power-of-two
bucket up to the chunk budget, and the decode step. The window runs the
traffic's calls back to back and ends with the first call that ends after
``--seconds``. An in-memory sink on ``engine.telemetry`` records the
engine's ``request`` and ``wave`` events on the host clock, on in every
run; each token's time is rebuilt from them.

After the window the engine and its weights are freed, and a sample of the
window's requests, drawn from the seed with the longest among them, goes
through the plain reference: teacher-forced on its prompt and served
tokens, the reference's logit of each served token must lie within the
limit of its best.
"""
from __future__ import annotations

import bisect
import gc
import time
import types
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, trace, weights
from bench.drivers.train import program_config, read_per_layer


class Recorder:
    """A telemetry sink that keeps the serve loop's events in memory,
    stamped with ``time.perf_counter()``."""

    KINDS = ("request", "wave")

    def __init__(self):
        self.events: List = []

    def emit(self, kind, **fields):
        if kind in self.KINDS:
            self.events.append((time.perf_counter(), kind, fields))

    def close(self):
        pass


def token_times(events) -> Dict[int, List[float]]:
    """Each request's token times from one ``generate`` call's events. A
    request's first token lands at its ``first_token`` event; it then
    gains one token in every decode wave from that wave's index through
    the one in which it finished (with speculation and preemption off)."""
    first, first_wave, last_wave, n_gen = {}, {}, {}, {}
    waves, stamps = [], []
    for t, kind, f in events:
        if kind == "wave" and f["mode"] == "decode":
            waves.append(f["wave"])
            stamps.append(t)
        elif kind == "request" and f["event"] == "first_token":
            first[f["uid"]], first_wave[f["uid"]] = t, f["wave"]
        elif kind == "request" and f["event"] == "finished":
            last_wave[f["uid"]], n_gen[f["uid"]] = f["wave"], f["n_generated"]
    out = {}
    for u, t in first.items():
        lo = bisect.bisect_left(waves, first_wave[u])
        hi = bisect.bisect_right(waves, last_wave[u])
        ts = [t] + (stamps[lo:hi] if n_gen[u] > 1 else [])
        if len(ts) != n_gen[u]:
            raise RuntimeError(f"request {u}: {len(ts)} token times for "
                               f"{n_gen[u]} tokens")
        out[u] = ts
    return out


def make_engine(config, devices, *, quant_mode=None):
    from jax.sharding import AxisType
    from repro.configs.base import ServeConfig
    from repro.models import build
    from repro.serve import make_serve_engine
    n = len(devices)
    mesh = jax.make_mesh((n, 1), ("data", "model"), devices=devices,
                         axis_types=(AxisType.Auto,) * 2)
    s = config["serve"]
    scfg = ServeConfig(
        max_batch=s["max_batch"], max_len=s["max_len"],
        block_size=s["block_size"], cache_mode=s["cache_mode"],
        prefill_chunk_tokens=s["prefill_chunk_tokens"],
        prefix_cache=s["prefix_cache"], temperature=0.0, spec_mode="off",
        preemption="off",
        quant_mode=quant_mode or config["path"]["quant_mode"],
        kernel_backend=config["path"]["kernel_backend"])
    return make_serve_engine(build(program_config(config)), scfg, mesh)


def warm(engine, params) -> None:
    """Compile and run each prefill bucket the chunk budget allows, the
    decode step and the sampler, on an empty cache."""
    from repro.serve.engine import prefill_bucket
    scfg = engine.serve_cfg
    B = scfg.max_batch
    tables = np.zeros((B, engine.blocks_per_slot), np.int32)
    idle = np.zeros((B,), bool)
    zeros = np.zeros((B,), np.int32)
    cache = engine.init_cache()
    S = prefill_bucket(1, scfg.prefill_bucket)
    top = min(prefill_bucket(scfg.prefill_chunk_tokens, scfg.prefill_bucket),
              scfg.max_len)
    while S <= top:
        logits, cache = engine.prefill_paged(
            params, cache, tables, np.zeros((B, S), np.int32), zeros,
            np.ones((B,), np.int32), idle)
        S *= 2
    logits, cache = engine.decode_paged(params, cache, tables,
                                        np.zeros((B, 1), np.int32))
    jax.block_until_ready(engine.sample(logits[:, 0], jax.random.PRNGKey(0),
                                        zeros, zeros))
    del cache


class Serving:
    """One engine and the seed's weights, for a run or for many seeds."""

    def __init__(self, cell, devices, *, quant_mode=None):
        self.cell = cell
        self.engine = make_engine(cell.config, devices, quant_mode=quant_mode)
        self.abstract = cell.reference.abstract(cell.config)
        prog = jax.tree.map(lambda s: (tuple(s.shape), "float32"),
                            self.engine.specs,
                            is_leaf=lambda x: hasattr(x, "logical"))
        if prog != jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                                self.abstract):
            raise RuntimeError("the program's parameter tree differs from "
                               "the reference's layout")
        self.make_w = jax.jit(lambda k: weights.make(self.abstract, k))
        self.recorder = Recorder()
        from repro.telemetry import Telemetry
        self.engine.telemetry = Telemetry()
        self.engine.telemetry.sink = self.recorder

    def weights(self, seed: int):
        wkey = jax.random.split(common.raw_key(seed))[0]
        with jax.set_mesh(self.engine.mesh), self.engine.shard_ctx():
            return jax.jit(lambda k: weights.make(self.abstract, k),
                           out_shardings=self.engine.param_shardings)(wkey)

    def serve(self, params, seed: int, seconds: float):
        """Calls of the traffic from index 1 until one ends after
        ``seconds``; returns (calls, window seconds)."""
        kind, mix, cfg = self.cell.kind, self.cell.traffic, self.cell.config
        calls = []
        t0 = time.perf_counter()
        i = 1
        while True:
            prompts, budgets = kind.call(mix, cfg, seed, i)
            self.recorder.events = []
            with jax.profiler.TraceAnnotation("bench.generate"):
                gens, _ = self.engine.generate(params, prompts,
                                               max_new_tokens=budgets)
            calls.append((prompts, budgets, gens, self.recorder.events))
            i += 1
            if time.perf_counter() - t0 >= seconds:
                return calls, time.perf_counter() - t0


def check(cell, make_w, seed: int, calls):
    """The widest gap by which the reference's logit of a served token lies
    below its best, over a sample of the window's requests: the one with
    the most tokens and others drawn from the seed."""
    from bench.reference.common import make_linear
    reqs = [(p, g) for prompts, _, gens, _ in calls
            for p, g in zip(prompts, gens) if g]
    n = min(cell.traffic["check_requests"], len(reqs))
    longest = max(range(len(reqs)), key=lambda i: len(reqs[i][0])
                  + len(reqs[i][1]))
    rng = common.np_rng(seed, 7)
    others = [i for i in rng.permutation(len(reqs)) if i != longest]
    pick = [reqs[i] for i in [longest] + others[:n - 1]]
    L = cell.config["serve"]["max_len"]
    P = max(len(g) for _, g in pick)
    toks = np.zeros((n, L), np.int32)
    pos = np.zeros((n, P), np.int32)
    served = np.zeros((n, P), np.int32)
    valid = np.zeros((n, P), bool)
    for i, (p, g) in enumerate(pick):
        seq = list(p) + list(g[:-1])
        toks[i, :len(seq)] = seq
        pos[i, :len(g)] = np.arange(len(p) - 1, len(p) - 1 + len(g))
        pos[i, len(g):] = len(p) - 1
        served[i, :len(g)] = g
        valid[i, :len(g)] = True
    wkey = jax.random.split(common.raw_key(seed))[0]

    @jax.jit
    def gaps(params, toks, pos, served):
        logits = cell.reference.logits_at(params, toks, pos, cell.config,
                                          make_linear(None))
        mine = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
        return jnp.max(logits, -1) - mine

    g = np.asarray(gaps(make_w(wkey), toks, pos, served))
    return float(g[valid].max()), int(valid.sum())


def itl_gaps(calls) -> np.ndarray:
    out = []
    for _, _, _, events in calls:
        for ts in token_times(events).values():
            out.extend(np.diff(ts))
    return np.asarray(out)


def run(cell, seed: int, seconds: float, do_trace: bool, devices,
        t_start: float, *, limits: Dict[str, float]) -> str:
    sv = Serving(cell, devices)
    params = sv.weights(seed)
    warm(sv.engine, params)
    # a first short call drives the host loop once before the window
    prompts, budgets = cell.kind.call(cell.traffic, cell.config, seed, 0)
    sv.engine.generate(params, prompts[:4], max_new_tokens=[2] * 4)
    setup_s = time.perf_counter() - t_start
    common.log(f"[bench] set-up {setup_s:.1f}s")

    compiles = common.CompileCounter()
    tdir = trace.start() if do_trace else None
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        calls, window_s = sv.serve(params, seed, seconds)
    if do_trace:
        trace.stop()
    compiles.stop()
    device = common.device_info(devices)
    attempted = sum(len(b) for _, b, _, _ in calls)
    failed = sum(len(g) != b for _, bs, gs, _ in calls
                 for b, g in zip(bs, gs))
    tokens = sum(len(g) for _, _, gs, _ in calls for g in gs)
    gaps = itl_gaps(calls)
    common.log(f"[bench] window {window_s:.3f}s, {len(calls)} calls, "
               f"{attempted} requests, {tokens} tokens, {len(gaps)} gaps, "
               f"{compiles.n} compiles inside")
    requests = [(len(p), len(g)) for ps, _, gs, _ in calls
                for p, g in zip(ps, gs)]
    make_w = sv.make_w
    del params, sv
    gc.collect()

    t_ref = time.perf_counter()
    gap, n_tok = check(cell, make_w, seed, calls)
    common.log(f"[bench] reference {time.perf_counter() - t_ref:.1f}s over "
               f"{n_tok} served tokens")
    checks = [common.Check("logit_gap", gap, limits["logit_gap"])]

    if do_trace:
        red = trace.reduce_dir(tdir)
        trace.cleanup(tdir)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = types.SimpleNamespace(cell=cell, red=red, window_s=window_s,
                                    requests=requests, device=device)
        return common.result_line(
            checks=checks, attempted=attempted, failed=failed,
            metrics=read_per_layer(cell, ctx), device=device,
            breakdown=red.breakdown())
    metrics = {"setup_s": {"value": setup_s, "unit": "s"},
               "serve_tokens_per_s": {"value": tokens / window_s,
                                      "unit": "tokens/s"},
               "serve_itl_p95_ms": {"value": float(np.percentile(gaps, 95))
                                    * 1e3, "unit": "ms"}}
    return common.result_line(checks=checks, attempted=attempted,
                              failed=failed, metrics=metrics, device=device)
