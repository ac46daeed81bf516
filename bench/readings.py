"""The readings a training cell's limits are set from, many seeds in one
process (the benchmark's own runs do not run this).

    python3 bench/readings.py --workload <cell> --mode program --seeds 1,2,3
    python3 bench/readings.py --workload <cell> --mode controls --seeds 1,2,3

``program``: the cell's own engine and compiled step, driven from each
seed through its first steps exactly as a run's set-up drives it, against
the plain reference: the lower readings. ``controls``: the reference put
in the program's place, once in the precision below the configuration's
(SwitchBack at int4 for an int8 configuration) and once with half of each
batch left out (the mean over the rest), against the float32 reference:
the upper readings. A bf16 configuration's control is the program's own
int8 path: ``--mode program --quant-mode int8_switchback``.

A serving cell has ``program`` only: each seed serves one call of its
traffic at the cell's load and compares a sample of it with the reference
as a run does; its control is the program's int8 path, as above.

Each seed prints one JSON line: mode, seed and the compared numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def worst(prog, ref, n: int = 3):
    """The leaves with the largest gaps, for the look behind a reading."""
    from bench.reference import common as refc
    return {k: sorted(v.items(), key=lambda kv: -kv[1])[:n]
            for k, v in refc.leaf_gaps(prog, ref).items()}


def train_readings(cell, mode: str, seeds, devices, quant_mode=None):
    """One dict per seed: the compared numbers and the worst leaves."""
    import jax
    from bench import common, weights
    from bench.drivers import train as T
    from bench.reference import common as refc
    from repro.train import Trainer
    abstract = cell.reference.abstract(cell.config)
    make_w = jax.jit(lambda k: weights.make(abstract, k))
    steps = cell.config["train"]["check_steps"]
    engine = step = None
    for seed in seeds:
        ring = cell.kind.make(cell.traffic, cell.config, seed)
        wkey, dkey = jax.random.split(common.raw_key(seed))
        out = {"mode": mode, "seed": seed}
        ref = T.reference_readings(cell, make_w, ring, wkey, dkey)
        if mode == "program":
            if engine is None:
                engine = T.make_engine(cell.config, devices, ring[0],
                                       quant_mode=quant_mode)
                step = engine.lower().compile()
            state = T.init_state(engine, abstract, seed)[0]
            trainer = Trainer(step, state, log_every=10 ** 9,
                              state_shardings=engine.state_shardings)
            del state
            prog = T.program_readings(
                trainer, lambda i: engine.shard_batch(ring[i % len(ring)]),
                make_w, wkey, steps)
            del trainer
            gc.collect()
            out.update(refc.compare_train(prog, ref),
                       quant_mode=engine.policy.mode,
                       losses=[prog["losses"], ref["losses"]],
                       worst=worst(prog, ref))
        else:
            low = {"int8_switchback": 4}[cell.config["path"]["quant_mode"]]
            for name, kw in ((f"int{low}", {"bits": low}),
                             ("half_batch", {"rows": slice(
                                 0, cell.traffic["batch"] // 2)})):
                ctl = T.reference_readings(cell, make_w, ring, wkey, dkey,
                                           **kw)
                out[name] = dict(refc.compare_train(ctl, ref),
                                 worst=worst(ctl, ref))
        yield out


def serve_readings(cell, mode: str, seeds, devices, quant_mode=None):
    """One dict per seed: one call of the traffic at the cell's load and
    its ``logit_gap``. A serving cell's control is the program's own lower
    precision, so there is only ``program``."""
    from bench.drivers import serve as S
    if mode != "program":
        raise SystemExit("a serving cell reads --mode program")
    sv = S.Serving(cell, devices, quant_mode=quant_mode)
    for i, seed in enumerate(seeds):
        params = sv.weights(seed)
        if i == 0:
            S.warm(sv.engine, params)
        calls, call_s = sv.serve(params, seed, 0.0)
        del params
        gap, n_tok = S.check(cell, sv.make_w, seed, calls)
        yield {"mode": mode, "seed": seed, "quant_mode": sv.engine.policy.mode,
               "logit_gap": gap, "tokens_checked": n_tok, "call_s": call_s}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", choices=("program", "controls"), required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--quant-mode", default=None)
    args = ap.parse_args()

    import jax
    from bench import common
    from repro.compile_cache import enable_compile_cache
    cell = common.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        common.log(f"[readings] no TPU: JAX found {devices[0].platform!r}")
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    read = {"train": train_readings,
            "serve": serve_readings}[cell.kind.DRIVER]
    for out in read(cell, args.mode, [int(s) for s in args.seeds.split(",")],
                    devices[:cell.chips], args.quant_mode):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
