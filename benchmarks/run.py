"""Benchmark harness entrypoint: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all, fast settings
    PYTHONPATH=src python -m benchmarks.run --only stability --steps 300

Benchmarks:
  variance     App. C      quantization variance vs inner dim k
  accuracy     Figs 1-2    precision modes vs training accuracy (CLIP)
  fp8          Fig 5       tensor-wise fp8 + zero-init layer-scale
  stability    Figs 6-10   loss spikes, RMS predictor, StableAdamW
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import (bench_accuracy, bench_fp8_layerscale, bench_stability,
                        bench_variance)

ALL = ("variance", "accuracy", "fp8", "stability")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=ALL)
    ap.add_argument("--steps", type=int, default=None,
                    help="training steps for the training benches")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    which = (args.only,) if args.only else ALL

    t0 = time.time()
    for name in which:
        print(f"\n{'='*72}\n== bench: {name}\n{'='*72}")
        t1 = time.time()
        if name == "variance":
            bench_variance.run(out_json=f"{args.out}/variance.json")
        elif name == "accuracy":
            bench_accuracy.run(steps=args.steps or 200,
                               out_json=f"{args.out}/accuracy.json")
        elif name == "fp8":
            bench_fp8_layerscale.run(steps=args.steps or 150,
                                     out_json=f"{args.out}/fp8.json")
        elif name == "stability":
            bench_stability.run(steps=args.steps or 160,
                                out_json=f"{args.out}/stability.json")
        print(f"[{name} done in {time.time()-t1:.0f}s]")
    print(f"\nall benchmarks done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
