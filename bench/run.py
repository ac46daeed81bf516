"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The run sets
up (imports, weights from the seed, compile, warm-up of the cell's own
shapes), measures for about ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON object as the
last line of stdout: the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics from a profiler trace of the window with
``--trace 1``. It exits non-zero and prints no result when JAX finds no
TPU, or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import common
    cell = common.load_cell(args.workload)
    limits = json.loads((common.BENCH / "workloads"
                         / f"{cell.name}.json").read_text())["limits"]

    import jax
    from repro.compile_cache import enable_compile_cache
    devices = jax.devices()
    common.log(f"[bench] {cell.name}: platform {devices[0].platform}, "
               f"device_kind {devices[0].device_kind}, count {len(devices)}")
    if devices[0].platform != "tpu":
        common.log(f"[bench] no TPU: JAX found {devices[0].platform!r}")
        return 1
    if len(devices) < cell.chips:
        common.log(f"[bench] the cell asks for {cell.chips} chips, JAX "
                   f"found {len(devices)}")
        return 1
    common.log(f"[bench] compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = cell.driver.run(cell, args.seed, args.seconds, bool(args.trace),
                           devices[:cell.chips], T_START, limits=limits)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
