"""What every cell shares: its files found by name, seeds, the device and
the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. It names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). The mix names its generator kind
(``bench/traffic/<kind>.py``), the kind names its driver
(``bench/drivers/<driver>.py``), the configuration names its reference
(``bench/reference/<family>.py``), and each per-layer metric is read by
``bench/metrics/<metric>.py``. A new cell, configuration, mix, kind or
metric is a new file and a new entry; no file here changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC = ROOT / "BENCHMARK.json"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_module(path: Path):
    """Import a harness file by path (metric files carry dots in names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]           # bench/configs/<config>.json
    traffic: Dict[str, Any]          # bench/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]  # BENCHMARK.json entries this cell reports
    per_layer: List[Dict[str, Any]]

    @functools.cached_property
    def kind(self):
        return load_module(BENCH / "traffic" / f"{self.traffic['kind']}.py")

    @functools.cached_property
    def driver(self):
        return load_module(BENCH / "drivers" / f"{self.kind.DRIVER}.py")

    @functools.cached_property
    def reference(self):
        return load_module(BENCH / "reference"
                           / f"{self.config['family']}.py")


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: Path = SPEC) -> Cell:
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, w["chips"], config, traffic,
                [m for m in spec["end_to_end"] if _reports(m, name)],
                [m for m in spec["per_layer"] if _reports(m, name)])


def seed_words(seed: int) -> tuple:
    """A seed of up to 64 bits as the two 32-bit words of a threefry key
    (what ``jax.random.PRNGKey`` makes of a 64-bit seed)."""
    if not 0 <= seed < 2 ** 64:
        raise SystemExit(f"--seed must lie in [0, 2**64), got {seed}")
    return (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF


def raw_key(seed: int):
    import jax.numpy as jnp
    return jnp.asarray(seed_words(seed), jnp.uint32)


def np_rng(seed: int, stream: int):
    """Host generator for input stream ``stream`` of ``seed``."""
    import numpy as np
    return np.random.default_rng([*seed_words(seed), stream])


def device_info(devices) -> Dict[str, Any]:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``, with its limit (pass: ``<=``)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def result_line(*, checks: List[Check], attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    """The last line of stdout. Every compared number also goes to stderr
    as the last lines there, beside its limit."""
    correct = bool(checks) and all(c.ok for c in checks) and failed == 0
    out: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    for c in checks:
        log(f"[check] {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    return json.dumps(out)


class CompileCounter:
    """Counts the programs JAX lowers (each new shape of a jitted function,
    compiled or fetched from the cache) from construction until ``stop``:
    a measured window should lower none."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring
        self.n, self.on = 0, True
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.n += 1

    def stop(self) -> int:
        self.on = False
        return self.n
