"""Where a training cell's device time and idle went, by the program's own
spans and layer scopes (the benchmark's own runs do not run this).

    python3 bench/attribute.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints its result
line, then one JSON object read from the same profile:

- ``idle_by_span``: device idle seconds in the window, split exactly at
  span edges by the innermost ``train.*`` span of the thread that runs the
  loop (key ``null``: outside every such span); ``device_idle.host.train``
  (idle while the host runs its own code in the loop), and
  ``device_idle.blocked.train`` (idle while it waits in ``train.wait``),
  each as a share of the window in %;
- ``scope_s``: device self seconds per layer scope (the innermost of
  ``SCOPES`` in an operation's op-name path, JAX's transform wrappers
  stripped; ``null``: none), and ``attention_ms.train``, the ``attention``
  scope's per step. The trace's operation events carry no op name (their
  stats hold only offsets and durations); each event's instruction is
  looked up in the compiled step's HLO text, kept as the run compiles it;
  ``scope_ops``: each scope's operation groups (``bench.trace.op_group``)
  with the most self time;
- ``gaps``: each idle gap over 10 ms, with the program's and the
  benchmark's innermost span at its start and the host events that
  overlap it most.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, trace  # noqa: E402

PROGRAM = "train."
BLOCKED = "train.wait"
SCOPES = ("attention", "linear", "norm", "embed", "head", "optimizer")
STEP = "train.step"
GAP_S = 0.010
TOP = 10
_WRAPPER = re.compile(r"^(?:[a-z_]+\()+|\)+$")
_INSTR = re.compile(r"(ROOT )?%([\w.\-]+) = ")

Interval = Tuple[float, float]


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op-name path, from compiled HLO text. An
    instruction without metadata of its own takes that of its fusion's
    root, else that of its first operand (the copies XLA adds)."""
    names: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    first: Dict[str, str] = {}
    order: List[str] = []
    comp = None
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _INSTR.match(s)
        if not m:
            if s.endswith("{"):
                comp = s.split()[1 if s.startswith("ENTRY") else 0][1:]
            continue
        name = m.group(2)
        order.append(name)
        op = re.search(r'op_name="([^"]*)"', s)
        if op:
            names[name] = op.group(1)
        if m.group(1):
            roots[comp] = name
        c = re.search(r"calls=%([\w.\-]+)", s)
        if c:
            calls[name] = c.group(1)
        arg = re.search(r"\(%([\w.\-]+)", s)
        if arg:
            first[name] = arg.group(1)
    for name in order:
        if name not in names:
            got = (names.get(roots.get(calls.get(name)))
                   or names.get(first.get(name)))
            if got:
                names[name] = got
    return names


def op_name(ev, names: Dict[str, str]) -> Optional[str]:
    """The op-name path of an XLA operation's event (named by its HLO
    instruction's text)."""
    return names.get(ev.name.split(" = ", 1)[0].lstrip("%"))


def scope_of(name: Optional[str]) -> Optional[str]:
    """The innermost layer scope in an op-name path: ``jit(f)/transpose(
    jvp(checkpoint))/attention/jit(g)/add`` -> ``attention``."""
    if not name:
        return None
    hit = None
    for part in name.split("/"):
        if _WRAPPER.sub("", part) in SCOPES:
            hit = _WRAPPER.sub("", part)
    return hit


def _window_and_loop(pd):
    """The ``bench.window`` span and the host line that holds it."""
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW:
                    return (ev.start_ns, ev.end_ns), line
    raise RuntimeError(f"no {trace.WINDOW} span in the trace")


def _device_ops(pd, w0: float, w1: float):
    """Per device: the XLA operations' events clipped to the window."""
    out = []
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            out.append([(max(ev.start_ns, w0), min(ev.end_ns, w1), ev)
                        for line in plane.lines if line.name == trace.OPS_LINE
                        for ev in line.events
                        if min(ev.end_ns, w1) > max(ev.start_ns, w0)])
    if not out:
        raise RuntimeError("the trace holds no TPU device plane")
    return out


def _idle(ops, w0: float, w1: float) -> List[Interval]:
    u = trace._union([(a, b) for a, b, _ in ops])
    edges = [w0] + [x for ab in u for x in ab] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def _innermost(spans, t: float) -> Optional[str]:
    cover = [(a, -b, n) for a, b, n in spans if a <= t <= b]
    return max(cover)[2] if cover else None


def label_segments(spans, w0: float, w1: float):
    """The window cut at every span edge, each piece labelled with the
    innermost span covering it (spans of one thread nest)."""
    edges = sorted({w0, w1} | {min(max(x, w0), w1)
                               for a, b, _ in spans for x in (a, b)})
    return [(a, b, _innermost(spans, (a + b) / 2))
            for a, b in zip(edges, edges[1:]) if b > a]


def split_idle(idle: List[Interval], segments) -> Dict[Optional[str], float]:
    """Seconds of ``idle`` per label; both lists sorted and disjoint."""
    out: Dict[Optional[str], float] = {}
    j = 0
    for a, b in idle:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s0, s1, lab = segments[k]
            d = min(b, s1) - max(a, s0)
            if d > 0:
                out[lab] = out.get(lab, 0.0) + d * 1e-9
            k += 1
    return out


def self_times(ops) -> List[Tuple[object, float]]:
    """(event, self seconds) of each operation: its time less that of the
    operations nested in it, as ``bench.trace`` counts it."""
    iv = sorted(((a, -b, k) for k, (a, b, _) in enumerate(ops)))
    own = [(b - a) * 1e-9 for a, b, _ in ops]
    stack: List[Tuple[float, int]] = []
    for a, nb, k in iv:
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= (min(-nb, stack[-1][0]) - a) * 1e-9
        stack.append((-nb, k))
    return [(ops[k][2], own[k]) for k in range(len(ops))]


def attribute(pd, names: Dict[str, str]) -> Dict:
    """The reductions the module docstring lists, from a ProfileData and
    the op names of its instructions (``op_names``)."""
    (w0, w1), loop = _window_and_loop(pd)
    spans = [(ev.start_ns, ev.end_ns, ev.name) for ev in loop.events
             if ev.name.startswith(PROGRAM)]
    steps = sum(1 for a, b, n in spans if n == STEP and w0 <= a and b <= w1)
    segments = label_segments(spans, w0, w1)
    bench_spans = [(ev.start_ns, ev.end_ns, ev.name) for ev in loop.events
                   if ev.name.startswith(trace.SPAN_PREFIX)
                   and ev.name != trace.WINDOW]
    devices = _device_ops(pd, w0, w1)
    n = len(devices)
    by_span: Dict[Optional[str], float] = {}
    scope_s: Dict[Optional[str], float] = {}
    ops_s: Dict[Tuple[Optional[str], str], float] = {}
    gaps = []
    for ops in devices:
        idle = _idle(ops, w0, w1)
        for k, v in split_idle(idle, segments).items():
            by_span[k] = by_span.get(k, 0.0) + v / n
        for ev, s in self_times(ops):
            k = scope_of(op_name(ev, names))
            scope_s[k] = scope_s.get(k, 0.0) + s / n
            kg = (k, trace.op_group(ev.name))
            ops_s[kg] = ops_s.get(kg, 0.0) + s / n
        gaps += [(a, b) for a, b in idle if (b - a) * 1e-9 > GAP_S]
    window_s = (w1 - w0) * 1e-9
    host = sum(v for k, v in by_span.items() if k and k != BLOCKED)
    return {
        "window_s": window_s, "steps": steps,
        "idle_by_span": by_span, "scope_s": scope_s,
        "scope_ops": {k: sorted(([g, v] for (kk, g), v in ops_s.items()
                                 if kk == k), key=lambda gv: -gv[1])[:TOP]
                      for k in scope_s},
        "device_idle.host.train": 100.0 * host / window_s,
        "device_idle.blocked.train":
            100.0 * by_span.get(BLOCKED, 0.0) / window_s,
        "attention_ms.train": (1e3 * scope_s.get("attention", 0.0) / steps
                               if steps else None),
        "gaps": [_gap(pd, a, b, w0, spans, bench_spans) for a, b in gaps],
    }


def _gap(pd, a: float, b: float, w0: float, spans, bench_spans) -> Dict:
    """A long idle gap: where it lies, the innermost spans at its start,
    and the host events (any thread, by name) that cover most of it."""
    over: Dict[Tuple[str, str], List[Interval]] = {}
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                lo, hi = max(ev.start_ns, a), min(ev.end_ns, b)
                if hi > lo:
                    over.setdefault((line.name, ev.name[:80]), []).append(
                        (lo, hi))
    cover = {k: sum(y - x for x, y in trace._union(iv)) * 1e-9
             for k, iv in over.items()}
    top = sorted(cover.items(), key=lambda kv: -kv[1])[:TOP]
    return {"at_s": (a - w0) * 1e-9, "idle_s": (b - a) * 1e-9,
            "program_span": _innermost(spans, a),
            "bench_span": _innermost(bench_spans, a),
            "host_events": [[ln, nm, s] for (ln, nm), s in top]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import jax
    from jax.profiler import ProfileData
    run = common.load_module(common.BENCH / "run.py")
    found: Dict = {}
    names: Dict[str, str] = {}
    reduce_dir = trace.reduce_dir
    compile_ = jax.stages.Lowered.compile

    def compile_and_read(self, *a, **k):
        out = compile_(self, *a, **k)
        names.update(op_names(out.as_text()))
        return out

    def reduce_and_attribute(d):
        pd = ProfileData.from_file(trace.xplane_path(d))
        found.update(attribute(pd, names))
        return reduce_dir(d)

    argv = ["bench/run.py", "--workload", args.workload, "--seed",
            str(args.seed), "--seconds", str(args.seconds), "--trace", "1"]
    with mock.patch.object(trace, "reduce_dir", reduce_and_attribute), \
            mock.patch.object(jax.stages.Lowered, "compile",
                              compile_and_read), \
            mock.patch.object(sys, "argv", argv):
        rc = run.main()
    if found:
        print(json.dumps(found, default=str), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
