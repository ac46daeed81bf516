"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time over the traced window, device time per operation,
and the longest idle gaps, each labelled with the benchmark's own host
span (``jax.profiler.TraceAnnotation`` named ``bench.*``) that covered it.

The window is the host span named ``bench.window``. Device planes are
``/device:TPU:<n>``; their operations are the events of the line
``XLA Ops``, each named by its HLO instruction's text. Events nest (a
``while`` loop holds its body's operations), so an operation's time is
its self time: its duration less that of the events inside it. Operations
are grouped by the instruction's name without its numeric suffix, with
the fusion kind for fusions and `` (pallas)`` for Mosaic kernels: a
Pallas call's instruction carries the name of the function around it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # averaged over the devices
    ops: Dict[str, float]              # op group -> self seconds per device
    gaps: List[Tuple[str, float]]      # (host span, idle seconds), longest

    def seconds_matching(self, pattern: str) -> Optional[float]:
        """Device seconds of the op groups whose name matches ``pattern``
        from its start; None when none does."""
        rx = re.compile(pattern)
        hits = [s for n, s in self.ops.items() if rx.match(n)]
        return sum(hits) if hits else None

    def breakdown(self) -> Dict[str, list]:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def start() -> str:
    """Start tracing into a fresh directory under the temporary directory;
    returns the directory, which ``cleanup`` removes."""
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(d)
    return d


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def cleanup(d: str) -> None:
    shutil.rmtree(d, ignore_errors=True)


def xplane_path(d: str) -> str:
    paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {d}, "
                           f"found {paths}")
    return paths[0]


def op_group(text: str) -> str:
    """``%fusion.12 = ... kind=kLoop ...`` -> ``fusion:kLoop``;
    ``%flash_fwd_lse.3 = ... custom_call_target="tpu_custom_call"`` ->
    ``flash_fwd_lse (pallas)``."""
    head = re.sub(r"(\.\d+)+$", "", text.split(" = ", 1)[0].lstrip("%"))
    if head.endswith("fusion"):
        kind = re.search(r"kind=(k\w+)", text)
        return f"{head}:{kind.group(1) if kind else '?'}"
    if 'custom_call_target="tpu_custom_call"' in text:
        return f"{head} (pallas)"
    return head


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def reduce_profile(pd) -> Reduced:
    """``pd``: a ``jax.profiler.ProfileData``."""
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(a, b) for a, b, n in spans if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {windows}")
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    w0, w1 = windows[0]
    ops: Dict[str, float] = {}
    busy = 0.0
    gaps: List[Tuple[str, float]] = []
    inner = [s for s in spans if s[2] != WINDOW]
    for plane in devices:
        iv = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
                    if b > a:
                        iv.append((a, -b, ev.name))
        iv.sort()
        stack: List[Tuple[float, str]] = []       # (end, group) of parents
        for a, nb, name in iv:
            b = -nb
            while stack and stack[-1][0] <= a:
                stack.pop()
            g = op_group(name)
            ops[g] = ops.get(g, 0.0) + (b - a) * 1e-9
            if stack:
                pg = stack[-1][1]
                ops[pg] -= (min(b, stack[-1][0]) - a) * 1e-9
            stack.append((b, g))
        u = _union([(a, -nb) for a, nb, _ in iv])
        busy += sum(b - a for a, b in u) * 1e-9
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label(inner, (a + b) / 2), (b - a) * 1e-9))
    n = len(devices)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
                   ops={k: v / n for k, v in ops.items()}, gaps=gaps)


def _label(spans, t: float) -> str:
    """The innermost benchmark span covering time ``t``."""
    cover = [(b - a, name) for a, b, name in spans if a <= t <= b]
    return min(cover)[1] if cover else "outside any bench span"


def reduce_dir(d: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(xplane_path(d)))
