"""The reduction of a profiler trace to busy time, per-operation self time
and labelled idle gaps, on 40 ms of a smollm-360m int8 training step
traced on a TPU v5e and trimmed into ``data/`` (device operations of that
span and the benchmark's host spans, as name, start and end)."""
import gzip
import json
import types
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "trace_smollm_int8_40ms.json.gz"


def profile():
    raw = json.loads(gzip.open(DATA, "rt").read())
    ev = lambda e: types.SimpleNamespace(name=e[0], start_ns=e[1], end_ns=e[2])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=p["name"], lines=[
            types.SimpleNamespace(name=ln["name"],
                                  events=[ev(e) for e in ln["events"]])
            for ln in p["lines"]]) for p in raw["planes"]]), raw


def device_events(raw):
    dev = [p for p in raw["planes"] if p["name"] == "/device:TPU:0"][0]
    return [e for ln in dev["lines"] if ln["name"] == trace.OPS_LINE
            for e in ln["events"]]


def window(raw):
    return [e for p in raw["planes"] for ln in p["lines"]
            for e in ln["events"] if e[0] == trace.WINDOW][0][1:]


def covered(intervals, w0, w1):
    """Busy time by brute force: every elementary segment between event
    edges counts if any event covers it."""
    edges = sorted({w0, w1} | {min(max(x, w0), w1)
                               for _, a, b in intervals for x in (a, b)})
    return sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for _, s, e in intervals))


def test_busy_and_window():
    pd, raw = profile()
    red = trace.reduce_profile(pd)
    w0, w1 = window(raw)
    assert red.window_s == pytest.approx((w1 - w0) * 1e-9)
    busy = covered(device_events(raw), w0, w1) * 1e-9
    assert red.busy_s == pytest.approx(busy, rel=1e-9)
    assert 0 < red.busy_s < red.window_s


def test_self_times_partition_busy_time():
    pd, raw = profile()
    red = trace.reduce_profile(pd)
    assert sum(red.ops.values()) == pytest.approx(red.busy_s, rel=1e-9)
    assert all(v >= -1e-12 for v in red.ops.values())


def test_kernel_groups_sum_their_events():
    """A Pallas kernel is a leaf: its group's time is the summed duration
    of its events inside the window."""
    pd, raw = profile()
    red = trace.reduce_profile(pd)
    w0, w1 = window(raw)
    want = sum(min(b, w1) - max(a, w0) for n, a, b in device_events(raw)
               if n.startswith("%flash_fwd_lse.") and b > w0 and a < w1)
    assert want > 0
    assert red.ops["flash_fwd_lse (pallas)"] == pytest.approx(want * 1e-9)
    assert red.seconds_matching(r"flash_fwd_lse \(pallas\)$") == \
        pytest.approx(want * 1e-9)
    assert red.seconds_matching(r"no_such_kernel") is None


def test_op_group_names():
    assert trace.op_group("%fusion.12 = f32[2] fusion(x), kind=kLoop, "
                          "calls=%c") == "fusion:kLoop"
    assert trace.op_group('%flash_fwd_lse.3 = bf16[2] custom-call(x), '
                          'custom_call_target="tpu_custom_call"') == \
        "flash_fwd_lse (pallas)"
    assert trace.op_group("%copy-start.82 = (s32[4]) copy-start(x)") == \
        "copy-start"


def test_gaps_are_labelled_by_the_covering_bench_span():
    pd, raw = profile()
    red = trace.reduce_profile(pd)
    w0, w1 = window(raw)
    idle = (w1 - w0) * 1e-9 - red.busy_s
    assert sum(s for _, s in red.gaps) == pytest.approx(idle, rel=1e-9)
    assert [s for _, s in red.gaps] == sorted((s for _, s in red.gaps),
                                              reverse=True)
    spans = {e[0] for p in raw["planes"] for ln in p["lines"]
             for e in ln["events"] if e[0].startswith("bench.")}
    assert {n for n, _ in red.gaps} <= (spans - {trace.WINDOW}) | {
        "outside any bench span"}
    # the window opens while the host puts the first batch on the device
    first = min(device_events(raw), key=lambda e: e[1])
    feed = [e for p in raw["planes"] for ln in p["lines"]
            for e in ln["events"] if e[0] == "bench.feed"][0]
    assert feed[1] < first[1]
    assert "bench.feed" in {n for n, _ in red.gaps}


def test_breakdown_lists_at_most_ten():
    pd, _ = profile()
    bd = trace.reduce_profile(pd).breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][1] >= bd["device_ops"][-1][1]
