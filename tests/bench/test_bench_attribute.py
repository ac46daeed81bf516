"""Attribution of a traced window to the program's spans and layer scopes
(``bench/attribute.py``): device idle split at span edges by the innermost
``train.*`` span, and device self time by the innermost layer scope of
each operation's op name, read from the compiled program's HLO text. A
synthetic trace gives hand-computed values; the recorded smollm trace
(benchmark spans only, no op names) checks the totals against
``bench.trace``."""
import types

import pytest

from bench import attribute as A
from bench import trace

from test_bench_trace import profile as recorded

U = 100_000                                  # ns per tick: 0.1 ms
KLOOP = " = f32[8] fusion(x), kind=kLoop, calls=%c"
PALLAS = ' = bf16[8] custom-call(x), custom_call_target="tpu_custom_call"'


NAMES = {"fusion.1": "jit(step)/transpose(jvp(checkpoint))/attention/add",
         "flash_fwd.1": "jit(step)/jvp(checkpoint)/attention/"
                        "jit(flash_fwd_lse)/flash_fwd/pallas_call",
         "fusion.2": "jit(step)/linear/dot_general",
         "convolution.3": "jit(step)/head/norm/mul"}


def ns(*events):
    return [types.SimpleNamespace(name=e[0], start_ns=e[1] * U,
                                  end_ns=e[2] * U) for e in events]


def synthetic():
    host = ns(["bench.window", 100, 1100],
              ["train.step", 110, 600],
              ["train.batch", 120, 200],
              ["bench.feed", 130, 190],
              ["train.dispatch", 200, 300],
              ["bench.dispatch", 200, 300],
              ["train.wait", 300, 500],
              ["train.bookkeeping", 500, 550],
              ["train.step", 600, 1050],
              ["train.batch", 600, 700],
              ["train.dispatch", 700, 800],
              ["train.wait", 1050, 1090])
    dev = ns(["%fusion.1" + KLOOP, 90, 250],
             ["%flash_fwd.1" + PALLAS, 150, 200],
             ["%fusion.2" + KLOOP, 400, 480],
             ["%convolution.3 = f32[8] convolution(x, y)", 620, 900],
             ["%copy.4 = f32[8] copy(x)", 950, 1000])
    line = types.SimpleNamespace
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[
            line(name="python", events=host),
            line(name="runtime", events=ns(["TransferToDevice", 260, 390]))]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line(name=trace.OPS_LINE, events=dev),
            line(name="Steps", events=ns(["0", 0, 2000]))])])


def test_idle_split_at_span_edges_by_innermost_train_span():
    got = A.attribute(synthetic(), NAMES)
    s = U * 1e-9
    want = {"train.dispatch": 50, "train.wait": 160, "train.bookkeeping": 50,
            "train.step": 150, "train.batch": 20, None: 10}
    assert got["idle_by_span"].keys() == want.keys()
    for k, v in want.items():
        assert got["idle_by_span"][k] == pytest.approx(v * s)
    assert got["window_s"] == pytest.approx(1000 * s)
    assert got["device_idle.host.train"] == pytest.approx(27.0)
    assert got["device_idle.blocked.train"] == pytest.approx(16.0)


def test_host_blocked_and_outside_sum_to_device_idle():
    pd = synthetic()
    got = A.attribute(pd, NAMES)
    red = trace.reduce_profile(pd)
    idle = 100.0 * (1.0 - red.busy_s / red.window_s)
    outside = 100.0 * got["idle_by_span"][None] / got["window_s"]
    assert idle == pytest.approx(44.0)
    assert got["device_idle.host.train"] + got["device_idle.blocked.train"] \
        + outside == pytest.approx(idle, abs=1e-9)


def test_scope_self_seconds_and_attention_per_step():
    got = A.attribute(synthetic(), NAMES)
    s = U * 1e-9
    want = {"attention": 150, "linear": 80, "norm": 280, None: 50}
    assert got["scope_s"].keys() == want.keys()
    for k, v in want.items():
        assert got["scope_s"][k] == pytest.approx(v * s)
    assert got["steps"] == 2
    assert got["attention_ms.train"] == pytest.approx(1e3 * 150 * s / 2)
    ops = {k: dict(v) for k, v in got["scope_ops"].items()}
    assert ops["attention"] == pytest.approx(
        {"fusion:kLoop": 100 * s, "flash_fwd (pallas)": 50 * s})
    assert ops[None] == pytest.approx({"copy": 50 * s})


def test_long_gaps_name_their_spans_and_host_events():
    gaps = A.attribute(synthetic(), NAMES)["gaps"]
    assert [round(g["idle_s"] * 1e3, 6) for g in gaps] == [15.0, 14.0]
    assert gaps[0]["program_span"] == "train.dispatch"
    assert gaps[0]["bench_span"] == "bench.dispatch"
    assert ["runtime", "TransferToDevice", pytest.approx(130 * U * 1e-9)] \
        in gaps[0]["host_events"]
    # nested events of one name on one thread count once
    assert ["python", "train.step", pytest.approx(150 * U * 1e-9)] \
        in gaps[0]["host_events"]
    assert gaps[1]["program_span"] == "train.wait"


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/transpose(jvp(checkpoint))/attention/jit(g)/add", "attention"),
    ("jit(f)/head/norm/mul", "norm"),
    ("jit(f)/embed/linear/jvp(dot_general)", "linear"),
    ("jit(f)/jvp(optimizer)/sqrt", "optimizer"),
    ("jit(f)/rematted_computation/mul", None),
    (None, None),
])
def test_scope_of(path, scope):
    assert A.scope_of(path) == scope


def test_recorded_trace_without_stats_or_program_spans():
    pd, _ = recorded()
    got = A.attribute(pd, {})
    red = trace.reduce_profile(pd)
    assert got["scope_s"].keys() == {None}
    assert got["scope_s"][None] == pytest.approx(red.busy_s, rel=1e-9)
    assert got["idle_by_span"].keys() == {None}
    assert got["idle_by_span"][None] == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    assert got["device_idle.host.train"] == 0.0
    assert got["steps"] == 0 and got["attention_ms.train"] is None


def test_op_names_from_compiled_hlo():
    """Scopes reach the compiled program's metadata; each instruction,
    fusions included, maps to its op-name path."""
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("linear"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("norm"):
            return y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True))

    x = jnp.ones((8, 16))
    text = jax.jit(f).lower(x, jnp.ones((16, 16))).compile().as_text()
    names = A.op_names(text)
    scopes = {A.scope_of(p) for p in names.values()}
    assert {"linear", "norm"} <= scopes
    entry = [ln.strip() for ln in text.splitlines()
             if ln.strip().startswith("ROOT %")][-1]
    root = entry.split(" = ")[0].split("%")[1]
    assert A.scope_of(names[root]) == "norm"


def test_op_names_fall_back_to_fusion_root_then_first_operand():
    text = """HloModule m

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(f)/attention/add"}
}

ENTRY %main.2 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %copy.2 = f32[8]{0} copy(%fusion.1)
  ROOT %copy.3 = f32[8]{0} copy(%x)
}
"""
    names = A.op_names(text)
    assert names["fusion.1"] == names["copy.2"] == "jit(f)/attention/add"
    assert "copy.3" not in names and "x" not in names

