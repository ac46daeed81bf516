"""Flight-recorder telemetry suite (ISSUE 10).

Covers:
  * MetricsRegistry / streaming Histogram: percentile accuracy vs the
    numpy oracle, exactness at the extremes, zero bucket, declared-schema
    snapshot, fill_counters rejecting undeclared keys,
  * JsonlSink + validators: schema-versioned records, meta-first,
    corrupted lines / missing fields surfaced with line numbers, the
    report CLI's --validate exit codes, Chrome trace export,
  * stats-schema unification (S1/S2): ServeEngine's empty and populated
    stats rows carry identical key sets (ring AND paged), supervisor_*
    counters surface a stability source's report,
  * no-extra-sync / bit-identity (S4): telemetry on vs off is loss-bitwise
    identical with an identical device_get count; quant-health metrics on
    vs off is loss-bitwise identical (independent reductions),
  * end-to-end flight recordings: a supervised NaN-fault train run emits
    anomaly + rewind events/spans and validates; a preemption-churn
    chunked-prefill spec serve run emits full per-request lifecycles and
    converts to a loadable Chrome trace.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.configs.base import (ParallelConfig, ServeConfig,
                                SupervisorConfig, TrainConfig)
from repro.core.precision import QuantPolicy
from repro.data import BigramLM
from repro.launch.mesh import make_test_mesh
from repro.models import build
from repro.serve import make_serve_engine
from repro.telemetry import (SCHEMA_VERSION, Histogram, MetricsRegistry,
                             Telemetry, parse_profile_steps,
                             to_chrome_trace, validate_file)
from repro.telemetry import report as tele_report
from repro.train import (FaultPlan, FaultSpec, Trainer, TrainSupervisor,
                         init_train_state, make_train_setup,
                         make_train_step)

ARCH = "smollm-360m"

# --------------------------------------------------------------------------
# registry / histogram math
# --------------------------------------------------------------------------


def test_histogram_percentiles_match_numpy():
    for seed, scale in ((0, 1e-3), (1, 1.0), (2, 50.0)):
        xs = np.random.default_rng(seed).gamma(2.0, scale, size=500)
        h = Histogram("x")
        h.observe_many(xs)
        for p in (5, 25, 50, 75, 95):
            ref = float(np.percentile(xs, p))
            # one geometric bucket is x1.12 wide; the per-bucket (min,
            # max) tightening keeps observed error well under that
            assert h.percentile(p) == pytest.approx(ref, rel=0.12)
        assert h.percentile(0) == float(xs.min())     # exact extremes
        assert h.percentile(100) == float(xs.max())
        assert h.sum == pytest.approx(float(xs.sum()))
        assert h.mean == pytest.approx(float(xs.mean()))


def test_histogram_zero_bucket_and_empty():
    h = Histogram("x")
    assert h.percentile(50) == 0.0                    # empty -> 0
    h.observe_many([0.0, 0.0, 0.0, 5.0])
    assert h.percentile(0) == 0.0
    assert h.percentile(100) == 5.0
    assert h.n == 4


def test_histogram_single_value_exact():
    h = Histogram("x")
    h.observe_many([0.25] * 40)
    for p in (0, 50, 95, 100):
        assert h.percentile(p) == 0.25


def test_registry_snapshot_schema_stable_and_fill_counters():
    reg = MetricsRegistry()
    reg.counter("a")
    reg.gauge("b")
    reg.histogram("lat", percentiles=(50, 95), suffix="_s")
    empty = reg.snapshot()
    reg.counter("a").inc(3)
    reg.gauge("b").set(1.5)
    reg.histogram("lat").observe(0.1)
    full = reg.snapshot()
    assert set(empty) == set(full) == {"a", "b", "lat_p50_s", "lat_p95_s"}
    assert full["a"] == 3 and full["b"] == 1.5
    reg.fill_counters({"a": 7})
    assert reg.snapshot()["a"] == 7
    with pytest.raises(KeyError):
        reg.fill_counters({"nope": 1})


def test_parse_profile_steps():
    assert parse_profile_steps("3:7") == (3, 7)
    assert parse_profile_steps(None) is None
    assert parse_profile_steps("") is None
    with pytest.raises(ValueError):
        parse_profile_steps("7:3")
    with pytest.raises(ValueError):
        parse_profile_steps("abc")


# --------------------------------------------------------------------------
# sink, validators, report CLI, Chrome export
# --------------------------------------------------------------------------


def _records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _spans(d, prefix="train."):
    """(name, start, end, stats) of every host event named ``prefix...``
    in the profile that ``jax.profiler`` wrote under ``d``, by start."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
           for p in pd.planes if p.name.startswith("/host:")
           for ln in p.lines for e in ln.events
           if e.name.startswith(prefix)]
    return sorted(evs, key=lambda e: e[1])


def test_sink_meta_first_and_validates(tmp_path):
    p = str(tmp_path / "t.jsonl")
    with Telemetry(p, program="test", meta={"arch": "x"}) as t:
        assert t.enabled
        t.emit("train_step", step=0, loss=1.25)
        t.emit_span("flush", 100.0, 0.5, step=0, n_steps=1)
        with t.span("phase"):
            pass
    assert validate_file(p) == []
    recs = _records(p)
    assert recs[0]["kind"] == "meta"
    assert recs[0]["schema"] == SCHEMA_VERSION
    assert recs[0]["program"] == "test" and recs[0]["arch"] == "x"
    span = recs[2]
    assert span["kind"] == "span" and span["dur_s"] == 0.5
    assert span["ts"] == pytest.approx(100.5)         # ts = span end


def test_validate_flags_corruption_and_missing_fields(tmp_path):
    p = str(tmp_path / "t.jsonl")
    with Telemetry(p, program="test") as t:
        t.emit("train_step", step=0, loss=1.0)
    with open(p, "a") as f:
        f.write("{not json\n")
        f.write(json.dumps({"ts": 1.0, "kind": "rewind", "step": 3}) + "\n")
    errs = validate_file(p)
    assert any("invalid JSON" in e for e in errs)
    # one error per missing required field, named with its line
    assert sum("rewind" in e and "line 4" in e for e in errs) == 2
    # unknown kinds are forward-compatible, not errors
    with open(p, "a") as f:
        f.write(json.dumps({"ts": 1.0, "kind": "future_thing"}) + "\n")
    assert validate_file(p) == errs


def test_disabled_telemetry_is_inert(tmp_path):
    t = Telemetry()
    assert not t.enabled
    t.emit("train_step", step=0, loss=1.0)            # all no-ops
    t.emit_span("x", 0.0, 1.0)
    with t.span("y"):
        pass
    t.maybe_profile(0)
    t.close()


def test_span_annotates_the_profiler_trace_sink_or_not(tmp_path):
    """A span is a profiler annotation under its own name whether or not
    a sink exists; with a sink it is also a JSONL record under its record
    name. Fields added inside the span go into both; a span left by an
    exception writes no record."""
    path = str(tmp_path / "t.jsonl")
    sink = Telemetry(path, program="test")
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for t in (Telemetry(), sink):
            with t.span("unit.phase", record="phase", k=1) as fields:
                fields["m"] = 2
        with pytest.raises(RuntimeError):
            with sink.span("unit.failed"):
                raise RuntimeError("x")
    finally:
        jax.profiler.stop_trace()
        sink.close()
    evs = _spans(tmp_path / "trace", "unit.")
    assert [(e[0], e[3]) for e in evs] == [
        ("unit.phase", {"k": 1, "m": 2})] * 2 + [("unit.failed", {})]
    recs = [r for r in _records(path) if r["kind"] == "span"]
    assert [(r["name"], r["k"], r["m"]) for r in recs] == [("phase", 1, 2)]


def test_report_cli_validate_exit_codes(tmp_path, capsys):
    good = str(tmp_path / "good.jsonl")
    with Telemetry(good, program="t") as t:
        t.emit("train_step", step=0, loss=2.0)
    assert tele_report.main([good, "--validate"]) == 0
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write("garbage\n")
    assert tele_report.main([bad, "--validate"]) != 0
    capsys.readouterr()


def test_chrome_trace_export(tmp_path):
    p = str(tmp_path / "t.jsonl")
    with Telemetry(p, program="t") as t:
        t.emit("request", uid=4, event="submitted")
        t.emit_span("flush", 10.0, 0.25, step=3)
    trace = to_chrome_trace(_records(p))
    blob = json.loads(json.dumps(trace))              # round-trips
    evs = blob["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans and spans[0]["dur"] == pytest.approx(0.25e6)
    req = [e for e in evs if e.get("tid") == 5]       # uid+1 track
    assert req and req[0]["ph"] == "i"


# --------------------------------------------------------------------------
# serve stats schema (S1 / S2)
# --------------------------------------------------------------------------


def _engines(**kw):
    cfg = get_reduced_config(ARCH)
    mesh = make_test_mesh((1, 1))
    common = dict(max_batch=2, max_len=32, quant_mode="int8_switchback",
                  **kw)
    ring = make_serve_engine(build(cfg), ServeConfig(**common), mesh)
    paged = make_serve_engine(
        build(cfg), ServeConfig(cache_mode="paged", block_size=4, **common),
        mesh)
    return ring, paged, cfg


def test_stats_schema_identical_empty_vs_populated(reduced):
    """The registry-declared schema makes empty-return and measured stats
    rows the same key set by construction — ring and paged."""
    ring, paged, cfg = _engines()
    params = jax.device_get(ring.init_params(0))
    prompts = [[1, 2, 3], [4, 5]]
    for eng in (ring, paged):
        empty = eng.generate(eng.shard_params(params), prompts,
                             max_new_tokens=0)[1]
        full = eng.generate(eng.shard_params(params), prompts,
                            max_new_tokens=3)[1]
        assert set(empty) == set(full)
        assert full["new_tokens"] > 0 and empty["new_tokens"] == 0
        assert full["itl_p95_s"] <= full["itl_wall_p95_s"]
    # supervisor counters are part of the row even without a source
    assert paged._empty_stats()["supervisor_rewinds"] == 0


def test_stats_surface_supervisor_counters(reduced):
    ring, _, cfg = _engines()
    ring.stability_source = {"rewinds": 3, "incidents": 2,
                             "save_failures": 1}
    s = ring._empty_stats()
    assert s["supervisor_rewinds"] == 3
    assert s["supervisor_incidents"] == 2
    assert s["supervisor_save_failures"] == 1
    assert s["supervisor_escalations"] == 0

    class Src:                                         # report() duck type
        def report(self):
            return {"rewinds": 9, "escalations": 4, "other_junk": 1}

    ring.stability_source = Src()
    s = ring._empty_stats()
    assert s["supervisor_rewinds"] == 9 and s["supervisor_escalations"] == 4
    ring.stability_source = object()
    with pytest.raises(TypeError):
        ring._empty_stats()
    ring.stability_source = None


# --------------------------------------------------------------------------
# train-side: no extra syncs, bit identity
# --------------------------------------------------------------------------

BATCH, SEQ = 2, 16


@pytest.fixture(scope="module")
def qloop(reduced):
    """Jitted int8 train step (quant-health metrics ON) + a matching
    qh-OFF step, fresh-state factory, and a cached deterministic batch
    feed — shared across the train-side tests."""
    cfg, bundle, params = reduced(ARCH)

    def make(qh):
        tc = TrainConfig(learning_rate=1e-3, warmup_steps=2,
                         total_steps=100, beta2=0.95, loss_scaler="none",
                         quant_mode="int8_switchback",
                         quant_health_metrics=qh)
        opt, scaler = make_train_setup(tc)
        fn = jax.jit(make_train_step(bundle, QuantPolicy("int8_switchback"),
                                     ParallelConfig(remat="block"), tc,
                                     opt, scaler))
        return fn, lambda: init_train_state(params, opt, scaler)

    cache = {}

    def data_fn(j):
        if j not in cache:
            d = BigramLM(cfg.vocab_size, seed=1000 + j, temperature=0.3)
            cache[j] = jax.tree.map(jnp.asarray, d.batch(BATCH, SEQ))
        return cache[j]

    return make, data_fn


def test_telemetry_on_off_bit_identical_same_transfers(qloop, tmp_path,
                                                       monkeypatch):
    """The no-extra-sync contract: recording telemetry changes neither the
    loss trajectory (bitwise) nor the number of device->host transfers —
    events are built from values the flush already fetched."""
    make, data_fn = qloop
    fn, fresh = make(True)
    real_get = jax.device_get
    counts = {"n": 0}

    def counting_get(x):
        counts["n"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    runs = []
    for tele in (None, Telemetry(str(tmp_path / "t.jsonl"),
                                 program="train")):
        counts["n"] = 0
        tr = Trainer(fn, fresh(), log_every=2, telemetry=tele)
        tr.run(data_fn, 6)
        runs.append((counts["n"], [h["loss"] for h in tr.history]))
        if tele is not None:
            tele.close()
    (n_off, loss_off), (n_on, loss_on) = runs
    assert n_on == n_off                 # identical transfer count
    assert loss_on == loss_off           # bitwise-identical trajectory
    assert validate_file(str(tmp_path / "t.jsonl")) == []
    recs = _records(str(tmp_path / "t.jsonl"))
    steps = [r for r in recs if r["kind"] == "train_step"]
    assert len(steps) == 6
    # device quant-health scalars ride the flush fetch into the events
    assert any(k.startswith("qh/") for k in steps[0])
    assert {r["kind"] for r in recs} >= {"meta", "train_step", "flush",
                                         "span"}


def test_trainer_spans_in_profiler_trace(qloop, tmp_path, monkeypatch):
    """With telemetry disabled the Trainer's phases still land in the
    profiler trace, on its clock: each step is a ``train.step`` with its
    ``step_num`` holding ``train.batch`` (with the batch's bytes) and
    ``train.dispatch``; each flush is a ``train.wait`` carrying
    ``n_steps`` and a ``train.bookkeeping``. No JSONL is written and the
    device->host transfers are the flushes' alone."""
    make, data_fn = qloop
    fn, fresh = make(True)
    real_get = jax.device_get
    counts = {"n": 0}

    def counting_get(x):
        counts["n"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    tr = Trainer(fn, fresh(), log_every=2)
    assert not tr.telemetry.enabled
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        tr.run(data_fn, 3)
    finally:
        jax.profiler.stop_trace()
    assert counts["n"] == 2                  # flushes after steps 0 and 2
    assert not list(tmp_path.glob("**/*.jsonl"))
    evs = _spans(tmp_path / "trace")
    named = lambda n: sorted((e for e in evs if e[0] == n),
                             key=lambda e: e[1])
    steps = named("train.step")
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2]
    nbytes = [sum(x.nbytes for x in jax.tree.leaves(data_fn(i)))
              for i in range(3)]
    for name in ("train.batch", "train.dispatch"):
        inner = named(name)
        assert len(inner) == 3
        for (_, a, b, _), (_, s0, s1, _) in zip(inner, steps):
            assert s0 <= a <= b <= s1
    assert [e[3]["nbytes"] for e in named("train.batch")] == nbytes
    # the run's first wait reads the state's step; then one per flush
    waits = named("train.wait")
    assert [e[3].get("n_steps") for e in waits] == [None, 1, 2]
    assert len(named("train.bookkeeping")) == 2


def test_quant_health_metrics_off_bit_identical(qloop):
    """qh reductions are independent device work: disabling them must not
    perturb the update math (losses and params stay bitwise equal)."""
    make, data_fn = qloop
    fn_on, fresh_on = make(True)
    fn_off, fresh_off = make(False)
    s_on, s_off = fresh_on(), fresh_off()
    for i in range(4):
        b = data_fn(i)
        s_on, m_on = fn_on(s_on, b)
        s_off, m_off = fn_off(s_off, b)
        assert float(m_on["loss"]) == float(m_off["loss"])
    assert any(k.startswith("qh/") for k in m_on)
    assert not any(k.startswith("qh/") for k in m_off)
    l_on = jax.device_get(jax.tree.leaves(s_on.params)[0])
    l_off = jax.device_get(jax.tree.leaves(s_off.params)[0])
    np.testing.assert_array_equal(l_on, l_off)


def test_supervised_fault_run_flight_recording(qloop, tmp_path):
    """A NaN-grad fault under the supervisor leaves a complete, valid
    recording: the anomaly, the rewind (event + span), the checkpoint
    saves, and a Chrome-trace conversion that loads."""
    make, data_fn = qloop
    fn, fresh = make(True)
    path = str(tmp_path / "run.jsonl")
    tele = Telemetry(path, program="train", meta={"arch": ARCH})
    cfg = SupervisorConfig(checkpoint_every=5, keep_checkpoints=10,
                           log_every=0, detect_warmup=5,
                           grad_norm_ratio=12.0, loss_jump_ratio=2.0,
                           spike_min_history=100)
    plan = FaultPlan([FaultSpec(step=7, kind="nan_grad")])
    sup = TrainSupervisor(fn, fresh(), data_fn,
                          checkpoint_dir=str(tmp_path / "ck"), config=cfg,
                          fault_plan=plan, telemetry=tele)
    sup.run(12)
    tele.close()
    assert sup.counters["rewinds"] == 1
    assert validate_file(path) == []
    recs = _records(path)
    kinds = {r["kind"] for r in recs}
    assert {"meta", "train_step", "flush", "checkpoint", "anomaly",
            "rewind", "span"} <= kinds
    anomaly = next(r for r in recs if r["kind"] == "anomaly")
    assert anomaly["step"] == 7 and anomaly["anomaly"] == "nonfinite"
    rewind = next(r for r in recs if r["kind"] == "rewind")
    assert rewind["restored_step"] <= 7 <= rewind["step"]
    assert rewind["skipped"] >= 1
    assert any(r["kind"] == "span" and r["name"] == "rewind"
               for r in recs)
    trace = json.loads(json.dumps(to_chrome_trace(recs)))
    assert len(trace["traceEvents"]) == len(recs)


# --------------------------------------------------------------------------
# serve-side flight recording under churn
# --------------------------------------------------------------------------


def test_serve_churn_flight_recording(reduced, tmp_path):
    """Chunked prefill + pool-pressure preemption + ngram spec, recorded:
    every request's lifecycle is complete (submitted -> admitted ->
    chunked prefill -> first token -> finished), at least one preemption
    is recorded, waves and the stats row land, and the file both
    validates and converts to a Chrome trace."""
    cfg = get_reduced_config(ARCH)
    eng = make_serve_engine(
        build(cfg), ServeConfig(cache_mode="paged", block_size=4,
                                max_batch=2, max_len=32, num_blocks=8,
                                quant_mode="int8_switchback",
                                prefill_chunk_tokens=6,
                                preemption="recompute",
                                spec_mode="ngram"),
        make_test_mesh((1, 1)))
    params = eng.init_params(0)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, size=10).tolist()
               for _ in range(2)]
    path = str(tmp_path / "serve.jsonl")
    eng.telemetry = Telemetry(path, program="serve")
    gens, stats = eng.generate(params, prompts, max_new_tokens=20)
    eng.telemetry.close()
    assert stats["sched_preempted"] >= 1
    assert validate_file(path) == []
    recs = _records(path)
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["kind"], []).append(r)
    assert {"meta", "request", "wave", "span", "serve_stats"} <= set(by_kind)
    # full lifecycle per request
    for uid in (0, 1):
        evs = [r["event"] for r in by_kind["request"] if r["uid"] == uid]
        for needed in ("submitted", "admitted", "prefill_chunk",
                       "first_token", "finished"):
            assert needed in evs, f"uid {uid} missing {needed}: {evs}"
    assert any(r["event"] == "preempted" for r in by_kind["request"])
    fin = [r for r in by_kind["request"] if r["event"] == "finished"]
    assert all(r["reason"] == "evicted_budget" for r in fin)
    assert all(r["n_generated"] == 20 for r in fin)
    modes = {r["mode"] for r in by_kind["wave"]}
    assert {"prefill", "decode"} <= modes
    # the stats event mirrors the returned row
    assert by_kind["serve_stats"][0]["sched_preempted"] == \
        stats["sched_preempted"]
    trace = json.loads(json.dumps(to_chrome_trace(recs)))
    assert len(trace["traceEvents"]) == len(recs)
    # telemetry never perturbs generation: a silent rerun matches
    eng.telemetry = None
    gens2, _ = eng.generate(params, prompts, max_new_tokens=20)
    assert gens == gens2


def test_report_summarizes_serve_and_train(tmp_path, capsys):
    p = str(tmp_path / "mix.jsonl")
    with Telemetry(p, program="train") as t:
        for i in range(3):
            t.emit("train_step", step=i, loss=3.0 - i, dt=0.01,
                   **{"qh/mlp/w_absmax": 0.5 + i})
        t.emit("anomaly", step=2, anomaly="nan_loss")
        t.emit("rewind", step=2, restored_step=0, skipped=1)
    assert tele_report.main([p]) == 0
    out = capsys.readouterr().out
    assert "anomal" in out.lower()
    assert "qh/mlp/w_absmax" in out
    assert "3.0000 -> 1.0000" in out or "loss" in out
