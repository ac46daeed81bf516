"""The comparison that decides ``correct``, driven through a whole run of
each cell's driver at a size the CPU holds (the look for a chip is
skipped): sound runs pass, and each fault the cell can have, planted in
the timed path, and each cell's control fail under the cell's own
limits."""
import json
import time

import jax
import pytest

from bench import common

TRAIN = "smollm-360m.train-int8"
SERVE = "smollm-360m.serve-offline"
# Between what the 64-slot deployment read on a v5e: sound 0.031-0.051,
# the program's own int8 path 0.177-0.231 (PERF.md).
SERVE_LIMITS = {"logit_gap": 0.1}


def limits(name):
    if name == SERVE:
        return SERVE_LIMITS
    return json.loads((common.BENCH / "workloads" / f"{name}.json")
                      .read_text())["limits"]


def run(cell, name, seed=2 ** 33 + 5):
    line = cell.driver.run(cell, seed, 0.5, False, jax.devices()[:1],
                           time.perf_counter(), limits=limits(name))
    return json.loads(line)


def plant_step(monkeypatch, fault):
    """Wrap the program's train step: ``fault(step, state, batch)``."""
    import repro.train.engine as eng
    real = eng.make_train_step

    def make(*a, **k):
        step = real(*a, **k)
        return lambda state, batch: fault(step, state, batch)

    monkeypatch.setattr(eng, "make_train_step", make)


def test_sound_training_run_is_correct(tiny_cell):
    out = run(tiny_cell(TRAIN), TRAIN)
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_step_ms"]["value"] > 0


def test_clip_training_run_follows_the_reference(tiny_cell):
    """CLIP's limits hold at its own size; at this size a contrastive
    batch of 8 pairs moves its loss by a few 1e-3 under bf16 rounding
    alone, so this run is held to a tenth of what a lost update reads."""
    name = "clip-vit-huge-16v12t.train-int8"
    out = run(tiny_cell(name), name)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["value"] < 0.1 for c in out["checks"].values()), \
        out["checks"]
    assert out["metrics"]["train_step_ms"]["value"] > 0


def test_step_that_returns_its_state_unchanged_fails(tiny_cell,
                                                     monkeypatch):
    plant_step(monkeypatch, lambda step, s, b: (s, step(s, b)[1]))
    out = run(tiny_cell(TRAIN), TRAIN)
    assert not out["correct"]
    assert out["checks"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_fails(tiny_cell, monkeypatch):
    half = lambda b: jax.tree.map(lambda x: x[:x.shape[0] // 2], b)
    plant_step(monkeypatch, lambda step, s, b: step(s, half(b)))
    out = run(tiny_cell(TRAIN), TRAIN)
    assert not out["correct"], out["checks"]


def test_training_control_fails(tiny_cell):
    """The control: the reference put in the program's place, at int4 (the
    precision below the configuration's int8), against the reference."""
    from bench import weights
    from bench.drivers import train as T
    from bench.reference import common as refc
    cell = tiny_cell(TRAIN)
    abstract = cell.reference.abstract(cell.config)
    make_w = jax.jit(lambda k: weights.make(abstract, k))
    ring = cell.kind.make(cell.traffic, cell.config, 9)
    wkey, dkey = jax.random.split(common.raw_key(9))
    ref = T.reference_readings(cell, make_w, ring, wkey, dkey)
    ctl = T.reference_readings(cell, make_w, ring, wkey, dkey, bits=4)
    gaps = refc.compare_train(ctl, ref)
    lim = limits(TRAIN)
    assert any(gaps[k] > lim[k] for k in lim), gaps


def test_sound_serving_run_is_correct(tiny_cell):
    out = run(tiny_cell(SERVE), SERVE)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["serve_itl_p95_ms"]["value"] > 0


def test_served_token_altered_where_sampled_fails(tiny_cell, monkeypatch):
    from repro.serve.engine import ServeEngine
    real = ServeEngine.sample

    def sample(self, logits, key, uids, steps):
        """Every request's fourth token comes out one id off."""
        tok = real(self, logits, key, uids, steps)
        return jax.numpy.where(jax.numpy.asarray(steps) == 3,
                               (tok + 1) % logits.shape[-1], tok)

    monkeypatch.setattr(ServeEngine, "sample", sample)
    out = run(tiny_cell(SERVE), SERVE)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_traced_run_reports_per_layer_metrics(tiny_cell, monkeypatch, name):
    """A ``--trace 1`` run, with the chip's trace stood in for by the
    recorded one in ``data/``: the line carries the cell's per-layer
    metrics that find something to read, busy and window seconds, and the
    breakdown."""
    from bench import trace
    from test_bench_trace import profile
    red = trace.reduce_profile(profile()[0])
    monkeypatch.setattr(trace, "start", lambda: "unused")
    monkeypatch.setattr(trace, "stop", lambda: None)
    monkeypatch.setattr(trace, "reduce_dir", lambda d: red)
    monkeypatch.setattr(trace, "cleanup", lambda d: None)
    info = common.device_info
    monkeypatch.setattr(common, "device_info", lambda devs: dict(
        info(devs), kind="TPU v5 lite"))
    cell = tiny_cell(name)
    out = json.loads(cell.driver.run(cell, 7, 0.5, True, jax.devices()[:1],
                                     time.perf_counter(),
                                     limits=limits(name)))
    names = {m["name"] for m in cell.per_layer}
    assert set(out["metrics"]) <= names
    want = {"mfu.train", "flash_attn_roofline", "switchback_roofline",
            "device_idle.train"} if name == TRAIN else {"mfu.serve",
                                                        "device_idle.serve"}
    assert want <= set(out["metrics"])
    assert out["device"]["busy_s"] == red.busy_s
    assert out["device"]["window_s"] == red.window_s
    assert out["breakdown"] == json.loads(json.dumps(red.breakdown()))
