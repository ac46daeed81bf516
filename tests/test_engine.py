"""TrainEngine suite: sharded-vs-single-device parity, spec-driven
optimizer-state sharding, donation, and sharded save→restore→resume.

The multi-device tests need fake host devices:

    REPRO_DRYRUN_DEVICES=8 PYTHONPATH=src python -m pytest tests/test_engine.py

(the sharded CI lane); on the default 1-device fast lane they skip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.configs.base import ParallelConfig, TrainConfig
from repro.core.precision import QuantPolicy
from repro.data import BigramLM
from repro.launch.mesh import make_test_mesh
from repro.models import build
from repro.train import Trainer, make_engine

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="sharded lane only (REPRO_DRYRUN_DEVICES=8)")

BATCH, SEQ, STEPS = 8, 16, 5


def _batch(i, vocab=512):
    d = BigramLM(vocab, seed=1000 + i, temperature=0.3)
    return jax.tree.map(jnp.asarray, d.batch(BATCH, SEQ))


def _engine(mesh, *, optimizer="stable_adamw", n_micro=1, **par_kw):
    cfg = get_reduced_config("smollm-360m")
    tc = TrainConfig(optimizer=optimizer, learning_rate=1e-3,
                     warmup_steps=2, total_steps=100, loss_scaler="none",
                     microbatch_steps=n_micro)
    par = ParallelConfig(mesh_shape=tuple(mesh.devices.shape),
                         mesh_axes=tuple(mesh.axis_names),
                         remat="block", **par_kw)
    # f32 compute: parity differences then come only from reduction order,
    # not bf16 rounding — tight tolerances stay meaningful
    pol = QuantPolicy("bf16", compute_dtype=jnp.float32)
    return make_engine(build(cfg), tc, par, mesh, _batch(0), policy=pol)


def _trajectory(engine, n=STEPS, seed=0):
    state = engine.init_state(seed)
    out = []
    for i in range(n):
        state, m = engine.step(state, engine.shard_batch(_batch(i)))
        out.append((float(m["loss"]), float(m["grad_norm"])))
    return out, state


@pytest.fixture(scope="module")
def single_device_trajectory():
    eng = _engine(make_test_mesh((1, 1)))
    traj, _ = _trajectory(eng)
    return traj


def _assert_partitioned(tree):
    leaves = jax.tree.leaves(tree)
    assert any(not l.sharding.is_fully_replicated for l in leaves), \
        "expected at least one actually-partitioned leaf"


@needs8
@pytest.mark.parametrize("par_kw", [dict(fsdp=True), dict(pure_dp=True)],
                         ids=["fsdp", "pure_dp"])
def test_sharded_matches_single_device_trajectory(
        par_kw, single_device_trajectory):
    eng = _engine(make_test_mesh((2, 4)), **par_kw)
    traj, state = _trajectory(eng)
    if not par_kw.get("pure_dp"):      # pure_dp shards only the batch
        _assert_partitioned(state.params)
        _assert_partitioned(state.opt_state.exp_avg)
    np.testing.assert_allclose(np.asarray(traj),
                               np.asarray(single_device_trajectory),
                               rtol=5e-3, atol=5e-3)


@needs8
def test_fsdp_shards_embed_over_data(single_device_trajectory):
    """fsdp=True must land ZeRO-3-style data-axis shardings on params AND
    their AdamW moments (spec-driven, not the old _replace hack)."""
    eng = _engine(make_test_mesh((2, 4)), fsdp=True)
    state = eng.init_state()
    p_sh = {str(k): v.sharding
            for k, v in zip(jax.tree_util.tree_leaves_with_path(state.params),
                            jax.tree.leaves(state.params))}
    data_sharded = [s for s in jax.tree.leaves(
        jax.tree.map(lambda l: "data" in str(l.sharding.spec), state.params))]
    assert any(data_sharded), p_sh
    # moments shard exactly like their params
    for p, m in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(state.opt_state.exp_avg)):
        assert p.sharding == m.sharding


@needs8
def test_adafactor_factored_state_gets_1d_pspecs():
    """Adafactor's vr/vc are means over one param axis — their shardings
    must keep the surviving axis's mesh mapping, not replicate."""
    eng = _engine(make_test_mesh((2, 4)), optimizer="adafactor", fsdp=True)
    state = eng.init_state()
    specs = jax.tree.leaves(eng.specs, is_leaf=lambda x: hasattr(x, "logical"))
    factored = [m for m in jax.tree.leaves(
        state.opt_state.moments,
        is_leaf=lambda x: isinstance(x, dict) and "vr" in x)
        if isinstance(m, dict) and "vr" in m]
    assert factored, "no factored moments found"
    assert any(not m["vr"].sharding.is_fully_replicated or
               not m["vc"].sharding.is_fully_replicated for m in factored)
    for m in factored:                 # 1-D leaves carry 1-D pspecs
        assert m["vr"].ndim == m["vc"].ndim
        assert len(m["vr"].sharding.spec) <= m["vr"].ndim


def test_step_donates_input_state():
    """donate_argnums=(0,): the input state's buffers must be deleted after
    the step — the engine reuses them for the output state."""
    n = jax.device_count()
    mesh = make_test_mesh((2, n // 2) if n >= 2 else (1, 1))
    eng = _engine(mesh)
    state = eng.init_state()
    new_state, _ = eng.step(state, eng.shard_batch(_batch(0)))
    assert all(l.is_deleted() for l in jax.tree.leaves(state.params))
    assert all(l.is_deleted() for l in jax.tree.leaves(state.opt_state))
    assert not any(l.is_deleted() for l in jax.tree.leaves(new_state.params))


def test_microbatch_metrics_match_single_batch_keys():
    """n_micro>1 must report the same metric keys as n_micro=1 (model
    metrics used to be dropped as `metrics = {}` in the scan path)."""
    mesh = make_test_mesh((1, 1))
    e1 = _engine(mesh)
    e2 = _engine(mesh, n_micro=2)
    s1 = e1.init_state()
    s2 = e2.init_state()
    _, m1 = e1.step(s1, e1.shard_batch(_batch(0)))
    _, m2 = e2.step(s2, e2.shard_batch(_batch(0)))
    assert set(m1) == set(m2)
    assert "ce" in m2                  # the model metric that was dropped
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=5e-3, atol=5e-3)


@needs8
def test_sharded_save_restore_resume_equivalence(tmp_path):
    """Checkpoint under the sharded engine, crash, resume through
    restore(shardings=...): trajectory matches an uninterrupted run and
    the resumed state lands on the engine's shardings."""
    def trainer(ckpt_dir):
        eng = _engine(make_test_mesh((2, 4)), fsdp=True)
        state = eng.init_state()
        tr = Trainer(eng.step, state, checkpoint_dir=ckpt_dir,
                     checkpoint_every=2, log_every=0,
                     state_shardings=eng.state_shardings)
        return tr, eng

    t_full, eng = trainer(str(tmp_path / "a"))
    t_full.run(lambda i: eng.shard_batch(_batch(i)), 6)
    losses_full = [h["loss"] for h in t_full.history]

    t1, eng1 = trainer(str(tmp_path / "b"))
    t1.run(lambda i: eng1.shard_batch(_batch(i)), 4)
    del t1                                     # "crash"
    t2, eng2 = trainer(str(tmp_path / "b"))
    start = t2.maybe_resume()
    assert start == 4
    for leaf, want in zip(jax.tree.leaves(t2.state.params),
                          jax.tree.leaves(eng2.state_shardings.params)):
        assert leaf.sharding == want
    _assert_partitioned(t2.state.params)
    t2.run(lambda i: eng2.shard_batch(_batch(i)), 2)
    losses_resumed = [h["loss"] for h in t2.history]
    np.testing.assert_allclose(losses_full[4:], losses_resumed,
                               rtol=2e-2, atol=2e-2)


@needs8
def test_supervised_nan_recovery_on_sharded_engine(tmp_path):
    """The self-healing supervisor over the 8-device sharded engine: a NaN
    injection mid-run is detected, recovery restores the verified
    checkpoint onto the engine's shardings (params stay partitioned), and
    the run finishes every step finite."""
    from repro.configs.base import SupervisorConfig
    from repro.train import FaultPlan, FaultSpec

    eng = _engine(make_test_mesh((2, 4)), fsdp=True)
    sup = eng.make_supervisor(
        eng.init_state(), _batch, checkpoint_dir=str(tmp_path),
        config=SupervisorConfig(checkpoint_every=4, log_every=0,
                                detect_warmup=4, spike_min_history=100),
        fault_plan=FaultPlan([FaultSpec(step=9, kind="nan_grad")]))
    hist = sup.run(16)
    rep = sup.report()
    assert rep["rewinds"] >= 1
    assert rep["incident_kinds"].get("nonfinite") == 1
    assert rep["post_recovery_spikes"] == []
    assert len(hist) == 16
    assert all(np.isfinite(h["loss"]) for h in hist)
    _assert_partitioned(sup.trainer.state.params)
    for leaf, want in zip(jax.tree.leaves(sup.trainer.state.params),
                          jax.tree.leaves(eng.state_shardings.params)):
        assert leaf.sharding == want
