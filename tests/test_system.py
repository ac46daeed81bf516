"""System-level integration tests: the full train→crash→resume cycle,
sharded multi-device execution (subprocess with fake devices), and the
gradient-compression collective.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.slow
def test_train_crash_resume_is_deterministic(tmp_path):
    """Train 6 steps with checkpoints every 2; 'crash'; resume from step 4
    and verify the resumed trajectory matches an uninterrupted one."""
    from repro.configs import get_reduced_config
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.core.precision import QuantPolicy
    from repro.data import BigramLM
    from repro.models import build
    from repro.models.params import init_params
    from repro.train import (Trainer, init_train_state, make_train_setup,
                             make_train_step)

    cfg = get_reduced_config("smollm-360m")
    bundle = build(cfg)

    def make(ckpt_dir):
        params = init_params(bundle.param_specs, jax.random.PRNGKey(0))
        tc = TrainConfig(learning_rate=1e-3, warmup_steps=2,
                         total_steps=100, beta2=0.95, loss_scaler="none")
        opt, scaler = make_train_setup(tc)
        fn = jax.jit(make_train_step(
            bundle, QuantPolicy("bf16"), ParallelConfig(remat="block"),
            tc, opt, scaler))
        state = init_train_state(params, opt, scaler)
        cache = {}

        def batch_at(i):       # deterministic per-step batches
            if i not in cache:
                d = BigramLM(cfg.vocab_size, seed=1000 + i, temperature=0.3)
                cache[i] = jax.tree.map(jnp.asarray, d.batch(2, 16))
            return cache[i]

        return Trainer(fn, state, checkpoint_dir=ckpt_dir,
                       checkpoint_every=2, log_every=0), batch_at

    # uninterrupted run
    t_full, batch_at = make(str(tmp_path / "a"))
    t_full.run(lambda i: batch_at(i), 6)
    losses_full = [h["loss"] for h in t_full.history]

    # interrupted run: 4 steps, crash, resume, 2 more
    t1, batch_at2 = make(str(tmp_path / "b"))
    t1.run(lambda i: batch_at2(i), 4)
    del t1                                    # "crash"
    t2, batch_at3 = make(str(tmp_path / "b"))
    start = t2.maybe_resume()
    assert start == 4
    t2.run(lambda i: batch_at3(i), 2)
    losses_resumed = [h["loss"] for h in t2.history]

    np.testing.assert_allclose(losses_full[4:], losses_resumed,
                               rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_sharded_dryrun_subprocess():
    """The dry-run machinery end-to-end on 8 fake devices in a subprocess
    (cannot run in-process: the test session owns a 1-device jax)."""
    env = dict(os.environ, REPRO_DRYRUN_DEVICES="8",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "smollm-360m",
         "--shape", "decode_32k", "--mesh", "single", "--no-probes",
         "--out", "/tmp/repro_test_dryrun"],
        env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "all requested cells compiled OK" in out.stdout
    with open("/tmp/repro_test_dryrun/smollm-360m_decode_32k_single.json") as f:
        row = json.load(f)
    assert row["bottleneck"] in ("compute", "memory", "collective")


@pytest.mark.slow
def test_compressed_gradient_allreduce_subprocess():
    """int8-compressed DP gradient sync (shard_map) on 8 fake devices:
    result ≈ exact mean within int8 quantization error."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, %r)
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.distributed.compression import compressed_allreduce_mean, wire_bytes_saved

mesh = jax.make_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32), jnp.float32)

f = shard_map(lambda x: compressed_allreduce_mean(x[0], "data"),
              mesh=mesh, in_specs=P("data"), out_specs=P(),
              check_vma=False)          # all_gather output is replicated
got = f(g)
want = jnp.mean(g, axis=0)
err = float(jnp.max(jnp.abs(got - want)))
scale = float(jnp.max(jnp.abs(g))) / 127.0
assert err <= scale + 1e-6, (err, scale)
stats = wire_bytes_saved(10_000_000, 8)
assert stats["reduction"] > 3.0
print("OK", err)
""" % SRC
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
