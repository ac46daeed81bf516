"""System-level integration test: the full train→crash→resume cycle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


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

