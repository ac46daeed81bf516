"""The jittable training step: loss -> (scaled) grads -> clip -> optimizer.

Features wired here:
  * microbatch gradient accumulation (lax.scan) — activation memory / n_micro
  * loss scaling (paper §3.6 tensor-level fixed scaler, or dynamic baseline)
  * global-norm clipping (paper's comparison intervention, Fig. 10)
  * StableAdamW / AdamW / AdaFactor via the Optimizer protocol
  * per-tensor RMS_t surfaced for the stability monitor (paper Fig. 9)
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ParallelConfig, TrainConfig
from repro.core.precision import QuantPolicy
from repro.optim import (clip_by_global_norm, global_norm, make_optimizer,
                         make_scaler, warmup_cosine)
from repro.telemetry import health


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    scaler_state: Any
    step: jax.Array
    rng: jax.Array


def make_train_setup(train_cfg: TrainConfig):
    sched = warmup_cosine(train_cfg.learning_rate, train_cfg.warmup_steps,
                          train_cfg.total_steps)
    opt = make_optimizer(
        train_cfg.optimizer, sched,
        beta1=train_cfg.beta1, beta2=train_cfg.beta2,
        weight_decay=train_cfg.weight_decay,
    ) if train_cfg.optimizer != "adafactor" else make_optimizer(
        "adafactor", sched, weight_decay=train_cfg.weight_decay)
    scaler = make_scaler(train_cfg.loss_scaler)
    return opt, scaler


def init_train_state(params, opt, scaler, seed: int = 0) -> TrainState:
    return TrainState(params, opt.init(params), scaler.init(),
                      jnp.zeros((), jnp.int32),
                      jax.random.PRNGKey(seed))


def _split_microbatches(batch: Dict, n: int) -> Dict:
    return jax.tree.map(lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]),
                        batch)


def make_train_step(bundle, policy: QuantPolicy, parallel: ParallelConfig,
                    train_cfg: TrainConfig, opt, scaler) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics). Donation-safe."""

    n_micro = max(1, train_cfg.microbatch_steps)

    def scaled_loss(params, mb, rng, scaler_state):
        loss, metrics = bundle.loss_fn(params, mb, policy, parallel,
                                       patch_drop_rng=rng)
        return scaler.scale(loss, scaler_state), (loss, metrics)

    def train_step(state: TrainState, batch: Dict):
        rng, sub = jax.random.split(state.rng)
        grad_fn = jax.grad(scaled_loss, has_aux=True)

        if n_micro == 1:
            grads, (loss, metrics) = grad_fn(state.params, batch, sub,
                                             state.scaler_state)
        else:
            mbs = _split_microbatches(batch, n_micro)

            def acc_body(carry, mb):
                g_acc, l_acc, rng = carry
                rng, sub = jax.random.split(rng)
                g, (l, m) = grad_fn(state.params, mb, sub, state.scaler_state)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l, rng), m

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss, _), mb_metrics = jax.lax.scan(
                acc_body, (g0, jnp.zeros((), jnp.float32), sub), mbs)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss / n_micro
            # same keys as n_micro=1: average float metrics over the
            # microbatches, take the last value for integral ones
            metrics = jax.tree.map(
                lambda m: (jnp.mean(m, axis=0)
                           if jnp.issubdtype(m.dtype, jnp.inexact)
                           else m[-1]), mb_metrics)

        with jax.named_scope("optimizer"):
            grads, skip_mask, scaler_state, sstats = scaler.unscale(
                grads, state.scaler_state)
            gnorm = global_norm(grads)
            if train_cfg.grad_clip_norm > 0:
                grads, _ = clip_by_global_norm(grads,
                                               train_cfg.grad_clip_norm)
            params, opt_state, aux = opt.update(
                state.params, state.opt_state, grads, skip_mask=skip_mask)
        out_metrics = {
            **metrics,
            "loss": loss, "grad_norm": gnorm,
            "lr": aux.get("lr", jnp.zeros(())),
            "n_skipped_tensors": sstats["n_skipped_tensors"],
            "loss_scale": sstats["loss_scale"],
        }
        # quant-health scalars (telemetry/health.py): independent device
        # reductions on (params, grads) at the top level — outside the
        # grad transform and the microbatch scan, so no tracer crosses a
        # custom_vjp/scan boundary, and removing them cannot change the
        # update. Fetched with the rest of the metrics at flush time.
        out_metrics.update(health.quant_health(state.params, grads,
                                               train_cfg))
        if "rms" in aux:                       # per-tensor RMS_t (Fig. 9)
            out_metrics["rms"] = aux["rms"]
        new_state = TrainState(params, opt_state, scaler_state,
                               state.step + 1, rng)
        return new_state, out_metrics

    return train_step


def make_eval_step(bundle, policy: QuantPolicy, parallel: ParallelConfig):
    def eval_step(params, batch):
        loss, metrics = bundle.loss_fn(params, batch, policy, parallel)
        return {"loss": loss, **{k: v for k, v in metrics.items()
                                 if jnp.ndim(v) == 0}}
    return eval_step
