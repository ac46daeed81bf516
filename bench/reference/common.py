"""Pieces every plain reference shares: float32 matmuls at full precision,
the SwitchBack-shaped low-precision linear for the control, StableAdamW
(paper Algorithm 2) with its warmup-cosine schedule, and the readings of a
three-step training run.

Nothing here imports the program under test.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dot(x, w):
    """(..., n) @ (n, m) in float32 at full precision."""
    return jnp.einsum("...n,nm->...m", x, w, precision=HIGHEST)


def _q_rows(x, qmax):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12)
    return jnp.round(x * (qmax / s)) * (s / qmax)


def _q_tensor(w, qmax):
    s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-12)
    return jnp.round(w * (qmax / s)) * (s / qmax)


@functools.lru_cache(maxsize=None)
def make_linear(bits: Optional[int] = None) -> Callable:
    """``linear(x, w)``. ``bits=None``: exact float32. Otherwise the
    SwitchBack shape at ``bits``-bit integers: row-wise inputs and
    output-gradients, tensor-wise weights in the forward and input-gradient
    matmuls, the weight gradient exact."""
    if bits is None:
        return dot
    qmax = 2.0 ** (bits - 1) - 1

    @jax.custom_vjp
    def linear(x, w):
        return dot(_q_rows(x, qmax), _q_tensor(w, qmax))

    def fwd(x, w):
        return linear(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        dx = jnp.einsum("...m,nm->...n", _q_rows(g, qmax),
                        _q_tensor(w, qmax), precision=HIGHEST)
        dw = jnp.einsum("...n,...m->nm", x, g, precision=HIGHEST)
        return dx, dw

    linear.defvjp(fwd, bwd)
    return linear


def warmup_cosine(step, peak, warmup, total):
    step = jnp.asarray(step, jnp.float32)
    warm = peak * step / max(1.0, warmup)
    prog = jnp.clip((step - warmup) / max(1.0, total - warmup), 0.0, 1.0)
    cos = 0.5 * peak * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < warmup, warm, cos)


def stable_adamw(p, m, v, g, t: int, hp: Dict):
    """One StableAdamW update (paper Algorithm 2) of one tensor; ``t`` is
    the 1-based step. Decay applies to matrices only."""
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["eps"]
    b1t = b1 * (1 - b1 ** (t - 1)) / (1 - b1 ** t)
    b2t = b2 * (1 - b2 ** (t - 1)) / (1 - b2 ** t)
    m = b1t * m + (1 - b1t) * g
    v = b2t * v + (1 - b2t) * g * g
    rms = jnp.sqrt(jnp.mean(g * g / jnp.maximum(v, eps * eps)))
    lr = warmup_cosine(t - 1, hp["learning_rate"], hp["warmup_steps"],
                       hp["total_steps"])
    eta = lr / jnp.maximum(1.0, rms)
    wd = hp["weight_decay"] if p.ndim >= 2 else 0.0
    p = p - eta * wd * p - eta * m / (jnp.sqrt(v) + eps)
    return p, m, v


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


def flat_norms(tree) -> Dict[str, float]:
    """{leaf path: norm} on the host."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def train_readings(loss_fn: Callable, make_params: Callable, batches,
                   keys, hp: Dict, steps: int) -> Dict:
    """Train ``steps`` steps from ``make_params()`` on ``batches`` (one per
    step, each with its dropout key from ``keys``) and read: each step's
    loss, each leaf's norm of the first gradient, and each leaf's norm of
    the change of the parameters after all steps. One jitted call per
    step, state donated, so that the reference holds one copy of the
    parameters, both moments and the gradient."""

    def step(p, m, v, batch, key, t):
        loss, g = jax.value_and_grad(loss_fn)(p, batch, key)
        new = jax.tree.map(lambda pp, mm, vv, gg: stable_adamw(
            pp, mm, vv, gg, t, hp), p, m, v, g)
        pick = lambda i: jax.tree.map(lambda tup: tup[i], new,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2), loss, leaf_norms(g)

    p = make_params()
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    jstep = jax.jit(step, donate_argnums=(0, 1, 2))
    losses, g1 = [], None
    for t in range(1, steps + 1):
        p, m, v, loss, gn = jstep(p, m, v, batches[t - 1], keys[t - 1],
                                  jnp.float32(t))
        losses.append(float(loss))
        if t == 1:
            g1 = flat_norms(gn)
    del m, v
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(p, make_params())
    return {"losses": losses, "grad1": g1, "delta": flat_norms(delta)}


def compare_train(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers that decide a training cell's ``correct``.

    ``loss_gap``: the largest relative gap of a step's loss. ``grad1_gap``
    and ``delta_gap``: by the worst leaf, the gap between the program's
    norm and the reference's, over the larger of the reference's norm of
    that leaf and of the median leaf. Leaves whose first reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone under Adam and are left out of ``delta_gap``."""
    n = len(ref["losses"])
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(prog["losses"][:n], ref["losses"]))
    return {"loss_gap": loss_gap,
            **{k: max(v.values()) for k, v in leaf_gaps(prog, ref).items()}}


def leaf_gaps(prog: Dict, ref: Dict) -> Dict[str, Dict[str, float]]:
    """Each leaf's gap, for ``grad1_gap`` and ``delta_gap``."""
    def gap(key, leaves):
        r = ref[key]
        med = float(np.median([r[k] for k in leaves]))
        return {k: abs(prog[key][k] - r[k]) / max(r[k], med) for k in leaves}

    g_med = float(np.median(list(ref["grad1"].values())))
    moved = [k for k, v in ref["grad1"].items() if v >= 1e-3 * g_med]
    return {"grad1_gap": gap("grad1", ref["grad1"]),
            "delta_gap": gap("delta", moved)}


def log_softmax_ce(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked


def gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))
