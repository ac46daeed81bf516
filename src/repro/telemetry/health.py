"""On-device quant/stability health scalars — zero extra syncs.

These functions run *inside* the jitted train step (traced jnp on params
and grads, at the top level of ``make_train_step`` — after the grad
transform, so no custom_vjp / scan boundary is crossed) and return a
flat dict of ``"qh/<group>/<metric>"`` device scalars that ride the
existing metrics dict. The host fetches them only at the Trainer's
``_flush`` boundaries, in the same single ``device_get`` the loss
already uses — telemetry adds **no** per-step host sync.

Monitored metrics per layer group (embed / attn / mlp / other):

  * ``w_absmax`` — max |w|: the tensor-quantize scale driver; a drifting
    absmax is the early warning for int8/fp8 range trouble.
  * ``int8_sat_frac`` (int8 modes) — fraction of weight elements that
    tensor-quantize to the clip value ±127.
  * ``fp8_fallback_frac`` (fp8_mixed) — fraction of gradient blocks the
    dynamic-fallback criterion (absmax > ratio × median, the *same*
    formula the mixed kernel applies to activation tiles at quantize
    time — ``kernels/fp8_matmul/ops.fallback_mask``) would route to
    bf16. The kernel's own activation mask lives inside a custom_vjp
    under the layer scan and cannot be tapped without leaking tracers;
    the gradient-block rate is the observable proxy with identical
    scale statistics (DESIGN.md §15).

The App.-D ratio ``E[g²]/v_t`` needs no new device work at all: the
StableAdamW aux already surfaces per-tensor ``RMS_t = sqrt(mean(g²/v))``
in ``metrics["rms"]`` — :func:`summarize_rms` reduces the fetched tree
to per-group host floats at flush time.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

#: ordered group patterns; first substring match of the leaf path wins
GROUPS = ("embed", "attn", "mlp")


def group_of(path: str) -> str:
    for g in GROUPS:
        if g in path:
            return g
    return "other"


def _grouped_leaves(tree, min_ndim: int = 2):
    """path-grouped leaves: {group: [leaf, ...]} for float leaves with
    ndim >= min_ndim (vectors — norms, biases — are not quantized)."""
    out: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if jnp.ndim(leaf) < min_ndim or not jnp.issubdtype(
                jnp.result_type(leaf), jnp.floating):
            continue
        out.setdefault(group_of(jax.tree_util.keystr(path)), []).append(leaf)
    return out


def _block_absmax(x: jax.Array, br: int, bc: int) -> jax.Array:
    """(R, C) -> (⌈R/br⌉, ⌈C/bc⌉) per-block absmax (plain jnp; zero pads
    cannot raise a block's absmax). Leading dims are folded into rows."""
    x2 = x.reshape(-1, x.shape[-1])
    R, C = x2.shape
    br, bc = min(br, R), min(bc, C)
    Rp, Cp = -(-R // br) * br, -(-C // bc) * bc
    xp = jnp.pad(jnp.abs(x2.astype(jnp.float32)),
                 ((0, Rp - R), (0, Cp - C)))
    return xp.reshape(Rp // br, br, Cp // bc, bc).max(axis=(1, 3))


def quant_health(params, grads, train_cfg) -> Dict[str, jax.Array]:
    """Device-side health scalars keyed ``qh/<group>/<metric>``.

    Empty dict when ``train_cfg.quant_health_metrics`` is off or the
    policy is plain bf16 (nothing is quantized — nothing to watch).
    Everything here is independent reductions: adding or removing these
    metrics cannot change the parameter update, which is what makes the
    on/off bit-identity test in tests/test_telemetry.py structural.
    """
    mode = train_cfg.quant_mode
    if not getattr(train_cfg, "quant_health_metrics", False) \
            or mode == "bf16":
        return {}
    out: Dict[str, jax.Array] = {}
    int8 = mode.startswith("int8")
    for group, leaves in sorted(_grouped_leaves(params).items()):
        absmaxes = [jnp.max(jnp.abs(w.astype(jnp.float32))) for w in leaves]
        out[f"qh/{group}/w_absmax"] = jnp.max(jnp.stack(absmaxes))
        if int8:
            # tensor-quantize clip fraction: elements whose |w| rounds to
            # the top int8 code under scale absmax/127
            fracs = [jnp.mean((jnp.abs(w.astype(jnp.float32))
                               > a * (126.5 / 127.0)).astype(jnp.float32))
                     for w, a in zip(leaves, absmaxes)]
            out[f"qh/{group}/int8_sat_frac"] = jnp.mean(jnp.stack(fracs))
    if mode == "fp8_mixed":
        from repro.kernels.fp8_matmul.ops import fallback_mask
        br, bc = train_cfg.fp8_block_rows, train_cfg.fp8_block_cols
        ratio = train_cfg.fp8_fallback_ratio
        for group, leaves in sorted(_grouped_leaves(grads).items()):
            fracs = [jnp.mean(fallback_mask(_block_absmax(g, br, bc), ratio))
                     for g in leaves]
            out[f"qh/{group}/fp8_fallback_frac"] = jnp.mean(jnp.stack(fracs))
    return out


# -- host-side helpers (operate on fetched metrics) --------------------------

def qh_items(metrics: Dict) -> Dict[str, float]:
    """The qh/ scalars of one fetched metrics dict, as floats."""
    return {k: float(v) for k, v in metrics.items() if k.startswith("qh/")}


def moe_items(metrics: Dict) -> Dict[str, float]:
    """The MoE routing counters of one fetched metrics dict (``moe/``:
    slots routed to the held experts, max/mean load over the held experts
    and over all of them, the balance loss), as floats."""
    return {k: float(v) for k, v in metrics.items() if k.startswith("moe/")}


def summarize_rms(rms_tree) -> Dict[str, float]:
    """Per-group mean of the fetched StableAdamW RMS_t tree — the paper's
    App.-D ``sqrt(E[g²]/v_t)`` spike-precursor signal, grouped like the
    device-side health metrics."""
    groups: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(rms_tree)[0]:
        groups.setdefault(group_of(jax.tree_util.keystr(path)),
                          []).append(float(leaf))
    return {f"qh/{g}/adamw_rms": sum(v) / len(v)
            for g, v in sorted(groups.items())}
