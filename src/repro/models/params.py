"""ParamSpec machinery: one declarative tree drives real init, abstract
(ShapeDtypeStruct) init for the dry-run, and NamedSharding assignment.

Every model defines ``param_specs(cfg) -> nested dict of ParamSpec``; the
three consumers derive everything else:

    params    = init_params(specs, key)            # smoke tests / examples
    abstract  = abstract_params(specs)             # dry-run, no allocation
    shardings = specs_to_shardings(specs, mesh, rules)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

# model code reaches the sharding context through this module (PRM.*)
from repro.core.sharding import (  # noqa: F401
    ShardCtx, _divisible, constrain, logical_to_pspec)


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]   # logical axis name per dim
    init: str = "normal"                 # normal|zeros|ones|constant|embed
    scale: float = 0.02                  # stddev for normal / value for constant
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(specs, key: jax.Array):
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))

    def one(spec: ParamSpec, k):
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, spec.dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, spec.dtype)
        if spec.init == "constant":
            return jnp.full(spec.shape, spec.scale, spec.dtype)
        # fan-in-scaled normal: scale interpreted as a multiplier on 1/sqrt(fan_in)
        if spec.init == "fan_in":
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            std = spec.scale / math.sqrt(fan_in)
            return std * jax.random.normal(k, spec.shape, spec.dtype)
        return spec.scale * jax.random.normal(k, spec.shape, spec.dtype)

    return treedef.unflatten([one(s, k) for s, k in zip(leaves, keys)])


def abstract_params(specs):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs,
        is_leaf=is_spec)


# ---------------------------------------------------------------------------
# logical-axis -> mesh-axis rules (MaxText-style)
# ---------------------------------------------------------------------------

def default_rules(parallel) -> Dict[str, object]:
    """Map logical param/activation axes onto mesh axes.

    ``model`` carries TP (heads / ff / experts / vocab); ``data``(+``pod``)
    carries DP; with fsdp=True the embed axis of weights is sharded over
    data as well (ZeRO-3-style parameter sharding).

    ``pure_dp`` (§Perf iteration 2): models too small to need TP fold the
    model axis into data parallelism — batch shards over every mesh axis,
    no tensor dim maps to "model", so blocks have NO activation collectives
    at all (weight gathers + grad reduce-scatters only).
    """
    data = parallel.data_axes            # ("data",) or ("pod", "data")
    if parallel.pure_dp:
        all_axes = tuple(parallel.mesh_axes)
        rules = {
            "batch": all_axes, "embed": None, "seq": None, "heads": None,
            "kv_heads": None, "head_dim": None, "mlp": None, "experts": None,
            "expert_capacity": all_axes, "vocab": None, "layers": None,
            "conv": None, "state": None, "lora": None, "frames": None,
        }
        if parallel.fsdp:
            rules["embed"] = data        # ZeRO shards storage over data
        return rules
    rules = {
        "batch": data,
        "embed": None,
        "seq": None,
        "heads": "model",
        "kv_heads": ("model" if getattr(parallel, "shard_kv_heads", True)
                     else None),
        "head_dim": None,
        "mlp": "model",
        "experts": "model",
        "expert_capacity": data,
        "vocab": "model",
        "layers": None,
        "conv": None,
        "state": None,
        "lora": None,
        "frames": None,
    }
    if parallel.fsdp:
        rules["embed"] = data            # ZeRO-3: shard the big axis over data
    if parallel.sequence_parallel:
        # Korthikanti-style SP: the residual stream between blocks shards
        # the seq dim over `model`; matmul inputs all-gather it back and
        # block outputs reduce-scatter — replacing 2x-wire all-reduces
        # with RS+AG pairs (half the bytes) and sharding norms/residuals.
        rules["seq"] = "model"
    return rules


def specs_to_shardings(specs, mesh: Mesh, rules):
    def one(s: ParamSpec):
        ps = logical_to_pspec(s.logical, rules)
        ps = _divisible(s.shape, ps, mesh)
        return NamedSharding(mesh, ps)
    return jax.tree.map(one, specs, is_leaf=is_spec)


def specs_to_pspecs(specs, mesh: Mesh, rules):
    def one(s: ParamSpec):
        return _divisible(s.shape, logical_to_pspec(s.logical, rules), mesh)
    return jax.tree.map(one, specs, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# weights under the active ShardCtx
# ---------------------------------------------------------------------------

def use_weight(w: jax.Array, logical: Tuple[Optional[str], ...],
               dtype=None) -> jax.Array:
    """Prepare a weight for use in a matmul.

    With ``gather_fsdp`` on: cast to the compute dtype FIRST (halves the
    wire bytes) and pin the no-FSDP sharding — XLA emits one all-gather of
    the (small) weight instead of an all-reduce of the (large) activation
    partial-sums, and the backward pass symmetrically reduce-scatters the
    weight gradient (exactly ZeRO-3). No-op outside a ShardCtx.

    ``gather_wire == "int8"`` (§Perf iteration 2, ZeRO++-style): the weight
    crosses the wire tensor-wise int8-quantized (the paper's Eq. 2 — under
    the int8_switchback policy this is the SAME quantization the forward
    matmul applies, so the gather compression is algorithmically free) and
    is dequantized locally after the gather.
    """
    ctx = ShardCtx._current
    if dtype is not None:
        w = w.astype(dtype)
    if ctx is None or not ctx.gather_fsdp or ctx.mesh is None:
        return w
    ps = logical_to_pspec(logical, ctx.rules_nofsdp)
    ps = _divisible(w.shape, ps, ctx.mesh)
    sh = NamedSharding(ctx.mesh, ps)
    if ctx.gather_wire == "int8":
        import jax.numpy as jnp
        absmax = jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32))), 1e-12)
        q = jnp.round(w.astype(jnp.float32) * (127.0 / absmax)) \
            .astype(jnp.int8)
        q = jax.lax.with_sharding_constraint(q, sh)    # int8 on the wire
        return (q.astype(jnp.float32) * (absmax / 127.0)).astype(w.dtype)
    return jax.lax.with_sharding_constraint(w, sh)


def nofsdp_rules(rules: dict, data_axes) -> dict:
    """The same rule table with the FSDP (data-over-embed) mapping removed."""
    out = dict(rules)
    if out.get("embed") == data_axes or out.get("embed") in ("data",):
        out["embed"] = None
    return out
