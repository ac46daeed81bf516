"""Logical-axis sharding under the active mesh, shared by core and models.

``ShardCtx`` carries (mesh, rules) while a step is traced; ``constrain``
pins an activation's sharding from its logical axis names, and
``kernel_call`` runs a Pallas TPU kernel under the same context.
Parameter specs and the rule tables live in ``repro.models.params``.
"""
from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def logical_to_pspec(logical: Tuple[Optional[str], ...], rules) -> P:
    axes = []
    used = set()
    for name in logical:
        if name is None:
            axes.append(None)
            continue
        mesh_axis = rules.get(name)
        # a mesh axis may appear once per pspec; later duplicates unshard
        parts = (mesh_axis if isinstance(mesh_axis, tuple)
                 else (mesh_axis,)) if mesh_axis is not None else ()
        if mesh_axis is None or any(p in used for p in parts):
            axes.append(None)
        else:
            axes.append(mesh_axis)
            used.update(parts)
    return P(*axes)


def _divisible(shape, pspec: P, mesh: Mesh) -> P:
    """Drop shardings that don't divide the dim (e.g. kv_heads=1 over 16)."""
    out = []
    for dim, ax in zip(shape, tuple(pspec) + (None,) * (len(shape) - len(pspec))):
        if ax is None:
            out.append(None)
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= mesh.shape[a]
        out.append(ax if dim % size == 0 else None)
    return P(*out)


class ShardCtx:
    """Carries (mesh, rules) so model code can pin activation shardings:
        x = ctx.constrain(x, ("batch", "seq", "embed"))
    Outside jit/mesh (smoke tests on 1 device) it is a no-op.

    Enter it around TRACING (e.g. ``with ShardCtx(...): f.lower(...)``) —
    the constraints are staged into the jaxpr at trace time.
    """
    _current: Optional["ShardCtx"] = None

    def __init__(self, mesh: Optional[Mesh], rules: Optional[dict]):
        self.mesh = mesh
        self.rules = rules

    def constrain(self, x: jax.Array, logical: Tuple[Optional[str], ...]):
        if self.mesh is None or self.rules is None:
            return x
        ps = logical_to_pspec(logical, self.rules)
        ps = _divisible(x.shape, ps, self.mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, ps))

    def __enter__(self):
        self._prev = ShardCtx._current
        ShardCtx._current = self
        return self

    def __exit__(self, *a):
        ShardCtx._current = self._prev


def constrain(x: jax.Array, logical: Tuple[Optional[str], ...]) -> jax.Array:
    ctx = ShardCtx._current
    if ctx is None:
        return x
    return ctx.constrain(x, logical)


def kernel_call(fn: Callable, *args, batch_dims: Tuple[Optional[int], ...],
                backend: str):
    """Call ``fn(*args)`` under the active ShardCtx. ``backend`` is the
    kernel backend ``fn`` runs on.

    On ``xla``, outside a ShardCtx, or on one device this is a plain call.
    Otherwise ``fn`` runs Pallas kernels, which XLA cannot partition, so
    the call goes through ``shard_map``: argument i is split over the batch
    mesh axes along dim ``batch_dims[i]`` (None = replicated, so a sharded
    operand is gathered onto every device) and every output along its
    dim 0. A batch dim that does not divide over those axes raises."""
    ctx = ShardCtx._current
    if (backend == "xla" or ctx is None or ctx.mesh is None
            or ctx.mesh.size == 1):
        return fn(*args)
    axes = ctx.rules.get("batch")
    axes = tuple(a for a in (axes if isinstance(axes, tuple) else (axes,))
                 if a)
    n = math.prod(ctx.mesh.shape[a] for a in axes)
    bad = [(i, a.shape[d]) for i, (a, d) in enumerate(zip(args, batch_dims))
           if d is not None and a.shape[d] % n]
    if bad:
        raise ValueError(
            f"kernel_call: batch dims {bad} (argument, size) do not divide "
            f"over the {n} devices of mesh axes {axes}")

    def spec(ndim, d):
        return P(*[axes if (i == d and axes) else None for i in range(ndim)])

    # check_vma=False: pallas_call declares no varying-axes types for its
    # outputs; shard_map then psums the cotangents of replicated inputs
    return jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=tuple(spec(a.ndim, d) for a, d in zip(args, batch_dims)),
        out_specs=P(axes or None), check_vma=False)(*args)
