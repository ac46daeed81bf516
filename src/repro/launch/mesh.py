"""Production mesh definition (assignment-required API).

Defined as functions, not module constants, so importing never touches jax
device state. Single-pod: 16x16 = 256 chips ("data", "model"); multi-pod:
2x16x16 = 512 chips ("pod", "data", "model") — the pod axis folds into the
data-parallel dimension for batch sharding, so the only cross-pod (DCN)
traffic is the once-per-step gradient reduction.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    have = jax.device_count()
    if have < need:
        # test mode (REPRO_DRYRUN_DEVICES): shrink proportionally, keeping
        # the axis structure so sharding rules are exercised identically.
        shape = (2, 2, 2) if multi_pod else (2, have // 2)
        print(f"[mesh] only {have} devices — using reduced test mesh "
              f"{shape} {axes}")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-style sharding tests (8 fake devices)."""
    return _make_mesh(shape, axes)


def make_cli_mesh(kind: str):
    """Shared CLI mesh selection (train + serve launchers). ``auto``
    data-parallels over whatever devices exist (1 device => a degenerate
    (1,1) mesh — the sharded step is still the step); ``test`` is the
    CI-style (2, n/2) mesh; ``single``/``multi`` are the production
    runbook meshes."""
    n = jax.device_count()
    if kind == "auto":
        return make_test_mesh((n, 1))
    if kind == "test":
        assert n >= 2, "--mesh test needs >=2 devices (REPRO_DRYRUN_DEVICES)"
        return make_test_mesh((2, n // 2))
    # production meshes shrink to (2, n/2) / (2,2,2) when devices are few —
    # below that the fallback itself is degenerate
    need = 8 if kind == "multi" else 2
    assert n >= need, (f"--mesh {kind} needs >={need} devices "
                       "(use --devices N or REPRO_DRYRUN_DEVICES)")
    return make_production_mesh(multi_pod=(kind == "multi"))
