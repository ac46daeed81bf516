"""CLI meshes for the train and serve launchers.

Defined as functions, not module constants, so importing never touches jax
device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-style sharding tests (8 fake devices)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_cli_mesh(kind: str):
    """Shared CLI mesh selection (train + serve launchers). ``auto``
    data-parallels over whatever devices exist (1 device => a degenerate
    (1,1) mesh — the sharded step is still the step); ``test`` is the
    CI-style (2, n/2) mesh."""
    n = jax.device_count()
    if kind == "auto":
        return make_test_mesh((n, 1))
    assert n >= 2, "--mesh test needs >=2 devices (REPRO_DRYRUN_DEVICES)"
    return make_test_mesh((2, n // 2))
