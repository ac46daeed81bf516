"""Forced-host-device CPU meshes: one shared pre-jax-import knob.

jax locks the device count at first backend init, so the XLA flag must be
appended to the environment before anything queries a backend. Call this
at the very top of an entry point, before importing jax (this module
deliberately imports nothing that does).

Sources, in precedence order: explicit ``n``, ``--devices N`` /
``--devices=N`` in ``argv`` (an explicit flag beats the ambient env), the
REPRO_DRYRUN_DEVICES env var (the CI and test convention), then
``default``.
"""
from __future__ import annotations

import os
import sys
from typing import Optional


def force_host_device_count(n: Optional[int] = None, *, argv=None,
                            default: Optional[int] = None) -> Optional[str]:
    val = str(n) if n else None
    if not val:
        args = list(sys.argv if argv is None else argv)
        for i, a in enumerate(args):
            if a == "--devices":
                val = args[i + 1] if i + 1 < len(args) else None
            elif a.startswith("--devices="):
                val = a.split("=", 1)[1]
    val = val or os.environ.get("REPRO_DRYRUN_DEVICES")
    if not val and default:
        val = str(default)
    if val:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=" + val)
    return val
