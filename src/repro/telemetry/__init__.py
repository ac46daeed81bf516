"""Flight recorder: unified telemetry for train + serve (DESIGN.md §15).

One ``Telemetry`` object bundles the three observability primitives:

  * a :class:`~repro.telemetry.sink.JsonlSink` writing schema-versioned
    event records (``--telemetry PATH``),
  * host-side :meth:`Telemetry.span` phases: annotations in the
    ``jax.profiler`` trace, always, and with a sink records with
    ``kind="span"`` (exported to Chrome traces by ``telemetry/report``),
  * a ``jax.profiler`` window (``--profile-steps A:B``) started/stopped
    by :meth:`Telemetry.maybe_profile` at step granularity.

Built without a path, everything but the spans' profiler annotations is
a no-op: the disabled object is safe to thread through
Trainer/ServeEngine unconditionally,
and the hot loops never branch on more than one attribute check — the
no-extra-sync contract (telemetry never calls ``device_get`` or
``block_until_ready``; it only records what the host already knows) is
pinned by ``tests/test_telemetry.py``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Optional, Tuple

from repro.telemetry.registry import (Counter, Gauge, Histogram,  # noqa: F401
                                      MetricsRegistry)
from repro.telemetry.sink import (SCHEMA_VERSION, JsonlSink,  # noqa: F401
                                  to_chrome_trace, validate_file,
                                  validate_record)


def parse_profile_steps(spec: Optional[str]) -> Optional[Tuple[int, int]]:
    """Parse an ``A:B`` CLI window into an inclusive (start, stop) pair.

    >>> parse_profile_steps("3:7")
    (3, 7)
    >>> parse_profile_steps(None) is None
    True
    """
    if not spec:
        return None
    try:
        a, b = spec.split(":")
        a, b = int(a), int(b)
    except ValueError:
        raise ValueError(f"--profile-steps wants 'A:B', got {spec!r}")
    if a > b or a < 0:
        raise ValueError(f"--profile-steps window {a}:{b} is empty")
    return a, b


class Telemetry:
    """Sink + spans + profiler window behind one object.

    ``Telemetry()`` (no path, no profile window) is disabled: ``emit``
    is a no-op, ``span`` only annotates the profiler trace, and
    ``enabled`` is False, so callers thread it unconditionally and skip
    building event payloads with one ``if tele.enabled`` check.
    """

    def __init__(self, path: Optional[str] = None, *,
                 profile_steps: Optional[Tuple[int, int]] = None,
                 profile_dir: str = "/tmp/repro-profile",
                 program: str = "", meta: Optional[Dict[str, Any]] = None):
        self.sink = (JsonlSink(path, program=program, meta=meta)
                     if path else None)
        self.profile_steps = profile_steps
        self.profile_dir = profile_dir
        self._profiling = False
        self._closed = False

    @classmethod
    def from_config(cls, cfg, *, program: str = "",
                    meta: Optional[Dict[str, Any]] = None) -> "Telemetry":
        """Build from a :class:`repro.configs.base.TelemetryConfig`
        (or None → disabled)."""
        if cfg is None:
            return cls()
        return cls(cfg.path, profile_steps=cfg.profile_steps,
                   profile_dir=cfg.profile_dir, program=program, meta=meta)

    @property
    def enabled(self) -> bool:
        return self.sink is not None

    # -- events ------------------------------------------------------------
    def emit(self, kind: str, **fields) -> None:
        if self.sink is not None:
            self.sink.emit(kind, **fields)

    def emit_span(self, name: str, t_start: float, dur_s: float,
                  **fields) -> None:
        """Record an already-timed phase. ``t_start`` is ``time.time()``
        epoch seconds of the span start (so the Chrome export places it
        correctly); ``ts`` of the record is the span *end*."""
        if self.sink is not None:
            self.sink.emit("span", ts=t_start + dur_s, name=name,
                           dur_s=dur_s, **fields)

    @contextlib.contextmanager
    def span(self, name: str, record: Optional[str] = None, **fields):
        """Mark a host-side phase.

        The phase is a ``jax.profiler.TraceAnnotation`` named ``name``, on
        the profiler's clock beside the device's operations, whether or not
        a sink exists (about a microsecond when no trace is active). With a
        sink, a phase that completes is also written as a ``span`` record
        named ``record`` (``name`` by default). Yields ``fields``: what the
        phase adds to it goes into both.
        """
        from jax.profiler import TraceAnnotation
        t0 = time.time()
        with TraceAnnotation(name) as ann:
            yield fields
            if fields:
                ann.set_metadata(**fields)
        if self.sink is not None:
            self.emit_span(record or name, t0, time.time() - t0, **fields)

    # -- profiler window ----------------------------------------------------
    def maybe_profile(self, step: int) -> None:
        """Start/stop a ``jax.profiler`` trace around the configured
        inclusive step window. Call once per step/wave; idempotent."""
        if self.profile_steps is None:
            return
        a, b = self.profile_steps
        if not self._profiling and a <= step <= b:
            import jax
            try:
                jax.profiler.start_trace(self.profile_dir)
                self._profiling = True
                self.emit("profile", event="start", step=step,
                          dir=self.profile_dir)
            except Exception as e:          # profiling must never kill a run
                self.emit("profile", event="error", step=step, error=str(e))
                self.profile_steps = None
        elif self._profiling and step > b:
            self._stop_profile(step)

    def _stop_profile(self, step: int) -> None:
        import jax
        try:
            jax.profiler.stop_trace()
            self.emit("profile", event="stop", step=step,
                      dir=self.profile_dir)
        except Exception as e:
            self.emit("profile", event="error", step=step, error=str(e))
        self._profiling = False
        self.profile_steps = None

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._profiling:
            self._stop_profile(-1)
        if self.sink is not None:
            self.sink.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


#: module-level disabled instance — the default for every instrumented
#: consumer (a shared no-op is fine: it holds no state when disabled)
NULL = Telemetry()


def as_telemetry(t: Optional[Telemetry]) -> Telemetry:
    """Normalize an optional telemetry argument to a usable object."""
    return NULL if t is None else t
