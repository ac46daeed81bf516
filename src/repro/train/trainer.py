"""Host training loop with the full fault-tolerance story:

  * auto-resume from the latest *valid* checkpoint (deterministic data
    resume — the pipeline is a pure function of step; corrupt or
    mid-rename checkpoint directories are skipped, not crashed on),
  * async rotating checkpoints (atomic renames, per-leaf crc32 verified on
    restore; a failed async write surfaces as CheckpointWriteError at the
    next checkpoint boundary, attributed to the step that failed),
  * straggler watchdog (per-step EMA timing; slow steps trigger an early
    checkpoint so a failing host loses minimal work — counted in
    ``counters["early_checkpoints"]``),
  * stability monitoring: per-tensor RMS_t recording + loss-spike detection
    (paper §3.4 / App. D) with the RMS→loss-spike predictive analysis,
  * deterministic fault injection (``fault_plan=``, default off) for the
    self-healing harness: NaN/Inf/exploding grads, poisoned batches,
    checkpoint write failures and corruption, simulated crashes
    (``train/faults.py``); recovery lives in ``train/supervisor.py``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.distributed.straggler import StragglerWatchdog
from repro.stability import LossSpikeDetector, RMSMonitor
from repro.telemetry import as_telemetry
from repro.telemetry.health import moe_items, qh_items, summarize_rms
from repro.train.train_step import TrainState


@dataclasses.dataclass
class TrainerHooks:
    on_step: Optional[Callable[[int, Dict], None]] = None
    on_checkpoint: Optional[Callable[[int], None]] = None
    on_spike: Optional[Callable[[int], None]] = None
    on_slow: Optional[Callable[[Dict], None]] = None


class Trainer:
    def __init__(self, train_step_fn: Callable, state: TrainState, *,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, keep_checkpoints: int = 3,
                 watch_layers=("patch_embed", "embed"),
                 hooks: Optional[TrainerHooks] = None,
                 log_every: int = 10,
                 state_shardings: Optional[TrainState] = None,
                 fault_plan=None,
                 early_checkpoint_on_slow: bool = True,
                 telemetry=None):
        self.step_fn = train_step_fn
        self.telemetry = as_telemetry(telemetry)
        self.state = state
        self.state_shardings = state_shardings
        self.fault_plan = fault_plan
        if checkpoint_dir and fault_plan is not None:
            from repro.train.faults import make_checkpoint_manager
            self.ckpt = make_checkpoint_manager(
                checkpoint_dir, keep_checkpoints, fault_plan)
        else:
            self.ckpt = (CheckpointManager(checkpoint_dir, keep_checkpoints)
                         if checkpoint_dir else None)
        self.checkpoint_every = checkpoint_every
        self.watchdog = StragglerWatchdog()
        self.watchdog.on_slow = self._on_slow
        self.rms_monitor = RMSMonitor(watch_layers=watch_layers)
        self.spike_detector = LossSpikeDetector(ignore_first=0)
        self.hooks = hooks or TrainerHooks()
        self.log_every = log_every
        self.history: List[Dict] = []
        self.early_checkpoint_on_slow = early_checkpoint_on_slow
        self.counters: Dict[str, int] = {
            "slow_steps": 0, "early_checkpoints": 0}
        self._early_ckpt_wanted = False
        self._last_saved_step: Optional[int] = None

    # ------------------------------------------------------------------
    def maybe_resume(self) -> int:
        """Restore the latest valid checkpoint if one exists. Returns start
        step.  Corrupt / torn checkpoints are skipped (CheckpointManager
        falls back to the newest directory that verifies).

        With ``state_shardings`` (the engine's), each leaf is device_put
        straight onto its mesh sharding — resumed state lands sharded, no
        host round-trip through replicated single-device arrays."""
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return int(self.state.step)
        return self.restore_checkpoint()

    def restore_checkpoint(self, step: Optional[int] = None) -> int:
        """Load checkpoint ``step`` (default newest valid) into
        ``self.state``; returns the restored step."""
        if self.state_shardings is not None:
            tree, step, extra = self.ckpt.restore(
                step, like=self.state, shardings=self.state_shardings)
            self.state = (TrainState(*tree)
                          if isinstance(tree, (list, tuple)) else tree)
            return step
        tree, step, extra = self.ckpt.restore(step, like=self.state)
        self.state = jax.tree.map(
            lambda ref, arr: jax.device_put(np.asarray(arr)).astype(ref.dtype)
            if hasattr(ref, "dtype") else arr, self.state,
            TrainState(*tree) if isinstance(tree, (list, tuple)) else tree)
        return step

    # ------------------------------------------------------------------
    def _on_slow(self, ev: Dict) -> None:
        self.counters["slow_steps"] += 1
        self._early_ckpt_wanted = True
        if self.hooks.on_slow:
            self.hooks.on_slow(ev)

    def _flush(self, pending: List) -> None:
        """Fetch a block of device metrics in one transfer (``train.wait``)
        and run the host bookkeeping (spike detector, RMS monitor,
        watchdog, history, hooks; ``train.bookkeeping``).

        device_get blocks until every step in the window has executed, so
        (now - window start) / len(window) is the true amortized per-step
        wall time — the per-step watchdog timing would only see async
        dispatch overhead."""
        if not pending:
            return
        tele = self.telemetry
        # the one blocking device_get for the whole window: host sync time
        # is this span (its record is the flight recorder's "flush" span)
        with tele.span("train.wait", record="flush", step=pending[-1][0],
                       n_steps=len(pending)):
            fetched = jax.device_get([m for _, m in pending])
        with jax.profiler.TraceAnnotation("train.bookkeeping"):
            self._bookkeeping(pending, fetched)

    def _bookkeeping(self, pending: List, fetched: List) -> None:
        tele = self.telemetry
        dt = (time.monotonic() - self._window_t0) / len(pending)
        for (i, _), metrics in zip(pending, fetched):
            timing = self.watchdog.record(i, dt)
            loss = float(metrics["loss"])
            new_spikes = self.spike_detector.observe(i, loss)
            if new_spikes:
                for s in new_spikes:
                    tele.emit("spike", step=int(s), observed_at=i)
                    if self.hooks.on_spike:
                        self.hooks.on_spike(s)
            if "rms" in metrics:
                self.rms_monitor.record(i, metrics["rms"])
            rec = {"step": i, "loss": loss,
                   "grad_norm": float(metrics["grad_norm"]),
                   "lr": float(metrics["lr"]),
                   "n_skipped": int(metrics["n_skipped_tensors"]),
                   "dt": timing["dt"], "slow": timing["slow"],
                   **moe_items(metrics)}
            if tele.enabled:
                ev = dict(rec, **qh_items(metrics))
                if "rms" in metrics:
                    ev.update(summarize_rms(metrics["rms"]))
                tele.emit("train_step", **ev)
            self.history.append(rec)
            if self.hooks.on_step:
                self.hooks.on_step(i, rec)
            if self.log_every and i % self.log_every == 0:
                print(f"[trainer] step {i} loss {loss:.4f} "
                      f"gnorm {rec['grad_norm']:.3f} dt {timing['dt']*1e3:.0f}ms"
                      + (" SLOW" if timing["slow"] else ""))
        tele.emit("flush", step=pending[-1][0], n_steps=len(pending))
        pending.clear()
        self._window_t0 = time.monotonic()

    def _save(self, step: int) -> None:
        # the span times the synchronous device->host snapshot inside
        # save_async (the write itself is off-thread)
        with self.telemetry.span("train.checkpoint", record="checkpoint_save",
                                 step=step):
            self.ckpt.save_async(step, self.state)
        self._last_saved_step = step
        self.telemetry.emit("checkpoint", step=step)
        if self.hooks.on_checkpoint:
            self.hooks.on_checkpoint(step)
        # the synchronous device->host snapshot must not be billed to the
        # next window's step timing
        self._window_t0 = time.monotonic()

    def run(self, batch_iter, n_steps: int) -> List[Dict]:
        """Run ``n_steps`` steps. Each is a ``train.step`` annotation in
        the profiler trace (``step_num`` its index), holding ``train.batch``
        (``batch_iter``; ``nbytes`` the batch's size), ``train.dispatch``
        (the step function) and, at flush and checkpoint boundaries,
        ``train.wait``, ``train.bookkeeping`` and ``train.checkpoint``."""
        with jax.profiler.TraceAnnotation("train.wait"):
            start = int(self.state.step)
        # Metrics stay on device between flush boundaries so the step can
        # dispatch asynchronously — float(loss) every step would block the
        # host on every device step and serialize the pipeline. The cost:
        # spike/straggler detection sees per-step values only at flush
        # granularity (a single slow step is averaged over its window);
        # log_every=1 restores per-step timing where that matters.
        pending: List = []
        self._window_t0 = time.monotonic()
        for i in range(start, start + n_steps):
            self.telemetry.maybe_profile(i)
            with jax.profiler.StepTraceAnnotation("train.step", step_num=i):
                self._step(i, batch_iter, pending)
        self._flush(pending)
        if self.ckpt is not None:
            self.ckpt.wait()
        return self.history

    def _step(self, i: int, batch_iter, pending: List) -> None:
        plan = self.fault_plan
        with jax.profiler.TraceAnnotation("train.batch") as ann:
            if hasattr(batch_iter, "__next__"):
                data_idx, batch = next(batch_iter)
            else:
                out = batch_iter(i)
                data_idx, batch = out if (isinstance(out, tuple)
                                          and len(out) == 2) else (i, out)
            if plan is not None:
                batch = plan.apply_batch(data_idx, batch)
            ann.set_metadata(nbytes=sum(getattr(x, "nbytes", 0)
                                        for x in jax.tree.leaves(batch)))
        with jax.profiler.TraceAnnotation("train.dispatch"):
            self.state, metrics = self.step_fn(self.state, batch)
        if plan is not None:
            self.state, metrics = plan.apply_post_step(
                i, data_idx, self.state, metrics)
            plan.maybe_crash(i)
        pending.append((i, metrics))

        at_ckpt = (self.ckpt is not None and self.checkpoint_every
                   and (i + 1) % self.checkpoint_every == 0)
        if at_ckpt or not self.log_every or i % self.log_every == 0:
            self._flush(pending)
            if self.ckpt is not None:
                # a failed async write surfaces here, at the next
                # checkpoint/flush boundary, attributed to its step
                self.ckpt.poll_error()
        if at_ckpt:
            self._save(i + 1)
        elif self._early_ckpt_wanted and self.early_checkpoint_on_slow \
                and self.ckpt is not None and self.checkpoint_every:
            # straggler watchdog fired: bank progress now, a failing
            # host should lose minimal work.  At most one early save
            # per checkpoint window.
            self._flush(pending)
            if self._last_saved_step is None or \
                    i + 1 - self._last_saved_step >= \
                    max(self.checkpoint_every // 2, 1):
                self._save(i + 1)
                self.counters["early_checkpoints"] += 1
        self._early_ckpt_wanted = False

    # ------------------------------------------------------------------
    def rollback(self, step: int) -> None:
        """Forget all host-side bookkeeping for steps >= ``step`` (the
        supervisor restored a checkpoint there; those steps re-execute)."""
        self.history = [h for h in self.history if h["step"] < step]
        self.spike_detector.rollback(step)
        self.rms_monitor.rollback(step)

    def stability_report(self, layer: Optional[str] = None) -> Dict:
        spikes = self.spike_detector.spike_steps()
        report: Dict[str, Any] = {"loss_spike_steps": spikes,
                                  "counters": dict(self.counters)}
        layers = ([layer] if layer else self.rms_monitor.layers())
        for name in layers:
            report[name] = self.rms_monitor.predicts_loss_spike(name, spikes)
        return report
