"""Executor-side reporter: bridges user code and the heartbeat thread.

Parity: reference `maggy/core/reporter.py` — `broadcast(metric, step)` with
type checks, monotonic-step enforcement, latest-value store, and raising
`EarlyStopException` inside the user's training loop once the driver's STOP
reply has set the flag (:78-102); `log()` buffered for heartbeat shipping
(:104-133); `get_data()` drain (:135-141); `reset()` between trials
(:143-156); `early_stop()` armed only after >=1 reported metric (:158-161).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import numpy as np

from maggy_tpu import exceptions
from maggy_tpu.telemetry import runnerstats


class Reporter:
    def __init__(self, log_file: Optional[str] = None, print_tee: bool = False):
        self.lock = threading.RLock()
        self.metric: Optional[float] = None  # guarded-by: lock
        self.step: Optional[int] = None  # guarded-by: lock
        self.trial_id: Optional[str] = None  # guarded-by: lock
        # Telemetry span id assigned by the driver for this trial; rides
        # the TRIAL reply and is echoed on METRIC/FINAL so driver-side
        # span timelines attribute every hop without guessing.
        self.span: Optional[str] = None
        # Runner-side stat buffer (telemetry.runnerstats.RunnerStats),
        # attached by the executor: broadcast() feeds it the step cadence
        # and time-to-first-metric signals. None = no-op.
        self.stats = None
        self._stop_flag = False  # guarded-by: lock
        # The current stop is a scheduler preemption (STOP reply carried
        # ``preempt``): the executor acks with a preempted FINAL instead
        # of finalizing. Consumed via take_preempt().
        self._preempt_flag = False  # guarded-by: lock
        self._log_buffer: List[str] = []  # guarded-by: lock
        self._log_file = log_file
        self._print_tee = print_tee
        self._metric_cache = None  # guarded-by: lock  # (device_array, float, step) identity triple
        self._async_kick = None  # guarded-by: lock  # device array with an in-flight D2H copy
        # ---- vectorized (K-lane) trial blocks (train/vmap.py) ----
        # Lane descriptors for the current block, in lane order:
        # [{"trial_id", "span", "lane"}, ...]. None = scalar trial.
        self._lanes = None  # guarded-by: lock
        self._lane_vec = None  # guarded-by: lock  # lazy (K,) loss vector
        self._lane_step: Optional[int] = None  # guarded-by: lock
        self._lane_cache = None  # guarded-by: lock  # (vec_identity, [floats], step)
        # Lane trial ids the driver flagged for early stop; _new holds the
        # ones the training loop hasn't consumed (take_stopped_lanes) yet.
        self._lane_stops: set = set()  # guarded-by: lock
        self._lane_stops_new: set = set()  # guarded-by: lock

    # ------------------------------------------------------------- user API

    @staticmethod
    def _scalar_like(metric) -> bool:
        """Accept plain numbers AND lazy single-element device arrays (jax
        Array / 0-d numpy) WITHOUT forcing a device sync — shape/dtype are
        metadata. Booleans are rejected either way."""
        if isinstance(metric, bool):
            return False
        if isinstance(metric, (int, float, np.number)):
            return True
        shape = getattr(metric, "shape", None)
        dtype = getattr(metric, "dtype", None)
        if shape is None or dtype is None:
            return False
        try:
            # Abstract tracers (broadcast called from INSIDE a jitted
            # function) have shape/dtype but no value — rejecting them here
            # keeps the user error in the user's thread instead of blowing
            # up the heartbeat thread at materialization time.
            from jax.core import Tracer

            if isinstance(metric, Tracer):
                return False
        except Exception:  # noqa: BLE001 - no jax in this process
            pass
        try:
            if not (np.issubdtype(dtype, np.floating) or np.issubdtype(dtype, np.integer)):
                return False
            return int(np.prod(shape)) == 1
        except TypeError:
            return False

    def broadcast(self, metric, step: Optional[int] = None) -> None:
        """Report an interim metric from the training loop. Raises
        `EarlyStopException` if the driver has flagged this trial.

        ``metric`` may be a plain number OR a single-element device array
        (e.g. the jax scalar a jitted train step returns). Device arrays are
        kept LAZY: the training loop never blocks on a device->host sync —
        the heartbeat thread materializes the newest value in `get_data()`.
        A blocking `float(loss)` per reporting step would wait for the
        device each time and serialize the pipelined step stream."""
        # ``report`` in the profiler's trace (annotation only; nothing
        # where no session is open or jax was never imported).
        with runnerstats.span("report"), self.lock:
            if not self._scalar_like(metric):
                raise exceptions.BroadcastMetricTypeError(metric)
            if step is not None and (not isinstance(step, (int, np.integer)) or isinstance(step, bool)):
                raise exceptions.BroadcastStepTypeError(step)
            if step is None:
                step = self.step + 1 if self.step is not None else 0
            elif self.step is not None and step <= self.step:
                raise exceptions.BroadcastStepValueError(step, self.step)
            self.metric = float(metric) \
                if isinstance(metric, (int, np.number)) else metric
            self.step = int(step)
            stats = self.stats
            if stats is not None:
                # Pure arithmetic (runnerstats.RunnerStats.on_broadcast):
                # cadence + time-to-first-metric, recorded BEFORE the stop
                # check so the early-stopped step still counts.
                stats.on_broadcast(self.step)
            if self._stop_flag:
                raise exceptions.EarlyStopException(self._materialize(self.metric))

    def broadcast_lanes(self, values, step: Optional[int] = None) -> None:
        """Vectorized-trial analogue of `broadcast()`: report the per-lane
        loss vector of a K-lane block (train/vmap.py `VmapTrainer.step`
        output). ``values`` must have length K (one entry per lane, masked
        lanes included — their entries are dead compute and are dropped at
        ship time). Kept LAZY like `broadcast()`: a jax (K,) array is not
        synced here; the heartbeat thread materializes it in `get_data()`.

        Raises `EarlyStopException` when the whole BLOCK is stopped (a
        scheduler preemption) — per-lane stops never raise; they surface
        via `take_stopped_lanes()` so the training loop can mask the lane
        without tearing down the block."""
        with self.lock:
            if self._lanes is None:
                raise exceptions.BroadcastMetricTypeError(values)
            k = len(self._lanes)
            shape = getattr(values, "shape", None)
            n = shape[0] if shape else len(values)
            if shape is not None and len(shape) != 1 or n != k:
                raise exceptions.BroadcastMetricTypeError(values)
            if step is not None and (not isinstance(step, (int, np.integer)) or isinstance(step, bool)):
                raise exceptions.BroadcastStepTypeError(step)
            if step is None:
                step = self._lane_step + 1 if self._lane_step is not None else 0
            elif self._lane_step is not None and step <= self._lane_step:
                raise exceptions.BroadcastStepValueError(step, self._lane_step)
            self._lane_vec = values
            self._lane_step = int(step)
            # Mirror into the scalar fields so code keyed on "has this
            # trial reported yet" (early_stop arming, preempt acks) works:
            # the block's leader beat is step-aligned with the lanes.
            self.step = self._lane_step
            stats = self.stats
            if stats is not None:
                stats.on_broadcast(self._lane_step)
            if self._stop_flag:
                raise exceptions.EarlyStopException(None)

    def stop_lanes(self, trial_ids) -> None:
        """Flag individual lanes of the current block for early stop (the
        heartbeat thread applies the server's ``stop_lanes`` reply here).
        Unknown / stale trial ids are ignored."""
        with self.lock:
            if not self._lanes:
                return
            known = {entry["trial_id"] for entry in self._lanes}
            for tid in trial_ids or ():
                if tid in known and tid not in self._lane_stops:
                    self._lane_stops.add(tid)
                    self._lane_stops_new.add(tid)

    def take_stopped_lanes(self) -> List[str]:
        """Consume newly stop-flagged lane trial ids (each id is returned
        exactly once). The training loop polls this between steps and masks
        the named lanes (`VmapTrainer.mask_lane`) — no recompile, no
        exception."""
        with self.lock:
            fresh = sorted(self._lane_stops_new)
            self._lane_stops_new = set()
            return fresh

    def stopped_lanes(self) -> List[str]:
        """All lane trial ids flagged so far this block (consumed or not)."""
        with self.lock:
            return sorted(self._lane_stops)

    @staticmethod
    def _materialize(metric):
        """Device array -> float (blocks until the step producing it ran)."""
        return metric if metric is None or isinstance(metric, float) else float(metric)

    def log(self, message: str, verbose: bool = True) -> None:
        with self.lock:
            self._log_buffer.append(str(message))
            if self._log_file:
                try:
                    with open(self._log_file, "a") as f:
                        f.write(str(message) + "\n")
                except OSError:
                    pass
        if verbose and self._print_tee:
            print(message)

    # ------------------------------------------------------- heartbeat side

    def get_data(self) -> Dict[str, Any]:
        with self.lock:
            metric, step, tid = self.metric, self.step, self.trial_id
            span = self.span
            cached = self._metric_cache
        # The newest step the loop has broadcast, beside the one that ships
        # (older, or none, while the newest loss is still on the device).
        newest_step = step
        if metric is not None and not isinstance(metric, float):
            # Materialize OUTSIDE the lock: the device sync must not block
            # the training thread's broadcast.
            # Identity-cache so back-to-back heartbeats on the same value
            # don't re-fetch. Runs BEFORE the log drain below — if the
            # device value is poisoned and float() raises, the buffered
            # logs stay queued for the next beat instead of vanishing.
            #
            # NON-BLOCKING: if the step producing the value hasn't finished,
            # don't park the heartbeat thread on it (concurrent blocking
            # fetches from N runner heartbeats contend on the device link) —
            # kick an async D2H copy and ship the previous materialized
            # (metric, step) pair this beat; the driver dedups by step.
            if cached is not None and cached[0] is metric:
                metric = cached[1]
            else:
                try:
                    ready = metric.is_ready()
                except AttributeError:  # 0-d numpy etc.: materialize now
                    ready = True
                if not ready:
                    # Kick bookkeeping under the lock, with the same
                    # rolled-over guard as the cache below: reset()
                    # clears _async_kick when the trial rolls over, and
                    # an unlocked write landing after it would resurrect
                    # the RETIRED trial's device array as the next
                    # trial's in-flight kick (found by the guarded-by
                    # checker: every other _async_kick write holds the
                    # lock). copy_to_host_async is non-blocking.
                    with self.lock:
                        if self._async_kick is not metric \
                                and self.trial_id == tid:
                            metric.copy_to_host_async()
                            self._async_kick = metric
                if ready:
                    value = self._materialize(metric)
                    with self.lock:
                        # Only cache if the trial hasn't rolled over while
                        # materializing: a write landing after reset() would
                        # resurrect THIS trial's value into the next trial's
                        # ship-previous-pair branch below.
                        if self.trial_id == tid:
                            self._metric_cache = (metric, value, step)
                            self._async_kick = None
                    metric = value
                elif cached is not None:
                    metric, step = cached[1], cached[2]
                else:
                    metric, step = None, None
        lanes_out = self._lane_data(tid)
        with self.lock:
            logs = self._log_buffer
            self._log_buffer = []
        # trial_id/span are the ones the (metric, step) pair belongs to —
        # callers must ship THESE, not re-read reporter fields (which may
        # have rolled over to the next trial mid-call).
        data = {"metric": metric, "step": step, "logs": logs,
                "trial_id": tid, "span": span, "newest_step": newest_step}
        if lanes_out is not None:
            data["lanes"] = lanes_out
        return data

    def _lane_data(self, tid) -> Optional[List[Dict[str, Any]]]:
        """Materialize the newest per-lane loss vector into lane-tagged beat
        entries (one dict per LIVE lane). None when not in lane mode or no
        vector was broadcast yet. Runs on the heartbeat thread — the single
        (K,) device sync here replaces K scalar syncs."""
        with self.lock:
            lanes, vec, vstep = self._lanes, self._lane_vec, self._lane_step
            stops = set(self._lane_stops)
            cached = self._lane_cache
        if lanes is None or vec is None:
            return None
        if cached is not None and cached[0] is vec:
            values, vstep = cached[1], cached[2]
        else:
            values = [float(v) for v in np.asarray(vec).reshape(-1)]
            with self.lock:
                if self.trial_id == tid:
                    self._lane_cache = (vec, values, vstep)
        return [{"trial_id": entry["trial_id"], "value": values[i],
                 "step": vstep, "span": entry.get("span"),
                 "lane": entry.get("lane", i)}
                for i, entry in enumerate(lanes)
                if entry["trial_id"] not in stops]

    def early_stop(self, trial_id: Optional[str] = None,
                   preempt: bool = False) -> None:
        """Arm the stop flag (only once a metric exists, reference
        `reporter.py:158-161`). ``trial_id``, when given, must match the
        current trial: a STOP reply to a heartbeat that shipped the
        PREVIOUS trial's data must not stop the trial that replaced it.
        ``preempt`` marks the stop as a scheduler preemption."""
        with self.lock:
            if self._lanes is not None:
                # Vectorized block: a preempt stops the WHOLE block (the
                # executor acks and the driver requeues every lane); a
                # plain per-lane stop is routed to the lane-mask path.
                lane_ids = {entry["trial_id"] for entry in self._lanes}
                if trial_id is not None and trial_id != self.trial_id \
                        and trial_id not in lane_ids:
                    return
                if preempt:
                    if self._lane_step is not None or self.metric is not None:
                        self._stop_flag = True
                        self._preempt_flag = True
                elif trial_id is not None:
                    self.stop_lanes([trial_id])
                return
            if trial_id is not None and trial_id != self.trial_id:
                return
            if self.metric is not None:
                self._stop_flag = True
                if preempt:
                    self._preempt_flag = True

    def take_preempt(self) -> bool:
        """Consume the preemption marker: True exactly once per preempted
        stop (the executor's EarlyStopException handler decides between
        finalize and preempt-ack on it)."""
        with self.lock:
            flag = self._preempt_flag
            self._preempt_flag = False
            return flag

    def reset(self, trial_id: Optional[str] = None,
              span: Optional[str] = None) -> None:
        with self.lock:
            self.metric = None
            self.step = None
            self._stop_flag = False
            self._preempt_flag = False
            self._log_buffer = []
            self.trial_id = trial_id
            self.span = span
            self._metric_cache = None
            self._async_kick = None
            self._lanes = None
            self._lane_vec = None
            self._lane_step = None
            self._lane_cache = None
            self._lane_stops = set()
            self._lane_stops_new = set()

    def reset_lanes(self, trial_id: str, span: Optional[str],
                    lanes: List[Dict[str, Any]]) -> None:
        """Arm the reporter for a vectorized K-lane block. ``trial_id`` /
        ``span`` are the block LEADER's (the trial the partition is
        assigned); ``lanes`` are the per-lane descriptors from the TRIAL
        reply's ``vmap_block`` info — each needs at least trial_id/span/lane.
        """
        self.reset(trial_id=trial_id, span=span)
        with self.lock:
            self._lanes = [{"trial_id": entry["trial_id"],
                            "span": entry.get("span"),
                            "lane": entry.get("lane", i)}
                           for i, entry in enumerate(lanes)]
