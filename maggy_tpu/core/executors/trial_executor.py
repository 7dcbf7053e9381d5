"""Trial-runner executor loop for HPO / ablation experiments.

Parity: reference `maggy/core/executors/trial_executor.py:32-171` — the
wrapper each worker runs: connect client -> register -> start heartbeat ->
loop {get_suggestion -> prepare trial dir + .hparams.json -> call
train_fn(**params[, reporter]) -> validate/persist return -> catch
EarlyStopException and use its carried metric -> finalize_metric} until
GSTOP; ablation mode resolves declarative ablation specs before the call
(:103-108).

Redesign notes:
- the hand-off is pipelined (config.prefetch, default on): finalize_metric
  banks the next assignment piggybacked on the FINAL reply, so the
  get_suggestion at the top of the loop is usually wire-free — GET polling
  remains the fallback (first trial after registration, idle wake-ups,
  requeues).
- `builtins.print` is NOT patched by default (reference :71-81): the
  reporter tees to the runner log explicitly; user code gets the reporter
  for logging. ``ship_prints=True`` opts back into the reference behavior
  via a thread-scoped tee (prints inside train_fn also land in the
  reporter log channel and stream to the driver/monitor on heartbeats).
- per-trial TPU device pinning happens in the runner pool (process-level),
  not here: JAX binds devices at process start.
"""

from __future__ import annotations

import inspect
import os
import threading
import traceback
from typing import Callable, Optional, Tuple

# The JAX profiler allows one active trace per process.
_PROFILE_LOCK = threading.Lock()

# ---- opt-in print shipping (ship_prints=True) ----
# builtins.print is process-global but runners may be THREADS sharing it,
# so the installed tee dispatches through a thread-local: only the thread
# currently inside a shipping trial has a reporter registered; every other
# thread's prints pass through untouched. Installed once, never uninstalled
# (the pass-through is free), so concurrent experiments can't race the
# patch the way the reference's per-executor patching could.
_print_ship = threading.local()
_print_tee_lock = threading.Lock()
_orig_print = None


def _install_print_tee() -> None:
    global _orig_print
    with _print_tee_lock:
        if _orig_print is not None:
            return
        import builtins
        import sys

        _orig_print = builtins.print

        def tee_print(*args, **kwargs):
            _orig_print(*args, **kwargs)
            reporter = getattr(_print_ship, "reporter", None)
            if reporter is not None and kwargs.get("file") in (None, sys.stdout):
                try:
                    reporter.log(
                        str(kwargs.get("sep", " ")).join(str(a) for a in args),
                        verbose=False)
                except Exception:  # noqa: BLE001 - shipping must never break print
                    pass

        builtins.print = tee_print

from maggy_tpu import util
from maggy_tpu.core.environment import EnvSing
from maggy_tpu.core.reporter import Reporter
from maggy_tpu.core.rpc import Client
from maggy_tpu.exceptions import EarlyStopException


class TrialExecutor:
    """The worker each runner executes; a module-level class so process
    pools can pickle it (``train_fn`` must then be module-level too)."""

    def __init__(
        self,
        server_addr: Tuple[str, int],
        secret: str,
        hb_interval: float,
        exp_dir: str,
        optimization_key: str,
        train_fn: Callable,
        trial_type: str = "optimization",
        ablation_resolver: Optional[Callable] = None,
        profile: bool = False,
        ship_prints: bool = False,
        warm_start: bool = True,
        host_port: Optional[str] = None,
    ):
        self.server_addr = server_addr
        self.secret = secret
        self.hb_interval = hb_interval
        self.exp_dir = exp_dir
        self.optimization_key = optimization_key
        self.train_fn = train_fn
        self.trial_type = trial_type
        self.ablation_resolver = ablation_resolver
        self.profile = profile
        self.ship_prints = ship_prints
        self.warm_start = warm_start
        # Advertised "host:port" this runner can be reached on for
        # remote-gang rendezvous (a fleet agent's reserved coordinator
        # address). None for in-process runners — its presence in the
        # REG record is exactly how the driver tells a remote member
        # from a thread runner when stamping gang rendezvous info.
        self.host_port = host_port

    def __call__(self, partition_id: int) -> None:
        env = EnvSing.get_instance()
        exp_dir = self.exp_dir
        # Shared persistent XLA cache: successive trials (and sibling runner
        # processes) with recurring shapes skip recompilation (SURVEY.md
        # §7.3 "compile-cache churn").
        util.enable_compile_cache()
        # Warm-state harness: count warm-slot + persistent-cache events
        # through jax.monitoring so the journal carries the compile-once
        # hit rates (train/warm.py; never fatal).
        from maggy_tpu.train import warm

        warm.install_monitoring_listener()
        task_attempt = int(os.environ.get("MAGGY_TPU_TASK_ATTEMPT", "0"))
        reporter = Reporter(
            log_file="{}/executor_{}_{}.log".format(exp_dir, partition_id, task_attempt)
        )
        client = Client(self.server_addr, partition_id, task_attempt,
                        self.hb_interval, self.secret)
        # Runner-side telemetry: broadcast cadence + time-to-first-metric
        # feed in from the reporter, heartbeat RTT from the client, and
        # the client piggybacks the delta-encoded buffer on its METRIC
        # heartbeats (no new socket; driver merges it into the journal).
        from maggy_tpu.telemetry.runnerstats import RunnerStats

        stats = RunnerStats()
        reporter.stats = stats
        client.runner_stats = stats
        try:
            capacity = os.environ.get("MAGGY_TPU_CAPACITY")
            client.register(host_port=self.host_port,
                            capacity=int(capacity) if capacity else None)
            client.start_heartbeat(reporter)
            sig_params = inspect.signature(self.train_fn).parameters
            wants_reporter = "reporter" in sig_params
            wants_ctx = "ctx" in sig_params

            while not client.done:
                trial_id, params = client.get_suggestion()
                if trial_id is None:
                    break
                from maggy_tpu.core.rpc import RESIZE

                if trial_id == RESIZE:
                    # Elastic pool: exit so the dispatcher respawns this
                    # partition pinned to params["chips"] chips (the pin
                    # must precede backend init — no in-place resize).
                    resize_file = os.environ.get("MAGGY_TPU_RESIZE_FILE")
                    if resize_file:
                        import json as _json

                        with open(resize_file, "w") as f:
                            _json.dump({"chips": params["chips"]}, f)
                    reporter.log("resizing to {} chip(s); runner exiting "
                                 "for respawn".format(params["chips"]))
                    break
                if client.last_info.get("gang_role") == "member":
                    # Remote-gang MEMBER program: join the
                    # jax.distributed rendezvous and run the same SPMD
                    # program as the leader; only the leader reports and
                    # finalizes, so this path sends no FINAL and loops
                    # straight back to polling.
                    self._run_gang_member(trial_id, params, client,
                                          reporter)
                    continue
                if (client.last_info or {}).get("vmap_block"):
                    # Vectorized K-lane block (config.vmap_lanes): one
                    # delivery, K trials trained in lockstep as one
                    # vmapped program — or sequentially when the train fn
                    # doesn't take a ``lanes`` kwarg. Sends one FINAL per
                    # lane; the loop resumes polling after the last.
                    self._run_vmap_block(trial_id, params, client,
                                         reporter, stats, env, exp_dir,
                                         sig_params)
                    continue
                trial_dir = "{}/{}".format(exp_dir, trial_id)
                env.mkdir(trial_dir)
                env.dump(util.json_dumps_safe(params), trial_dir + "/.hparams.json")
                # The driver-minted telemetry span rides the TRIAL info;
                # arming the reporter with it makes every METRIC/FINAL this
                # trial sends attributable to its span timeline.
                reporter.reset(trial_id=trial_id,
                               span=client.last_info.get("span"))
                stats.trial_start(trial_id)
                try:
                    # Per-trial TensorBoard logdir + hparams record
                    # (reference `trial_executor.py:122-133`).
                    from maggy_tpu import tensorboard as tb

                    tb._register(os.path.join(trial_dir, "tensorboard"))
                    tb.write_hparams(params)
                except Exception:  # noqa: BLE001 - TB must never kill a trial
                    pass

                call_params = dict(params)
                if self.trial_type == "ablation":
                    # Declarative ablation spec -> concrete generators
                    # (replaces the reference's pickled callables,
                    # `loco.py:224-259`; SURVEY.md §7 hard part 3).
                    call_params = self.ablation_resolver(call_params)
                ctx = None
                try:
                    if wants_reporter:
                        call_params["reporter"] = reporter
                    if wants_ctx:
                        from maggy_tpu.core.executors.context import TrialContext

                        ctx = TrialContext(trial_id, trial_dir, exp_dir,
                                           params, client.last_info)
                        call_params["ctx"] = ctx
                    if (client.last_info or {}).get("forked_from"):
                        # Checkpoint fork (config.fork): stage the
                        # parent's checkpoint into THIS trial's dir so
                        # ctx.restore_checkpoint/resume_step behave
                        # exactly like a same-trial preemption resume.
                        # The load is timed into the trial's compile
                        # record (fork_load_ms) — the warm path keeps
                        # the compiled step while values come from the
                        # staged checkpoint, and the journal must show
                        # what the load cost.
                        self._stage_fork(ctx, trial_id, trial_dir,
                                         exp_dir, params, client,
                                         reporter, stats)
                    # Inside the scope, Trainers default to the warm
                    # path (config.warm_start) and compile telemetry
                    # lands in this runner's stats. The warm slot holds
                    # programs only, so a trial that RESUMES state
                    # (preemption resume / promoted parent / fork) needs
                    # no other treatment: it inits fresh like any trial
                    # and restores its checkpoint over that.
                    with warm.trial_scope(trial_id=trial_id,
                                          enabled=self.warm_start,
                                          stats=stats):
                        retval = self._run_trial(call_params, trial_dir,
                                                 reporter)
                    metric = util.handle_return_val(
                        retval, trial_dir, self.optimization_key, env
                    )
                    client.finalize_metric(metric, reporter)
                except EarlyStopException as e:
                    if reporter.take_preempt():
                        # Scheduler preemption (fleet rebalancing or a
                        # chaos preempt_trial fault), not an early-stop
                        # verdict: ack with the last checkpoint step so
                        # the driver requeues the trial to RESUME there
                        # (TrialCheckpointer layout under the trial dir;
                        # no checkpoint -> requeue-from-scratch).
                        from maggy_tpu.train.checkpoint import \
                            latest_checkpoint_step

                        step = latest_checkpoint_step(trial_dir)
                        reporter.log(
                            "Trial {} preempted{}.".format(
                                trial_id,
                                " at checkpoint step {}".format(step)
                                if step is not None
                                else " (no checkpoint; re-runs from "
                                     "scratch)"))
                        client.preempt_ack(trial_id, reporter, step=step)
                    else:
                        reporter.log(
                            "Trial {} early-stopped.".format(trial_id))
                        env.dump(
                            util.json_dumps_safe(
                                {self.optimization_key: e.metric}),
                            trial_dir + "/.outputs.json",
                        )
                        client.finalize_metric(e.metric, reporter)
                except Exception:  # noqa: BLE001 - report trial error, keep worker alive
                    reporter.log(
                        "Trial {} failed:\n{}".format(trial_id, traceback.format_exc())
                    )
                    # finalize_error, not a raw FINAL: the reply may
                    # piggyback this runner's next assignment (pipelined
                    # hand-off), which the next get_suggestion consumes
                    # without a round trip.
                    client.finalize_error(trial_id, reporter)
                finally:
                    stats.trial_end(trial_id)
                    if ctx is not None:
                        ctx.close()
        finally:
            try:
                # Close the last trial's TensorBoard session: writes its
                # hparams session_end record and flushes the event file
                # (short final trials would lose buffered events otherwise).
                from maggy_tpu import tensorboard as tb

                tb._close()
            except Exception:  # noqa: BLE001
                pass
            client.stop()


    def _stage_fork(self, ctx, trial_id: str, trial_dir: str,
                    exp_dir: str, params: dict, client, reporter,
                    stats) -> None:
        """Stage a forked trial's parent checkpoint into its trial dir
        (idempotent — a requeued fork re-stages to the SAME step). A
        staging failure (parent checkpoint vanished mid-flight, torn
        copy) downgrades the trial to a from-scratch run: the fork keys
        are stripped from the assignment info so ``ctx.resume_step``
        reads None and the train fn's resume branch never opens a
        checkpoint that is not there."""
        from maggy_tpu.telemetry.runnerstats import span

        fork = dict((client.last_info or {}).get("forked_from") or {})
        staged = None
        # Timed whether or not the parent's checkpoint is there: the
        # runner spent the time either way.
        with span("fork_stage", stats=stats, trial_id=trial_id) as staging:
            try:
                if ctx is not None:
                    staged = ctx.stage_fork()
                else:
                    from maggy_tpu.core.environment import EnvSing
                    from maggy_tpu.train.checkpoint import fork_checkpoint

                    staged = fork_checkpoint(
                        EnvSing.get_instance(), exp_dir, fork.get("trial"),
                        trial_dir, step=fork.get("step"))
            except Exception:  # noqa: BLE001 - a broken fork must not kill the trial
                staged = None
        if staged is None:
            reporter.log(
                "Trial {}: fork source {} step {} unavailable; running "
                "from scratch.".format(trial_id, fork.get("trial"),
                                       fork.get("step")))
            for key in ("forked_from", "resume_step"):
                client.last_info.pop(key, None)
                if ctx is not None:
                    ctx.info.pop(key, None)
            return
        stats.note_compile(forked=True)
        reporter.log("Trial {} forked from {} at checkpoint step {} "
                     "({}ms load).".format(
                         trial_id, fork.get("trial"), staged,
                         round((staging.t_end - staging.t_start) * 1e3, 1)))

    def _run_vmap_block(self, leader_id: str, params: dict, client,
                        reporter, stats, env, exp_dir: str,
                        sig_params) -> None:
        """Run a vectorized K-lane block: one delivery, K trials, one
        vmapped program (train/vmap.py). The train fn opts into
        vectorized execution by declaring a ``lanes`` keyword (a
        `LaneSet`); otherwise the block degrades to sequential scalar
        runs of each lane. Either way every lane sends its OWN FINAL —
        the last one (``last=True``) releases the partition and banks the
        piggybacked next assignment."""
        import traceback as _tb

        from maggy_tpu.core.executors.context import LaneSet
        from maggy_tpu.train import warm

        info = client.last_info or {}
        lane_descs = list((info.get("vmap_block") or {}).get("lanes") or ())
        if not lane_descs:
            # Defensive: a block stamp with no lanes — treat the leader
            # as a scalar trial failure rather than hanging the partition.
            client.finalize_error(leader_id, reporter)
            return
        for entry in lane_descs:
            lane_dir = "{}/{}".format(exp_dir, entry["trial_id"])
            env.mkdir(lane_dir)
            env.dump(util.json_dumps_safe(entry.get("params") or {}),
                     lane_dir + "/.hparams.json")
        if "lanes" not in sig_params:
            self._run_block_sequential(leader_id, lane_descs, client,
                                       reporter, stats, env, exp_dir,
                                       sig_params)
            return
        reporter.reset_lanes(leader_id, info.get("span"), lane_descs)
        stats.trial_start(leader_id)
        finalized = []

        def finalize(entry, metric, last=False, error=False):
            if entry["trial_id"] in finalized:
                return
            finalized.append(entry["trial_id"])
            if metric is not None and not error:
                lane_dir = "{}/{}".format(exp_dir, entry["trial_id"])
                env.dump(util.json_dumps_safe(
                    {self.optimization_key: metric}),
                    lane_dir + "/.outputs.json")
                env.dump(str(float(metric)), lane_dir + "/.metric")
            client.finalize_lane(entry["trial_id"], metric, reporter,
                                 lane=entry.get("lane", 0),
                                 block=leader_id,
                                 epoch=entry.get("epoch"),
                                 last=last, error=error)

        lanes = LaneSet(lane_descs, reporter, finalize)
        call_params = dict(params)
        call_params["lanes"] = lanes
        if "reporter" in sig_params:
            call_params["reporter"] = reporter
        try:
            with warm.trial_scope(trial_id=leader_id,
                                  enabled=self.warm_start, stats=stats):
                retval = self._run_trial(
                    call_params, "{}/{}".format(exp_dir, leader_id),
                    reporter)
            metrics = self._lane_metrics(retval, lane_descs)
            remaining = [e for e in lane_descs
                         if e["trial_id"] not in finalized]
            for i, entry in enumerate(remaining):
                finalize(entry, metrics.get(entry["trial_id"]),
                         last=(i == len(remaining) - 1))
            if not remaining:
                # Every lane was retired mid-block (all early-stopped):
                # the partition still holds the block — a release-shaped
                # FINAL (last=True, duplicate trial id the driver drops)
                # frees it and banks the piggybacked next assignment.
                client.finalize_lane(leader_id, None, reporter,
                                     lane=0, block=leader_id,
                                     epoch=lane_descs[0].get("epoch"),
                                     last=True)
        except EarlyStopException:
            if reporter.take_preempt():
                reporter.log("Block {} preempted; all lanes requeue."
                             .format(leader_id))
                client.preempt_ack(leader_id, reporter, step=None)
            else:
                # broadcast_lanes only raises on a whole-block stop
                # (preempt); anything else is a contract break — error
                # out the unfinalized lanes so none hangs the schedule.
                self._error_out_lanes(leader_id, lane_descs, finalized,
                                      client, reporter)
        except Exception:  # noqa: BLE001 - report block error, keep worker alive
            reporter.log("Block {} failed:\n{}".format(
                leader_id, _tb.format_exc()))
            self._error_out_lanes(leader_id, lane_descs, finalized,
                                  client, reporter)
        finally:
            stats.trial_end(leader_id)

    def _lane_metrics(self, retval, lane_descs) -> dict:
        """Normalize a lanes-capable train fn's return value to
        {trial_id: metric}: a dict keyed by lane trial id, or a sequence
        in lane order."""
        if isinstance(retval, dict):
            return {tid: retval.get(tid) for tid in
                    (e["trial_id"] for e in lane_descs)}
        if isinstance(retval, (list, tuple)) and \
                len(retval) == len(lane_descs):
            return {e["trial_id"]: float(v)
                    for e, v in zip(lane_descs, retval)}
        from maggy_tpu.exceptions import ReturnTypeError

        raise ReturnTypeError(self.optimization_key, retval)

    def _error_out_lanes(self, leader_id, lane_descs, finalized, client,
                         reporter) -> None:
        """FINAL every unfinalized lane as an error (last one releases
        the partition); if all lanes already finalized, send the
        release-shaped duplicate instead."""
        remaining = [e for e in lane_descs
                     if e["trial_id"] not in finalized]
        for i, entry in enumerate(remaining):
            finalized.append(entry["trial_id"])
            client.finalize_lane(entry["trial_id"], None, reporter,
                                 lane=entry.get("lane", 0),
                                 block=leader_id,
                                 epoch=entry.get("epoch"),
                                 last=(i == len(remaining) - 1),
                                 error=True)
        if not remaining:
            client.finalize_lane(leader_id, None, reporter, lane=0,
                                 block=leader_id,
                                 epoch=lane_descs[0].get("epoch"),
                                 last=True)

    def _run_block_sequential(self, leader_id: str, lane_descs, client,
                              reporter, stats, env, exp_dir: str,
                              sig_params) -> None:
        """Scalar fallback for a block whose train fn takes no ``lanes``
        kwarg: run each lane as an ordinary scalar trial on this runner,
        back to back — correctness degradation only, the block seam stays
        invisible to the user code (per-lane reporter resets, per-lane
        FINALs)."""
        import traceback as _tb

        from maggy_tpu.train import warm

        for i, entry in enumerate(lane_descs):
            tid = entry["trial_id"]
            last = i == len(lane_descs) - 1
            lane_dir = "{}/{}".format(exp_dir, tid)
            reporter.reset(trial_id=tid, span=entry.get("span"))
            stats.trial_start(tid)
            call_params = dict(entry.get("params") or {})
            if "reporter" in sig_params:
                call_params["reporter"] = reporter
            try:
                with warm.trial_scope(trial_id=tid,
                                      enabled=self.warm_start,
                                      stats=stats):
                    retval = self._run_trial(call_params, lane_dir,
                                             reporter)
                metric = util.handle_return_val(
                    retval, lane_dir, self.optimization_key, env)
                client.finalize_lane(tid, metric, reporter,
                                     lane=entry.get("lane", i),
                                     block=leader_id,
                                     epoch=entry.get("epoch"), last=last)
            except EarlyStopException as e:
                if reporter.take_preempt():
                    client.preempt_ack(leader_id, reporter, step=None)
                    return
                env.dump(util.json_dumps_safe(
                    {self.optimization_key: e.metric}),
                    lane_dir + "/.outputs.json")
                client.finalize_lane(tid, e.metric, reporter,
                                     lane=entry.get("lane", i),
                                     block=leader_id,
                                     epoch=entry.get("epoch"), last=last)
            except Exception:  # noqa: BLE001 - report lane error, run the rest
                reporter.log("Lane trial {} failed:\n{}".format(
                    tid, _tb.format_exc()))
                client.finalize_lane(tid, None, reporter,
                                     lane=entry.get("lane", i),
                                     block=leader_id,
                                     epoch=entry.get("epoch"), last=last,
                                     error=True)
            finally:
                stats.trial_end(tid)

    def _run_gang_member(self, trial_id: str, params: dict, client,
                         reporter) -> None:
        """One remote gang member's side of an SPMD gang trial: every
        process of the gang must call ``jax.distributed.initialize`` (or
        the leader's rendezvous hangs) and then run the SAME program so
        the collectives line up. The member's return value is discarded
        and it never finalizes — exactly one FINAL per trial, from the
        leader. Failures are logged, not raised: a broken member makes
        the leader's mesh fail, and the driver's member-loss/requeue
        machinery owns that recovery."""
        import traceback as _tb

        from maggy_tpu.core.executors.context import TrialContext

        trial_dir = "{}/{}".format(self.exp_dir, trial_id)
        try:
            ctx = TrialContext(trial_id, trial_dir, self.exp_dir, params,
                               client.last_info)
            gang = ctx.gang
            if gang is None:
                return
            gang.ensure_rendezvous()
            call_params = dict(params)
            sig_params = inspect.signature(self.train_fn).parameters
            if "ctx" in sig_params:
                call_params["ctx"] = ctx
            if "reporter" in sig_params:
                call_params["reporter"] = None
            self.train_fn(**call_params)
        except Exception:  # noqa: BLE001 - member failure: leader's mesh surfaces it
            reporter.log("gang member program for {} failed:\n{}".format(
                trial_id, _tb.format_exc()))

    def _run_trial(self, call_params: dict, trial_dir: str, reporter=None):
        """Invoke the user train_fn, optionally under a `jax.profiler`
        trace (SURVEY.md §5.1: the TPU-idiomatic stand-in for the
        reference's absent profiling — traces land in the trial's
        TensorBoard dir and open in its profile plugin).

        The JAX profiler is process-global (one trace at a time), so with
        an in-process thread pool tracing is best-effort: a trial whose
        start overlaps an already-traced trial runs untraced. Process/TPU
        pools have one trial per process and trace every trial."""
        from maggy_tpu.telemetry.runnerstats import span

        if self.ship_prints:
            _install_print_tee()
            _print_ship.reporter = reporter
        stats = getattr(reporter, "stats", None)
        try:
            # fn_enter to fn_exit on the runner's clock: the span that the
            # trial's phases (init, trace, compile, checkpoints) lie in.
            with span("trial", stats=stats,
                      trial_id=str(getattr(reporter, "trial_id", None))):
                if not self.profile:
                    return self.train_fn(**call_params)
                if not _PROFILE_LOCK.acquire(blocking=False):
                    # Another thread-pool trial holds the process-global
                    # profiler: this trial runs UNTRACED. Report it through
                    # the runner-stats channel so the journal carries a
                    # profile_skipped trial event — a missing TensorBoard
                    # trace must be explainable, not a mystery.
                    if stats is not None:
                        stats.note_profile_skipped(
                            getattr(reporter, "trial_id", None))
                    if reporter is not None:
                        reporter.log("profiler busy (thread-pool "
                                     "contention); trial runs untraced")
                    return self.train_fn(**call_params)
                try:
                    import jax

                    with jax.profiler.trace(
                            os.path.join(trial_dir, "tensorboard")):
                        return self.train_fn(**call_params)
                finally:
                    _PROFILE_LOCK.release()
        finally:
            _print_ship.reporter = None


def trial_executor_fn(**kwargs) -> TrialExecutor:
    """Factory kept for parity with the reference's
    `trial_executor.py:32` naming."""
    return TrialExecutor(**kwargs)
