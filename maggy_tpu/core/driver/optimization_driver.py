"""HPO experiment driver.

Parity: reference `maggy/core/experiment_driver/optimization_driver.py` —
optimizer registry (:35-43), executor clamping (:57-59), pruner/gridsearch
num_trials overrides (:63-69), controller wiring to trial/final stores
(:87-93), message callbacks METRIC/BLACK/FINAL/IDLE/REG (:331-457), result
aggregation best/worst/avg (:247-307), finalize writing result.json +
experiment summary (:158-194).
"""

from __future__ import annotations

import json
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

from maggy_tpu import constants, util
from maggy_tpu import gang as gang_mod
from maggy_tpu.config import OptimizationConfig
from maggy_tpu.core.driver.driver import Driver
from maggy_tpu.core.executors.trial_executor import trial_executor_fn
from maggy_tpu.core.rpc import OptimizationServer
from maggy_tpu.core.runner_pool import ThreadRunnerPool, resolve_num_workers
from maggy_tpu.earlystop import MedianStoppingRule, NoStoppingRule
from maggy_tpu.optimizers import PBT, Asha, GridSearch, RandomSearch, SingleRun
from maggy_tpu.optimizers.abstractoptimizer import AbstractOptimizer
from maggy_tpu.trial import Trial


def _lazy_gp(**kwargs):
    from maggy_tpu.optimizers.bayes import GP

    return GP(**kwargs)


def _lazy_tpe(**kwargs):
    from maggy_tpu.optimizers.bayes import TPE

    return TPE(**kwargs)


# "gp"/"tpe" resolve lazily: the BO stack pulls sklearn/scipy (~2.5 s of
# import), which must not tax experiments that never use it.
CONTROLLER_REGISTRY = {
    "randomsearch": RandomSearch,
    "gridsearch": GridSearch,
    "asha": Asha,
    "pbt": PBT,
    "tpe": _lazy_tpe,
    "gp": _lazy_gp,
    "none": SingleRun,
}

ES_REGISTRY = {"median": MedianStoppingRule, "none": NoStoppingRule}

#: Fork-step cache sentinel: "never looked" is distinct from "looked and
#: the parent has no checkpoint" (a legitimately cached None).
_UNRESOLVED = object()


class OptimizationDriver(Driver):
    controller_dict = CONTROLLER_REGISTRY

    def __init__(self, config: OptimizationConfig, app_id: str, run_id: int):
        self.controller = self._init_controller(config)
        # Pruner must exist BEFORE sizing the schedule: it owns num_trials
        # when multi-fidelity (reference `optimization_driver.py:63-65`).
        self.controller.init_pruner()
        if getattr(config, "resume", False):
            # Validate BEFORE super().__init__ re-registers the experiment
            # dir — a late failure would have already clobbered the
            # interrupted run's experiment.json.
            self._validate_resume()
        self.num_trials = self._resolve_num_trials(config)
        # Controllers whose schedule bounds concurrency below the trial
        # count (PBT: members are sequential chains, so at most
        # `population` trials can ever be in flight) cap the worker pool —
        # excess runners would hold hardware and idle-tick all experiment.
        max_conc = getattr(self.controller, "max_concurrency", None)
        ceiling = min(self.num_trials,
                      max_conc() if max_conc is not None else self.num_trials)
        # Gang-scheduled trials need N runners for ONE trial, so the
        # trial-count clamp must not shrink the pool below the largest
        # declared gang.
        max_gang = gang_mod.config_max_gang_chips(config)
        if max_gang > 1 and getattr(config, "pool", "thread") != "elastic":
            ceiling = max(ceiling, max_gang)
        self.num_executors = min(resolve_num_workers(config), ceiling)
        if max_gang > 1 and getattr(config, "pool", "thread") != "elastic" \
                and self.num_executors < max_gang:
            raise ValueError(
                "a declared gang needs {} chips but only {} runner(s) are "
                "configured (num_workers); a gang can never "
                "assemble".format(max_gang, self.num_executors))
        super().__init__(config, app_id, run_id)

        # Trial bookkeeping shared with the server thread.
        self._trial_store: Dict[str, Trial] = {}  # guarded-by: _store_lock
        self._final_store: List[Trial] = []  # guarded-by: _store_lock
        self._store_lock = threading.RLock()
        # Trials orphaned by a lost runner, waiting for reassignment. Served
        # by _assign_next ahead of fresh controller suggestions. Guarded by
        # the STORE lock, not _sched_lock: the LOST/BLACK callbacks and the
        # server event loop (periodic_check) touch the backlog without ever
        # taking the schedule lock.
        self._requeue: List[str] = []  # guarded-by: _store_lock
        # Trials parked for a runner of the RIGHT chip capacity (elastic
        # pools): the schedule already committed to them, but the runner
        # that triggered the suggestion is pinned to a different size.
        self._parked: List[str] = []  # guarded-by: _store_lock
        # Elastic respawn sizing reads chips_per_budget ONLY on the
        # elastic pool; on thread/fleet pools the same declaration means
        # gang scheduling (see below) and the elastic machinery stays off.
        pool_kind = getattr(config, "pool", "thread")
        self._chips_map = getattr(config, "chips_per_budget", None) \
            if pool_kind == "elastic" else None

        # ---- gang scheduling (multi-chip trials; maggy_tpu.gang) ----
        # A trial declaring N>1 chips (GangSpec per budget, or a
        # Searchspace GANG entry) is not assigned to one runner: the
        # driver reserves a contiguous chip block through the placer,
        # conscripts runners whose chips fall inside it as they free up
        # (gang holds in the reservation table), and dispatches the
        # trial to the lowest-chip member as LEADER once the block is
        # fully held. The members keep heartbeating/idle-polling —
        # their chips belong to the leader's mesh until the gang
        # releases (FINAL/error/preemption/member loss).
        self._gang_map = getattr(config, "chips_per_budget", None) \
            if pool_kind != "elastic" else None
        self._gang_mode = gang_mod.config_declares_gangs(config) \
            and pool_kind != "elastic"
        # The GANG-typed searchspace entry, found by TYPE — a user may
        # name it anything ("topology", "sharding", ...); a by-name
        # lookup would silently run every trial unsharded on one chip.
        sp = getattr(config, "searchspace", None)
        self._gang_param = next(
            (n for n in sp.names() if sp.get_type(n) == "GANG"),
            None) if sp is not None else None
        binding = getattr(config, "fleet", None)
        # Fleet mode: the placer spans thread runners PLUS agent slots —
        # a remote gang assembles across agent-held fleet runners too.
        placer_chips = (binding.fleet.num_runners
                        + getattr(binding.fleet, "max_agents", 0)) \
            if binding is not None else self.num_executors
        if self._gang_mode and max_gang > placer_chips:
            # The num_executors guard above covers thread pools; in
            # fleet mode the placer spans the FLEET's runners — an
            # oversized gang would wait in _gang_demand forever.
            raise ValueError(
                "a declared gang needs {} chips but the {} spans only "
                "{} runner(s); the gang can never assemble".format(
                    max_gang,
                    "fleet" if binding is not None else "runner pool",
                    placer_chips))
        self._placer = gang_mod.GangPlacer(
            placer_chips, telemetry=self.telemetry) \
            if self._gang_mode else None
        # Trials waiting for a gang (FIFO; requeued gang trials wait in
        # _requeue instead and take priority).
        self._gang_wait: List[str] = []  # guarded-by: _store_lock
        # ---- checkpoint-forking search (config.fork) ----
        # A suggestion whose info carries a parent (ASHA promotion, PBT
        # exploit/continue segment, BO near-duplicate) is stamped with
        # forked_from + resume_step at commit time, so the promoted
        # trial RESUMES the parent's checkpoint instead of re-training
        # its prefix (ROADMAP item 3 — the rung-ratio compute win).
        self._fork_enabled = bool(getattr(config, "fork", True))
        # Fork affinity: (deadline, preferred partition, trial_id) holds
        # for forked trials parked briefly for the runner that holds the
        # parent's warm slot + local checkpoint (extends the PR-14
        # prewarm lease hints from family-affinity to parent-affinity).
        self._fork_hold: List[tuple] = []  # guarded-by: _store_lock
        # Trials that already had their one affinity hold (a second hold
        # after expiry would starve the trial forever).
        self._fork_held: set = set()  # guarded-by: _store_lock
        # Parents whose checkpoint dir was garbage-collected (journaled
        # ckpt_gc): retirement is once-only and never repeats on disk.
        self._ckpt_gced: set = set()  # guarded-by: _store_lock
        # Resolved fork points: parent trial id -> latest ack'd
        # checkpoint step (or None). A finalized parent's checkpoints
        # never move, so the env round trip (isdir+ls — two object-store
        # hops on GCS) is paid once per parent, not once per child, and
        # repeat exploits of a popular PBT donor stamp lock-free.
        self._fork_step_cache: Dict[str, Optional[int]] = {}  # guarded-by: _store_lock
        # Assembled gangs: trial_id -> {chips, members, leader, mesh,
        # strategy, revoking}.
        self._gangs: Dict[str, Dict[str, Any]] = {}  # guarded-by: _store_lock
        # Fleet-level contiguous-block reservation held while gangs are
        # waiting or running (see FleetScheduler.request_gang).
        self._fleet_gang_active = False  # guarded-by: _store_lock
        # ---- vectorized micro-trials (config.vmap_lanes; train/vmap.py) ----
        # K>1: the dispatch path assembles up to K program-compatible
        # suggestions into ONE block delivered to a single runner, which
        # trains them in lockstep as one vmapped executable. K=1 keeps
        # every code path below bit-for-bit scalar.
        self._vmap_lanes = int(getattr(config, "vmap_lanes", 1) or 1)
        # Assembled blocks in flight: leader trial id ->
        # {"lanes": [trial_id, ...] (lane order), "partition": pid}.
        self._vmap_blocks: Dict[str, Dict[str, Any]] = {}  # guarded-by: _store_lock
        # Reverse map: lane trial id -> leader trial id.
        self._lane_leader: Dict[str, str] = {}  # guarded-by: _store_lock
        # Outstanding resize requests by target size: bounds the idle-runner
        # migration so a herd of idle runners doesn't all chase one parked
        # trial's size (decremented when a runner REGisters at that size).
        self._resize_inflight: Dict[int, int] = {}  # guarded-by: _store_lock
        # partition_id -> (monotonic request time, target chips): liveness
        # watch on resize respawns (see periodic_check).
        self._resize_watch: Dict[int, tuple] = {}  # guarded-by: _store_lock
        # Arm heartbeat-loss detection (SURVEY.md §5.3): a silent runner's
        # trial is requeued to whichever runner asks for work next. The
        # loss shape (floor + interval multiple) is per-experiment config
        # so soak/chaos tests can tighten detection without monkeypatching
        # the module-global defaults.
        self.server.hb_loss_timeout = config.resolved_hb_loss_timeout()
        self.earlystop_check = self._init_earlystop(config)
        self.es_interval = config.es_interval
        self.es_min = config.es_min
        self.direction = config.direction
        self.optimization_key = config.optimization_key

        # Wire the controller (reference `optimization_driver.py:87-93`).
        self.controller.searchspace = config.searchspace
        self.controller.num_trials = self.num_trials
        self.controller.trial_store = self._trial_store
        self.controller.final_store = self._final_store
        self.controller.direction = config.direction
        # Lanes-aware optimizers (ASHA's K-at-a-time rung drain, BO's
        # fork-lane discount) read this; everyone else ignores it.
        self.controller.vmap_lanes = self._vmap_lanes
        self.controller._initialize(exp_dir=self.exp_dir)

        self.result = {"best_id": None, "best_val": None, "best_hp": None,
                       "worst_id": None, "worst_val": None, "worst_hp": None,
                       "avg": None, "num_trials": 0, "early_stopped": 0}
        self.job_start: Optional[float] = None
        self.maggy_log = ""

        # ---- pipelined trial hand-off (config.prefetch) ----
        # The schedule lock serializes everything the single driver-worker
        # thread used to serialize implicitly, now that three threads can
        # touch the schedule: the worker (REG/IDLE/BLACK/LOST callbacks +
        # FINAL fallbacks), the RPC dispatch thread (the FINAL fast path),
        # and the suggester thread (prefetch refills). Ordering: sched ->
        # store lock, never the reverse.
        self._sched_lock = threading.RLock()
        self._prefetch_enabled = bool(getattr(config, "prefetch", True)) \
            and getattr(self.controller, "supports_prefetch",
                        lambda: False)()
        # The FINAL fast path persists trial.json before the hand-off, on
        # the RPC event loop — only tolerable when the env's writes are
        # local fs ops. Remote envs (GCS) keep FINAL processing on the
        # worker thread; the prefetch queue still feeds it, so only the
        # piggybacked reply (one GET round trip) is given up.
        self._inline_final_enabled = self._prefetch_enabled and \
            getattr(self.env, "FAST_LOCAL_WRITES", False)
        # Pre-materialized suggestions (oldest first), each stamped with
        # the controller's schedule_version at suggest time; a FINAL that
        # bumps the version invalidates the stale entries before dispatch.
        # Both guarded by _sched_lock.
        self._prefetched: List[Trial] = []  # guarded-by: _sched_lock
        self._prefetch_versions: Dict[str, int] = {}  # guarded-by: _sched_lock
        self._suggest_wake = threading.Event()
        # >0 while the FINAL fast path is executing on the RPC dispatch
        # thread (mutated under _sched_lock): an expensive suggest() must
        # fall back to the suggester instead of fitting on the event loop.
        self._inline_depth = 0  # guarded-by: _sched_lock
        self._suggester_thread: Optional[threading.Thread] = None

        if getattr(config, "resume", False):
            self._restore_previous_run()
        if self._prefetch_enabled:
            # Started AFTER resume restore: the suggester must never
            # sample from a controller whose state is still rebuilding.
            self._suggester_thread = threading.Thread(
                target=self._suggester_loop, daemon=True, name="suggester")
            self._suggester_thread.start()

    # --------------------------------------------------------------- set up

    @staticmethod
    def _init_controller(config) -> AbstractOptimizer:
        opt = config.optimizer
        if isinstance(opt, str):
            key = opt.lower()
            if key not in CONTROLLER_REGISTRY:
                raise ValueError(
                    "Unknown optimizer '{}'; choose from {} or pass an "
                    "AbstractOptimizer instance.".format(opt, sorted(CONTROLLER_REGISTRY))
                )
            return CONTROLLER_REGISTRY[key](seed=config.seed) if key != "none" \
                else SingleRun(seed=config.seed)
        if opt is None:
            return SingleRun(seed=config.seed)
        if not isinstance(opt, AbstractOptimizer):
            raise TypeError(
                "optimizer must be a registry name or AbstractOptimizer, got {}".format(type(opt))
            )
        return opt

    def _resolve_num_trials(self, config) -> int:
        # Pruner owns the schedule; gridsearch computes from the space
        # (reference `optimization_driver.py:63-69`); controllers with a
        # fixed combinatorial schedule (PBT: population x generations)
        # expose it via schedule_size().
        if self.controller.pruner is not None:
            return self.controller.pruner.num_trials()
        if isinstance(self.controller, GridSearch):
            return GridSearch.get_num_trials(config.searchspace)
        size = getattr(self.controller, "schedule_size", None)
        if size is not None:
            return size()
        return config.num_trials

    @staticmethod
    def _init_earlystop(config):
        pol = config.es_policy
        if isinstance(pol, str):
            if pol.lower() not in ES_REGISTRY:
                raise ValueError("Unknown es_policy '{}'".format(pol))
            return ES_REGISTRY[pol.lower()]
        return pol

    def _make_server(self):
        # Barrier sized to the CLAMPED worker count, and keyed by the
        # driver's per-experiment secret.
        return OptimizationServer(self.num_executors, secret=self.secret)

    def _make_runner_pool(self):
        pool = getattr(self.config, "pool", "thread")
        if pool == "thread":
            return ThreadRunnerPool(self.num_executors)
        from maggy_tpu.core.runner_pool import ProcessRunnerPool, TPURunnerPool

        if pool == "process":
            return ProcessRunnerPool(self.num_executors)
        if pool == "tpu":
            return TPURunnerPool(self.num_executors,
                                 chips_per_trial=self.config.chips_per_trial)
        if pool == "elastic":
            from maggy_tpu.core.runner_pool import (ElasticTPURunnerPool,
                                                    _probe_local_devices)

            total = getattr(self.config, "total_chips", None)
            if total is None:
                total = _probe_local_devices()[0]
            if self._chips_map:
                worst = max(self._chips_map.values())
                if worst > total:
                    raise ValueError(
                        "chips_per_budget asks for {} chips but only {} "
                        "are available to lease".format(worst, total))
            return ElasticTPURunnerPool(
                self.num_executors, total_chips=total,
                chips_per_trial=self.config.chips_per_trial,
                should_stop=lambda: self.experiment_done)
        if pool == "remote":
            from maggy_tpu.core.runner_pool import RemoteRunnerPool

            # Open JOIN admission: agents that dial in get a partition id
            # and this executor config.
            self.server.join_info = {
                "hb_interval": self.hb_interval,
                "exp_dir": self.exp_dir,
                "optimization_key": self.optimization_key,
                "trial_type": "optimization",
                "warm_start": getattr(self.config, "warm_start", True),
            }
            return RemoteRunnerPool(self)
        raise ValueError("Unknown pool type {!r}".format(pool))

    def _executor_fn(self, train_fn):
        return trial_executor_fn(
            server_addr=self.server_addr,
            secret=self.secret_for_clients(),
            hb_interval=self.hb_interval,
            exp_dir=self.exp_dir,
            optimization_key=self.optimization_key,
            train_fn=train_fn,
            trial_type="optimization",
            profile=getattr(self.config, "profile", False),
            ship_prints=getattr(self.config, "ship_prints", False),
            warm_start=getattr(self.config, "warm_start", True),
        )

    def _validate_resume(self) -> None:
        from maggy_tpu.optimizers.bayes.base import BaseAsyncBO

        if isinstance(self.controller, (RandomSearch, BaseAsyncBO)) \
                and self.controller.seed is None:
            raise ValueError(
                "resume=True with {} requires a fixed seed: an unseeded "
                "rerun presamples a different schedule and would re-run "
                "everything on top of the restored trials.".format(
                    type(self.controller).__name__))

    def _restore_previous_run(self) -> None:
        """Experiment resume (beyond the reference, SURVEY.md §5.4): reload
        every finalized trial.json from the experiment dir, rebuild result
        aggregates, and let the controller drop already-executed configs.
        The interrupted run's unfinished trials simply re-run."""
        swept = self.env.sweep_tmp_files(self.exp_dir)
        if swept:
            self._log("resume: swept {} orphaned tmp file(s)".format(swept))
        restored: List[Trial] = []
        for name in sorted(self.env.ls(self.exp_dir)):
            path = "{}/{}/trial.json".format(self.exp_dir, name)
            if not self.env.exists(path):
                continue
            try:
                trial = Trial.from_json(self.env.load(path))
            except (ValueError, KeyError):
                # Torn artifact from a hard kill mid-write (pre-atomic-dump
                # experiments): the trial was in flight, so treating it as
                # unfinished and re-running it is exactly resume semantics.
                self._log("resume: skipping unreadable {} (trial will "
                          "re-run)".format(path))
                continue
            if trial.status == Trial.FINALIZED and trial.final_metric is not None:
                restored.append(trial)
        with self._store_lock:
            self._final_store.extend(restored)
        for trial in restored:
            self._update_result(trial)
        # Carry the interrupted run's early-stop count so the resumed
        # result.json covers all the trials it claims to.
        self.result["early_stopped"] += sum(1 for t in restored if t.early_stop)
        # Crash-only recovery (core/driver/recovery.py): rebuild the
        # IN-FLIGHT half from the journal — committed-but-unfinalized
        # trials re-enter the store with their pre-crash run epochs and
        # holding partitions, the reservation table is re-seeded so
        # still-live runners re-bind (adopted) and dead ones requeue via
        # the ordinary slot-reclaim liveness. Runs BEFORE the controller
        # restore so buffer-backed samplers can drop the in-flight
        # configs too (they are already minted — re-suggesting them
        # would collide in the store).
        from maggy_tpu.core.driver import recovery as recovery_mod

        recovered_stats = recovery_mod.recover_optimization_driver(self)
        with self._store_lock:
            inflight = list(self._trial_store.values())
        self.controller.restore_from_finals(restored, inflight=inflight)
        if self.controller.pruner is not None:
            path = self.exp_dir + "/" + constants.PRUNER_STATE_FILE
            if not self.env.exists(path):
                if restored:
                    raise ValueError(
                        "resume=True with a pruner needs the bracket-state "
                        "checkpoint {}; this experiment predates pruner "
                        "checkpointing.".format(path))
            else:
                self.controller.pruner.load_state_dict(
                    json.loads(self.env.load(path)))
                self.controller.pruner.restore(
                    {t.trial_id for t in restored})
        if recovered_stats is not None:
            self.telemetry.event("experiment", phase="recovered",
                                 finalized=len(restored),
                                 **recovered_stats)
        self._log("resume: restored {} finalized trials from {}{}".format(
            len(restored), self.exp_dir,
            "; recovered {} in-flight trial(s) across {} partition(s) "
            "from the journal".format(
                recovered_stats["inflight"],
                recovered_stats["recovered_partitions"])
            if recovered_stats is not None else ""))

    # ------------------------------------------------------------ callbacks

    def _register_msg_callbacks(self) -> None:
        self.message_callbacks.update(
            METRIC=self._metric_msg_callback,
            BLACK=self._blacklist_msg_callback,
            FINAL=self._final_msg_callback,
            IDLE=self._idle_msg_callback,
            REG=self._register_msg_callback,
            LOST=self._lost_msg_callback,
            GANG_LOST=self._gang_lost_msg_callback,
        )

    def get_trial(self, trial_id):
        with self._store_lock:
            return self._trial_store.get(trial_id)

    def _metric_msg_callback(self, msg) -> None:
        """Append heartbeat metric; early-stop check every es_interval steps
        once es_min trials finalized (reference :331-361). A vectorized
        block's beat carries ``lanes`` — K lane-tagged (trial_id, value,
        step) entries, each applied as its own trial's metric so the
        early-stop rule sees K independent streams."""
        self.add_executor_logs(msg.get("logs"))
        lanes = msg.get("lanes")
        if lanes:
            for beat in lanes:
                self._apply_metric_beat(beat.get("trial_id"),
                                        beat.get("value"), beat.get("step"),
                                        msg.get("partition_id"),
                                        lane=beat.get("lane"))
            return
        self._apply_metric_beat(msg.get("trial_id"), msg.get("value"),
                                msg.get("step"), msg.get("partition_id"))

    def _apply_metric_beat(self, trial_id, value, step, partition_id,
                           lane=None) -> None:
        trial = self.get_trial(trial_id)
        if trial is None or value is None:
            return
        appended = trial.append_metric(value, step)
        if not appended:
            return
        with trial.lock:
            n_steps = len(trial.step_history)
        if n_steps == 1:
            # Scheduling pipeline milestone: time-to-first-signal. The
            # span's running->first_metric delta is the trial's
            # startup/compile cost as the control plane sees it. The lane
            # tag rides only on vectorized beats — scalar journals stay
            # bit-identical to the K=1 path.
            extra = {"lane": lane} if lane is not None else {}
            self.telemetry.trial_event(trial.trial_id, "first_metric",
                                       partition=partition_id, **extra)
        with self._store_lock:
            n_final = len(self._final_store)
        if n_final >= self.es_min and n_steps % self.es_interval == 0:
            with self._store_lock:
                final_snapshot = list(self._final_store)
            stopped = self.earlystop_check.earlystop_check(
                {trial.trial_id: trial}, final_snapshot, self.direction
            )
            for t in stopped:
                # The rule can re-return an already-flagged trial (its
                # heartbeats keep appending metrics until the STOP reply
                # lands) — counting it again inflated early_stopped vs the
                # distinct-trial truth the telemetry journal exposes.
                if t.get_early_stop():
                    continue
                t.set_early_stop()
                self.result["early_stopped"] += 1
                # Opening edge of the early-stop reaction latency: the
                # closing edge is this trial's "finalized".
                self.telemetry.trial_event(t.trial_id, "stop_flagged")

    def _blacklist_msg_callback(self, msg) -> None:
        """Executor died and re-registered: requeue its trial (reference
        :363-367 + `rpc.py:308-326`)."""
        # The pid now names a REPLACEMENT process: the dead one's gauges
        # and merged stats are stale (the new runner re-ships its own).
        self.telemetry.prune_partition(msg.get("partition_id"))
        trial = self.get_trial(msg["trial_id"])
        if trial is not None and self.gang_members(trial.trial_id):
            # A re-registered gang leader cannot simply take its trial
            # back — its mesh slice is gone. Revoke the gang and let the
            # backlog reassemble one.
            self._release_gang(trial.trial_id, why="leader_blacklisted",
                               partition=msg.get("partition_id"))
            trial.reset_run_state()
            with self._store_lock:
                if trial.trial_id not in self._requeue:
                    self._requeue.append(trial.trial_id)
            self.telemetry.trial_event(trial.trial_id, "requeued",
                                       partition=msg["partition_id"],
                                       reason="blacklist")
            return
        if trial is not None:
            # A blacklisted block leader: the non-leader lanes requeue as
            # individual trials; the leader (vmap stamps stripped by the
            # helper) is reassigned below as a plain scalar trial.
            self._requeue_vmap_block(trial.trial_id, msg["partition_id"],
                                     "vmap_block_lost")
            trial.reset_run_state()
            # Explicit requeue edge BEFORE the reassignment: recovery
            # latency (fault -> requeued -> assigned) must be derivable
            # from the journal (the chaos harness asserts on it).
            self.telemetry.trial_event(trial.trial_id, "requeued",
                                       partition=msg["partition_id"],
                                       reason="blacklist")
            # A re-registered slot re-running a FORKED (or preempted)
            # trial resumes like the backlog path would: verify the fork
            # source survived, journal the resume edge with its step.
            self._verify_fork_source(trial, msg["partition_id"])
            self.server.reservations.assign_trial(msg["partition_id"], trial.trial_id)
            self.telemetry.trial_event(trial.trial_id, "assigned",
                                       partition=msg["partition_id"],
                                       requeue="blacklist")
            self._journal_fork_edge(trial, msg["partition_id"])
            with trial.lock:
                resume_step = trial.info_dict.get("resume_step")
            if resume_step is not None:
                self.telemetry.trial_event(trial.trial_id, "resumed",
                                           partition=msg["partition_id"],
                                           from_step=int(resume_step))
            self._log("executor {} restarted; trial {} requeued".format(
                msg["partition_id"], msg["trial_id"]))

    def _requeue_vmap_block(self, leader_id: str, partition_id,
                            reason: str) -> bool:
        """Tear down a dead vectorized block: every live NON-leader lane
        requeues exactly once as an individual scalar trial (the leader
        rides the caller's existing single-trial requeue path, so the
        whole block — leader included — requeues exactly once: chaos
        invariant 16). Lanes that already finalized stay finalized (no
        phantom re-runs); vmap stamps are stripped so the re-dispatch is
        plain scalar. Returns False when ``leader_id`` leads no block."""
        with self._store_lock:
            block = self._vmap_blocks.pop(leader_id, None)
            if block is None:
                return False
            for tid in block["lanes"]:
                self._lane_leader.pop(tid, None)
        for tid in block["lanes"]:
            trial = self.get_trial(tid)
            if trial is None:
                continue
            with trial.lock:
                trial.info_dict.pop("vmap", None)
                trial.info_dict.pop("vmap_block", None)
                done = trial.final_metric is not None or \
                    trial.status == Trial.ERROR
            if done or tid == leader_id:
                continue
            trial.reset_run_state()
            with self._store_lock:
                if tid not in self._requeue:
                    self._requeue.append(tid)
            # Literal reasons so the journalvocab emit scan sees them.
            if reason == "preempted":
                self.telemetry.trial_event(tid, "requeued",
                                           partition=partition_id,
                                           reason="preempted")
            else:
                self.telemetry.trial_event(tid, "requeued",
                                           partition=partition_id,
                                           reason="vmap_block_lost")
        return True

    def vmap_block_lanes(self, leader_id: str) -> List[str]:
        """Lane trial ids of an in-flight block (empty when ``leader_id``
        leads none) — chaos/bench introspection."""
        with self._store_lock:
            block = self._vmap_blocks.get(leader_id)
            return list(block["lanes"]) if block else []

    def _lost_msg_callback(self, msg) -> None:
        """A runner's heartbeats went silent while holding a trial: the
        runner is presumed dead and the trial goes back into the schedule
        for whichever runner asks for work next (elastic recovery beyond
        the reference's same-executor blacklist, SURVEY.md §5.3)."""
        trial = self.get_trial(msg["trial_id"])
        if trial is None:
            return
        # A lost block leader takes all K lanes with it — the non-leader
        # lanes requeue here; the leader requeues below like any scalar.
        self._requeue_vmap_block(trial.trial_id, msg.get("partition_id"),
                                 "vmap_block_lost")
        trial.reset_run_state()
        with self._store_lock:
            if trial.trial_id not in self._requeue:
                self._requeue.append(trial.trial_id)
        # A lost gang LEADER takes its whole gang down: the members'
        # chips go back to the pool and the requeued trial re-assembles
        # a fresh gang (the placer avoids the dead chip).
        self._release_gang(trial.trial_id, why="leader_lost",
                           partition=msg.get("partition_id"))
        self.telemetry.trial_event(trial.trial_id, "lost",
                                   partition=msg.get("partition_id"))
        # The explicit re-queue edge: without it the journal only shows a
        # later "assigned" whose span timestamp is NOT overwritten (spans
        # keep first occurrences), leaving recovery latency underivable.
        self.telemetry.trial_event(trial.trial_id, "requeued",
                                   partition=msg.get("partition_id"),
                                   reason="heartbeat_loss")
        self.result["lost_runners"] = self.result.get("lost_runners", 0) + 1
        self._log("runner {} heartbeat lost; trial {} requeued for reassignment".format(
            msg["partition_id"], msg["trial_id"]))
        # Reap the hung worker so it cannot block the pool's final join: a
        # runner wedged inside a native call (compile stall, stuck device
        # op) never returns on its own. Process pools kill just that one
        # worker; the experiment completes on the survivors and the killed
        # runner surfaces as a survivable pool failure. Exception: a
        # chaos-faked preemption — the runner is HEALTHY by construction
        # and must stay alive to deliver the duplicate FINAL the fault
        # exists to provoke.
        if self.chaos is not None and \
                self.chaos.suppress_reap(msg.get("partition_id")):
            self._log("runner {} loss was a chaos-faked preemption; "
                      "reap suppressed".format(msg["partition_id"]))
            return
        pool = getattr(self, "_active_pool", None)
        if pool is not None and pool.kill_worker(msg["partition_id"]):
            self._log("runner {} killed after heartbeat loss (presumed "
                      "wedged)".format(msg["partition_id"]))
        # The dead runner's live gauges/stats must not outlive it: a
        # reaped partition's last RSS/cadence would sit in the registry
        # (and the /metrics exposition, and the health-engine medians)
        # forever. A respawned runner repopulates on its first beat.
        self.telemetry.prune_partition(msg.get("partition_id"))

    def _chips_for(self, trial: Trial) -> Optional[int]:
        """Chip requirement of a trial under chips_per_budget (None when
        elastic sizing is off)."""
        if self._chips_map is None:
            return None
        budget = trial.params.get("budget", trial.info_dict.get("budget"))
        return int(self._chips_map.get(
            budget, getattr(self.config, "chips_per_trial", 1)))

    def _maybe_migrate(self, partition_id: int, cap: int) -> bool:
        """Resize or retire an idle elastic runner when waiting work needs
        sizes its capacity cannot serve. Returns True if the runner was
        told to leave (caller must not re-arm its idle chain)."""
        with self._store_lock:
            waiting = [self._chips_for(self._trial_store[tid])
                       for tid in self._parked + self._requeue
                       if tid in self._trial_store]
            demand: Dict[int, int] = {}
            for n in waiting:
                if n is not None:
                    demand[n] = demand.get(n, 0) + 1
        if not demand or cap in demand:
            # Nothing waiting, or this runner's size IS in demand (a
            # matching trial will reach it via _pop_parked/_pop_requeue).
            return False
        live = self.server.reservations.capacities()
        with self._store_lock:
            for size in sorted(demand, reverse=True):
                supply = live.get(size, 0) + self._resize_inflight.get(size, 0)
                if demand[size] > supply:
                    self._resize_inflight[size] = \
                        self._resize_inflight.get(size, 0) + 1
                    self._resize_watch[partition_id] = (
                        time.monotonic(), size, self._pool_spawn_stamp(
                            partition_id))
                    self.server.reservations.request_resize(partition_id, size)
                    self._log("idle runner {} (capacity {}) resized toward "
                              "waiting work ({} chips)".format(
                                  partition_id, cap, size))
                    return True
        # Demand covered: this runner's size serves nothing that remains —
        # retire it so its chips free up for the pending spawns. Never
        # retire the LAST live runner UNLESS a resize respawn is already in
        # flight: that respawn re-registers and polls, so the pool is not
        # left pollerless — and NOT retiring would deadlock it (the pending
        # bigger spawn waits on exactly the chips this idle runner holds;
        # observed as TestElasticChipLeasing hanging at the 2+2 -> 4
        # consolidation when the resizing runner was already released).
        with self._store_lock:
            inflight = sum(self._resize_inflight.values())
        if sum(live.values()) <= 1 and inflight == 0:
            return False
        self.server.reservations.request_resize(partition_id, 0)
        self._log("idle runner {} (capacity {}) retired; chips released "
                  "for pending resizes".format(partition_id, cap))
        return True

    def _pool_spawn_stamp(self, partition_id: int):
        pool = getattr(self, "_active_pool", None)
        stamp_of = getattr(pool, "spawn_stamp", None)
        return stamp_of(partition_id) if stamp_of is not None else None

    def periodic_check(self) -> None:
        """Server event-loop hook: bound resize-respawn registration.

        A respawn that hangs BEFORE registering (in backend init, while
        another process still holds its chips) never heartbeats, so
        heartbeat-loss detection cannot see it — and with the last-runner
        retire rule the pool may have nobody else polling. Expired respawns are killed via the pool,
        which turns a silent infinite wait into a loud runner failure the
        driver surfaces. An expired entry whose process was still QUEUED
        for chips (kill_worker finds nothing) merely loses its in-flight
        credit — worst case another idle runner re-chases the demand."""
        pool = getattr(self, "_active_pool", None)
        stamp_of = getattr(pool, "spawn_stamp", None)
        now = time.monotonic()
        expired = []
        with self._store_lock:
            for pid, (t0, size, s0) in list(self._resize_watch.items()):
                if now - t0 <= constants.RESIZE_RESPAWN_TIMEOUT_S:
                    continue
                if stamp_of is None:
                    # No pool visibility: fall back to the request clock.
                    del self._resize_watch[pid]
                    if self._resize_inflight.get(size, 0) > 0:
                        self._resize_inflight[size] -= 1
                    expired.append((pid, size, "timed out (no pool "
                                               "visibility); killing it"))
                    continue
                stamp = stamp_of(pid)
                # Three healthy states re-arm the watch (expiring any of
                # them would drop an in-flight credit a later REGISTER
                # then double-decrements):
                # - stamp is None AND the pool still holds a pending
                #   respawn: QUEUED for chips — e.g. waiting behind
                #   another runner's minutes-long trial. stamp None
                #   WITHOUT a pending respawn means the process died (or
                #   crashed at spawn) before registering — nothing will
                #   ever register, so re-arming would leak the in-flight
                #   credit forever (and the stale credit would keep
                #   satisfying the last-runner-retire exemption);
                # - stamp == s0: the PRE-resize process is still winding
                #   down (it must not be killed for being old — its age
                #   predates the request by construction);
                # - a NEW process (stamp != s0) younger than the bound.
                # Only a post-request process older than the bound is a
                # wedged respawn.
                if stamp is None:
                    pending_of = getattr(pool, "pending_respawn", None)
                    if pending_of is None or pending_of(pid):
                        self._resize_watch[pid] = (now, size, s0)
                        continue
                    del self._resize_watch[pid]
                    if self._resize_inflight.get(size, 0) > 0:
                        self._resize_inflight[size] -= 1
                    expired.append((pid, size, "died before registering"))
                    continue
                if stamp == s0 or \
                        now - stamp <= constants.RESIZE_RESPAWN_TIMEOUT_S:
                    self._resize_watch[pid] = (now, size, s0)
                    continue
                del self._resize_watch[pid]
                if self._resize_inflight.get(size, 0) > 0:
                    self._resize_inflight[size] -= 1
                expired.append((pid, size, "spawned but did not re-register "
                                           "within {:.0f}s; killing it".format(
                                               constants.RESIZE_RESPAWN_TIMEOUT_S)))
        for pid, size, why in expired:
            self._log("resize respawn for runner {} ({} chips) {}".format(
                pid, size, why))
            if pool is not None:
                pool.kill_worker(pid)
        self._check_gang_members()

    def _pop_parked(self, capacity: Optional[int]) -> Optional[Trial]:
        """First parked trial this runner's capacity can serve (None
        capacity = non-elastic runner, matches anything)."""
        with self._store_lock:
            for i, tid in enumerate(self._parked):
                trial = self._trial_store.get(tid)
                if trial is None:
                    continue
                need = self._chips_for(trial)
                if capacity is None or need is None or need == capacity:
                    del self._parked[i]
                    return trial
        return None

    def _pop_requeue(self, capacity: Optional[int] = None) -> Optional[Trial]:
        """Next orphaned trial this runner can serve. Elastic pools match
        chip requirements here too — a budget-9 trial orphaned by a dead
        2-chip runner must NOT land on a 1-chip runner. Gang trials
        (N>1 chips) are skipped-but-RETAINED: a single undersized runner
        must never be served a trial whose mesh needs N chips — the
        backlog entry waits for gang assembly (_service_gangs) and is
        served intact to the whole gang, never split."""
        with self._store_lock:
            for i, tid in enumerate(list(self._requeue)):
                trial = self._trial_store.get(tid)
                if trial is None:
                    self._requeue.remove(tid)
                    continue
                spec = self._gang_spec_for(trial)
                if spec is not None and spec.chips > 1:
                    continue
                need = self._chips_for(trial)
                if capacity is None or need is None or need == capacity:
                    self._requeue.remove(tid)
                    return trial
        return None

    # ------------------------------------------------- gang scheduling

    def _gang_spec_for(self, trial: Trial) -> Optional["gang_mod.GangSpec"]:
        """The trial's declared gang shape (None = plain 1-runner
        trial): a sampled Searchspace GANG param wins, else the
        chips_per_budget entry for its budget."""
        if not self._gang_mode:
            return None
        g = trial.params.get(self._gang_param) \
            if self._gang_param is not None else None
        if g:
            return gang_mod.GangSpec.from_value(g)
        if self._gang_map:
            budget = trial.params.get("budget",
                                      trial.info_dict.get("budget"))
            v = self._gang_map.get(budget)
            if v is not None:
                return gang_mod.GangSpec.from_value(v)
        return None

    def _chip_of(self, partition_id: int) -> int:
        """The runner's chip/topology index. Thread pools: runner ≈
        chip, identity. Fleet mode: the fleet runner index this
        partition is currently leased to (FleetLeasedPool.chip_of), so
        contiguity means contiguous FLEET runners."""
        pool = getattr(self, "_active_pool", None)
        chip_of = getattr(pool, "chip_of", None)
        if chip_of is not None:
            chip = chip_of(partition_id)
            if chip is not None:
                return int(chip)
        return int(partition_id)

    # locked-by: _store_lock
    def _gang_demand_locked(self) -> List[str]:
        """Gang trials awaiting assembly, requeued (revoked/lost) ones
        first — store lock held."""
        demand = []
        for tid in self._requeue + self._gang_wait:
            if tid in demand or tid not in self._trial_store:
                continue
            trial = self._trial_store[tid]
            spec = self._gang_spec_for(trial)
            if spec is not None and spec.chips > 1 \
                    and tid not in self._gangs:
                demand.append(tid)
        return demand

    def _service_gangs_locked(self, partition_id: int) -> bool:
        """Reserve blocks for waiting gang trials, conscript this (and
        every other currently-free) runner whose chip falls inside one,
        and assemble any gang whose block became fully held. Returns
        True when the asking runner was conscripted — the caller must
        hand it no other work. Sched lock held."""
        if not self._gang_mode:
            return False
        res = self.server.reservations
        with self._store_lock:
            demand = self._gang_demand_locked()
            running = bool(self._gangs)
        self._sync_fleet_gang(bool(demand) or running)
        if not demand:
            return False
        bound = self.server.hb_loss_timeout
        free = [p for p in res.free_pids()
                if bound is None or not res.is_silent(p, bound)]
        chip_by_pid = {p: self._chip_of(p) for p in free}
        free_chips = set(chip_by_pid.values())
        # Chips whose runners can never come back (silent past the loss
        # bound, or released): a reserved block containing one would
        # park its gang forever.
        dead_chips = set()
        for pid, rec in res.all().items():
            if rec.get("released") or (
                    bound is not None and res.is_silent(pid, bound)):
                dead_chips.add(self._chip_of(pid))
        conscripted = False
        for tid in demand:
            trial = self.get_trial(tid)
            if trial is None:
                continue
            spec = self._gang_spec_for(trial)
            # Sticky reservations must not outlive their own viability: a
            # block containing a chip that DIED while busy (so it was
            # never gang-held and _check_gang_members never saw it) can
            # never fully free — release and re-plan around the dead
            # chip, or the gang parks forever.
            existing = self._placer.block_of(tid)
            if existing is not None and dead_chips & set(existing):
                self._release_gang(tid, why="block_chip_dead")
            block = self._placer.reserve(tid, spec.chips, free_chips,
                                         avoid=dead_chips - free_chips)
            if block is None:
                continue
            for p, c in list(chip_by_pid.items()):
                if c in block:
                    res.hold_for_gang(p, tid)
                    if p == partition_id:
                        conscripted = True
                    del chip_by_pid[p]
                    free_chips.discard(c)
            members = res.gang_members(tid)
            if len(members) >= spec.chips:
                self._assemble_gang_locked(tid, trial, spec, block,
                                           members)
        return conscripted

    def _assemble_gang_locked(self, tid: str, trial: Trial,
                              spec: "gang_mod.GangSpec", block: List[int],
                              members: List[int]) -> None:
        """All member chips held: designate the lowest-chip member as
        LEADER, stamp the gang geometry into the trial's info (it ships
        with the TRIAL reply -> ctx.gang), and assign the trial to the
        leader. Sched lock held."""
        leader = min(members, key=self._chip_of)
        info = {"chips": sorted(int(c) for c in block),
                "members": sorted(int(m) for m in members),
                "leader": int(leader), "mesh": dict(spec.mesh),
                "strategy": spec.strategy}
        # REMOTE gang: members registered from other processes (their
        # REG carried an advertised host_port — fleet agents do, thread
        # runners never) need a driver-coordinated jax.distributed
        # rendezvous instead of the runner≈chip-in-one-process
        # assumption. Stamped only when EVERY member is remote: each
        # agent is one OS process, so num_processes = len(members) and
        # every process runs the SPMD program. A MIXED thread+agent gang
        # must not be stamped — the co-process thread members would be
        # counted as distinct processes that can never all initialize
        # (one latch per process), hanging the world forever; it runs
        # the in-process path instead. Process ids in chip order, leader
        # = process 0, the leader's advertised address is the
        # coordinator.
        res = self.server.reservations
        coord_by_member = {
            m: (res.get(m) or {}).get("host_port") for m in members}
        if all(coord_by_member.get(m) for m in members):
            ordered = sorted(members, key=self._chip_of)
            info["rendezvous"] = {
                "coordinator": coord_by_member[ordered[0]],
                "num_processes": len(ordered),
                "process_ids": {str(int(m)): i
                                for i, m in enumerate(ordered)},
            }
        with trial.lock:
            trial.info_dict["gang"] = info
        with self._store_lock:
            self._gangs[tid] = dict(info)
            if tid in self._gang_wait:
                self._gang_wait.remove(tid)
            if tid in self._requeue:
                self._requeue.remove(tid)
        trial.set_status(Trial.SCHEDULED)
        self.server.reservations.assign_trial(leader, tid)
        self.telemetry.trial_event(tid, "gang_assembled", partition=leader,
                                   members=info["members"],
                                   chips=info["chips"],
                                   strategy=spec.strategy)
        self.telemetry.trial_event(tid, "assigned", partition=leader)
        self._log("gang assembled for trial {}: chips {} (leader runner "
                  "{}, strategy {})".format(tid, info["chips"], leader,
                                            spec.strategy))

    def _release_gang(self, tid: str, why: str,
                      partition: Optional[int] = None) -> None:
        """Return a gang's chips to the pool: drop the member holds,
        free the placer block, and journal the span edge. Idempotent —
        callable from every terminal path (FINAL, error, preemption,
        revocation, blacklist)."""
        with self._store_lock:
            info = self._gangs.pop(tid, None)
        freed = self.server.reservations.release_gang(tid)
        if self._placer is not None:
            self._placer.release(tid, reason=why)
        if info is None and not freed:
            return
        self.telemetry.trial_event(
            tid, "gang_released", partition=partition,
            members=(info or {}).get("members", freed), why=why)

    def _sync_fleet_gang(self, active: bool) -> None:
        """Keep the fleet-level contiguous-block reservation in step
        with gang demand: while gang trials wait or run, the fleet
        scheduler must route a contiguous runner block to THIS
        experiment (and protect it from preemption); when the last gang
        ends, the block goes back to fair share."""
        binding = getattr(self.config, "fleet", None)
        if binding is None or not hasattr(binding, "request_gang"):
            return
        with self._store_lock:
            was = self._fleet_gang_active
            self._fleet_gang_active = active
        if active and not was:
            got = binding.request_gang(
                gang_mod.config_max_gang_chips(self.config))
            if got is None:
                # No disjoint window right now (other experiments hold
                # blocks): stay un-latched so every subsequent demand
                # tick retries instead of running gangs without their
                # preemption-shielded block forever.
                with self._store_lock:
                    self._fleet_gang_active = False
        elif was and not active:
            binding.release_gang()

    def gang_members(self, trial_id: str) -> List[int]:
        """Members of an assembled gang (chaos's kill_gang_member picks
        its victim here); empty when the trial has no assembled gang."""
        with self._store_lock:
            info = self._gangs.get(trial_id)
            return list(info["members"]) if info else []

    def gang_info(self, trial_id: str) -> Optional[Dict[str, Any]]:
        """Snapshot of an assembled gang's geometry (None if not
        assembled) — the server's member-serve path reads the
        ``rendezvous`` block through this."""
        with self._store_lock:
            info = self._gangs.get(trial_id)
            return dict(info) if info else None

    def _check_gang_members(self) -> None:
        """Server event-loop scan: a silent member of an assembled gang
        means the gang's mesh is broken — revoke the WHOLE gang exactly
        once (the ``revoking`` flag dedupes rescans) via the worker
        thread. A silent member of a still-assembling gang just loses
        its hold so assembly re-plans around the dead chip."""
        bound = self.server.hb_loss_timeout
        if not self._gang_mode or bound is None:
            return
        res = self.server.reservations
        with self._store_lock:
            assembled = {tid: dict(info)
                         for tid, info in self._gangs.items()
                         if not info.get("revoking")}
        for tid, info in assembled.items():
            silent = [m for m in info["members"]
                      if res.is_silent(m, bound)]
            if not silent:
                continue
            with self._store_lock:
                live = self._gangs.get(tid)
                if live is None or live.get("revoking"):
                    continue
                live["revoking"] = True
            self.enqueue({"type": "GANG_LOST", "trial_id": tid,
                          "partition_id": silent[0]})
        # Pre-assembly holds on dead runners: release them so the
        # placer re-plans; the re-reserve path avoids dead chips.
        with self._store_lock:
            waiting = [tid for tid in self._gang_demand_locked()]
        for tid in waiting:
            for m in res.gang_members(tid):
                if res.is_silent(m, bound):
                    self._release_gang(tid, why="member_dead_assembling")
                    break

    def _gang_lost_msg_callback(self, msg) -> None:
        """Worker-thread half of gang revocation: requeue the trial
        EXACTLY once (reason ``gang_member_lost``), return the healthy
        members to the pool, and abort the (possibly still computing)
        leader through a reservation-level preempt STOP whose ack the
        idempotent preemption path drops."""
        tid = msg["trial_id"]
        pid = msg.get("partition_id")
        with self._sched_lock:
            with self._store_lock:
                info = self._gangs.get(tid)
            trial = self.get_trial(tid)
            if info is None or trial is None:
                return
            leader = info.get("leader")
            self._release_gang(tid, why="member_lost", partition=pid)
            self.server.reservations.clear_trial_if(leader, tid)
            trial.reset_run_state()
            with self._store_lock:
                if tid not in self._requeue:
                    self._requeue.append(tid)
            self.result["gang_revocations"] = \
                self.result.get("gang_revocations", 0) + 1
            self.telemetry.trial_event(tid, "requeued", partition=pid,
                                       reason="gang_member_lost")
            self._log("gang member (runner {}) lost for trial {}; gang "
                      "lease revoked, trial requeued".format(pid, tid))
            if leader is not None and leader != pid:
                # The leader is healthy but its mesh is gone: its next
                # heartbeat draws STOP(preempt); the ack finds the trial
                # already waiting and is dropped.
                self.server.reservations.request_stop(leader, tid)
        # The dead MEMBER's gauges must not linger (the healthy members
        # keep reporting their own).
        self.telemetry.prune_partition(pid)

    # ------------------------------------------- pipelined hand-off (prefetch)

    def _suggester_loop(self) -> None:
        """Dedicated suggester thread: keeps up to one pre-materialized
        suggestion per live runner, so an expensive suggest() (Bayes GP
        fit + acquisition) overlaps with device work instead of stalling
        whichever runner frees up next. Woken by REG/FINAL/dispatch; the
        idle tick bounds the wake-up latency when a signal is missed.
        A controller exception here is the same contract violation it
        would be on the worker thread: surface it and end the experiment
        rather than silently losing the pipeline."""
        while not self.worker_done and not self.experiment_done:
            try:
                refilled = self._refill_prefetch()
            except Exception as exc:  # noqa: BLE001 - mirror the worker contract
                # Both flags before the (slow, I/O-bound) traceback log:
                # anyone who observes the exception must already see the
                # experiment marked done.
                self.exception = exc
                # unguarded-ok: monotonic completion latch, polled lock-free by design
                self.experiment_done = True
                self._log("suggester error: {}".format(
                    traceback.format_exc()))
                return
            if not refilled:
                self._suggest_wake.wait(constants.DRIVER_IDLE_REQUEUE_TICK_S)
                self._suggest_wake.clear()

    def _prefetch_capacity(self) -> int:
        """Queue bound: one suggestion per live (registered, unreleased)
        runner, never more than the executor clamp (which already honors
        the controller's max_concurrency). Under vectorized trials the
        bound scales by K — a runner consumes up to K suggestions per
        hand-off, and a one-deep queue would starve block assembly down
        to scalar dispatches."""
        return min(self.num_executors,
                   self.server.reservations.live_count()) * self._vmap_lanes

    def _refill_prefetch(self) -> bool:
        """One refill attempt; True when a suggestion was materialized
        (the caller loops immediately to top the queue up)."""
        with self._sched_lock:
            if self.experiment_done or \
                    len(self._prefetched) >= self._prefetch_capacity():
                return False
            suggestion = self._timed_suggest(source="prefetch")
            if suggestion in (None, "IDLE"):
                return False
            self._admit_prefetched(suggestion)
            return True

    def _timed_suggest(self, source: str):
        """controller.suggest() with latency telemetry (sched lock held).
        Journals an ``ev: "suggest"`` event + the ``suggested`` span edge
        for every materialized trial; IDLE/None polls only feed the
        histogram."""
        t0 = time.monotonic()
        suggestion = self.controller.suggest()
        ms = (time.monotonic() - t0) * 1e3
        self.telemetry.observe_ms("controller.suggest_ms", ms)
        if suggestion in (None, "IDLE"):
            return suggestion
        self.telemetry.event("suggest", ms=round(ms, 3), source=source,
                             trial=suggestion.trial_id)
        self.telemetry.trial_event(suggestion.trial_id, "suggested")
        return suggestion

    # locked-by: _sched_lock
    def _admit_prefetched(self, trial: Trial) -> None:
        """Commit a prefetched suggestion (sched lock held): it enters the
        trial store NOW, so controller capacity checks — BO busy-location
        imputation, ASHA's in-flight rung-0 count — see it as in flight
        and cannot overshoot the schedule. The span's ``queued`` edge
        waits for dispatch, so chaos invariant 1 (every queued trial
        finalizes) is untouched by a later invalidation."""
        with self._store_lock:
            clash = self._trial_store.get(trial.trial_id)
            self._trial_store[trial.trial_id] = trial
        if clash is not None and clash is not trial:
            self._log("WARNING: controller re-issued trial id {} while it "
                      "was still in flight; the schedule may lose an "
                      "entry".format(trial.trial_id))
        self._prefetched.append(trial)
        self._prefetch_versions[trial.trial_id] = getattr(
            self.controller, "schedule_version", 0)

    # locked-by: _sched_lock
    def _invalidate_stale_prefetch(self) -> None:
        """Drop prefetched suggestions minted before the controller's
        current schedule_version (sched lock held): a FINAL that changed
        the schedule — ASHA promotion available, pruner stop, experiment
        done — must not be beaten to the runner by a pre-decision sample.
        Dropped trials leave the store and go back through
        controller.recycle(), so buffer-backed schedules lose nothing."""
        version = getattr(self.controller, "schedule_version", 0)
        stale = [t for t in self._prefetched
                 if self._prefetch_versions.get(t.trial_id) != version]
        if not stale:
            return
        for trial in stale:
            self._prefetched.remove(trial)
            self._prefetch_versions.pop(trial.trial_id, None)
            with self._store_lock:
                self._trial_store.pop(trial.trial_id, None)
            self.controller.recycle(trial)
        self.telemetry.event("prefetch_invalidated", n=len(stale),
                             version=version,
                             trials=[t.trial_id for t in stale])
        self.telemetry.metrics.counter("prefetch.invalidated").inc(len(stale))
        self._suggest_wake.set()

    # locked-by: _sched_lock
    def _ingest_final_report(self, last_trial: Trial) -> None:
        """The FINAL-path half of the split controller contract (sched
        lock held): rung/pruner/member bookkeeping, then stale-prefetch
        invalidation against the post-report schedule version."""
        self.controller.report(last_trial)
        self._invalidate_stale_prefetch()

    # locked-by: _sched_lock
    def _next_suggestion(self):
        """Controller-sourced candidate for a hand-off (sched lock held):
        the oldest still-valid prefetched suggestion when available, else
        a live suggest() — unless this is the RPC fast path and the
        controller is expensive (a GP fit must never run on the event
        loop; the reply falls back to OK and the suggester refills while
        the freed runner GET-polls)."""
        if self._prefetched:
            trial = self._prefetched.pop(0)
            self._prefetch_versions.pop(trial.trial_id, None)
            self._suggest_wake.set()  # a queue slot opened
            return trial
        if self._inline_depth > 0 and \
                getattr(self.controller, "SUGGEST_COST", "cheap") == "expensive":
            self._suggest_wake.set()
            return "IDLE"
        return self._timed_suggest(source="inline")

    def process_final_inline(self, msg) -> bool:
        """RPC-thread FINAL fast path (config.prefetch): finalize the
        trial, report it to the controller, invalidate stale prefetches,
        and decide the partition's next assignment — all before the FINAL
        reply is written, so the reply can carry the hand-off (the server
        serves the resulting assignment inline; see
        OptimizationServer._final). Returns True when fully processed
        (the caller must NOT also enqueue the message); False falls back
        to the worker path. The bounded lock wait is the event-loop
        protection: the lock is only contended while the suggester is
        mid-model-fit, and stalling every runner's heartbeats behind a GP
        fit is the exact pathology this pipeline removes. Remote envs
        (slow dump()) are excluded wholesale — persisting trial.json on
        the event loop would stall every heartbeat per FINAL."""
        if not self._inline_final_enabled or self.worker_done:
            return False
        if not self._sched_lock.acquire(
                timeout=constants.PREFETCH_FINAL_LOCK_TIMEOUT_S):
            self.telemetry.metrics.counter("prefetch.lock_fallbacks").inc()
            # This hand-off really fell back to GET polling: it must count
            # as a miss, or a Bayes sweep's hit rate would exclude exactly
            # the fit-contended FINALs misses are most common on.
            self.telemetry.trial_event(msg.get("trial_id"), "prefetch_miss",
                                       once=True,
                                       partition=int(msg["partition_id"]))
            return False
        try:
            self._inline_depth += 1
            try:
                self._final_msg_callback(msg)
            finally:
                self._inline_depth -= 1
            return True
        except Exception as exc:  # noqa: BLE001 - mirror the worker contract
            self.exception = exc
            self._log("FINAL fast-path error: {}".format(
                traceback.format_exc()))
            self.experiment_done = True
            return True
        finally:
            self._sched_lock.release()

    def _final_msg_callback(self, msg) -> None:
        """Finalize trial, persist artifacts, hand the executor new work
        (reference :369-417). Runs under the schedule lock in full: the
        trial-store pop below must never interleave with a suggester-held
        suggest() iterating the same dict (BO busy locations, ASHA
        in-flight counts) — on the worker fallback path that overlap is
        the COMMON case, since the fallback fires exactly because the
        suggester is mid-fit. Reentrant from process_final_inline."""
        with self._sched_lock:
            self._final_msg_locked(msg)

    def _final_msg_locked(self, msg) -> None:
        self.add_executor_logs(msg.get("logs"))
        # Any FINAL from this partition for this trial means the
        # computation a gang-revocation STOP (Reservations.request_stop)
        # was armed to abort has ended — consume it, or a stop orphaned
        # by a raced FINAL (dropped as stale below) would persist and
        # abort a healthy later re-run of the same trial on this runner.
        self.server.reservations.pop_stop(msg["partition_id"],
                                          msg.get("trial_id"))
        trial = self.get_trial(msg.get("trial_id"))
        if msg.get("preempted"):
            # A preemption ack is NOT a finalize: the trial goes back into
            # the schedule (resuming from its checkpoint step when it has
            # one), and the controller never sees a report for it.
            self._preempted_final(msg, trial)
            return
        if trial is None:
            # Duplicate FINAL (e.g. a falsely-declared-lost runner finishing a
            # trial another runner re-ran, or a retried FINAL whose first
            # delivery's reply was lost). The result is already recorded,
            # but the reporting runner still needs its next assignment or it
            # would poll GET empty-handed forever — UNLESS it already holds
            # an undelivered one (the retry raced the hand-off): assigning
            # again would orphan that trial in the store and hang the
            # experiment's in-flight wait.
            if self.server.reservations.get_assigned_trial(
                    msg["partition_id"]) is None:
                self._assign_next(msg["partition_id"], None)
            return
        msg_epoch = msg.get("epoch")
        with trial.lock:
            stale_epoch = msg_epoch is not None and \
                int(msg_epoch) != trial.run_epoch
        with self._store_lock:
            waiting = trial.trial_id in self._requeue
        if stale_epoch or (waiting and self.server.reservations
                           .get_assigned_trial(msg["partition_id"])
                           != trial.trial_id):
            # The trial was revoked/requeued out from under this runner
            # (gang member loss; a false loss detection) while its FINAL
            # was in flight: the requeue is authoritative — drop the
            # report and let the trial re-run. (A broken gang mesh could
            # not have produced a healthy FINAL on real hardware; the
            # CPU proxy would happily finalize it and the journal would
            # then show a requeue with no re-assembly.) The epoch check
            # catches what requeue-membership cannot: the dead run's
            # FINAL arriving AFTER the trial was re-dispatched — even
            # onto this same partition (a revoked gang reassembling onto
            # its old leader).
            self._log("dropping stale FINAL for requeued trial {} from "
                      "runner {}".format(trial.trial_id,
                                         msg["partition_id"]))
            if self.server.reservations.get_assigned_trial(
                    msg["partition_id"]) is None:
                self._assign_next(msg["partition_id"], None)
            return
        with trial.lock:
            if msg.get("error"):
                trial.status = Trial.ERROR
                trial.final_metric = None
            else:
                trial.status = Trial.FINALIZED
                trial.final_metric = float(msg["value"])
            trial.duration = time.time() - trial.start if trial.start else None
            was_error = trial.status == Trial.ERROR
            was_early_stop = trial.early_stop
        # "finalized": the hand-off gap's opening edge and the early-stop
        # reaction's closing edge — journaled BEFORE _assign_next so the
        # journal's event order matches the control flow it measures. Lane
        # FINALs tag their lane/block so per-lane spans close attributably
        # (and the goodput ledger can split block chip-time by lane).
        extra = {}
        if msg.get("block") is not None:
            extra = {"lane": msg.get("lane"), "block": msg.get("block")}
        self.telemetry.trial_event(trial.trial_id, "finalized",
                                   partition=msg.get("partition_id"),
                                   early_stop=was_early_stop,
                                   error=was_error, **extra)
        with self._store_lock:
            self._trial_store.pop(trial.trial_id, None)
            self._final_store.append(trial)
        # A finalized gang trial frees its whole mesh slice: members
        # return to the pool before the artifact dump below, so their
        # idle ticks can pick up work while the leader persists.
        self._release_gang(trial.trial_id,
                           why="error" if was_error else "finalized",
                           partition=msg.get("partition_id"))
        if trial.status == Trial.ERROR and self.controller.pruner is not None:
            report = getattr(self.controller.pruner, "report_failure", None)
            if report:
                report(trial.trial_id)
                self._checkpoint_pruner()
        self._update_result(trial)
        # Persist BEFORE the hand-off: assignment of the last trial flips
        # experiment_done and releases pool.run(), so a dump placed after it
        # could still be in flight (or fail unobserved) when lagom returns.
        self.env.dump(trial.to_json(),
                      "{}/{}/trial.json".format(self.exp_dir, trial.trial_id))
        if msg.get("block") is not None:
            leader_id = msg["block"]
            if not msg.get("last"):
                # Mid-block lane FINAL (early-stopped/masked lane, or any
                # lane before the closing one): the partition still holds
                # the block — report to the controller NOW (the optimizer
                # reacts at masking time, and stale prefetches drop) but
                # hand off nothing.
                with self._store_lock:
                    self._lane_leader.pop(trial.trial_id, None)
                if self._prefetch_enabled:
                    self._ingest_final_report(trial)
                else:
                    # Blocks only assemble from the prefetch queue, but a
                    # lane FINAL racing a config flip must not crash here.
                    report = getattr(self.controller, "report", None)
                    if report is not None:
                        report(trial)
                self._sweep_fork_gc()
                return
            # Closing lane: the block is done — drop its bookkeeping and
            # run the normal hand-off (report + piggybacked next block).
            with self._store_lock:
                block = self._vmap_blocks.pop(leader_id, None)
                for tid in (block or {}).get("lanes", ()):
                    self._lane_leader.pop(tid, None)
        self._assign_next(msg["partition_id"], trial)
        # AFTER the hand-off (the freed runner never waits on disk ops):
        # retire parent checkpoints this FINAL made unforkable.
        self._sweep_fork_gc()

    def _preempted_final(self, msg, trial: Optional[Trial]) -> None:
        """Requeue a preempted trial (sched lock held). Idempotent under
        at-least-once delivery: only a trial whose preempt flag is still
        armed is processed — a retried ack (severed reply) arrives after
        reset_run_state cleared it and is ignored. ``step`` is the
        runner's last checkpoint step: stored on the trial so the TRIAL
        reply that re-dispatches it ships ``resume_step`` to the next
        runner (ctx.resume_step); None = it never checkpointed and simply
        re-runs from scratch."""
        pid = msg.get("partition_id")
        if trial is None:
            return
        if not trial.get_preempt():
            # No armed preempt flag: either a RETRIED ack whose first
            # delivery already requeued the trial, or the evict race —
            # the worker assigned this trial AFTER request_evict but
            # before any flagging, so the GET path's synthetic preempted
            # FINAL is the trial's ONLY way back into the schedule.
            # Discriminate by where the trial is now: waiting or
            # re-dispatched or terminal => retry, drop it; otherwise it
            # is orphaned and must requeue (from scratch — it never ran
            # on the evicted runner).
            with self._store_lock:
                waiting = trial.trial_id in self._requeue \
                    or trial.trial_id in self._parked
            if waiting:
                return
            if any(rec.get("trial_id") == trial.trial_id
                   for rec in self.server.reservations.all().values()):
                return
            with trial.lock:
                if trial.final_metric is not None \
                        or trial.status == Trial.ERROR:
                    return
            msg = {**msg, "step": None}
        step = msg.get("step")
        # A preempted block leader takes its lanes with it: non-leader
        # lanes requeue here as scalar trials; the leader follows the
        # normal preemption path below.
        self._requeue_vmap_block(trial.trial_id, pid, "preempted")
        trial.reset_run_state()
        # A preempted gang trial releases its slice like any other
        # terminal path; reassembly happens from the requeue backlog.
        self._release_gang(trial.trial_id, why="preempted", partition=pid)
        with trial.lock:
            if step is not None:
                trial.info_dict["resume_step"] = int(step)
            else:
                fork = trial.info_dict.get("forked_from")
                if fork and fork.get("step") is not None:
                    # A FORKED trial preempted before it ever
                    # checkpointed (or even staged) still has its fork
                    # point: the re-dispatch resumes there, not from
                    # scratch.
                    trial.info_dict["resume_step"] = int(fork["step"])
                else:
                    trial.info_dict.pop("resume_step", None)
        with self._store_lock:
            if trial.trial_id not in self._requeue:
                self._requeue.append(trial.trial_id)
        self.result["preemptions"] = self.result.get("preemptions", 0) + 1
        self.telemetry.trial_event(trial.trial_id, "preempted",
                                   partition=pid, step=step,
                                   checkpointed=step is not None)
        # The explicit re-queue edge, like LOST/BLACK paths journal: the
        # chaos harness derives fault->requeue recovery from it.
        self.telemetry.trial_event(trial.trial_id, "requeued",
                                   partition=pid, reason="preempted")
        self._log("trial {} preempted on runner {} ({}); requeued".format(
            trial.trial_id, pid,
            "checkpoint step {}".format(step) if step is not None
            else "no checkpoint"))
        if not self.server.reservations.evict_requested(pid):
            # The runner stays with this experiment (chaos preemption, or
            # rebalancing without eviction): hand it work now — possibly
            # the preempted trial itself, which IS the resume path.
            self._assign_next_locked(pid, None)

    def preempt_partition(self, partition_id: int,
                          evict: bool = False) -> Optional[str]:
        """Gracefully preempt whatever ``partition_id`` is running:
        arm the trial's preempt + early-stop flags so the next heartbeat
        draws STOP(preempt) and the runner acks with a preempted FINAL
        carrying its checkpoint step. ``evict=True`` (fleet) additionally
        releases the runner from this experiment once the ack (or, when
        idle, its next GET) lands. Returns the preempted trial id, or
        None when the partition held nothing (eviction alone applies).
        Callable from any thread — touches only trial/reservation locks."""
        res = self.server.reservations
        if evict:
            res.request_evict(partition_id)
        trial_id = res.get_assigned_trial(partition_id)
        trial = self.get_trial(trial_id) if trial_id else None
        if trial is None:
            return None
        trial.set_preempt()
        trial.set_early_stop()
        self.telemetry.trial_event(trial.trial_id, "preempt_requested",
                                   partition=partition_id, evict=evict)
        return trial.trial_id

    def _register_msg_callback(self, msg) -> None:
        # A respawned elastic runner arriving at its new size satisfies one
        # outstanding resize request toward that capacity.
        cap = msg.get("capacity")
        if cap is not None:
            with self._store_lock:
                if self._resize_inflight.get(cap, 0) > 0:
                    self._resize_inflight[cap] -= 1
                self._resize_watch.pop(msg["partition_id"], None)
        self._assign_next(msg["partition_id"], None)

    def _idle_msg_callback(self, msg) -> None:
        """Re-poll the controller after a short tick (reference :419-439)."""
        self._assign_next(msg["partition_id"], msg.get("last_trial"))

    def _checkpoint_pruner(self) -> None:
        """Persist multi-fidelity bracket state (a few KB of JSON) so an
        interrupted Hyperband schedule resumes without re-running finalized
        rungs. Runs on the driver worker thread only."""
        pruner = self.controller.pruner
        if pruner is None or not hasattr(pruner, "state_dict"):
            return
        try:
            self.env.dump(json.dumps(pruner.state_dict()),
                          self.exp_dir + "/" + constants.PRUNER_STATE_FILE)
        except Exception:  # noqa: BLE001 - checkpointing must not kill a run
            pass

    def _rearm_idle(self, partition_id: int) -> None:
        msg = {"type": "IDLE", "partition_id": partition_id, "last_trial": None}
        timer = threading.Timer(constants.DRIVER_IDLE_REQUEUE_TICK_S,
                                self.enqueue, args=(msg,))
        timer.daemon = True
        timer.start()

    def _partition_state(self, partition_id: int) -> str:
        """'live', 'silent' (heartbeats stopped past the loss bound), or
        'released' (saw GSTOP — will never ask for work again). A
        dead-while-idle runner otherwise keeps winning work through its
        self-perpetuating IDLE timer chain — a requeued trial handed to it
        costs a full extra LOST cycle."""
        rec = self.server.reservations.get(partition_id)
        if rec is None:
            return "live"  # REG still in flight — not evidence of death
        if rec.get("released") or rec.get("evict"):
            # Evicted (fleet preemption): the runner is leaving this
            # experiment — fresh work must be rerouted, not assigned to it.
            return "released"
        bound = self.server.hb_loss_timeout
        if bound is not None and \
                self.server.reservations.is_silent(partition_id, bound):
            return "silent"
        return "live"

    def _assign_next(self, partition_id: int, last_trial: Optional[Trial]) -> None:
        # The controller, not a trial count, decides when the experiment is
        # over: multi-fidelity schedules (ASHA promotions, Hyperband brackets)
        # legitimately run more trials than `num_trials` rung-0 samples.
        if self.experiment_done:
            return
        with self._sched_lock:
            self._assign_next_locked(partition_id, last_trial)
        if self._prefetch_enabled:
            # Whatever happened (dispatch, finalize, registration), the
            # prefetch picture may have changed — let the suggester look.
            self._suggest_wake.set()

    def _assign_next_locked(self, partition_id: int,
                            last_trial: Optional[Trial]) -> None:
        # Iterative on purpose: a gang suggestion parks for assembly and
        # pulls the NEXT suggestion — an all-gang backlog must drain in
        # a loop, not one recursion frame per parked trial (a ~1k-trial
        # GANG-only sweep would blow the recursion limit).
        while self._assign_next_once_locked(partition_id, last_trial):
            last_trial = None

    # locked-by: _sched_lock
    def _assign_next_once_locked(self, partition_id: int,
                                 last_trial: Optional[Trial]
                                 ) -> Optional[bool]:
        """One assignment attempt; True = pull again (the suggestion was
        parked for gang assembly and this runner is still free)."""
        # A gang-held member is not free: its chip belongs to an
        # (assembling or running) gang's mesh slice. Keep its idle chain
        # ticking so it resumes work the moment the gang releases. A
        # FINAL-delivering runner is never held here — terminal paths
        # release the gang before assigning next work.
        if self._gang_mode and last_trial is None and \
                self.server.reservations.gang_of(partition_id) is not None:
            self._rearm_idle(partition_id)
            return
        # Orphaned trials (lost runners) take priority over fresh
        # suggestions — but never swallow a FINAL report: when last_trial is
        # set the controller must see it (ASHA rung bookkeeping, pruner
        # reports) before any reassignment happens.
        if last_trial is None:
            suggestion = "IDLE"
        elif self._prefetch_enabled:
            # Split contract: report on the FINAL path (dropping
            # schedule-stale prefetches), then source the hand-off from
            # the prefetch queue — suggest() only runs inline when the
            # queue is dry and the controller is cheap.
            self._ingest_final_report(last_trial)
            suggestion = self._next_suggestion()
        else:
            suggestion = self.controller.get_suggestion(last_trial)
        state = self._partition_state(partition_id)
        if state != "live":
            # The controller has seen the FINAL; route any fresh suggestion
            # to the requeue for a live runner instead of this one.
            if suggestion not in (None, "IDLE"):
                self._mint_span(suggestion)
                with self._store_lock:
                    self._trial_store[suggestion.trial_id] = suggestion
                    self._requeue.append(suggestion.trial_id)
                self.telemetry.trial_event(suggestion.trial_id, "requeued",
                                           partition=partition_id,
                                           reason="dead_partition")
            # 'released' partitions saw GSTOP and never come back — drop
            # their IDLE chain. A 'silent' one may be a transient stall
            # (network hiccup): keep ticking so it resumes getting work if
            # its heartbeats return, but without handing it trials now.
            if state == "silent":
                self._rearm_idle(partition_id)
            return
        if suggestion in (None, "IDLE"):
            # Gang service first: a free runner whose chip sits inside a
            # reserved block is conscripted here — skipped-but-retained
            # for the gang instead of grabbing 1-chip work the block
            # would then have to wait out. The idle chain stays armed:
            # it is how the member resumes work after the gang releases.
            if self._service_gangs_locked(partition_id):
                self._rearm_idle(partition_id)
                return
            cap = self.server.reservations.capacity(partition_id)
            held = self._pop_fork_hold(partition_id)
            if held is not None:
                # A forked trial held for this runner's warm parent
                # state (or an expired hold any runner may take).
                held.set_status(Trial.SCHEDULED)
                self.server.reservations.assign_trial(partition_id,
                                                      held.trial_id)
                self.telemetry.trial_event(held.trial_id, "assigned",
                                           partition=partition_id,
                                           fork_affinity=True)
                self._journal_fork_edge(held, partition_id)
                return
            parked = self._pop_parked(cap)
            if parked is not None:
                parked.set_status(Trial.SCHEDULED)
                self.server.reservations.assign_trial(partition_id, parked.trial_id)
                self.telemetry.trial_event(parked.trial_id, "assigned",
                                           partition=partition_id,
                                           requeue="parked")
                return
            requeued = self._pop_requeue(cap)
            if requeued is not None:
                # A requeued FORK must still have its resume point (the
                # staged child copy or the parent's original); a vanished
                # source downgrades it to from-scratch loudly.
                self._verify_fork_source(requeued, partition_id)
                self.server.reservations.assign_trial(partition_id, requeued.trial_id)
                # Neutral label: the backlog holds genuinely lost trials
                # AND fresh suggestions rerouted off dead partitions — a
                # lost trial is identifiable by its own "lost" phase
                # event, so don't stamp phantom losses here.
                self.telemetry.trial_event(requeued.trial_id, "assigned",
                                           partition=partition_id,
                                           requeue="backlog")
                self._journal_fork_edge(requeued, partition_id)
                with requeued.lock:
                    resume_step = requeued.info_dict.get("resume_step")
                if resume_step is not None:
                    # Checkpoint-assisted resume: the closing edge of a
                    # preemption (chaos invariant 7 asserts from_step
                    # matches the preempted checkpoint step).
                    self.telemetry.trial_event(requeued.trial_id, "resumed",
                                               partition=partition_id,
                                               from_step=int(resume_step))
                return
            if last_trial is None:
                suggestion = self._next_suggestion() if self._prefetch_enabled \
                    else self.controller.get_suggestion(None)
            # Only when the controller ALSO has nothing fresh: an idle
            # elastic runner whose size fits no waiting trial migrates
            # toward the waiting work — otherwise its chips stay leased to
            # a size the schedule no longer needs and the pool deadlocks.
            # Demand/supply-bounded so a herd of idle runners doesn't all
            # chase one trial; runners beyond the demand are RETIRED
            # (resize 0), freeing chips for pending bigger spawns. The
            # worker COUNT never grows back after retirement (chips
            # re-aggregate, they don't re-split), which is the honest
            # trade for a push-free pool protocol.
            if suggestion in (None, "IDLE") and cap is not None \
                    and self._maybe_migrate(partition_id, cap):
                return
        if suggestion is None:
            # The controller has no more work — but the experiment is only
            # over once nothing is in flight: a trial held by a (possibly
            # dying) runner may yet come back through LOST and need this
            # runner to pick it up.
            with self._store_lock:
                in_flight = bool(self._trial_store)
            if in_flight:
                suggestion = "IDLE"
            else:
                self.experiment_done = True
        if suggestion == "IDLE":
            # Requeue after the idle tick from a timer, NOT by sleeping on the
            # single worker thread (64 idle runners would stall METRIC/FINAL
            # processing by ~0.6 s per cycle otherwise).
            self._rearm_idle(partition_id)
        elif suggestion is not None:
            self._mint_span(suggestion)
            with self._store_lock:
                # Trial ids hash the params; a controller emitting two
                # distinct units of work with identical params silently
                # collapses them here (one store slot) and loses a
                # schedule entry — exactly how a PBT id-collision bug
                # dropped 2 of 9 segments. Make it loud.
                # ERRORED entries don't count: a controller retrying a
                # failed unit of work (PBT segment retry) legitimately
                # re-issues the identical params/id. A store entry that IS
                # this object is no collision either — prefetched
                # suggestions enter the store at admit time and come back
                # through here at dispatch.
                existing = self._trial_store.get(suggestion.trial_id)
                duplicate = ((existing is not None
                              and existing is not suggestion)
                             or any(t.trial_id == suggestion.trial_id
                                    and t.final_metric is not None
                                    for t in self._final_store))
                self._trial_store[suggestion.trial_id] = suggestion
            if duplicate:
                self._log("WARNING: controller re-issued trial id {} "
                          "(params hash-collide with an in-flight or "
                          "finalized trial); the schedule may lose an "
                          "entry".format(suggestion.trial_id))
            # The controller just mutated its schedule (Hyperband bound the
            # new run to a bracket slot) — persist so resume=True can pick
            # the bracket up mid-flight.
            self._checkpoint_pruner()
            # Gang trials are never assigned to ONE runner: park the
            # trial for assembly (the placer reserves a contiguous chip
            # block; runners are conscripted as they free), then give
            # THIS runner another turn — it may itself become the first
            # conscript, else it takes the next (possibly 1-chip)
            # suggestion.
            spec = self._gang_spec_for(suggestion)
            if spec is not None and spec.chips > 1:
                with self._store_lock:
                    if suggestion.trial_id not in self._gang_wait:
                        self._gang_wait.append(suggestion.trial_id)
                self._log("trial {} needs a {}-chip gang ({}); awaiting "
                          "assembly".format(suggestion.trial_id, spec.chips,
                                            spec.strategy))
                if self._service_gangs_locked(partition_id):
                    self._rearm_idle(partition_id)
                    return None
                return True  # runner still free: pull the next suggestion
            # 1-chip work must not land on a runner whose chip is
            # reserved for a waiting gang (the block would re-busy
            # instead of draining): retain the suggestion in the backlog
            # for an unreserved runner and conscript this one.
            if self._gang_mode and self._placer is not None and \
                    self._placer.owner_of(
                        self._chip_of(partition_id)) is not None:
                with self._store_lock:
                    if suggestion.trial_id not in self._requeue:
                        self._requeue.append(suggestion.trial_id)
                self._service_gangs_locked(partition_id)
                self._rearm_idle(partition_id)
                return
            # Elastic sub-slices: a trial whose budget calls for a different
            # chip count than this runner is pinned to gets PARKED, and the
            # runner is told to exit + respawn at the right size (pinning
            # happens before backend init; it cannot resize in place).
            need = self._chips_for(suggestion)
            cap = self.server.reservations.capacity(partition_id)
            if need is not None and cap is not None and need != cap:
                with self._store_lock:
                    self._parked.append(suggestion.trial_id)
                    # Count toward the herd bound: this runner is already
                    # on its way to ``need``, so idle runners must not
                    # also chase the same trial.
                    self._resize_inflight[need] = \
                        self._resize_inflight.get(need, 0) + 1
                    self._resize_watch[partition_id] = (
                        time.monotonic(), need, self._pool_spawn_stamp(
                            partition_id))
                self.server.reservations.request_resize(partition_id, need)
                self._log("trial {} needs {} chip(s); runner {} (capacity "
                          "{}) asked to resize".format(
                              suggestion.trial_id, need, partition_id, cap))
                return
            # Parent affinity: a fresh FORKED suggestion prefers the
            # runner holding its parent's warm slot + local checkpoint;
            # this runner pulls the next suggestion instead.
            if self._maybe_hold_for_parent(suggestion, partition_id):
                self._log("trial {} held for runner {} (fork parent "
                          "affinity)".format(
                              suggestion.trial_id,
                              self._parent_partition(
                                  suggestion.info_dict.get(
                                      "forked_from", {}).get("trial"))))
                return True  # runner still free: pull the next suggestion
            suggestion.set_status(Trial.SCHEDULED)
            if self._vmap_lanes > 1 and \
                    self._assemble_vmap_block_locked(suggestion,
                                                     partition_id):
                return
            self.server.reservations.assign_trial(partition_id, suggestion.trial_id)
            self.telemetry.trial_event(suggestion.trial_id, "assigned",
                                       partition=partition_id)
            self._journal_fork_edge(suggestion, partition_id)

    # --------------------------------- vectorized micro-trials (vmap blocks)

    def _vmap_blockable_locked(self, trial: Trial) -> bool:
        """Can this trial ride a vectorized block? Unhashable params (no
        program key), gang trials (multi-chip mesh), and checkpoint
        resumers/forks (per-lane state restore has no vmapped analogue)
        all fall back to scalar dispatch. A BO near-duplicate keeps its
        ``parent`` tag and is admitted as a FORK LANE — it trains from
        scratch next to its parent's family (warm-started-neighbor, not
        checkpoint-restored)."""
        try:
            hash(tuple(sorted(trial.params.items())))
        except TypeError:
            return False
        spec = self._gang_spec_for(trial)
        if spec is not None and spec.chips > 1:
            return False
        with trial.lock:
            info = dict(trial.info_dict)
        if info.get("resume_step") is not None or info.get("forked_from"):
            return False
        if info.get("parent") and not info.get("near_duplicate"):
            return False
        return True

    @staticmethod
    def _vmap_compatible(a: Trial, b: Trial) -> bool:
        """Same vmapped program? Proxy for the PR-6 warm-cache program key
        the runner will resolve: identical trial type and param names, and
        identical NON-FLOAT param values — float params are the stacked
        hyperparameter axis (swept_transform traces them as inputs, so any
        value shares one HLO), while ints/strings/bools steer model
        config, shapes, or optimizer family and force a separate program."""
        if a.trial_type != b.trial_type or set(a.params) != set(b.params):
            return False
        for key, va in a.params.items():
            vb = b.params[key]
            if isinstance(va, float) and isinstance(vb, float):
                continue
            if va != vb:
                return False
        return True

    # locked-by: _sched_lock
    def _assemble_vmap_block_locked(self, leader: Trial,
                                    partition_id: int) -> bool:
        """Assemble up to K program-compatible suggestions (the leader +
        prefetched candidates) into ONE block delivery. True = the block
        was assigned (>= 2 lanes); False = nothing to vectorize (or the
        leader itself is block-incompatible) — the caller dispatches the
        leader scalar, bit-for-bit the K=1 path."""
        if not self._vmap_blockable_locked(leader):
            return False
        lanes = [leader]
        for cand in list(self._prefetched):
            if len(lanes) >= self._vmap_lanes:
                break
            if not self._vmap_blockable_locked(cand) or \
                    not self._vmap_compatible(leader, cand):
                continue
            self._prefetched.remove(cand)
            self._prefetch_versions.pop(cand.trial_id, None)
            lanes.append(cand)
        if len(lanes) < 2:
            return False
        # The queue just drained by K-1: let the suggester top it up.
        self._suggest_wake.set()
        lane_descs = []
        for i, t in enumerate(lanes):
            if i > 0:
                # Prefetched lanes were admitted but never dispatched:
                # mint their spans now (queued edge), like the scalar
                # dispatch path does for the leader.
                self._mint_span(t)
                t.set_status(Trial.SCHEDULED)
            with t.lock:
                if t.info_dict.get("near_duplicate") and \
                        t.info_dict.get("parent"):
                    # BO fork_eps under lanes: the near-duplicate rides
                    # the block as a fork lane — fresh init next to the
                    # parent's program family, NOT a checkpoint restore
                    # (strip any fork stamp _mint_span applied).
                    t.info_dict.pop("forked_from", None)
                    t.info_dict.pop("resume_step", None)
                    t.info_dict["fork_lane"] = {
                        "parent": t.info_dict["parent"]}
                t.info_dict["vmap"] = {"lane": i, "block": leader.trial_id}
                t.info_dict["epoch"] = t.run_epoch
                lane_descs.append({"trial_id": t.trial_id, "lane": i,
                                   "params": dict(t.params),
                                   "span": t.info_dict.get("span"),
                                   "epoch": t.run_epoch,
                                   "fork_lane": t.info_dict.get(
                                       "fork_lane")})
        with leader.lock:
            leader.info_dict["vmap_block"] = {"lanes": lane_descs}
        with self._store_lock:
            self._vmap_blocks[leader.trial_id] = {
                "lanes": [t.trial_id for t in lanes],
                "partition": partition_id}
            for t in lanes:
                self._lane_leader[t.trial_id] = leader.trial_id
        self.server.reservations.assign_trial(partition_id,
                                              leader.trial_id)
        for i, t in enumerate(lanes):
            self.telemetry.trial_event(t.trial_id, "assigned",
                                       partition=partition_id, lane=i,
                                       block=leader.trial_id)
        self._log("vmap block {}: {} lanes assigned to runner {}".format(
            leader.trial_id, len(lanes), partition_id))
        return True

    def _mint_span(self, trial: Trial) -> None:
        """Mint the trial's telemetry span when the driver commits to it
        ("queued") and plant the span id in its info_dict — the TRIAL reply
        ships info, so the span travels to the runner for free and comes
        back on its METRIC/FINAL messages. The queued edge carries the
        trial's PARAMS: the journal is crash recovery's source of truth,
        and a committed-but-unfinalized trial must be reconstructible
        from it alone (trial ids are content-addressed over the params,
        so recovery can verify the round trip). The scheduler half of
        info_dict rides along too — an ASHA promotion's rung/parent or a
        PBT segment's member/generation must survive the crash, or the
        re-run's FINAL would bookkeep into the wrong ledger slot — and
        the fork stamp below is applied FIRST so forked_from/resume_step
        land on the queued edge and a driver crash cannot orphan a fork
        mid-flight (recovery rebuilds the lineage from exactly this
        event); dispatch-time keys (span/gang/partition/epoch) are
        rebuilt by recovery itself and stay out."""
        self._stamp_fork(trial)
        with trial.lock:
            sched_info = {k: v for k, v in trial.info_dict.items()
                          if k not in ("span", "gang", "partition", "epoch")}
        span = self.telemetry.trial_event(trial.trial_id, "queued",
                                          params=trial.params,
                                          trial_type=trial.trial_type,
                                          info=sched_info)
        if span is not None:
            with trial.lock:
                trial.info_dict["span"] = span

    # -------------------------------------------- checkpoint-forking search

    def _stamp_fork(self, trial: Trial) -> None:
        """Turn a parent-carrying suggestion into a checkpoint FORK: if
        the parent left an ack'd checkpoint, stamp ``forked_from`` =
        (parent, step) + ``resume_step`` into the trial's info so the
        TRIAL payload ships them, the executor stages the parent's
        checkpoint into the child's dir, and a ctx-aware train fn
        resumes at ``step + 1`` instead of re-training the prefix. A
        parent with no checkpoint (ctx-less train fn, GC'd dir) leaves
        the trial untouched — from-scratch promotion, the pre-fork
        behavior. config.fork=False disables the stamp wholesale
        (bit-for-bit from-scratch promotions)."""
        if not self._fork_enabled:
            return
        with trial.lock:
            parent = trial.info_dict.get("parent")
            already = trial.info_dict.get("forked_from")
        if parent is None or already is not None:
            return
        with self._store_lock:
            cached = self._fork_step_cache.get(parent, _UNRESOLVED)
        if cached is not _UNRESOLVED:
            step = cached
        else:
            from maggy_tpu.train.checkpoint import \
                latest_checkpoint_step_env

            try:
                step = latest_checkpoint_step_env(
                    self.env, "{}/{}".format(self.exp_dir, parent))
            except Exception:  # noqa: BLE001 - an unreadable dir = no fork
                step = None
            with self._store_lock:
                self._fork_step_cache[parent] = step
        if step is None:
            return
        with trial.lock:
            trial.info_dict["forked_from"] = {"trial": parent,
                                              "step": int(step)}
            trial.info_dict["resume_step"] = int(step)

    def _journal_fork_edge(self, trial: Trial, partition_id: int) -> None:
        """The genealogy span edge (once per span — a requeued fork's
        re-dispatch does not repeat it): parent -> child with the forked
        step, rendered by trace.py as a Perfetto flow arrow and counted
        by derive()'s fork block."""
        with trial.lock:
            fork = trial.info_dict.get("forked_from")
        if not fork:
            return
        self.telemetry.trial_event(trial.trial_id, "forked_from",
                                   once=True, partition=partition_id,
                                   parent=fork.get("trial"),
                                   step=fork.get("step"))

    def _verify_fork_source(self, trial: Trial, partition_id: int) -> None:
        """Before re-dispatching a requeued FORKED trial: its resume
        point must still exist — either the child's staged checkpoint
        (the first attempt got far enough to stage) or the parent's
        original (GC keeps it while a fork is schedulable, but disk loss
        or an operator wipe can race). A vanished source downgrades the
        trial to from-scratch LOUDLY (requeued reason=fork_source_lost +
        stripped fork keys) instead of letting the runner crash opening
        a checkpoint that is not there."""
        with trial.lock:
            fork = trial.info_dict.get("forked_from")
        if not fork:
            return
        step = fork.get("step")
        child = "{}/{}/checkpoints/{}".format(self.exp_dir, trial.trial_id,
                                              step)
        parent = "{}/{}/checkpoints/{}".format(self.exp_dir,
                                               fork.get("trial"), step)
        try:
            ok = self.env.isdir(child) or self.env.isdir(parent)
        except Exception:  # noqa: BLE001 - unreadable = assume gone
            ok = False
        if ok:
            return
        with trial.lock:
            trial.info_dict.pop("forked_from", None)
            trial.info_dict.pop("resume_step", None)
        self.telemetry.trial_event(trial.trial_id, "requeued",
                                   partition=partition_id,
                                   reason="fork_source_lost",
                                   parent=fork.get("trial"), step=step)
        self._log("fork source for trial {} (parent {} step {}) vanished; "
                  "re-running from scratch".format(
                      trial.trial_id, fork.get("trial"), step))

    def _parent_partition(self, parent_id: str) -> Optional[int]:
        """The partition that last ran (and checkpointed) the parent —
        where its warm slot and locally-staged checkpoint live."""
        return self.telemetry.spans.partition_of(parent_id)

    # locked-by: _sched_lock
    def _maybe_hold_for_parent(self, trial: Trial,
                               partition_id: int) -> bool:
        """Parent-affinity (the PR-14 prewarm hints extended from family
        to parent scope): a forked trial dispatched while the parent's
        runner is alive is briefly HELD for that runner — it already
        holds the family's warm slot AND the parent's checkpoint on
        local disk, so the fork loads without a cross-runner copy. Held
        at most once per trial and at most FORK_AFFINITY_HOLD_S (then
        any runner takes it), so affinity can never starve the trial.
        Returns True when held — the asking runner pulls its next
        suggestion."""
        if not self._fork_enabled or self._chips_map is not None:
            # Elastic pools size runners per budget: an affinity hold
            # would bypass the capacity matching below.
            return False
        with trial.lock:
            fork = trial.info_dict.get("forked_from")
        if not fork:
            return False
        preferred = self._parent_partition(fork.get("trial"))
        if preferred is None or int(preferred) == int(partition_id):
            return False
        with self._store_lock:
            if trial.trial_id in self._fork_held:
                return False
        if self._partition_state(int(preferred)) != "live":
            return False
        with self._store_lock:
            self._fork_held.add(trial.trial_id)
            self._fork_hold.append(
                (time.monotonic() + constants.FORK_AFFINITY_HOLD_S,
                 int(preferred), trial.trial_id))
        return True

    def _pop_fork_hold(self, partition_id: int) -> Optional[Trial]:
        """A trial held for THIS partition (parent affinity), or any
        EXPIRED hold — whoever idles first past the deadline takes it."""
        now = time.monotonic()
        with self._store_lock:
            for i, (deadline, preferred, tid) in enumerate(self._fork_hold):
                if preferred != int(partition_id) and now < deadline:
                    continue
                del self._fork_hold[i]
                trial = self._trial_store.get(tid)
                if trial is not None:
                    return trial
        return None

    # locked-by: _sched_lock
    def _sweep_fork_gc(self) -> None:
        """Checkpoint GC: retire a parent's checkpoint dir once the
        controller reports no live or schedulable child can still fork
        from it (Asha: the promotion child finalized; PBT: the segment
        was superseded as its member's population state). Never touches
        a LIVE trial — anything still in the store/backlogs keeps its
        latest ack'd step — and each retirement journals a ``ckpt_gc``
        event, so a forking sweep's disk stays bounded and auditable.
        Only the ELIGIBILITY decision runs here (cheap dict ops, sched
        lock held); the recursive dir deletions happen on a short-lived
        daemon thread — on the prefetch inline FINAL path this method
        runs on the RPC event loop before the reply is written, and
        tree deletions there would stall every tenant heartbeat."""
        if not self._fork_enabled:
            return
        eligible = getattr(self.controller, "fork_gc_eligible", None)
        if eligible is None:
            return
        try:
            candidates = list(eligible())
        except Exception:  # noqa: BLE001 - GC is an optimization, never fatal
            return
        todo = []
        with self._store_lock:
            for tid in candidates:
                if tid in self._ckpt_gced:
                    continue
                if (tid in self._trial_store or tid in self._requeue
                        or tid in self._parked):
                    continue
                # Claimed now so a racing next sweep cannot double-GC;
                # a failed delete un-claims for retry.
                self._ckpt_gced.add(tid)
                todo.append(tid)
        if todo:
            threading.Thread(target=self._fork_gc_worker, args=(todo,),
                             daemon=True, name="fork-gc").start()

    def _fork_gc_worker(self, todo: List[str]) -> None:
        """Off-hot-path half of checkpoint GC: the env I/O. Runs without
        any driver lock — a GC'd trial is finalized and non-live by the
        sweep's claim above, so nothing races the deletion (and even a
        pathological race only costs a fork its source, which the
        fork_source_lost downgrade absorbs loudly)."""
        for tid in todo:
            path = "{}/{}/checkpoints".format(self.exp_dir, tid)
            try:
                had = self.env.isdir(path)
                if had:
                    self.env.delete(path, recursive=True)
            except Exception:  # noqa: BLE001 - a failed delete retries next sweep
                with self._store_lock:
                    self._ckpt_gced.discard(tid)
                continue
            if had:
                with self._store_lock:
                    # A later stamp against this parent (a BO
                    # near-duplicate may pick ANY finalized trial) must
                    # see "no checkpoint", not the stale pre-GC step.
                    self._fork_step_cache[tid] = None
                try:
                    self.telemetry.event("ckpt_gc", trial=tid,
                                         why="no_schedulable_child")
                    self._log("ckpt_gc: retired checkpoints of "
                              "{}".format(tid))
                except Exception:  # noqa: BLE001 - the final sweep's worker may
                    # outlive experiment teardown (journal closed); the
                    # deletion itself already happened.
                    pass

    # -------------------------------------------------------------- results

    def _update_result(self, trial: Trial) -> None:
        if trial.final_metric is None:
            return
        metric, maximize = trial.final_metric, self.direction == "max"
        r = self.result
        r["num_trials"] += 1
        if r["best_val"] is None or (metric > r["best_val"] if maximize else metric < r["best_val"]):
            r.update(best_id=trial.trial_id, best_val=metric,
                     best_hp=self.controller._strip_budget(trial.params))
        if r["worst_val"] is None or (metric < r["worst_val"] if maximize else metric > r["worst_val"]):
            r.update(worst_id=trial.trial_id, worst_val=metric,
                     worst_hp=self.controller._strip_budget(trial.params))
        n = r["num_trials"]
        r["avg"] = metric if r["avg"] is None else r["avg"] + (metric - r["avg"]) / n

    def _exp_startup_callback(self) -> None:
        self.job_start = time.time()
        util.write_hparams_config(self.exp_dir, self.config.searchspace)

    def _exp_final_callback(self, job_end, exp_json):
        with self._store_lock:
            finalized = list(self._final_store)
        self.controller._finalize_experiment(finalized)
        duration = job_end - (self.job_start or job_end)
        self.result["duration_s"] = duration
        self.env.dump(json.dumps(self.result, indent=2, default=str),
                      self.exp_dir + "/result.json")
        # Aggregate per-trial artifacts (.hparams.json/.outputs.json) into
        # .summary.json (reference `util.py:126-148`).
        try:
            util.build_summary(self.exp_dir, env=self.env)
        except Exception:  # noqa: BLE001 - summary is best-effort
            pass
        self.maggy_log = self._result_summary(duration)
        if getattr(self.config, "verbose", False):
            print(self.maggy_log, flush=True)
        # Make the telemetry artifact durable at the finish line (the
        # flusher thread's cadence must not decide whether the last trials'
        # spans land), and mirror the derived scheduling numbers into
        # TensorBoard scalars next to the experiment's hparams config.
        self.telemetry.event("experiment", phase="finalized",
                             duration_s=duration)
        self.telemetry.flush()
        try:
            from maggy_tpu import tensorboard as tb

            tb.write_telemetry_scalars(self.exp_dir,
                                       self.telemetry.snapshot(fresh=True))
        except Exception:  # noqa: BLE001 - telemetry mirrors are best-effort
            pass
        self.env.finalize_experiment(
            self.exp_dir, "FINISHED",
            {"result": {k: self.result[k] for k in
                        ("best_id", "best_val", "avg", "num_trials", "early_stopped")}},
        )
        return dict(self.result)

    def _exp_exception_callback(self, exc) -> None:
        self.env.finalize_experiment(self.exp_dir, "FAILED", {"error": repr(exc)})
        raise exc

    def stop(self) -> None:
        # Retire the suggester BEFORE the base teardown: a mid-wait
        # suggester must not refill from a stopping controller (and a
        # mid-fit one gets the join bound; it is a daemon either way).
        # unguarded-ok: monotonic completion latch, polled lock-free by design
        self.experiment_done = True
        self._suggest_wake.set()
        t = self._suggester_thread
        if t is not None and t.is_alive():
            t.join(timeout=5)
        super().stop()

    def _result_summary(self, duration: float) -> str:
        """Human-readable final summary (the reference prints one to the
        notebook, `optimization_driver.py:172-194`)."""
        r = self.result
        lines = [
            "------ {} results ------ direction({})".format(
                type(self.controller).__name__, self.direction),
            "BEST combination {} -- metric {}".format(
                json.dumps(r["best_hp"], default=str), r["best_val"]),
            "WORST combination {} -- metric {}".format(
                json.dumps(r["worst_hp"], default=str), r["worst_val"]),
            "AVERAGE metric -- {}".format(r["avg"]),
            "EARLY STOPPED trials -- {}".format(r["early_stopped"]),
            "Total job time {:.2f} s ({} trials)".format(
                duration, r["num_trials"]),
        ]
        return "\n".join(lines)

    def obs_status(self) -> Dict[str, Any]:
        """Extend the base /status document with the HPO driver's live
        scheduling state: trial-store/backlog counts, assembled gangs (+
        placer blocks), and the fleet scheduler's share snapshot when
        fleet-attached. Locks are taken one at a time, never nested —
        this runs on an obs handler thread."""
        out = super().obs_status()
        with self._store_lock:
            out["store"] = {
                "trials": len(self._trial_store),
                "finalized": len(self._final_store),
                "requeue": len(self._requeue),
                "parked": len(self._parked),
                "gang_wait": len(self._gang_wait),
            }
            out["gangs"] = {
                tid: {"chips": info.get("chips"),
                      "members": list(info.get("members") or []),
                      "leader": info.get("leader"),
                      "strategy": info.get("strategy"),
                      "revoking": bool(info.get("revoking"))}
                for tid, info in self._gangs.items()}
        if self._placer is not None:
            out["pack"] = self._placer.snapshot()
        binding = getattr(self.config, "fleet", None)
        if binding is not None:
            out["fleet"] = binding.fleet.scheduler.snapshot()
        return out

    def progress_snapshot(self) -> Dict[str, Any]:
        with self._store_lock:
            done = len(self._final_store)
        with self._log_lock:
            log_total = len(self.executor_logs)
            log_tail = list(self.executor_logs[-20:])
        return {"num_trials": self.num_trials, "finalized": done,
                "best_val": self.result["best_val"],
                "early_stopped": self.result["early_stopped"],
                # Executor-log stream for the monitor CLI (reference's LOG
                # RPC carried executor prints to sparkmagic, rpc.py:369-377):
                # total count + tail window lets a poller print only new lines.
                "log_total": log_total, "log_tail": log_tail}
