"""Checkpoint-forking search: promotions and exploits resume, never
restart (ROADMAP item 3).

Covers the fork/copy helper (train/checkpoint.fork_checkpoint), the
driver's fork stamp + genealogy edge + fork-source verification +
checkpoint GC, controller GC eligibility (Asha / PBT), BO near-duplicate
warm starts, the derive() fork block + Perfetto fork flow arrows, journal
replay of fork lineage through crash recovery, the fleet scheduler's
parent-affinity tier, the shared bench A/B comparator, the offline
invariant-14 checker, and an end-to-end bitwise fork-parity sweep (warm
and cold, with the config.fork=False escape hatch restoring from-scratch
promotions bit-for-bit). The kill-mid-fork soak is ``python -m
maggy_tpu.chaos --fork``; the A/B gate is ``bench.py --fork``.
"""

from __future__ import annotations

import glob
import json
import os
import time

import pytest

from maggy_tpu.trial import Trial

pytestmark = pytest.mark.fork


def _write_ckpts(trial_dir, steps):
    for step in steps:
        d = os.path.join(trial_dir, "checkpoints", str(step))
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "state.json"), "w") as f:
            json.dump({"step": step}, f)


def _local_env(base):
    from maggy_tpu.core.environment.abstractenvironment import LocalEnv

    return LocalEnv(base_dir=str(base))


# --------------------------------------------------------- fork staging


class TestForkCheckpoint:
    def test_stages_latest_parent_step(self, tmp_path):
        from maggy_tpu.train.checkpoint import fork_checkpoint

        env = _local_env(tmp_path)
        exp = str(tmp_path / "exp")
        parent = os.path.join(exp, "parent")
        child = os.path.join(exp, "child")
        _write_ckpts(parent, [0, 1, 3])
        step = fork_checkpoint(env, exp, "parent", child)
        assert step == 3
        with open(os.path.join(child, "checkpoints", "3",
                               "state.json")) as f:
            assert json.load(f)["step"] == 3
        # Parent dir intact: a PBT winner donates to several members.
        assert os.path.isdir(os.path.join(parent, "checkpoints", "3"))

    def test_specific_step_and_idempotence(self, tmp_path):
        from maggy_tpu.train.checkpoint import fork_checkpoint

        env = _local_env(tmp_path)
        exp = str(tmp_path / "exp")
        _write_ckpts(os.path.join(exp, "parent"), [0, 1, 2])
        child = os.path.join(exp, "child")
        assert fork_checkpoint(env, exp, "parent", child, step=1) == 1
        # Re-staging (a requeued fork's re-dispatch) is a no-op copy.
        marker = os.path.join(child, "checkpoints", "1", "extra")
        with open(marker, "w") as f:
            f.write("x")
        assert fork_checkpoint(env, exp, "parent", child, step=1) == 1
        assert os.path.exists(marker)  # not re-copied over

    def test_torn_remote_copy_restaged(self, tmp_path):
        """The generic (object-store-shaped) staging path is crash-safe:
        a copy torn by a mid-staging death has no completion marker, so
        the requeued re-dispatch re-copies instead of restoring a
        half-staged checkpoint."""
        from maggy_tpu.train.checkpoint import fork_checkpoint

        env = _local_env(tmp_path)
        env.FAST_LOCAL_WRITES = False  # take the env-abstracted path
        exp = str(tmp_path / "exp")
        parent = os.path.join(exp, "parent")
        _write_ckpts(parent, [2])
        child = os.path.join(exp, "child")
        # Simulate the torn first attempt: dir exists, file missing,
        # NO .fork_complete marker.
        os.makedirs(os.path.join(child, "checkpoints", "2"),
                    exist_ok=True)
        assert fork_checkpoint(env, exp, "parent", child) == 2
        assert os.path.exists(os.path.join(child, "checkpoints", "2",
                                           "state.json"))
        marker = os.path.join(child, "checkpoints", ".fork_complete.2")
        assert os.path.exists(marker)
        # Marker present => idempotent (no re-copy).
        probe = os.path.join(child, "checkpoints", "2", "probe")
        with open(probe, "w") as f:
            f.write("x")
        assert fork_checkpoint(env, exp, "parent", child) == 2
        assert os.path.exists(probe)
        # And the marker never pollutes the step listing.
        from maggy_tpu.train.checkpoint import latest_checkpoint_step

        assert latest_checkpoint_step(child) == 2

    def test_missing_parent_returns_none(self, tmp_path):
        from maggy_tpu.train.checkpoint import fork_checkpoint

        env = _local_env(tmp_path)
        exp = str(tmp_path / "exp")
        os.makedirs(exp, exist_ok=True)
        assert fork_checkpoint(env, exp, "ghost",
                               os.path.join(exp, "child")) is None
        assert fork_checkpoint(env, exp, "ghost",
                               os.path.join(exp, "child"), step=7) is None

    def test_latest_step_env(self, tmp_path):
        from maggy_tpu.train.checkpoint import latest_checkpoint_step_env

        env = _local_env(tmp_path)
        trial_dir = str(tmp_path / "t")
        assert latest_checkpoint_step_env(env, trial_dir) is None
        _write_ckpts(trial_dir, [2, 5])
        assert latest_checkpoint_step_env(env, trial_dir) == 5


class TestContextFork:
    def test_ctx_stage_fork(self, tmp_path):
        from maggy_tpu.core.environment import EnvSing
        from maggy_tpu.core.executors.context import TrialContext

        env = _local_env(tmp_path)
        EnvSing.set_instance(env)
        try:
            exp = str(tmp_path / "exp")
            _write_ckpts(os.path.join(exp, "par"), [0, 4])
            ctx = TrialContext(
                "child", os.path.join(exp, "child"), exp, {"lr": 0.1},
                info={"forked_from": {"trial": "par", "step": 4},
                      "resume_step": 4, "parent": "par"})
            assert ctx.forked_from == {"trial": "par", "step": 4}
            assert ctx.stage_fork() == 4
            assert ctx.resume_step == 4
            assert os.path.isdir(os.path.join(exp, "child",
                                              "checkpoints", "4"))
        finally:
            EnvSing.reset()


# ------------------------------------------------------- driver stamping


def _driver(tmp_path, optimizer="randomsearch", fork=True, **kw):
    from maggy_tpu import OptimizationConfig, Searchspace
    from maggy_tpu.core.driver.optimization_driver import OptimizationDriver

    base = dict(
        name="forkunit", num_trials=4, optimizer=optimizer,
        searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2])),
        direction="max", num_workers=2, seed=5, es_policy="none",
        experiment_dir=str(tmp_path / "exp"), hb_loss_timeout=30.0,
        health=False, fork=fork)
    base.update(kw)
    return OptimizationDriver(OptimizationConfig(**base), "forkunit", 0)


class TestDriverStamp:
    def test_stamp_fork_resolves_parent_checkpoint(self, tmp_path):
        driver = _driver(tmp_path)
        try:
            _write_ckpts(os.path.join(driver.exp_dir, "par"), [0, 1, 2])
            trial = Trial({"lr": 0.1, "budget": 2},
                          info_dict={"parent": "par", "rung": 1,
                                     "sample_type": "promoted"})
            driver._stamp_fork(trial)
            assert trial.info_dict["forked_from"] == {"trial": "par",
                                                      "step": 2}
            assert trial.info_dict["resume_step"] == 2
        finally:
            driver.stop()

    def test_stamp_skips_when_disabled_or_uncheckpointed(self, tmp_path):
        driver = _driver(tmp_path, fork=False)
        try:
            _write_ckpts(os.path.join(driver.exp_dir, "par"), [0])
            trial = Trial({"lr": 0.1}, info_dict={"parent": "par"})
            driver._stamp_fork(trial)
            assert "forked_from" not in trial.info_dict
            assert "resume_step" not in trial.info_dict
        finally:
            driver.stop()
        driver = _driver(tmp_path)
        try:
            # Parent never checkpointed: from-scratch promotion.
            trial = Trial({"lr": 0.15}, info_dict={"parent": "nockpt"})
            driver._stamp_fork(trial)
            assert "forked_from" not in trial.info_dict
        finally:
            driver.stop()

    def test_mint_span_journals_fork_lineage(self, tmp_path):
        driver = _driver(tmp_path)
        try:
            _write_ckpts(os.path.join(driver.exp_dir, "par"), [0, 3])
            trial = Trial({"lr": 0.1, "budget": 2},
                          info_dict={"parent": "par",
                                     "sample_type": "promoted"})
            driver._mint_span(trial)
            queued = [ev for ev in driver.telemetry.events()
                      if ev.get("phase") == "queued"
                      and ev.get("trial") == trial.trial_id]
            assert queued, "queued edge missing"
            info = queued[-1]["info"]
            assert info["forked_from"] == {"trial": "par", "step": 3}
            assert info["resume_step"] == 3
        finally:
            driver.stop()

    def test_fork_source_lost_downgrades_loudly(self, tmp_path):
        driver = _driver(tmp_path)
        try:
            trial = Trial({"lr": 0.1, "budget": 2},
                          info_dict={"parent": "gone",
                                     "forked_from": {"trial": "gone",
                                                     "step": 5},
                                     "resume_step": 5})
            driver._verify_fork_source(trial, 0)
            assert "forked_from" not in trial.info_dict
            assert "resume_step" not in trial.info_dict
            edges = [ev for ev in driver.telemetry.events()
                     if ev.get("phase") == "requeued"
                     and ev.get("reason") == "fork_source_lost"]
            assert len(edges) == 1
        finally:
            driver.stop()

    def test_fork_source_survives_with_staged_copy(self, tmp_path):
        driver = _driver(tmp_path)
        try:
            trial = Trial({"lr": 0.1, "budget": 2},
                          info_dict={"forked_from": {"trial": "gone",
                                                     "step": 5},
                                     "resume_step": 5})
            # The CHILD's staged copy alone keeps the fork alive.
            _write_ckpts(os.path.join(driver.exp_dir, trial.trial_id), [5])
            driver._verify_fork_source(trial, 0)
            assert trial.info_dict["resume_step"] == 5
        finally:
            driver.stop()

    def test_ckpt_gc_never_touches_live_trials(self, tmp_path):
        driver = _driver(tmp_path)
        try:
            _write_ckpts(os.path.join(driver.exp_dir, "livet"), [0])
            _write_ckpts(os.path.join(driver.exp_dir, "donet"), [0])
            live = Trial({"lr": 0.11})
            with driver._store_lock:
                driver._trial_store["livet"] = live
            driver.controller.fork_gc_eligible = lambda: ["livet", "donet"]
            with driver._sched_lock:
                driver._sweep_fork_gc()
            # Deletions run on the GC worker thread (off the FINAL hot
            # path): wait for them.
            deadline = time.monotonic() + 10
            gone = os.path.join(driver.exp_dir, "donet", "checkpoints")
            while os.path.isdir(gone) and time.monotonic() < deadline:
                time.sleep(0.01)
            assert os.path.isdir(os.path.join(driver.exp_dir, "livet",
                                              "checkpoints"))
            assert not os.path.isdir(gone)
            gcs = [ev for ev in driver.telemetry.events()
                   if ev.get("ev") == "ckpt_gc"]
            assert [ev["trial"] for ev in gcs] == ["donet"]
            # Idempotent: a second sweep never re-journals.
            with driver._sched_lock:
                driver._sweep_fork_gc()
            time.sleep(0.05)
            assert len([ev for ev in driver.telemetry.events()
                        if ev.get("ev") == "ckpt_gc"]) == 1
        finally:
            driver.stop()

    def test_ckpt_gc_spares_top_rung_winner_on_exhaustion(self, tmp_path):
        from maggy_tpu import Searchspace
        from maggy_tpu.optimizers import Asha

        asha = Asha(reduction_factor=2, resource_min=1, resource_max=2,
                    seed=0)
        asha.searchspace = Searchspace(lr=("DOUBLE", [0.0, 0.2]))
        asha.num_trials = 2
        asha.trial_store = {}
        asha.final_store = []
        asha.direction = "max"
        parent = Trial({"lr": 0.1, "budget": 1}, info_dict={"rung": 0})
        parent.status = Trial.FINALIZED
        parent.final_metric = 0.9
        winner = Trial({"lr": 0.1, "budget": 2},
                       info_dict={"rung": 1, "parent": parent.trial_id})
        winner.status = Trial.FINALIZED
        winner.final_metric = 0.95
        asha.final_store.extend([parent, winner])
        asha.rungs[0].append(parent.trial_id)
        asha.rungs.setdefault(1, []).append(winner.trial_id)
        asha._exhausted = True
        # The top-rung survivor's trained state is the sweep's PRODUCT:
        # exhaustion retires everything else, never the winner.
        eligible = asha.fork_gc_eligible()
        assert parent.trial_id in eligible
        assert winner.trial_id not in eligible


# -------------------------------------------------- controller eligibility


class TestForkGcEligibility:
    def _asha(self):
        from maggy_tpu import Searchspace
        from maggy_tpu.optimizers import Asha

        asha = Asha(reduction_factor=2, resource_min=1, resource_max=2,
                    seed=0)
        asha.searchspace = Searchspace(lr=("DOUBLE", [0.0, 0.2]))
        asha.num_trials = 2
        asha.trial_store = {}
        asha.final_store = []
        asha.direction = "max"
        return asha

    @staticmethod
    def _finalized(params, metric, info):
        t = Trial(params, info_dict=info)
        t.status = Trial.FINALIZED
        t.final_metric = metric
        return t

    def test_asha_parent_spent_only_after_child_finalizes(self):
        asha = self._asha()
        parent = self._finalized({"lr": 0.1, "budget": 1}, 0.9, {"rung": 0})
        asha.final_store.append(parent)
        asha.rungs[0].append(parent.trial_id)
        asha.promoted[0] = [parent.trial_id]
        # Child still in flight: parent must stay forkable.
        assert asha.fork_gc_eligible() == []
        child = self._finalized({"lr": 0.1, "budget": 2}, 0.95,
                                {"rung": 1, "parent": parent.trial_id})
        asha.final_store.append(child)
        asha.rungs.setdefault(1, []).append(child.trial_id)
        assert asha.fork_gc_eligible() == [parent.trial_id]
        # Exhausted: every finalized checkpoint is spent EXCEPT the
        # top-rung survivors' (the winner's weights are the product).
        asha._exhausted = True
        assert asha.fork_gc_eligible() == [parent.trial_id]

    def test_asha_unpromoted_trial_stays(self):
        asha = self._asha()
        t = self._finalized({"lr": 0.12, "budget": 1}, 0.5, {"rung": 0})
        asha.final_store.append(t)
        asha.rungs[0].append(t.trial_id)
        # Not promoted yet — eligibility GROWS as the rung fills, so the
        # checkpoint must be kept.
        assert asha.fork_gc_eligible() == []

    def test_pbt_superseded_segment_spent(self):
        from maggy_tpu import Searchspace
        from maggy_tpu.optimizers import PBT

        pbt = PBT(population=2, generations=3, seed=0)
        pbt.searchspace = Searchspace(lr=("DOUBLE", [0.0, 0.2]))
        pbt.trial_store = {}
        pbt.final_store = []
        pbt.direction = "max"
        g0 = self._finalized({"lr": 0.1, "generation": 0, "member": 0,
                              "budget": 1}, 0.5,
                             {"member": 0, "generation": 0})
        g1 = self._finalized({"lr": 0.1, "generation": 1, "member": 0,
                              "budget": 1}, 0.6,
                             {"member": 0, "generation": 1,
                              "parent": g0.trial_id})
        pbt.final_store.extend([g0, g1])
        # g0 superseded by g1 (member 0's latest) and nothing pending
        # names it: spent. g1 is population state: kept.
        assert pbt.fork_gc_eligible() == [g0.trial_id]
        # A pending segment naming g0 as parent keeps it alive.
        pbt._pending.append(Trial({"lr": 0.2, "generation": 2,
                                   "member": 1, "budget": 1},
                                  info_dict={"member": 1, "generation": 2,
                                             "parent": g0.trial_id}))
        assert pbt.fork_gc_eligible() == []


class TestBoNearDuplicate:
    def _bo(self, fork_eps):
        from maggy_tpu import Searchspace
        from maggy_tpu.optimizers.bayes.base import BaseAsyncBO

        class Fixed(BaseAsyncBO):
            def update_model(self, budget=0):
                self.models[budget] = object()

            def sampling_routine(self, budget=0):
                return {"lr": 0.1001}

        bo = Fixed(num_warmup_trials=0, random_fraction=0.0,
                   fork_eps=fork_eps, seed=3)
        bo.searchspace = Searchspace(lr=("DOUBLE", [0.0, 0.2]))
        bo.num_trials = 10
        bo.trial_store = {}
        bo.final_store = []
        bo.direction = "max"
        for lr, metric in ((0.1, 0.9), (0.19, 0.2)):
            t = Trial({"lr": lr, "budget": 0})
            t.status = Trial.FINALIZED
            t.final_metric = metric
            bo.final_store.append(t)
        for _ in range(2):  # clear the have-data floor (>= dims + 1... )
            t = Trial({"lr": 0.05 + 0.001 * len(bo.final_store),
                       "budget": 0})
            t.status = Trial.FINALIZED
            t.final_metric = 0.3
            bo.final_store.append(t)
        return bo

    def test_model_proposal_inherits_neighbor_parent(self):
        bo = self._bo(fork_eps=0.05)
        trial = bo._propose(0)
        assert trial.info_dict.get("sample_type") == "model"
        donor = bo.final_store[0]  # lr 0.1 — nearest to 0.1001
        assert trial.info_dict.get("parent") == donor.trial_id
        assert trial.info_dict.get("near_duplicate") is True

    def test_off_by_default(self):
        bo = self._bo(fork_eps=None)
        trial = bo._propose(0)
        assert trial.info_dict.get("sample_type") == "model"
        assert "parent" not in trial.info_dict


# ------------------------------------------------------ telemetry + replay


def _fork_events():
    return [
        {"t": 1.0, "ev": "trial", "trial": "par", "phase": "queued",
         "params": {"lr": 0.1}, "info": {}},
        {"t": 1.5, "ev": "trial", "trial": "par", "phase": "running",
         "partition": 0},
        {"t": 2.0, "ev": "trial", "trial": "par", "phase": "finalized",
         "partition": 0},
        {"t": 2.1, "ev": "trial", "trial": "kid", "phase": "queued",
         "params": {"lr": 0.1, "budget": 2},
         "info": {"parent": "par",
                  "forked_from": {"trial": "par", "step": 3},
                  "resume_step": 3}},
        {"t": 2.2, "ev": "trial", "trial": "kid", "phase": "assigned",
         "partition": 1},
        {"t": 2.2, "ev": "trial", "trial": "kid", "phase": "forked_from",
         "partition": 1, "parent": "par", "step": 3},
        {"t": 2.3, "ev": "trial", "trial": "kid", "phase": "running",
         "partition": 1},
        {"t": 3.0, "ev": "trial", "trial": "kid", "phase": "finalized",
         "partition": 1},
        {"t": 3.1, "ev": "trial", "trial": "scr", "phase": "queued",
         "params": {"lr": 0.2, "budget": 2}, "info": {"parent": "par"}},
        {"t": 3.2, "ev": "trial", "trial": "scr", "phase": "finalized",
         "partition": 0},
        {"t": 3.5, "ev": "ckpt_gc", "trial": "par",
         "why": "no_schedulable_child"},
    ]


class TestDeriveForkBlock:
    def test_counts_and_steps_saved(self):
        from maggy_tpu.telemetry.spans import derive

        fork = derive(_fork_events())["fork"]
        assert fork["forked"] == 1
        assert fork["from_scratch"] == 1  # "scr" carried a parent, no edge
        assert fork["steps_saved"] == 4   # fork at step 3 skips 0..3
        assert fork["ckpt_gc"] == 1
        assert fork["downgrades"] == 0

    def test_empty_without_forks(self):
        from maggy_tpu.telemetry.spans import derive

        assert derive([{"t": 1.0, "ev": "trial", "trial": "a",
                        "phase": "queued", "params": {},
                        "info": {}}])["fork"] == {}

    def test_downgrade_counted(self):
        from maggy_tpu.telemetry.spans import derive

        events = _fork_events() + [
            {"t": 4.0, "ev": "trial", "trial": "kid", "phase": "requeued",
             "partition": 1, "reason": "fork_source_lost"}]
        assert derive(events)["fork"]["downgrades"] == 1


class TestTraceForkFlows:
    def test_flow_arrows_parent_to_child(self):
        from maggy_tpu.telemetry.trace import build_trace, validate_trace

        trace = build_trace(_fork_events())
        validate_trace(trace)
        assert trace["otherData"]["fork_flows"] == 1
        flows = [e for e in trace["traceEvents"]
                 if e.get("cat") == "flow" and e["name"] == "fork-flow"]
        assert {e["ph"] for e in flows} == {"s", "f"}
        start = next(e for e in flows if e["ph"] == "s")
        end = next(e for e in flows if e["ph"] == "f")
        assert start["pid"] == 1  # parent finalized on partition 0
        assert end["pid"] == 2    # child running on partition 1
        assert start["ts"] <= end["ts"]

    def test_forked_instant_rendered(self):
        from maggy_tpu.telemetry.trace import build_trace

        names = [e.get("name", "") for e in
                 build_trace(_fork_events())["traceEvents"]]
        assert any(n.startswith("forked_from:") for n in names)


class TestRecoveryForkLineage:
    def test_replay_keeps_fork_info(self):
        from maggy_tpu.core.driver.recovery import replay_recovery_state

        params = {"lr": 0.1, "budget": 2}
        tid = Trial._compute_id(params, "optimization")
        events = [
            {"t": 1.0, "ev": "trial", "trial": tid, "phase": "queued",
             "params": params, "trial_type": "optimization",
             "info": {"parent": "par",
                      "forked_from": {"trial": "par", "step": 3},
                      "resume_step": 3}},
            {"t": 1.1, "ev": "trial", "trial": tid, "phase": "running",
             "partition": 0, "epoch": 0},
        ]
        state = replay_recovery_state(events)
        facts = state.trials[tid]
        assert facts.info["forked_from"] == {"trial": "par", "step": 3}
        assert facts.info["resume_step"] == 3
        assert [f.trial_id for f in state.inflight()] == [tid]


# -------------------------------------------------- invariant 14 (offline)


class TestInvariant14Offline:
    def _journal(self, resumed_step=3, fork_edges=1, resumed=True):
        events = [
            {"t": 0.5, "ev": "experiment", "phase": "start"},
            {"t": 1.0, "ev": "trial", "trial": "kid", "phase": "queued"},
        ]
        for _ in range(fork_edges):
            events.append({"t": 1.1, "ev": "trial", "trial": "kid",
                           "phase": "forked_from", "partition": 0,
                           "parent": "par", "step": 3})
        events.append({"t": 1.2, "ev": "chaos", "kind": "kill_fork",
                       "trial": "kid", "partition": 0})
        events.append({"t": 1.5, "ev": "trial", "trial": "kid",
                       "phase": "requeued", "partition": 0,
                       "reason": "heartbeat_loss"})
        if resumed:
            events.append({"t": 1.6, "ev": "trial", "trial": "kid",
                           "phase": "resumed", "partition": 1,
                           "from_step": resumed_step})
        events += [
            {"t": 2.0, "ev": "trial", "trial": "kid", "phase": "running",
             "partition": 1},
            {"t": 3.0, "ev": "trial", "trial": "kid", "phase": "finalized",
             "partition": 1},
            {"t": 4.0, "ev": "experiment", "phase": "finalized"},
        ]
        return events

    def _check(self, **kw):
        from maggy_tpu.chaos.harness import check_invariants

        return check_invariants(self._journal(**kw))

    def test_clean_fork_recovery_passes(self):
        report = self._check()
        assert report["ok"], report["violations"]
        assert report["forks"] == [{"trial": "kid", "partition": 0,
                                    "step": 3,
                                    "outcome": "resumed_from_fork",
                                    "from_step": 3}]

    def test_missing_resume_flagged(self):
        report = self._check(resumed=False)
        assert any("fork lost" in v for v in report["violations"])

    def test_wrong_fork_point_flagged(self):
        report = self._check(resumed_step=0)
        assert any("fork point drifted" in v
                   for v in report["violations"])

    def test_duplicate_lineage_flagged(self):
        report = self._check(fork_edges=2)
        assert any("lineage not exactly-once" in v
                   for v in report["violations"])


# --------------------------------------------- fleet parent affinity


class TestSchedulerParentAffinity:
    def _scheduler(self):
        from maggy_tpu.fleet.scheduler import FleetPolicy, FleetScheduler

        sched = FleetScheduler(1, max_size=4)
        entries = []
        for name in ("expA", "expB"):
            e = sched.submit(name, FleetPolicy())
            e.train_fn_path = "pkg.mod:train"  # SAME family on purpose
            e.state = "active"
            sched._active[name] = e
            e.executor_fn = lambda pid: None
            e.agent_info = {"train_fn": "pkg.mod:train",
                            "family": "pkg.mod:train"}
            e.slots = 4
            e.free_pids = {0, 1, 2, 3}
            entries.append(e)
        sched._queued_count = 0
        return sched, entries

    def test_same_experiment_beats_same_family(self):
        sched, (ea, eb) = self._scheduler()
        slot = sched.agent_slot_attach()
        with sched._lock:
            # Both experiments share a family; the agent last served B.
            sched._slot_family[slot] = "pkg.mod:train"
            sched._slot_exp[slot] = "expB"
            picked = sched._pick_locked(slot)
        assert picked is eb  # parent affinity: checkpoints live there

    def test_lease_event_grades_affinity(self):
        sched, (ea, _eb) = self._scheduler()
        slot = sched.agent_slot_attach()
        recorded = []
        sched._event = lambda ev, **f: recorded.append((ev, f))
        with sched._lock:
            sched._lease_locked(slot, ea)
        assert recorded[-1][1]["warm_affinity"] is None  # cold
        with sched._lock:
            sched.release_binding(slot, ea, recorded[-1][1]["pid"])
        with sched._lock:
            sched._lease_locked(slot, ea)
        assert recorded[-1][1]["warm_affinity"] == "experiment"
        # Detach wipes both hints (fresh interpreter on slot reuse).
        sched.agent_slot_detach(slot)
        with sched._lock:
            assert slot not in sched._slot_exp

    def test_replay_counts_experiment_affinity(self, tmp_path):
        from maggy_tpu.fleet.scheduler import replay_fleet_journal

        path = str(tmp_path / "fleet.jsonl")
        with open(path, "w") as f:
            for ev in [
                {"t": 1.0, "ev": "lease", "exp": "a", "runner": 2,
                 "pid": 0, "phase": "start", "warm_hint": False},
                {"t": 2.0, "ev": "lease", "exp": "a", "runner": 2,
                 "pid": 0, "phase": "end", "reason": "released"},
                {"t": 3.0, "ev": "lease", "exp": "a", "runner": 2,
                 "pid": 0, "phase": "start", "warm_hint": True,
                 "warm_affinity": "experiment"},
            ]:
                f.write(json.dumps(ev) + "\n")
        replay = replay_fleet_journal(path)
        assert replay["agents"]["warm_hint_hits"] == 1
        assert replay["agents"]["warm_affinity_exp"] == 1


class TestDriverForkAffinity:
    def test_hold_and_pop(self, tmp_path):
        driver = _driver(tmp_path)
        try:
            # Parent ran on partition 1 (span partition).
            driver.telemetry.trial_event("par", "running", partition=1)
            driver.telemetry.trial_event("par", "finalized", partition=1)
            driver.server.reservations.add({"partition_id": 0,
                                            "task_attempt": 0})
            driver.server.reservations.add({"partition_id": 1,
                                            "task_attempt": 0})
            trial = Trial({"lr": 0.1, "budget": 2},
                          info_dict={"parent": "par",
                                     "forked_from": {"trial": "par",
                                                     "step": 3},
                                     "resume_step": 3})
            with driver._store_lock:
                driver._trial_store[trial.trial_id] = trial
            with driver._sched_lock:
                held = driver._maybe_hold_for_parent(trial, 0)
            assert held  # partition 1 holds the parent's warm state
            # Partition 0 cannot take it before the deadline...
            assert driver._pop_fork_hold(0) is None
            # ...but the preferred partition gets it immediately.
            assert driver._pop_fork_hold(1) is trial
            # Held at most once: a re-dispatch attempt never re-holds.
            with driver._sched_lock:
                assert not driver._maybe_hold_for_parent(trial, 0)
        finally:
            driver.stop()

    def test_expired_hold_taken_by_anyone(self, tmp_path, monkeypatch):
        from maggy_tpu import constants

        monkeypatch.setattr(constants, "FORK_AFFINITY_HOLD_S", 0.0)
        driver = _driver(tmp_path)
        try:
            driver.telemetry.trial_event("par", "running", partition=1)
            driver.server.reservations.add({"partition_id": 1,
                                            "task_attempt": 0})
            trial = Trial({"lr": 0.1, "budget": 2},
                          info_dict={"forked_from": {"trial": "par",
                                                     "step": 3}})
            with driver._store_lock:
                driver._trial_store[trial.trial_id] = trial
            with driver._sched_lock:
                assert driver._maybe_hold_for_parent(trial, 0)
            time.sleep(0.01)
            assert driver._pop_fork_hold(0) is trial  # deadline passed
        finally:
            driver.stop()


# ------------------------------------------------------- bench comparator


class TestBenchForkHelpers:
    def test_journal_schedule_parity(self):
        import bench

        a = [{"ev": "trial", "phase": "finalized", "trial": "x"},
             {"ev": "trial", "phase": "finalized", "trial": "y"}]
        b = list(a)
        rec = bench.journal_schedule_parity(a, b)
        assert rec["match"] and rec["symmetric_difference"] == []
        rec = bench.journal_schedule_parity(
            a, a[:1], label_a="fork_trials", label_b="scratch_trials")
        assert not rec["match"]
        assert rec["fork_trials"] == 2 and rec["scratch_trials"] == 1
        assert rec["symmetric_difference"] == ["y"]


# ------------------------------------------------------------ e2e parity


def _fork_sweep(tmp_path, name, fork=True, warm_start=True, seed=7):
    from maggy_tpu import OptimizationConfig, Searchspace, experiment
    from maggy_tpu.chaos.harness import fork_ckpt_train_fn
    from maggy_tpu.optimizers import Asha
    from maggy_tpu.telemetry import JOURNAL_NAME, read_events

    base = str(tmp_path / name)
    config = OptimizationConfig(
        name=name, num_trials=4,
        optimizer=Asha(reduction_factor=2, resource_min=1,
                       resource_max=2, seed=seed),
        searchspace=Searchspace(lr=("DOUBLE", [0.05, 0.2])),
        direction="max", num_workers=2, hb_interval=0.02,
        es_policy="none", seed=seed, fork=fork, warm_start=warm_start,
        experiment_dir=base)
    result = experiment.lagom(fork_ckpt_train_fn, config)
    exp_dir = sorted(d for d in glob.glob(os.path.join(base, "*"))
                     if os.path.isdir(d))[-1]
    events = read_events(os.path.join(exp_dir, JOURNAL_NAME))
    trials = {}
    for td in glob.glob(os.path.join(exp_dir, "*", "trial.json")):
        with open(td) as f:
            d = json.load(f)
        trials[d["id"]] = d
    return result, events, trials


@pytest.mark.timeout(180)
class TestForkParityE2E:
    """Bitwise fork parity: a promoted trial's losses equal the parent's
    continuation from the forked checkpoint — warm and cold — and
    config.fork=False restores from-scratch promotions bit-for-bit."""

    def _forked_children(self, events, trials):
        forked = {ev["trial"]: ev for ev in events
                  if ev.get("ev") == "trial"
                  and ev.get("phase") == "forked_from"}
        return {tid: (trials[tid], ev["step"])
                for tid, ev in forked.items() if tid in trials}

    def _assert_continuation_parity(self, children):
        """Every forked child's recorded trajectory equals a
        from-checkpoint continuation of its parent, bit for bit (the
        trial body is a closed form of (lr, step), so the continuation
        is computable without re-running the parent)."""
        from maggy_tpu.chaos.harness import fork_step_metric

        for tid, (t, fork_step) in children.items():
            lr = t["params"]["lr"]
            total = 4 * int(t["params"]["budget"])
            recorded = dict(zip(t["step_history"], t["metric_history"]))
            # Never re-trains the parent's prefix...
            assert not [s for s in recorded if s <= fork_step]
            # ...and every recorded step equals the parent's
            # from-checkpoint continuation, bit for bit.
            for s, v in recorded.items():
                assert v == fork_step_metric(lr, int(s))
            assert t["final_metric"] == fork_step_metric(lr, total - 1)

    def test_forked_losses_equal_parent_continuation(self, tmp_path):
        _, events, trials = _fork_sweep(tmp_path, "fork_on", fork=True)
        children = self._forked_children(events, trials)
        assert children, "no promotion forked"
        self._assert_continuation_parity(children)

    def test_cold_runners_identical_parity(self, tmp_path):
        # warm_start=False: the warm harness is out of the path entirely;
        # fork parity must hold identically (fresh-state discipline is
        # not what makes forks correct — the staged checkpoint is).
        # Deliberately NOT compared child-by-child against a second warm
        # sweep: ASHA's exhaustion latch makes the promotion TAIL
        # timing-dependent, so two runs may promote different children —
        # the closed-form continuation is the run-independent oracle.
        _, events, trials = _fork_sweep(tmp_path, "fork_cold", fork=True,
                                        warm_start=False)
        children = self._forked_children(events, trials)
        assert children, "no promotion forked (cold)"
        self._assert_continuation_parity(children)

    def test_fork_false_restores_from_scratch_bit_for_bit(self, tmp_path):
        from maggy_tpu.chaos.harness import fork_step_metric

        _, events, trials = _fork_sweep(tmp_path, "fork_off", fork=False)
        assert not [ev for ev in events
                    if ev.get("phase") == "forked_from"], \
            "fork=False must never stamp lineage"
        assert not [ev for ev in events if ev.get("ev") == "ckpt_gc"]
        promoted = {tid: t for tid, t in trials.items()
                    if (t.get("info_dict") or {}).get("parent")}
        assert promoted, "no promotions ran"
        for tid, t in promoted.items():
            # From-scratch: the prefix IS re-trained (step 0 present or
            # at least steps below the parent budget's horizon), and the
            # final equals the same closed form — identical to the
            # pre-fork behavior.
            lr = t["params"]["lr"]
            total = 4 * int(t["params"]["budget"])
            assert min(t["step_history"]) < total // 2
            assert t["final_metric"] == fork_step_metric(lr, total - 1)

    def test_fork_and_scratch_same_rung0_schedule(self, tmp_path):
        # The promotion TAIL is timing-dependent (forking tops the
        # ladder sooner, and ASHA's exhaustion latch ends the sweep);
        # parity is well-defined over the seeded rung-0 base schedule,
        # which both arms must execute identically.
        import bench

        _, ev_on, _ = _fork_sweep(tmp_path, "sched_on", fork=True)
        _, ev_off, _ = _fork_sweep(tmp_path, "sched_off", fork=False)
        assert bench.journal_schedule_parity(
            bench.rung0_events(ev_on), bench.rung0_events(ev_off))["match"]
