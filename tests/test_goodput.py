"""Chip-time goodput ledger tests (maggy_tpu.telemetry.goodput).

The fold is a PURE function over journal events, so most tests here
hand-build journals with known wall-clock geometry and assert the
ledger to float precision. The load-bearing identity — pinned in
several shapes below — is exact closure: ``sum(buckets) == held_chip_s``
per partition and fleet-wide, with drift surfacing as ``unaccounted``
instead of silently vanishing. The end of the file exercises the seams
(rotation, driver failover, sink-merged sources, skewed clocks), the
live surfaces (TELEM snapshot, gauges, CLI), and the real elastic
PROCESS-pool recovery soak whose dead attempt must land in ``rework``.
"""

import json
import os
import time

import pytest

from maggy_tpu.telemetry.goodput import (GOODPUT_BUCKETS, compute_goodput,
                                         merge_corrected, render_goodput)

pytestmark = pytest.mark.goodput

EPS = 1e-6


# ------------------------------------------------------------ journal DSL


def _reg(t, pid):
    return {"t": t, "ev": "runner", "phase": "registered", "partition": pid}


def _tev(t, trial, phase, **fields):
    return {"t": t, "ev": "trial", "trial": trial, "span": trial,
            "phase": phase, **fields}


def _end(t):
    return {"t": t, "ev": "experiment", "phase": "end"}


def _assert_closure(gp):
    """The tested identity: buckets sum exactly to held time, fleet-wide
    and per partition."""
    assert abs(sum(gp["buckets"].values()) - gp["held_chip_s"]) < EPS
    for pid, p in gp["per_partition"].items():
        assert abs(sum(p["buckets"].values()) - p["held_s"]) < EPS, \
            "partition {} leaks chip-time".format(pid)


# ------------------------------------------------------------ pure fold


class TestFold:

    def test_empty_and_runnerless_journals(self):
        assert compute_goodput([]) == {}
        assert compute_goodput([_end(5.0)]) == {}

    def test_single_trial_all_train(self):
        gp = compute_goodput([
            _reg(0.0, 0),
            _tev(0.0, "t1", "running", partition=0),
            _tev(10.0, "t1", "finalized", partition=0),
            _end(10.0),
        ])
        assert abs(gp["held_chip_s"] - 10.0) < EPS
        assert abs(gp["buckets"]["train"] - 10.0) < EPS
        assert gp["goodput_fraction"] == 1.0
        assert gp["unaccounted_fraction"] == 0.0
        assert set(gp["buckets"]) == set(GOODPUT_BUCKETS)
        _assert_closure(gp)

    def test_compile_and_ckpt_subslices(self):
        gp = compute_goodput([
            _reg(0.0, 0),
            _tev(0.0, "t1", "running", partition=0),
            _tev(2.0, "t1", "compiled", partition=0,
                 init_ms=1000.0, trace_ms=500.0, compile_ms=1500.0),
            _tev(5.0, "t1", "ckpt_saved", partition=0,
                 save_ms=1000.0, restore_ms=500.0, saves=2, restores=1),
            _tev(10.0, "t1", "finalized", partition=0),
            _end(10.0),
        ])
        bk = gp["buckets"]
        assert abs(bk["init"] - 1.0) < EPS
        assert abs(bk["trace"] - 0.5) < EPS
        assert abs(bk["compile"] - 1.5) < EPS
        assert abs(bk["ckpt_save"] - 1.0) < EPS
        assert abs(bk["ckpt_restore"] - 0.5) < EPS
        assert abs(bk["train"] - 5.5) < EPS  # 10 - 4.5 attributed
        _assert_closure(gp)

    def test_fork_stage_subslice(self):
        gp = compute_goodput([
            _reg(0.0, 0),
            _tev(0.0, "c", "running", partition=0),
            _tev(1.0, "c", "compiled", partition=0, fork_load_ms=2000.0),
            _tev(8.0, "c", "finalized", partition=0),
            _end(8.0),
        ])
        assert abs(gp["buckets"]["fork_stage"] - 2.0) < EPS
        assert abs(gp["buckets"]["train"] - 6.0) < EPS
        _assert_closure(gp)

    def test_subslices_attach_once_not_per_attempt(self):
        # The dead first attempt books pure rework; the compiled record
        # attaches exactly once, to the surviving attempt.
        gp = compute_goodput([
            _reg(0.0, 0), _reg(0.0, 1),
            _tev(0.0, "t1", "running", partition=0),
            _tev(0.5, "t1", "compiled", partition=0, compile_ms=1000.0),
            _tev(2.0, "t1", "requeued", partition=0, reason="runner_lost"),
            _tev(2.0, "t1", "running", partition=1),
            _tev(6.0, "t1", "finalized", partition=1),
            _end(6.0),
        ])
        bk = gp["buckets"]
        assert abs(bk["rework"] - 2.0) < EPS
        assert abs(bk["compile"] - 1.0) < EPS
        assert abs(bk["train"] - 3.0) < EPS
        _assert_closure(gp)

    def test_dead_attempt_books_rework_not_unaccounted(self):
        gp = compute_goodput([
            _reg(0.0, 0), _reg(0.0, 1),
            _tev(1.0, "t1", "running", partition=0),
            _tev(3.0, "t1", "requeued", partition=0, reason="runner_lost"),
            _tev(3.5, "t1", "running", partition=1),
            _tev(6.0, "t1", "finalized", partition=1),
            _end(6.0),
        ])
        bk = gp["buckets"]
        assert abs(bk["rework"] - 2.0) < EPS
        assert abs(bk["train"] - 2.5) < EPS
        assert bk["unaccounted"] < EPS
        assert abs(gp["per_trial"]["t1"]["rework"] - 2.0) < EPS
        _assert_closure(gp)

    def test_preemption_closes_productively(self):
        # requeued with reason=preempted preserved its checkpoint: the
        # first attempt's work is NOT re-trained, so no rework.
        gp = compute_goodput([
            _reg(0.0, 0),
            _tev(0.0, "t1", "running", partition=0),
            _tev(3.0, "t1", "requeued", partition=0, reason="preempted"),
            _tev(3.0, "t1", "running", partition=0),
            _tev(6.0, "t1", "finalized", partition=0),
            _end(6.0),
        ])
        assert gp["buckets"]["rework"] < EPS
        assert abs(gp["buckets"]["train"] - 6.0) < EPS
        _assert_closure(gp)

    def test_scratch_promotion_carves_parent_prefix_into_rework(self):
        base = [
            _reg(0.0, 0),
            _tev(0.0, "p", "running", partition=0),
            _tev(4.0, "p", "finalized", partition=0),
            _tev(4.0, "c", "queued", info={"parent": "p"}),
            _tev(4.0, "c", "running", partition=0),
            _tev(10.0, "c", "finalized", partition=0),
            _end(10.0),
        ]
        gp = compute_goodput(base)
        # c re-trains p's 4 s prefix from scratch before new work.
        assert abs(gp["per_trial"]["c"]["rework"] - 4.0) < EPS
        assert abs(gp["per_trial"]["c"]["train"] - 2.0) < EPS
        assert abs(gp["buckets"]["train"] - 6.0) < EPS
        _assert_closure(gp)
        # The same child actually forked resumes the parent checkpoint:
        # nothing is re-trained.
        forked = base[:4] + [_tev(4.0, "c", "forked_from", parent="p")] \
            + base[4:]
        gp2 = compute_goodput(forked)
        assert "rework" not in gp2["per_trial"]["c"]
        assert abs(gp2["per_trial"]["c"]["train"] - 6.0) < EPS

    def test_gang_members_multiply_chip_time(self):
        gp = compute_goodput([
            _reg(0.0, 0), _reg(0.0, 1), _reg(0.0, 2), _reg(0.0, 3),
            _tev(0.0, "g1", "gang_assembled", partition=0,
                 members=[0, 1, 2, 3]),
            _tev(0.0, "g1", "running", partition=0),
            _tev(10.0, "g1", "finalized", partition=0),
            _tev(10.0, "g1", "gang_released", partition=0),
            _end(10.0),
        ])
        # 4 chips x 10 wall seconds.
        assert abs(gp["held_chip_s"] - 40.0) < EPS
        assert abs(gp["buckets"]["train"] - 40.0) < EPS
        assert abs(gp["per_trial"]["g1"]["train"] - 40.0) < EPS
        for pid in (0, 1, 2, 3):
            assert abs(gp["per_partition"][pid]["held_s"] - 10.0) < EPS
        _assert_closure(gp)

    def test_gang_members_mirror_leader_proportions(self):
        gp = compute_goodput([
            _reg(0.0, 0), _reg(0.0, 1),
            _tev(0.0, "g1", "gang_assembled", partition=0, members=[0, 1]),
            _tev(0.0, "g1", "running", partition=0),
            _tev(1.0, "g1", "compiled", partition=0, compile_ms=5000.0),
            _tev(10.0, "g1", "finalized", partition=0),
            _tev(10.0, "g1", "gang_released", partition=0),
            _end(10.0),
        ])
        # Leader: 5 compile + 5 train; member 1 mirrors the split.
        m = gp["per_partition"][1]["buckets"]
        assert abs(m["compile"] - 5.0) < EPS
        assert abs(m["train"] - 5.0) < EPS
        _assert_closure(gp)

    def test_queue_wait_handoff_idle_gap_classification(self):
        gp = compute_goodput([
            _reg(0.0, 0),
            _tev(1.0, "t1", "running", partition=0),
            _tev(4.0, "t1", "finalized", partition=0),
            _tev(4.5, "t2", "running", partition=0),
            _tev(6.0, "t2", "finalized", partition=0),
            _tev(9.0, "t3", "running", partition=0),
            _tev(10.0, "t3", "finalized", partition=0),
            _end(11.0),
        ])
        bk = gp["buckets"]
        assert abs(bk["queue_wait"] - 1.0) < EPS   # registered -> first run
        assert abs(bk["handoff"] - 0.5) < EPS      # 4 -> 4.5, under the cap
        assert abs(bk["idle"] - 4.0) < EPS         # 6->9 barrier + 10->11
        assert abs(bk["train"] - 5.5) < EPS
        _assert_closure(gp)

    def test_assigned_never_running_is_explicit_unaccounted(self):
        gp = compute_goodput([
            _reg(0.0, 0),
            _tev(1.0, "t1", "assigned", partition=0),
            _tev(3.0, "t1", "lost", partition=0),
            _end(5.0),
        ])
        bk = gp["buckets"]
        assert abs(bk["unaccounted"] - 2.0) < EPS
        assert abs(bk["queue_wait"] - 1.0) < EPS
        assert abs(bk["idle"] - 2.0) < EPS
        _assert_closure(gp)


# ------------------------------------------------- merged / skewed sources


class TestMergedSources:

    def test_merge_corrected_offset_forms(self):
        a = [{"t": 10.0, "ev": "x"}]
        b = [{"t": 107.0, "ev": "y"}]
        merged = merge_corrected({"a": a, "b": b},
                                 {"b": {"offset_s": 100.0}})
        assert [e["ev"] for e in merged] == ["y", "x"]
        assert merged[0]["t"] == 7.0
        assert b[0]["t"] == 107.0  # input stream untouched
        # Plain-float offsets are accepted too.
        merged2 = merge_corrected({"b": b}, {"b": 100.0})
        assert merged2[0]["t"] == 7.0

    def test_skewed_clock_fold_is_corrected(self):
        # The agent's clock reads 100 s ahead of the driver's. Without
        # correction the fold stretches held time across the skew;
        # corrected, the ledger matches the real geometry.
        driver = [_reg(0.0, 0), _end(10.0)]
        agent = [_tev(100.0, "t1", "running", partition=0),
                 _tev(108.0, "t1", "finalized", partition=0)]
        skewed = compute_goodput(
            merge_corrected({"driver": driver, "agent": agent}))
        corrected = compute_goodput(
            merge_corrected({"driver": driver, "agent": agent},
                            {"agent": 100.0}))
        assert abs(corrected["held_chip_s"] - 10.0) < EPS
        assert abs(corrected["buckets"]["train"] - 8.0) < EPS
        assert corrected["goodput_fraction"] == 0.8
        _assert_closure(corrected)
        assert skewed["held_chip_s"] > 100.0  # the skew, made visible
        assert skewed["goodput_fraction"] < 0.1

    def test_sink_merge_is_exactly_once(self):
        from maggy_tpu.telemetry.sink import merge_source_events

        local = [dict(_reg(0.0, 0), sid=1),
                 dict(_tev(0.0, "t1", "running", partition=0), sid=2),
                 dict(_tev(8.0, "t1", "finalized", partition=0), sid=3),
                 dict(_end(10.0), sid=4)]
        shipped = [dict(ev) for ev in local]
        merged = merge_source_events(shipped, local)
        assert len(merged) == len(local)
        gp = compute_goodput(merged)
        assert abs(gp["held_chip_s"] - 10.0) < EPS  # not doubled
        assert abs(gp["buckets"]["train"] - 8.0) < EPS


# ------------------------------------------------------------ journal seams


class TestJournalSeams:

    def test_rotation_seam_is_transparent(self, tmp_path):
        from maggy_tpu.telemetry import read_events

        events = [
            _reg(0.0, 0),
            _tev(1.0, "t1", "running", partition=0),
            _tev(4.0, "t1", "finalized", partition=0),
            _tev(4.5, "t2", "running", partition=0),
            _tev(9.0, "t2", "finalized", partition=0),
            _end(9.0),
        ]
        path = tmp_path / "telemetry.jsonl"
        # First three events landed in a sealed rotation segment, the
        # rest in the active file — one continuous stream to readers.
        with open("{}.000001".format(path), "w") as f:
            f.write("".join(json.dumps(e) + "\n" for e in events[:3]))
        with open(path, "w") as f:
            f.write("".join(json.dumps(e) + "\n" for e in events[3:]))
        gp_disk = compute_goodput(read_events(str(path)))
        gp_mem = compute_goodput(events)
        assert gp_disk == gp_mem
        _assert_closure(gp_disk)

    def test_failover_seam_across_two_driver_epochs(self):
        # Epoch 1 dies mid-trial (no terminal journaled); epoch 2
        # re-registers the runner and re-dispatches. The torn attempt
        # closes conservatively at the next dispatch and the ledger
        # still sums exactly — a crash must not manufacture
        # unaccounted time.
        gp = compute_goodput([
            _reg(0.0, 0),
            _tev(1.0, "t1", "running", partition=0),
            # -- driver crash; epoch 2 below --
            _reg(5.0, 0),
            _tev(5.5, "t1", "running", partition=0),
            _tev(8.0, "t1", "finalized", partition=0),
            _end(8.0),
        ])
        assert abs(gp["held_chip_s"] - 8.0) < EPS
        assert gp["buckets"]["unaccounted"] < EPS
        assert abs(gp["buckets"]["queue_wait"] - 1.0) < EPS
        assert abs(gp["buckets"]["train"] - 7.0) < EPS
        _assert_closure(gp)


# ---------------------------------------------------------- fleet roll-up


class TestFleetRollup:

    def _write_tenant(self, exp_dir, with_sids=False):
        events = [
            _reg(100.0, 0),
            _tev(100.5, "t1", "running", partition=0),
            _tev(108.5, "t1", "finalized", partition=0),
            _end(110.0),
        ]
        if with_sids:
            events = [dict(e, sid=i + 1) for i, e in enumerate(events)]
        os.makedirs(exp_dir, exist_ok=True)
        with open(os.path.join(exp_dir, "telemetry.jsonl"), "w") as f:
            f.write("".join(json.dumps(e) + "\n" for e in events))
        return events

    def _write_fleet(self, home, exp_dir):
        lines = [
            {"t": 100.0, "ev": "lease", "exp": "a", "runner": "r0",
             "pid": 0, "phase": "start", "exp_dir": exp_dir},
            {"t": 109.5, "ev": "lease", "exp": "a", "runner": "r0",
             "pid": 0, "phase": "end", "reason": "experiment_done",
             "duration_s": 9.5},
        ]
        with open(os.path.join(home, "fleet.jsonl"), "w") as f:
            f.write("".join(json.dumps(e) + "\n" for e in lines))

    def test_per_tenant_ledger_from_fleet_replay(self, tmp_path):
        from maggy_tpu.fleet.scheduler import replay_fleet_journal

        home = str(tmp_path / "fleet")
        exp_dir = os.path.join(home, "exp_a")
        os.makedirs(home, exist_ok=True)
        self._write_tenant(exp_dir)
        self._write_fleet(home, exp_dir)
        replay = replay_fleet_journal(home)
        block = replay["goodput"]
        tenant = block["tenants"]["a"]
        assert tenant["chip_seconds"] == 9.5  # lease-derived
        gp = tenant["goodput"]
        # Tenant journal: held 100 -> 110, train 100.5 -> 108.5.
        assert abs(gp["held_chip_s"] - 10.0) < EPS
        assert gp["goodput_fraction"] == 0.8
        assert block["goodput_fraction"] == 0.8
        assert block["chip_seconds"] == 9.5
        _assert_closure(gp)

    def test_sink_merged_tenant_counts_once(self, tmp_path):
        # The tenant's surviving local journal AND its sink-shipped
        # segment both exist: the roll-up merges them exactly-once by
        # event sid, so held time is NOT doubled.
        from maggy_tpu.fleet.scheduler import replay_fleet_journal
        from maggy_tpu.telemetry.sink import SINK_DIR_NAME, sanitize_source

        home = str(tmp_path / "fleet")
        exp_dir = os.path.join(home, "exp_a")
        os.makedirs(home, exist_ok=True)
        events = self._write_tenant(exp_dir, with_sids=True)
        sink_dir = os.path.join(home, SINK_DIR_NAME)
        os.makedirs(sink_dir, exist_ok=True)
        shipped = os.path.join(sink_dir,
                               sanitize_source("a") + ".jsonl")
        with open(shipped, "w") as f:
            f.write("".join(json.dumps(e) + "\n" for e in events))
        self._write_fleet(home, exp_dir)
        gp = replay_fleet_journal(home)["goodput"]["tenants"]["a"]["goodput"]
        assert abs(gp["held_chip_s"] - 10.0) < EPS
        assert abs(gp["buckets"]["train"] - 8.0) < EPS


# ----------------------------------------------------- ckpt ship channel


class TestCkptChannel:

    def test_note_ckpt_accumulates_and_ships_once(self):
        from maggy_tpu.telemetry.runnerstats import RunnerStats

        stats = RunnerStats()
        stats.trial_start("t1")
        stats.note_ckpt(save_ms=100.0, saves=1, step=3)
        stats.note_ckpt(save_ms=50.0, restore_ms=30.0, saves=1, restores=1)
        stats.trial_end("t1")
        delta = stats.snapshot_delta()
        (rec,) = delta["ckpt_events"]
        assert rec["trial"] == "t1"
        assert rec["save_ms"] == 150.0
        assert rec["restore_ms"] == 30.0
        assert rec["saves"] == 2 and rec["restores"] == 1
        assert rec["step"] == 3  # non-accumulating field: first write wins
        # Delta encoding: already-shipped records don't ship again.
        assert "ckpt_events" not in stats.snapshot_delta()

    def test_requeue_delta_restores_unshipped_records(self):
        from maggy_tpu.telemetry.runnerstats import RunnerStats

        stats = RunnerStats()
        stats.trial_start("t1")
        stats.note_ckpt(save_ms=100.0, saves=1)
        stats.trial_end("t1")
        delta = stats.snapshot_delta()
        assert delta["ckpt_events"]
        stats.requeue_delta(delta)  # the ship failed; put them back
        assert stats.snapshot_delta()["ckpt_events"] == delta["ckpt_events"]

    def test_warm_ckpt_span_noop_outside_trial_scope(self):
        from maggy_tpu.train import warm

        with warm.span("ckpt_save") as sp:  # must not raise
            pass
        assert sp.t_start is None  # no trial scope: nothing recorded


# ------------------------------------------------------------ surfaces


class TestSurfaces:

    def test_vocab_pin_closed_taxonomy(self):
        # The closed, canonical bucket vocabulary: consumers (monitor,
        # Prometheus exposition, bench gates) match these literals.
        assert GOODPUT_BUCKETS == (
            "train", "init", "trace", "compile", "ckpt_save",
            "ckpt_restore", "fork_stage", "rework", "handoff",
            "queue_wait", "idle", "lane_idle", "unaccounted")

    def test_telem_snapshot_carries_goodput_and_gauges(self):
        from maggy_tpu.telemetry import Telemetry

        telem = Telemetry(enabled=True)
        telem.event("runner", phase="registered", partition=0)
        telem.trial_event("t1", "running", partition=0)
        time.sleep(0.05)
        telem.trial_event("t1", "finalized", partition=0)
        gp = telem.snapshot(fresh=True)["spans"]["goodput"]
        assert gp and gp["held_chip_s"] > 0
        assert set(gp["buckets"]) == set(GOODPUT_BUCKETS)
        block = telem.refresh_goodput_gauges()
        assert block["goodput_fraction"] == gp["goodput_fraction"]
        assert telem.metrics.gauge("goodput.fraction").value == \
            block["goodput_fraction"]
        assert telem.metrics.gauge("goodput.held_chip_s").value > 0
        assert telem.metrics.gauge(
            "goodput.fraction.p0").value is not None

    def test_disabled_telemetry_refresh_is_empty(self):
        from maggy_tpu.telemetry import Telemetry

        assert Telemetry(enabled=False).refresh_goodput_gauges() == {}

    def test_render_goodput_lines(self):
        assert render_goodput({}) == \
            ["goodput: no runner activity in journal"]
        gp = compute_goodput([
            _reg(0.0, 0),
            _tev(1.0, "t1", "running", partition=0),
            _tev(9.0, "t1", "finalized", partition=0),
            _end(10.0),
        ])
        lines = render_goodput(gp)
        assert "goodput: 80.0%" in lines[0]
        assert any("badput" in ln for ln in lines)
        assert any(ln.strip().startswith("p0") for ln in lines)

    def test_cli_goodput_exits_zero(self, tmp_path, capsys):
        from maggy_tpu.telemetry.__main__ import main

        exp_dir = tmp_path / "exp"
        exp_dir.mkdir()
        events = [
            _reg(0.0, 0),
            _tev(1.0, "t1", "running", partition=0),
            _tev(9.0, "t1", "finalized", partition=0),
            _end(10.0),
        ]
        with open(exp_dir / "telemetry.jsonl", "w") as f:
            f.write("".join(json.dumps(e) + "\n" for e in events))
        assert main(["goodput", str(exp_dir)]) == 0
        assert "goodput: 80.0%" in capsys.readouterr().out
        assert main(["goodput", "--json", str(exp_dir)]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["goodput_fraction"] == 0.8

    def test_cli_goodput_fleet_home(self, tmp_path, capsys):
        from maggy_tpu.telemetry.__main__ import main

        home = tmp_path / "fleet"
        home.mkdir()
        exp_dir = os.path.join(str(home), "exp_a")
        roll = TestFleetRollup()
        roll._write_tenant(exp_dir)
        roll._write_fleet(str(home), exp_dir)
        assert main(["goodput", str(home)]) == 0
        out = capsys.readouterr().out
        assert "tenant a: 9.5 leased chip-seconds" in out
        assert "goodput: 80.0%" in out


# --------------------------------------------- elastic PROCESS recovery


class TestElasticRecovery:

    @pytest.mark.timeout(150)
    def test_dead_attempt_lands_in_rework_not_unaccounted(self, tmp_path):
        """A SIGKILLed elastic-pool worker process loses its trial; the
        re-run's predecessor attempt must book ``rework`` chip-time —
        attributed to the faulted trial — while the ledger still closes
        within the 5% unaccounted bound."""
        from maggy_tpu.chaos.harness import run_soak
        from maggy_tpu.chaos.plan import FaultPlan, FaultSpec

        plan = FaultPlan([FaultSpec(
            "kill_runner", trigger={"on_phase": "running", "nth": 2})],
            seed=5)
        report = run_soak(
            plan=plan, seed=5, num_trials=5, workers=2, pool="elastic",
            hb_interval=0.2, hb_loss_timeout=2.0,
            base_dir=str(tmp_path / "esoak"),
            config_overrides={"total_chips": 2, "chips_per_trial": 1})
        assert report["violations"] == []
        gp = report["goodput"]
        assert gp, "elastic soak journal produced no goodput ledger"
        assert gp["buckets"]["rework"] > 0, \
            "the killed attempt's chip-time did not book as rework"
        assert gp["unaccounted_fraction"] is not None
        assert gp["unaccounted_fraction"] <= 0.05
        # Invariant 15's attribution: the rework belongs to the
        # requeue-seamed trial(s), and the report names them.
        assert report["rework"]["trials"]
        assert set(report["rework"]["trials"]) <= set(report["rework"]
                                                      ["seamed"])
