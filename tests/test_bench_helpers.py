"""Unit tests for bench.py's analysis helpers (the judged artifact's
measurement code must itself be trustworthy)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


class TestHandoffGaps:
    def _trial(self, partition, start, duration):
        return {"info_dict": {"partition": partition}, "start": start,
                "duration": duration}

    def test_gaps_are_per_partition(self):
        trials = [
            self._trial(0, 0.0, 1.0),   # p0: ends 1.0
            self._trial(0, 1.01, 1.0),  # p0: 10ms gap
            self._trial(1, 0.0, 2.0),   # p1: ends 2.0
            self._trial(1, 2.05, 1.0),  # p1: 50ms gap
        ]
        out = bench.handoff_gaps(trials)
        assert out["n"] == 2
        assert out["median_ms"] in (10.0, 50.0)

    def test_barrier_idle_excluded(self):
        trials = [
            self._trial(0, 0.0, 1.0),
            self._trial(0, 4.0, 1.0),   # 3s idle: rung barrier, not overhead
            self._trial(0, 5.002, 1.0),  # 2ms: real hand-off
        ]
        out = bench.handoff_gaps(trials)
        assert out["n"] == 1
        assert out["median_ms"] == pytest.approx(2.0, abs=0.5)

    def test_requeue_overlap_excluded(self):
        # A requeued trial can START before the falsely-lost original ended:
        # negative gaps must not pollute the overhead stat.
        trials = [
            self._trial(0, 0.0, 2.0),
            self._trial(0, 1.5, 1.0),
        ]
        assert bench.handoff_gaps(trials) == {}

    def test_missing_fields_skipped(self):
        # The two invalid rows would create spurious gaps if NOT skipped
        # (an info-less trial grouped under partition None, and a
        # start-less one under partition 0 between the two valid runs).
        trials = [
            {"info_dict": {}, "start": 0.2, "duration": 1.0},
            {"info_dict": {"partition": 0}, "start": None, "duration": 1.0},
            self._trial(0, 0.0, 1.0),
            self._trial(0, 1.02, 1.0),
        ]
        out = bench.handoff_gaps(trials)
        assert out["n"] == 1
        assert out["median_ms"] == pytest.approx(20.0, abs=0.5)


class TestChipPeak:
    def test_known_kinds_map(self, monkeypatch):
        class FakeDev:
            def __init__(self, kind):
                self.device_kind = kind

        import jax

        for kind, peak in [("TPU v5 lite", 197e12), ("TPU v4", 275e12),
                           ("TPU v5p x", 459e12)]:
            monkeypatch.setattr(jax, "devices", lambda k=kind: [FakeDev(k)])
            got_kind, got_peak = bench.chip_peak_flops()
            assert got_kind == kind and got_peak == peak

    def test_unknown_kind_raises(self, monkeypatch):
        """A device that is not in the table is an error, not a default:
        a utilisation against a guessed peak is not a measurement."""
        class FakeDev:
            device_kind = "TPU v99 mega"

        import jax

        monkeypatch.setattr(jax, "devices", lambda: [FakeDev()])
        with pytest.raises(ValueError, match="TPU v99 mega"):
            bench.chip_peak_flops()


class TestStageBaselines:
    """The baselines' scheduling mechanics, with train_mnist stubbed out."""

    def _record_runs(self, monkeypatch):
        import threading

        runs, active, peak = [], [0], [0]
        lock = threading.Lock()

        def fake_train(lr, batch=256, budget=1, reporter=None):
            with lock:
                active[0] += 1
                peak[0] = max(peak[0], active[0])
            try:
                import time

                time.sleep(0.02 * budget)
                with lock:
                    runs.append((lr, batch, budget))
            finally:
                with lock:
                    active[0] -= 1

        monkeypatch.setattr(bench, "train_mnist", fake_train)
        return runs, peak

    def test_packed_runs_everything_with_bounded_concurrency(self, monkeypatch):
        runs, peak = self._record_runs(monkeypatch)
        sched = [(0.1 * i, 128, 1 + (i % 3)) for i in range(10)]
        bench.run_packed_baseline(sched, workers=3)
        assert sorted(runs) == sorted(sched)
        # Actually packed: overlap happened (sleepy trials + 3 workers),
        # but never more than the worker count.
        assert 2 <= peak[0] <= 3

    def test_packed_propagates_trial_failure(self, monkeypatch):
        def boom(lr, batch=256, budget=1, reporter=None):
            raise RuntimeError("trial exploded")

        monkeypatch.setattr(bench, "train_mnist", boom)
        with pytest.raises(RuntimeError, match="exploded"):
            bench.run_packed_baseline([(0.1, 128, 1)], workers=2)

    def test_sync_sha_orders_rungs_with_barriers(self, monkeypatch):
        runs, _ = self._record_runs(monkeypatch)
        rungs = {0: [(0.1, 128, 1), (0.2, 256, 1), (0.3, 512, 1)],
                 1: [(0.1, 128, 3)],
                 2: [(0.1, 128, 9)]}
        bench.run_sync_sha_baseline(rungs, workers=2)
        budgets = [b for (_, _, b) in runs]
        # Barrier between rungs: every rung-0 run completes before the
        # rung-1 run starts, which completes before rung 2.
        assert budgets.index(3) >= 3
        assert budgets.index(9) == len(budgets) - 1
        assert len(runs) == 5


class TestFailedPhaseIsNonZeroExit:
    """The orchestrator's exit code: a headline that produced no result
    prints NO metric line, and an extra that crashed, timed out or was
    skipped turns the run non-zero (the measured headline still prints)."""

    HEADLINE = {"metric": bench.HEADLINE_METRIC, "value": 1234.5,
                "unit": bench.HEADLINE_UNIT, "vs_baseline": 1.1, "detail": {}}

    def _run(self, monkeypatch, capsys, children, extras="bert"):
        monkeypatch.setenv("MAGGY_TPU_BASE_DIR", "/nonexistent-unused")
        monkeypatch.setenv("BENCH_EXTRAS", extras)
        monkeypatch.delenv("BENCH_SKIP_EXTRAS", raising=False)
        monkeypatch.setattr(bench, "log", lambda *a, **k: None)
        monkeypatch.setattr(
            bench, "_run_child", lambda argv, timeout_s: children[argv[0]])
        rc = bench.main()
        lines = [json.loads(line) for line in
                 capsys.readouterr().out.splitlines() if line.startswith("{")]
        return rc, lines

    def test_crashed_extra_is_nonzero(self, monkeypatch, capsys):
        rc, lines = self._run(monkeypatch, capsys, {
            "--headline": ("ok", dict(self.HEADLINE)),
            "--extra": ("crash", {"stderr_tail": "boom"})})
        assert rc == 1
        assert lines[0]["value"] == 1234.5
        assert lines[-1]["detail"]["bert"]["error"].startswith("crashed")

    def test_timed_out_extra_is_nonzero(self, monkeypatch, capsys):
        rc, lines = self._run(monkeypatch, capsys, {
            "--headline": ("ok", dict(self.HEADLINE)),
            "--extra": ("timeout", None)})
        assert rc == 1
        assert lines[-1]["detail"]["bert"]["error"].startswith("timeout")

    def test_failed_headline_prints_no_metric(self, monkeypatch, capsys):
        for child in (("crash", {"stderr_tail": "no TPU"}), ("timeout", None)):
            rc, lines = self._run(monkeypatch, capsys, {"--headline": child})
            assert rc == 1 and lines == []

    def test_all_phases_ok_is_zero(self, monkeypatch, capsys):
        rc, lines = self._run(monkeypatch, capsys, {
            "--headline": ("ok", dict(self.HEADLINE)),
            "--extra": ("ok", {"mfu": 0.4, "platform": "tpu"})})
        assert rc == 0 and len(lines) == 2
        assert lines[1]["detail"]["bert"]["platform"] == "tpu"


class TestTraceArtifact:
    """bench.py must validate the emitted timeline parses as Chrome-trace
    JSON before recording its path — a BENCH artifact must never point at
    an unloadable file."""

    def _journal(self, exp_dir):
        import json as _json

        from maggy_tpu.telemetry import JOURNAL_NAME

        events = [
            {"t": 1.0, "ev": "trial", "trial": "a", "phase": "queued"},
            {"t": 1.1, "ev": "trial", "trial": "a", "phase": "assigned",
             "partition": 0},
            {"t": 1.2, "ev": "trial", "trial": "a", "phase": "running",
             "partition": 0},
            {"t": 2.0, "ev": "trial", "trial": "a", "phase": "finalized",
             "partition": 0},
        ]
        with open(os.path.join(exp_dir, JOURNAL_NAME), "w") as f:
            for ev in events:
                f.write(_json.dumps(ev) + "\n")

    def test_valid_journal_records_path(self, tmp_path):
        import json as _json

        exp_dir = str(tmp_path)
        self._journal(exp_dir)
        path = bench._export_trace_artifact(exp_dir)
        assert path == os.path.join(exp_dir, "trace.json")
        with open(path) as f:
            assert _json.load(f)["traceEvents"]

    def test_missing_journal_records_none(self, tmp_path):
        assert bench._export_trace_artifact(str(tmp_path)) is None

    def test_unwritable_or_invalid_trace_records_none(self, tmp_path,
                                                      monkeypatch):
        exp_dir = str(tmp_path)
        self._journal(exp_dir)
        # Simulate a writer that produced garbage: validation must refuse
        # to record the path.
        import maggy_tpu.telemetry.trace as trace_mod

        def bad_write(events, out, env=None):
            with open(out, "w") as f:
                f.write("NOT JSON")
            return 1

        monkeypatch.setattr(bench, "log", lambda *a, **k: None)
        real = trace_mod.write_trace
        monkeypatch.setattr(trace_mod, "write_trace", bad_write)
        try:
            assert bench._export_trace_artifact(exp_dir) is None
        finally:
            monkeypatch.setattr(trace_mod, "write_trace", real)


class TestSchedulingTelemetryCompile:
    """detail.compile rides the same journal replay as handoff/suggest —
    and pre-warm journals (or the trial.json fallback) degrade to an
    empty block instead of crashing the bench."""

    def _write_journal(self, exp_dir, events):
        import json as _json

        from maggy_tpu.telemetry import JOURNAL_NAME

        with open(os.path.join(exp_dir, JOURNAL_NAME), "w") as f:
            for ev in events:
                f.write(_json.dumps(ev) + "\n")

    def test_compile_block_replayed(self, tmp_path):
        exp_dir = str(tmp_path)
        self._write_journal(exp_dir, [
            {"t": 1.0, "ev": "trial", "trial": "a", "phase": "compiled",
             "partition": 0, "warm": False, "ttfm_ms": 4000.0,
             "compile_ms": 2000.0},
            {"t": 2.0, "ev": "trial", "trial": "b", "phase": "compiled",
             "partition": 0, "warm": True, "ttfm_ms": 30.0},
        ])
        sched = bench.scheduling_telemetry(exp_dir, [])
        assert sched["source"] == "telemetry_journal"
        assert sched["compile"]["warm_hits"] == 1
        assert sched["compile"]["ttfm_cold"]["median_ms"] == 4000.0

    def test_pre_warm_journal_empty_block(self, tmp_path):
        exp_dir = str(tmp_path)
        self._write_journal(exp_dir, [
            {"t": 1.0, "ev": "trial", "trial": "a", "phase": "queued"},
        ])
        assert bench.scheduling_telemetry(exp_dir, [])["compile"] == {}

    def test_trial_json_fallback_has_empty_block(self, tmp_path):
        sched = bench.scheduling_telemetry(str(tmp_path), [])
        assert sched["source"] == "trial_json_fallback"
        assert sched["compile"] == {}


class TestAnalysisDetail:
    """detail.analysis carries the static posture (and, for soaks, the
    witness edge count) so concurrency-discipline drift is visible in the
    bench trajectory without re-running the analyzer."""

    def test_posture_on_clean_repo(self):
        d = bench.analysis_detail()
        assert d["findings"] == 0
        assert set(d["per_checker"]) == {"guards", "lockorder", "rpcconf",
                                         "journalvocab"}
        assert d["locks"] >= 30 and d["order_edges"] >= 20
        assert "witness_edges" not in d  # no soak ran under the witness

    def test_witness_block_merged(self):
        d = bench.analysis_detail(
            {"edge_count": 17, "violations": ["lock-order violation: x"]})
        assert d["witness_edges"] == 17
        assert d["witness_violations"] == 1

    def test_analyzer_failure_is_best_effort(self, monkeypatch):
        import maggy_tpu.analysis as _an

        def boom(*a, **kw):
            raise RuntimeError("parse exploded")

        monkeypatch.setattr(_an, "run_analysis", boom)
        d = bench.analysis_detail({"edge_count": 3, "violations": []})
        assert "parse exploded" in d["error"]
        assert d["witness_edges"] == 3
