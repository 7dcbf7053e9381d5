"""Unified telemetry subsystem: metrics registry semantics, trial-span
lifecycle across a real driver+runner round trip, journal crash/resume
replay, the TELEM RPC verb + monitor rendering, the bounded-overhead
contract (no blocking I/O on the message hot path), and regression pins
for the satellite fixes that shipped with the subsystem (exclusive-create
registry writes, the resize-watch credit leak, bench orphan remediation,
custom-root registry URIs)."""

import json
import os
import threading
import time

import numpy as np
import pytest

from maggy_tpu import monitor
from maggy_tpu.core.environment import EnvSing
from maggy_tpu.core.environment.abstractenvironment import GCSEnv, LocalEnv
from maggy_tpu.core.rpc import MessageSocket, OptimizationServer
from maggy_tpu.exceptions import AuthenticationError
from maggy_tpu.telemetry import (JOURNAL_NAME, MetricsRegistry, Telemetry,
                                 TelemetryJournal, derive, read_events,
                                 replay_journal)
from maggy_tpu.telemetry.journal import FLUSHER_THREAD_NAME


@pytest.fixture(autouse=True)
def local_env(tmp_path):
    env = LocalEnv(base_dir=str(tmp_path / "exp"))
    EnvSing.set_instance(env)
    yield env
    EnvSing.reset()


# ------------------------------------------------------------------ metrics


class TestMetricsRegistry:
    def test_counter_gauge_semantics(self):
        reg = MetricsRegistry()
        reg.counter("trials").inc()
        reg.counter("trials").inc(4)
        reg.gauge("workers").set(3)
        assert reg.counter("trials").value == 5
        assert reg.gauge("workers").value == 3.0

    def test_histogram_buckets_and_percentiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", bounds=(1.0, 10.0, 100.0))
        for v in (0.5, 0.7, 5.0, 50.0, 5000.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["buckets"] == {"1.0": 2, "10.0": 1, "100.0": 1}
        assert snap["overflow"] == 1
        assert snap["min"] == 0.5 and snap["max"] == 5000.0
        # Upper-bound estimates from the CDF; the +inf bucket reports max.
        assert h.percentile(0.5) == 10.0
        assert h.percentile(0.99) == 5000.0

    def test_get_or_create_returns_same_metric(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        assert reg.histogram("h") is reg.histogram("h")

    def test_snapshot_is_plain_json(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.gauge("g").set(1.5)
        reg.histogram("h").observe(2.0)
        # Must round-trip through json: the TELEM verb ships it verbatim.
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["counters"]["c"] == 1
        assert snap["histograms"]["h"]["count"] == 1

    def test_thread_safety_under_contention(self):
        reg = MetricsRegistry()

        def work():
            for _ in range(1000):
                reg.counter("n").inc()
                reg.histogram("h").observe(1.0)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.counter("n").value == 8000
        assert reg.histogram("h").count == 8000


# ------------------------------------------------------------------- derive


def _trial_events(seq):
    """[(t, trial, phase, extra)] -> journal event dicts."""
    return [{"t": t, "ev": "trial", "trial": trial, "span": "s" + trial,
             "phase": phase, **extra} for t, trial, phase, extra in seq]


class TestDerive:
    def test_handoff_gap_per_partition(self):
        events = _trial_events([
            (10.0, "a", "finalized", {"partition": 0}),
            (10.020, "b", "running", {"partition": 0}),   # 20 ms gap
            (10.5, "c", "finalized", {"partition": 1}),
            (10.540, "d", "running", {"partition": 1}),   # 40 ms gap
        ])
        out = derive(events)
        assert out["handoff"]["n"] == 2
        assert out["handoff"]["median_ms"] == pytest.approx(40.0)

    def test_barrier_idle_and_overlap_excluded(self):
        events = _trial_events([
            (10.0, "a", "finalized", {"partition": 0}),
            (15.0, "b", "running", {"partition": 0}),     # 5 s rung barrier
            (20.0, "c", "finalized", {"partition": 1}),
            (19.0, "d", "running", {"partition": 1}),     # requeue overlap
        ])
        assert derive(events)["handoff"] == {}

    def test_early_stop_reaction(self):
        events = _trial_events([
            (10.0, "a", "stop_flagged", {}),
            (10.150, "a", "finalized", {"partition": 0, "early_stop": True}),
        ])
        out = derive(events)
        assert out["early_stop_reaction"]["median_ms"] == pytest.approx(150.0)
        assert out["trials"]["early_stopped"] == 1

    def test_requeued_trial_counted_once(self):
        # A resumed experiment's continuous journal re-queues in-flight
        # trials: created counts distinct trials, not queued events.
        events = _trial_events([
            (1.0, "a", "queued", {}),
            (2.0, "a", "queued", {}),
            (3.0, "b", "queued", {}),
        ])
        assert derive(events)["trials"]["created"] == 2

    def test_pure_and_deterministic(self):
        events = _trial_events([
            (1.0, "a", "queued", {}),
            (2.0, "a", "finalized", {"partition": 0}),
            (2.001, "b", "running", {"partition": 0}),
        ])
        assert derive(events) == derive(list(events))


# ------------------------------------------------------------------ journal


class _CountingEnv(LocalEnv):
    """LocalEnv recording which THREAD performed each dump — the probe for
    the no-blocking-I/O-on-the-hot-path contract."""

    def __init__(self, base_dir):
        super().__init__(base_dir=base_dir)
        self.dump_threads = []

    def dump(self, data, path):
        self.dump_threads.append((threading.current_thread().name, path))
        super().dump(data, path)


class TestJournal:
    def test_record_is_buffer_only_flush_persists(self, tmp_path):
        env = _CountingEnv(str(tmp_path / "j"))
        path = str(tmp_path / "j" / "telemetry.jsonl")
        # Long flush interval: any dump before the explicit flush() would
        # be a hot-path write.
        journal = TelemetryJournal(env, path, flush_interval_s=3600)
        for i in range(100):
            journal.record({"t": float(i), "ev": "trial", "trial": "x",
                            "phase": "queued"})
        assert env.dump_threads == []  # record() never touched the env
        journal.flush()
        assert len(read_events(path)) == 100
        journal.close()

    def test_flusher_thread_owns_the_io(self, tmp_path):
        env = _CountingEnv(str(tmp_path / "j"))
        path = str(tmp_path / "j" / "telemetry.jsonl")
        journal = TelemetryJournal(env, path, flush_interval_s=0.05)
        journal.record({"t": 1.0, "ev": "trial", "trial": "x",
                        "phase": "queued"})
        deadline = time.monotonic() + 5
        while not env.dump_threads and time.monotonic() < deadline:
            time.sleep(0.01)
        journal.close()
        assert env.dump_threads, "flusher never persisted the journal"
        assert all(name == FLUSHER_THREAD_NAME
                   for name, _ in env.dump_threads)

    def test_crash_resume_keeps_one_continuous_journal(self, tmp_path, local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        first = TelemetryJournal(local_env, path, flush_interval_s=3600)
        first.record({"t": 1.0, "ev": "trial", "trial": "a", "phase": "queued"})
        first.flush()
        # Simulated crash: no close(), a second driver process resumes.
        second = TelemetryJournal(local_env, path, flush_interval_s=3600)
        restored = second.load_existing()
        assert restored == 1
        second.record({"t": 2.0, "ev": "trial", "trial": "b", "phase": "queued"})
        second.close()
        events = read_events(path)
        assert [e["trial"] for e in events] == ["a", "b"]

    def test_incremental_flush_appends_only_new_events(self, tmp_path):
        env = _CountingEnv(str(tmp_path / "j"))
        path = str(tmp_path / "j" / "telemetry.jsonl")
        # Stale file from an unrelated run at the same path: the first
        # flush must truncate it, not append after it.
        env.dump('{"t": 0.0, "ev": "stale"}\n', path)
        env.dump_threads.clear()
        journal = TelemetryJournal(env, path, flush_interval_s=3600)
        journal.record({"t": 1.0, "ev": "trial", "trial": "a", "phase": "queued"})
        journal.flush()   # full rewrite (truncates stale)
        journal.record({"t": 2.0, "ev": "trial", "trial": "b", "phase": "queued"})
        journal.flush()   # append-only
        journal.close()
        assert [e["ev"] for e in read_events(path)] == ["trial", "trial"]
        # Exactly ONE full dump (the first flush); the second went through
        # append mode.
        assert len(env.dump_threads) == 1

    def test_stop_sent_journaled_once_per_span(self, tmp_path, local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        telem = Telemetry(env=local_env, journal_path=path,
                          flush_interval_s=3600)
        for _ in range(5):  # heartbeats keep drawing STOP replies
            telem.trial_event("a", "stop_sent", once=True, partition=0)
        stop_events = [e for e in telem.events()
                       if e.get("phase") == "stop_sent"]
        telem.close()
        assert len(stop_events) == 1
        assert telem.metrics.counter("trial.phase.stop_sent").value == 1

    def test_torn_tail_line_is_skipped(self, tmp_path, local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        local_env.dump('{"t": 1.0, "ev": "trial", "trial": "a"}\n{"t": 2.0, "ev"',
                       path)
        events = read_events(path)
        assert len(events) == 1 and events[0]["trial"] == "a"

    def test_torn_lines_counted_not_hidden(self, tmp_path, local_env):
        """Satellite: skipped lines must be COUNTED — a journal quietly
        shrinking (corruption beyond the expected torn tail) has to be
        visible in read_events, replay_journal, and the TELEM snapshot."""
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        local_env.dump(
            '{"t": 1.0, "ev": "trial", "trial": "a", "phase": "queued"}\n'
            'GARBAGE LINE\n'
            '[1, 2]\n'   # valid JSON, not an event object
            '{"t": 2.0, "ev": "trial", "trial": "a", "phase": "finalized"}\n'
            '{"t": 3.0, "ev"', path)
        events = read_events(path)
        assert len(events) == 2
        assert events.torn_lines == 3
        replayed = replay_journal(path)
        assert replayed["torn_lines"] == 3
        assert replayed["trials"]["finalized"] == 1
        # A resuming journal surfaces the count in the live snapshot.
        journal = TelemetryJournal(local_env, path, flush_interval_s=3600)
        assert journal.load_existing() == 2
        telem = Telemetry(enabled=True)
        telem.journal = journal
        assert telem.snapshot(fresh=True)["journal"]["torn_lines"] == 3
        journal.close()

    def test_clean_journal_reports_zero_torn_lines(self, tmp_path,
                                                   local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        local_env.dump('{"t": 1.0, "ev": "trial", "trial": "a", '
                       '"phase": "queued"}\n', path)
        assert read_events(path).torn_lines == 0
        assert replay_journal(path)["torn_lines"] == 0

    def test_resume_repairs_torn_tail_instead_of_appending_after_it(
            self, tmp_path, local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        # Hard kill mid-append left a partial last line with no newline.
        local_env.dump('{"t": 1.0, "ev": "trial", "trial": "a", '
                       '"phase": "queued"}\n{"t": 2.0, "ev"', path)
        journal = TelemetryJournal(local_env, path, flush_interval_s=3600)
        assert journal.load_existing() == 1
        journal.record({"t": 3.0, "ev": "trial", "trial": "b",
                        "phase": "queued"})
        journal.close()
        # The torn tail is gone and the new event is NOT glued onto it.
        assert [e["trial"] for e in read_events(path)] == ["a", "b"]

    def test_concurrent_flushes_do_not_duplicate_events(self, tmp_path,
                                                        local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")

        class SlowAppendEnv(LocalEnv):
            def open_file(self, p, mode="r"):
                if "a" in mode:
                    time.sleep(0.05)  # widen the race window
                return super().open_file(p, mode)

        env = SlowAppendEnv(base_dir=str(tmp_path / "exp"))
        journal = TelemetryJournal(env, path, flush_interval_s=3600)
        journal.record({"t": 1.0, "ev": "trial", "trial": "a",
                        "phase": "queued"})
        journal.flush()  # first: full rewrite
        journal.record({"t": 2.0, "ev": "trial", "trial": "b",
                        "phase": "queued"})
        threads = [threading.Thread(target=journal.flush) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        journal.close()
        assert [e["trial"] for e in read_events(path)] == ["a", "b"]

    def test_replay_reproduces_derivation_exactly(self, tmp_path, local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        telem = Telemetry(env=local_env, journal_path=path,
                          flush_interval_s=3600)
        telem.trial_event("a", "queued")
        telem.trial_event("a", "running", partition=0)
        telem.trial_event("a", "finalized", partition=0, early_stop=False)
        telem.trial_event("b", "running", partition=0)
        live = telem.snapshot()["spans"]
        telem.close()
        replayed = replay_journal(path)
        # replay additionally reports journal health; a clean journal has
        # zero torn lines and otherwise matches the live derivation bit
        # for bit.
        assert replayed.pop("torn_lines") == 0
        assert replayed == live


# ------------------------------------------- driver+runner round trip (e2e)


def _train(lr, units, reporter=None):
    acc = 1.0 - ((lr - 0.1) ** 2 + ((units - 32) / 64.0) ** 2)
    if reporter is not None:
        for step in range(3):
            reporter.broadcast(acc * (step + 1) / 3.0, step=step)
        time.sleep(0.05)  # let >=1 heartbeat ship a METRIC with the span
    return {"metric": acc}


@pytest.mark.timeout(120)
class TestJournalRotation:
    """Satellite (PR 10): size-based rotation — MAGGY_TPU_JOURNAL_MAX_MB
    (or max_mb) seals the active file into numbered segments; replay and
    resume transparently read the segments in order."""

    def _ev(self, i):
        return {"t": float(i), "ev": "trial", "trial": "t{}".format(i),
                "phase": "queued", "pad": "x" * 64}

    def test_rotation_seals_segments_and_replay_is_continuous(
            self, tmp_path, local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        # ~100-byte events, 1 KB cap -> several segments over 100 events.
        journal = TelemetryJournal(local_env, path, flush_interval_s=3600,
                                   max_mb=1024 / (1024 * 1024.0))
        for i in range(100):
            journal.record(self._ev(i))
            if i % 10 == 9:
                journal.flush()
        journal.close()
        segments = sorted(f for f in os.listdir(str(tmp_path / "exp"))
                          if f.startswith("telemetry.jsonl."))
        assert len(segments) >= 2, "cap never rotated"
        # The active file stays small; the stream reads back complete
        # and IN ORDER across segments + active.
        assert os.path.getsize(path) < 4096
        events = read_events(path)
        assert [e["trial"] for e in events] == \
            ["t{}".format(i) for i in range(100)]
        assert events.torn_lines == 0

    def test_rotation_off_by_default(self, tmp_path, local_env,
                                     monkeypatch):
        monkeypatch.delenv("MAGGY_TPU_JOURNAL_MAX_MB", raising=False)
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        journal = TelemetryJournal(local_env, path, flush_interval_s=3600)
        for i in range(50):
            journal.record(self._ev(i))
            journal.flush()
        journal.close()
        assert [f for f in os.listdir(str(tmp_path / "exp"))
                if f.startswith("telemetry.jsonl.")] == []
        assert len(read_events(path)) == 50

    def test_env_var_arms_rotation(self, tmp_path, local_env, monkeypatch):
        monkeypatch.setenv("MAGGY_TPU_JOURNAL_MAX_MB",
                           str(1024 / (1024 * 1024.0)))
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        journal = TelemetryJournal(local_env, path, flush_interval_s=3600)
        for i in range(60):
            journal.record(self._ev(i))
            if i % 10 == 9:
                journal.flush()
        journal.close()
        assert [f for f in os.listdir(str(tmp_path / "exp"))
                if f.startswith("telemetry.jsonl.")]
        assert len(read_events(path)) == 60

    def test_replay_journal_identical_to_unrotated(self, tmp_path,
                                                   local_env):
        """Same events, rotated vs not: replay_journal must produce the
        same numbers — rotation is a storage detail, not a semantic."""
        rotated = str(tmp_path / "exp" / "rot.jsonl")
        plain = str(tmp_path / "exp" / "plain.jsonl")
        events = []
        for i in range(40):
            events.append({"t": 10.0 + i, "ev": "trial",
                           "trial": "t{}".format(i % 8),
                           "phase": "queued" if i < 8 else "finalized",
                           "partition": i % 2, "pad": "y" * 80})
        j1 = TelemetryJournal(local_env, rotated, flush_interval_s=3600,
                              max_mb=1024 / (1024 * 1024.0))
        j2 = TelemetryJournal(local_env, plain, flush_interval_s=3600)
        for e in events:
            j1.record(dict(e))
            j2.record(dict(e))
            j1.flush()
        j2.flush()
        j1.close()
        j2.close()
        assert [f for f in os.listdir(str(tmp_path / "exp"))
                if f.startswith("rot.jsonl.")]
        assert replay_journal(rotated) == replay_journal(plain)

    def test_resume_restores_across_segments_and_keeps_appending(
            self, tmp_path, local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        cap = 1024 / (1024 * 1024.0)
        first = TelemetryJournal(local_env, path, flush_interval_s=3600,
                                 max_mb=cap)
        for i in range(50):
            first.record(self._ev(i))
            if i % 10 == 9:
                first.flush()
        first.flush()
        # Simulated crash: no close(); a second driver resumes.
        second = TelemetryJournal(local_env, path, flush_interval_s=3600,
                                  max_mb=cap)
        assert second.load_existing() == 50
        for i in range(50, 70):
            second.record(self._ev(i))
            if i % 10 == 9:
                second.flush()
        second.close()
        events = read_events(path)
        assert [e["trial"] for e in events] == \
            ["t{}".format(i) for i in range(70)]
        # The resumed writer must NOT have resurrected the sealed
        # segments into the active file (no duplicates anywhere).
        assert len({e["trial"] for e in events}) == 70

    def test_rotation_with_rewrite_only_backend(self, tmp_path):
        """Object-store-shaped env (no append): the rewrite path must
        rewrite only the ACTIVE file's share, so rotation still bounds
        per-flush work and replay stays exact."""

        class NoAppendEnv(LocalEnv):
            def open_file(self, p, mode="r"):
                if mode == "a":
                    raise OSError("append not supported")
                return super().open_file(p, mode)

        env = NoAppendEnv(base_dir=str(tmp_path / "exp"))
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        journal = TelemetryJournal(env, path, flush_interval_s=3600,
                                   max_mb=1024 / (1024 * 1024.0))
        for i in range(60):
            journal.record(self._ev(i))
            if i % 10 == 9:
                journal.flush()
        journal.close()
        assert [f for f in os.listdir(str(tmp_path / "exp"))
                if f.startswith("telemetry.jsonl.")]
        assert [e["trial"] for e in read_events(path)] == \
            ["t{}".format(i) for i in range(60)]

    def test_torn_lines_summed_across_segments(self, tmp_path, local_env):
        path = str(tmp_path / "exp" / "telemetry.jsonl")
        local_env.dump('{"t": 1.0, "ev": "trial", "trial": "a", '
                       '"phase": "queued"}\nGARBAGE\n',
                       path + ".000001")
        local_env.dump('{"t": 2.0, "ev": "trial", "trial": "b", '
                       '"phase": "queued"}\n{"t": 3.0, "ev"', path)
        events = read_events(path)
        assert [e["trial"] for e in events] == ["a", "b"]
        assert events.torn_lines == 2


class TestDriverRoundTrip:
    def _run(self, local_env, **overrides):
        from maggy_tpu import OptimizationConfig, Searchspace, experiment

        config = OptimizationConfig(
            name="telem_e2e", num_trials=4, optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE", [0.0, 0.2]),
                                    units=("INTEGER", [8, 64])),
            direction="max", num_workers=2, hb_interval=0.02, seed=3,
            es_policy="none", **overrides)
        result = experiment.lagom(_train, config)
        exp_dir = os.path.join(local_env.base_dir,
                               os.listdir(local_env.base_dir)[0])
        return result, exp_dir

    def test_span_lifecycle_lands_in_journal(self, local_env):
        result, exp_dir = self._run(local_env)
        assert result["num_trials"] == 4
        events = read_events(os.path.join(exp_dir, JOURNAL_NAME))
        by_trial = {}
        for ev in events:
            if ev["ev"] == "trial":
                by_trial.setdefault(ev["trial"], []).append(ev)
        assert len(by_trial) == 4
        for trial_id, evs in by_trial.items():
            phases = {e["phase"]: e["t"] for e in evs}
            # Full pipeline: queued -> assigned -> running -> finalized,
            # in causal order, all on ONE span id.
            for phase in ("queued", "assigned", "running", "finalized"):
                assert phase in phases, (trial_id, sorted(phases))
            assert phases["queued"] <= phases["assigned"] \
                <= phases["running"] <= phases["finalized"]
            assert len({e["span"] for e in evs}) == 1
        # Runner registrations and experiment lifecycle are journaled too.
        kinds = {(e["ev"], e.get("phase")) for e in events}
        assert ("runner", "registered") in kinds
        assert ("experiment", "start") in kinds
        # 2 runners x 4 trials: at least two hand-offs derive from the
        # journal, and replaying the file reproduces them exactly.
        derived = replay_journal(os.path.join(exp_dir, JOURNAL_NAME))
        assert derived["trials"]["finalized"] == 4
        assert derived["handoff"].get("n", 0) >= 1

    def test_hot_path_threads_never_write_the_journal(self, tmp_path):
        env = _CountingEnv(str(tmp_path / "hot"))
        EnvSing.set_instance(env)
        result, exp_dir = self._run(env)
        journal_dumps = [name for name, path in env.dump_threads
                         if path.endswith(JOURNAL_NAME)]
        assert journal_dumps, "journal was never persisted"
        # The heartbeat-RATE paths (METRIC handling on the RPC loop, the
        # driver's message worker, runner/heartbeat threads) must never
        # persist the journal — buffering + the flusher thread own that.
        assert not [t for t in journal_dumps
                    if t.startswith(("driver-worker", "runner-",
                                     "heartbeat"))], journal_dumps
        # The ONE deliberate exception is the FINAL-path durability
        # barrier (crash-only recovery): the rpc-server thread may flush
        # once per FINAL, before the reply is written, so an acknowledged
        # FINAL can never be absent from the recovery source of truth —
        # PER-TRIAL rate, never per-heartbeat. Bound it: more rpc-thread
        # persistence than FINALs means something heartbeat-rate started
        # writing on the event loop again.
        rpc_dumps = [t for t in journal_dumps if t.startswith("rpc-server")]
        assert len(rpc_dumps) <= result["num_trials"], rpc_dumps

    def test_telemetry_opt_out(self, local_env):
        _, exp_dir = self._run(local_env, telemetry=False)
        assert not os.path.exists(os.path.join(exp_dir, JOURNAL_NAME))

    def test_trace_export_acceptance(self, local_env):
        """`python -m maggy_tpu.telemetry trace` on a finished
        experiment's journal: valid Chrome-trace JSON, >= 1 slice per
        finalized trial, one track per partition that ran."""
        from maggy_tpu.telemetry.__main__ import main as telem_cli
        from maggy_tpu.telemetry.trace import validate_trace

        _, exp_dir = self._run(local_env)
        out = os.path.join(exp_dir, "trace.json")
        assert telem_cli(["trace", exp_dir, "-o", out]) == 0
        with open(out) as f:
            trace = json.load(f)
        validate_trace(trace)
        evs = trace["traceEvents"]
        finalized = {e["trial"] for e in read_events(
            os.path.join(exp_dir, JOURNAL_NAME))
            if e.get("ev") == "trial" and e.get("phase") == "finalized"}
        sliced = {e["args"]["trial"] for e in evs
                  if e["ph"] == "X" and e.get("cat") == "trial"}
        assert finalized and finalized <= sliced
        tracks = {e["args"]["name"] for e in evs
                  if e.get("name") == "process_name"}
        # 2 workers: driver + a track per partition that served a trial.
        assert "driver" in tracks
        assert {t for t in tracks if t.startswith("partition")}


# ----------------------------------------------------- TELEM RPC + monitor


class _TelemDriver:
    def __init__(self):
        self.experiment_done = False

    def enqueue(self, msg):
        pass

    def get_trial(self, trial_id):
        return None

    def progress_snapshot(self):
        return {}


@pytest.fixture
def telem_server():
    server = OptimizationServer(num_executors=1)
    server.attach_driver(_TelemDriver())
    telem = Telemetry(enabled=True)
    telem.trial_event("a", "queued")
    telem.trial_event("a", "running", partition=0)
    telem.trial_event("a", "finalized", partition=0)
    telem.trial_event("b", "running", partition=0)
    server.telemetry = telem
    addr = server.start()
    yield server, addr
    server.stop()


class TestTelemRpc:
    def test_telem_round_trip(self, telem_server):
        server, addr = telem_server
        snap = monitor.poll_telemetry(addr, server.secret_hex)
        assert snap["type"] == "TELEM" and snap["enabled"]
        assert snap["spans"]["trials"]["finalized"] == 1
        # The TELEM poll itself was timed by the server.
        snap2 = monitor.poll_telemetry(addr, server.secret_hex)
        assert snap2["metrics"]["histograms"]["rpc.handle_ms.TELEM"]["count"] >= 1

    def test_telem_without_telemetry_is_err(self):
        server = OptimizationServer(num_executors=1)
        server.attach_driver(_TelemDriver())
        addr = server.start()
        try:
            snap = monitor.poll_telemetry(addr, server.secret_hex)
            assert snap["type"] == "ERR"
            assert "telemetry" in snap["error"]
        finally:
            server.stop()

    def test_telem_requires_auth(self, telem_server):
        server, addr = telem_server
        import socket as socketlib

        sock = socketlib.create_connection(addr, timeout=5)
        try:
            MessageSocket.send_msg(sock, {"type": "TELEM"}, b"wrong-secret")
            with pytest.raises((AuthenticationError, ConnectionError, OSError)):
                MessageSocket.recv_msg(sock, b"wrong-secret")
        finally:
            sock.close()

    def test_monitor_telem_rendering(self, telem_server, capsys):
        server, addr = telem_server
        rc = monitor.main(["--driver", "{}:{}".format(*addr),
                           "--secret", server.secret_hex, "--once", "--telem"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "hand-off gap" in out
        assert "early-stop reaction" in out
        assert "finalized" in out

    def test_render_telem_disabled_and_err(self):
        assert "disabled" in monitor.render_telem(
            {"type": "TELEM", "enabled": False})
        assert "nope" in monitor.render_telem({"type": "ERR", "error": "nope"})

    def test_telem_and_logs_flags_conflict(self, capsys):
        with pytest.raises(SystemExit):
            monitor.main(["--driver", "127.0.0.1:1", "--secret", "00",
                          "--telem", "--logs"])
        assert "--logs" in capsys.readouterr().err


# -------------------------------------------- satellite regression pins


class TestExclusiveCreate:
    def test_local_env_second_writer_loses(self, local_env, tmp_path):
        path = str(tmp_path / "exp" / "x" / "v1.json")
        assert local_env.exclusive_create("first", path) is True
        assert local_env.exclusive_create("second", path) is False
        assert local_env.load(path) == "first"

    def test_gcs_env_second_writer_loses(self):
        fsspec = pytest.importorskip("fsspec.implementations.memory")
        fs = fsspec.MemoryFileSystem()
        fs.store.clear()
        env = GCSEnv("gs://bucket/exp", fs=fs)
        path = "gs://bucket/exp/datasets/toy/v1.json"
        assert env.exclusive_create("first", path) is True
        assert env.exclusive_create("second", path) is False
        assert env.load(path) == "first"

    def test_registry_concurrent_same_version_fails_loudly(self, local_env,
                                                           tmp_path,
                                                           monkeypatch):
        from maggy_tpu.train.registry import DatasetRegistry

        p = str(tmp_path / "d.npz")
        np.savez(p, x=np.arange(4, dtype=np.float32))
        reg = DatasetRegistry()
        # Race simulation: both writers pass the exists() precheck (it
        # reports "free" for everyone), so only the exclusive-create
        # primitive separates winner from loser.
        monkeypatch.setattr(local_env, "exists", lambda path: False)
        assert reg.register("toy", p, version=1) == 1
        with pytest.raises(ValueError, match="concurrently"):
            reg.register("toy", p, version=1)


class TestResizeWatchCreditLeak:
    """ADVICE #2: a respawn whose process died BEFORE registering must
    expire the watch and reclaim the in-flight credit — only a respawn
    still queued for chips may re-arm forever."""

    @pytest.fixture
    def edriver(self, tmp_path):
        from maggy_tpu import OptimizationConfig
        from maggy_tpu.core.driver.optimization_driver import OptimizationDriver
        from maggy_tpu.searchspace import Searchspace

        EnvSing.set_instance(LocalEnv(base_dir=str(tmp_path / "exp")))
        config = OptimizationConfig(
            name="leak_unit", num_trials=4, optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE", [0.0, 1.0])),
            direction="max", num_workers=1, seed=2, es_policy="none",
            pool="elastic", chips_per_trial=1, total_chips=4,
            chips_per_budget={1: 1, 9: 4},
        )
        drv = OptimizationDriver(config, "app", 0)
        yield drv
        drv.stop()
        EnvSing.reset()

    def _expire_with(self, edriver, monkeypatch, pending):
        from maggy_tpu import constants

        killed = []

        class FakePool:
            def spawn_stamp(self, pid):
                return None

            def pending_respawn(self, pid):
                return pending

            def kill_worker(self, pid):
                killed.append(pid)
                return False

        monkeypatch.setattr(constants, "RESIZE_RESPAWN_TIMEOUT_S", 0.01)
        edriver._active_pool = FakePool()
        edriver._resize_inflight = {4: 1}
        edriver._resize_watch = {1: (time.monotonic() - 10, 4, 123.0)}
        edriver.periodic_check()
        return killed

    def test_died_before_registering_reclaims_credit(self, edriver,
                                                     monkeypatch):
        self._expire_with(edriver, monkeypatch, pending=False)
        assert edriver._resize_watch == {}
        assert edriver._resize_inflight.get(4) == 0

    def test_queued_for_chips_still_rearms(self, edriver, monkeypatch):
        killed = self._expire_with(edriver, monkeypatch, pending=True)
        assert killed == []
        assert 1 in edriver._resize_watch
        assert edriver._resize_inflight.get(4) == 1

    def test_pool_tracks_pending_respawns(self):
        from maggy_tpu.core.runner_pool import ElasticTPURunnerPool

        pool = ElasticTPURunnerPool(1, total_chips=2)
        assert pool.pending_respawn(0) is False
        with pool._lock:
            pool._pending_respawns.append((0, 2))
        assert pool.pending_respawn(0) is True


class TestRegistryCustomRoot:
    """ADVICE #4: registries at a non-default root are URI-addressable via
    $MAGGY_TPU_REGISTRY_ROOT or an explicit root/registry_root param."""

    def _register(self, tmp_path, root):
        from maggy_tpu.train.registry import DatasetRegistry

        p = str(tmp_path / "d.npz")
        np.savez(p, x=np.arange(6, dtype=np.float32).reshape(3, 2),
                 y=np.arange(3, dtype=np.int64))
        DatasetRegistry(root=root).register("toy", p)
        return p

    def test_env_var_threads_root_through_loader(self, tmp_path, monkeypatch):
        from maggy_tpu.train.data import load_path_dataset

        root = str(tmp_path / "custom_datasets")
        p = self._register(tmp_path, root)
        with pytest.raises(KeyError):  # default root cannot see it
            load_path_dataset("registry://toy")
        monkeypatch.setenv("MAGGY_TPU_REGISTRY_ROOT", root)
        data = load_path_dataset("registry://toy")
        assert sorted(data) == ["x", "y"] and data["x"].shape == (3, 2)
        assert p  # registered path resolved

    def test_explicit_registry_root_param(self, tmp_path):
        from maggy_tpu.train.data import load_path_dataset
        from maggy_tpu.train.registry import resolve_path

        root = str(tmp_path / "custom_datasets")
        p = self._register(tmp_path, root)
        assert resolve_path("registry://toy", root=root) == p
        data = load_path_dataset("registry://toy", registry_root=root)
        assert sorted(data) == ["x", "y"]
