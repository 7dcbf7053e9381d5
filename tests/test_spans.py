"""The runner's one span helper (telemetry/runnerstats.py `span`), the
per-step annotations in a profiler trace, and the heartbeat's freshness
counters.

One `jax.profiler` session may be open per process, so the traced test
lives here with the rest and no other file starts a session of its own.
"""

import subprocess
import sys
import time

import numpy as np
import pytest

from maggy_tpu.core.reporter import Reporter
from maggy_tpu.telemetry.runnerstats import (SPAN_FIELDS, RunnerStats,
                                             span)


def _records(stats):
    delta = stats.snapshot_delta()
    return (delta.get("compile_events") or [None])[0], \
        (delta.get("ckpt_events") or [None])[0]


# ------------------------------------------------------------ the helper


@pytest.mark.parametrize("name", sorted(SPAN_FIELDS))
def test_span_adds_to_its_ms_field_and_records_its_times(name):
    """Two spans of one phase accumulate into the ``*_ms`` field that
    note_compile / note_ckpt accumulated, bump the count where there is
    one, and leave ``[name, t_start, t_end]`` inside the trial span."""
    kind, field, count = SPAN_FIELDS[name]
    stats = RunnerStats()
    stats.trial_start("t1")
    with span("trial", stats=stats, trial_id="t1"):
        for _ in range(2):
            with span(name, stats=stats) as sp:
                time.sleep(0.01)
            assert sp.t_start <= sp.t_end
    stats.trial_end("t1")
    compiled, ckpt = _records(stats)
    record = compiled if kind == "compile" else ckpt
    assert record["trial"] == "t1"
    (trial, t0, t1), *phases = record["spans"]
    assert trial == "trial" and [p[0] for p in phases] == [name, name]
    for _n, s0, s1 in phases:
        assert t0 <= s0 <= s1 <= t1
    # The field is the sum of the recorded durations (rounded to 0.1 ms
    # where the record always was: the three compile phases and both
    # checkpoint phases; fork_load_ms ships unrounded, as before).
    assert record[field] == pytest.approx(
        sum(s1 - s0 for _n, s0, s1 in phases) * 1e3, abs=0.06)
    assert record[field] >= 20.0
    if count is not None:
        assert record[count] == 2


def test_span_and_note_calls_accumulate_into_one_field():
    stats = RunnerStats()
    stats.trial_start("t1")
    stats.note_compile(init_ms=100.0, warm=True)
    with span("init", stats=stats):
        pass
    stats.note_ckpt(save_ms=50.0, saves=1)
    with span("ckpt_save", stats=stats):
        pass
    stats.trial_end("t1")
    compiled, ckpt = _records(stats)
    assert 100.0 <= compiled["init_ms"] < 105.0 and compiled["warm"] is True
    assert 50.0 <= ckpt["save_ms"] < 55.0 and ckpt["saves"] == 2
    assert [s[0] for s in compiled["spans"]] == ["init"]  # no trial span
    assert [s[0] for s in ckpt["spans"]] == ["ckpt_save"]


def test_a_trial_with_no_phase_ships_no_record():
    """The trial span alone makes no ``compiled`` record: a train_fn that
    never touched a Trainer or a checkpoint journals what it did before."""
    stats = RunnerStats()
    stats.trial_start("t1")
    with span("trial", stats=stats, trial_id="t1"):
        pass
    stats.trial_end("t1")
    assert _records(stats) == (None, None)


def test_unrecorded_names_and_no_stats_record_nothing():
    stats = RunnerStats()
    stats.trial_start("t1")
    with span("place_batch", stats=stats) as a, span("init") as b:
        pass
    stats.trial_end("t1")
    assert a.t_start is None and b.t_start is None
    assert _records(stats) == (None, None)


def test_span_records_even_when_the_body_raises():
    stats = RunnerStats()
    stats.trial_start("t1")
    with pytest.raises(RuntimeError):
        with span("ckpt_save", stats=stats):
            raise RuntimeError("disk full")
    stats.trial_end("t1")
    _compiled, ckpt = _records(stats)
    assert ckpt["saves"] == 1 and len(ckpt["spans"]) == 1


@pytest.mark.parametrize("blocked", [False, True])
def test_span_neither_imports_jax_nor_needs_it(blocked):
    """A driver or orchestrator that never imported jax stays off it, and
    a process where jax cannot be imported at all still times its spans."""
    code = """
import sys
if {blocked}:
    sys.modules["jax"] = None  # any later `import jax` raises ImportError
from maggy_tpu.telemetry.runnerstats import RunnerStats, span
from maggy_tpu.core.reporter import Reporter
stats = RunnerStats()
stats.trial_start("t")
with span("trial", stats=stats, trial_id="t"), span("init", stats=stats):
    pass
reporter = Reporter()
reporter.stats = stats
reporter.reset(trial_id="t")
reporter.broadcast(1.0, step=0)  # opens the `report` annotation
stats.trial_end("t")
record, = stats.snapshot_delta()["compile_events"]
assert [s[0] for s in record["spans"]] == ["trial", "init"], record
assert sys.modules.get("jax") is None
assert not any(m.startswith("jax.") for m in sys.modules)
print("ok")
""".format(blocked=blocked)
    proc = subprocess.run([sys.executable, "-c", code],
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    assert proc.stdout.decode().strip() == "ok"


# ------------------------------------------------- the trainer's stamps


def _tiny_trainer():
    import jax
    import jax.numpy as jnp
    import optax

    from maggy_tpu.models import MnistCNN
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import (Trainer, cross_entropy_loss,
                                 swept_transform)

    model = MnistCNN(kernel_size=3, pool_size=2, features=4, num_classes=2)
    rng = np.random.default_rng(0)
    batch = {"inputs": (rng.normal(size=(8, 8, 8, 1)).astype(np.float32),),
             "labels": (rng.normal(size=(8,)) > 0).astype(np.int32)}

    def loss_fn(logits, b):
        return cross_entropy_loss(logits, b["labels"])

    trainer = Trainer(
        model, swept_transform(optax.adam, learning_rate=1e-3), loss_fn,
        make_mesh({"data": 1}, devices=jax.devices()[:1]))
    return trainer, batch, (jnp.zeros((1, 8, 8, 1)),)


def test_trainer_phases_and_first_dispatch_land_on_the_compiled_record():
    import jax

    from maggy_tpu.train import clear_warm, warm

    clear_warm()
    stats = RunnerStats()
    stats.trial_start("t1")
    with warm.trial_scope(trial_id="t1", stats=stats), \
            span("trial", stats=stats, trial_id="t1"):
        trainer, batch, example = _tiny_trainer()
        trainer.init(jax.random.key(0), example)
        for _ in range(3):
            loss = trainer.step(trainer.place_batch(batch))
        float(loss)
    stats.trial_end("t1")
    clear_warm()
    compiled, _ckpt = _records(stats)
    names = [s[0] for s in compiled["spans"]]
    assert names == ["trial", "init", "trace", "compile"]
    by_name = {s[0]: s for s in compiled["spans"]}
    for key, field in (("init", "init_ms"), ("trace", "trace_ms"),
                       ("compile", "compile_ms")):
        _n, s0, s1 = by_name[key]
        assert compiled[field] == pytest.approx((s1 - s0) * 1e3, abs=0.06)
    # Stamped once, at the first dispatch: after the compile, inside the
    # trial, and not moved by the second and third step.
    assert by_name["compile"][2] <= compiled["first_dispatch"] \
        <= by_name["trial"][2]


def test_the_flash_plan_of_the_traced_step_lands_on_the_compiled_record(
        monkeypatch):
    """A trial that traces a step holding flash attention notes the plan
    the shape chose as ``flash_plan`` (here the chip's dispatch with the
    kernel stubbed: the CPU cannot build it); a warm trial, which traces
    nothing, notes none."""
    import jax
    import jax.numpy as jnp
    import optax

    from maggy_tpu.models import BertConfig, BertEncoder
    from maggy_tpu.ops import attention
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.telemetry.vocab import COMPILED_FIELDS
    from maggy_tpu.train import (Trainer, clear_warm, cross_entropy_loss,
                                 swept_transform, warm)

    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    monkeypatch.setattr(
        attention, "flash_attention_planned",
        lambda q, k, v, mask, causal, plan, interpret:
        attention.attention_reference(
            q, k, v, causal=causal,
            mask=None if mask is None else mask[:, None, None, :]))
    cfg = BertConfig(vocab_size=64, hidden_dim=128, intermediate_dim=128,
                     num_layers=2, num_heads=2, max_seq_len=128, dropout=0.0)
    rng = np.random.default_rng(0)
    batch = {"inputs": (rng.integers(0, 64, size=(2, 128)).astype(np.int32),
                        np.ones((2, 128), bool)),
             "labels": np.zeros((2,), np.int32)}

    def loss_fn(logits, b):  # one object: part of the program's identity
        return cross_entropy_loss(logits, b["labels"])

    def trial(trial_id):
        stats = RunnerStats()
        stats.trial_start(trial_id)
        with warm.trial_scope(trial_id=trial_id, stats=stats), \
                span("trial", stats=stats, trial_id=trial_id):
            trainer = Trainer(
                BertEncoder(cfg),
                swept_transform(optax.adam, learning_rate=1e-3), loss_fn,
                make_mesh({"data": 1}, devices=jax.devices()[:1]))
            trainer.init(jax.random.key(0), (jnp.zeros((1, 128), jnp.int32),))
            float(trainer.step(trainer.place_batch(batch)))
        stats.trial_end(trial_id)
        return _records(stats)[0]

    clear_warm()
    cold, warm_trial = trial("t1"), trial("t2")
    clear_warm()
    plan = attention.tile_plan(128, 128, 64, 2, 2, 2, False, True).describe()
    assert cold["flash_plan"] == plan == \
        "fwd q128 k128 h2 kq; bwd q128 k128 h2"
    assert cold["trace_ms"] > 0 and cold["warm"] is False
    assert warm_trial["warm"] is True and "trace_ms" not in warm_trial
    assert "flash_plan" not in warm_trial
    assert "flash_plan" in COMPILED_FIELDS


def test_a_profiler_session_holds_the_loops_annotations(tmp_path):
    """A 0.2 s session around three steps of `Trainer.fit`'s loop holds
    ``place_batch``, ``train_step`` (``step_num`` 0, 1, 2) and ``report``,
    all on the loop's thread, under the benchmark's profiler options."""
    import jax
    from jax.profiler import ProfileData

    from maggy_tpu.train import clear_warm

    clear_warm()
    trainer, batch, example = _tiny_trainer()
    trainer.init(jax.random.key(0), example)
    float(trainer.step(trainer.place_batch(batch)))  # compile outside
    trainer.init(jax.random.key(0), example)         # step_num starts anew
    reporter = Reporter()
    reporter.reset(trial_id="t1")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        started = time.time()
        for i in range(3):
            loss = trainer.step(trainer.place_batch(batch))
            reporter.broadcast(loss, step=i)
        float(loss)
        time.sleep(max(0.0, 0.2 - (time.time() - started)))
    finally:
        jax.profiler.stop_trace()
    clear_warm()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            events = [(e.name, dict(e.stats)) for e in line.events
                      if e.name in ("place_batch", "train_step", "report")]
            if events:
                found[(plane.name, line.name)] = events
    assert len(found) == 1, sorted(found)  # one thread: the loop's
    (events,) = found.values()
    assert [n for n, _s in events] == ["place_batch", "train_step",
                                       "report"] * 3
    assert [s["step_num"] for n, s in events if n == "train_step"] \
        == [0, 1, 2]


# --------------------------------------------- the heartbeat's freshness


class _Lazy:
    """A device scalar as `Reporter` sees one: shape and dtype without a
    sync, and ``is_ready`` as the test says."""

    shape, dtype = (), np.dtype("float32")

    def __init__(self, value, ready):
        self.value, self.ready = value, ready

    def is_ready(self):
        return self.ready

    def copy_to_host_async(self):
        pass

    def __float__(self):
        return float(self.value)


def _beats(ready, n=5, steps_per_beat=3):
    stats = RunnerStats()
    reporter = Reporter()
    reporter.stats = stats
    reporter.reset(trial_id="t1")
    stats.trial_start("t1")
    seen, step = [], 0
    for _ in range(n):
        for _ in range(steps_per_beat):
            reporter.broadcast(_Lazy(1.0 / (step + 1), ready), step=step)
            step += 1
        data = reporter.get_data()  # what Client.start_heartbeat does
        stats.on_heartbeat(data["step"], data["newest_step"])
        seen.append(stats.snapshot())
    return seen


def test_heartbeats_over_a_metric_that_is_never_ready_carry_nothing_fresh():
    seen = _beats(ready=False)
    assert [s["hb_beats"] for s in seen] == [1, 2, 3, 4, 5]
    assert [s["hb_fresh"] for s in seen] == [0] * 5
    # Nothing ever shipped: the lag is every broadcast since the trial began.
    assert [s["metric_lag_steps"] for s in seen] == [3, 6, 9, 12, 15]


def test_heartbeats_over_a_metric_that_is_always_ready_are_all_fresh():
    seen = _beats(ready=True)
    assert [s["hb_beats"] for s in seen] == [1, 2, 3, 4, 5]
    assert [s["hb_fresh"] for s in seen] == [1, 2, 3, 4, 5]
    assert [s["metric_lag_steps"] for s in seen] == [0] * 5


def test_a_repeated_pair_is_not_fresh_and_no_trial_counts_no_beat():
    stats = RunnerStats()
    stats.on_heartbeat(None, None)  # between trials: not counted
    assert "hb_beats" not in stats.snapshot()
    stats.trial_start("t1")
    stats.on_heartbeat(4, 4)
    stats.on_heartbeat(4, 9)  # the cached pair again, the loop 5 ahead
    snap = stats.snapshot()
    assert (snap["hb_beats"], snap["hb_fresh"]) == (2, 1)
    assert snap["metric_lag_steps"] == 5
    # The next trial's first beat is judged against its own steps.
    stats.trial_end("t1")
    stats.trial_start("t2")
    stats.on_heartbeat(0, 0)
    assert stats.snapshot()["hb_fresh"] == 2


def test_the_counters_ship_in_the_runner_stats_delta_and_reach_metrics():
    from maggy_tpu.telemetry import Telemetry

    stats = RunnerStats()
    stats.trial_start("t1")
    stats.on_heartbeat(None, 7)
    delta = stats.snapshot_delta()
    assert (delta["hb_beats"], delta["hb_fresh"]) == (1, 0)
    telem = Telemetry()
    telem.record_runner_stats(2, delta)
    event, = [e for e in telem.events() if e.get("ev") == "runner_stats"]
    assert event["hb_beats"] == 1 and event["hb_fresh"] == 0
    gauges = telem.metrics.snapshot()["gauges"]
    assert gauges["runner.hb_beats.p2"] == 1
    assert gauges["runner.hb_fresh.p2"] == 0


# ------------------------------------------- what reads the new records


def _tev(t, trial, phase, **fields):
    return {"t": t, "ev": "trial", "trial": trial, "span": trial,
            "phase": phase, **fields}


def _journal(with_spans):
    spans = {"spans": [["trial", 0.2, 9.9], ["init", 0.3, 1.3],
                       ["trace", 1.4, 1.9], ["compile", 1.9, 3.4]],
             "first_dispatch": 3.5} if with_spans else {}
    ckpt_spans = {"spans": [["trial", 0.2, 9.9], ["ckpt_restore", 3.6, 4.1],
                            ["ckpt_save", 8.0, 9.0]]} if with_spans else {}
    return [
        {"t": 0.0, "ev": "runner", "phase": "registered", "partition": 0},
        _tev(0.0, "t1", "assigned", partition=0),
        _tev(0.0, "t1", "running", partition=0),
        _tev(2.0, "t1", "compiled", partition=0, init_ms=1000.0,
             trace_ms=500.0, compile_ms=1500.0, fork_load_ms=200.0, **spans),
        _tev(5.0, "t1", "ckpt_saved", partition=0, save_ms=1000.0,
             restore_ms=500.0, saves=1, restores=1, **ckpt_spans),
        _tev(10.0, "t1", "finalized", partition=0),
        {"t": 10.0, "ev": "experiment", "phase": "end"},
    ]


def test_the_goodput_fold_gives_the_same_buckets_with_spans_as_without():
    from maggy_tpu.telemetry.goodput import compute_goodput

    plain, spanned = (compute_goodput(_journal(w)) for w in (False, True))
    assert spanned["buckets"] == plain["buckets"]
    assert spanned["buckets"]["init"] == pytest.approx(1.0)
    assert spanned["buckets"]["ckpt_save"] == pytest.approx(1.0)


def test_build_trace_draws_each_recorded_span_inside_the_trial_slice():
    from maggy_tpu.telemetry.trace import build_trace

    slices = {e["name"]: e for e in build_trace(_journal(True))["traceEvents"]
              if e.get("cat") == "span"}
    assert sorted(slices) == ["ckpt_restore", "ckpt_save", "compile", "init",
                              "trace", "train_fn"]  # the trial span once
    trial, = [e for e in build_trace(_journal(True))["traceEvents"]
              if e.get("cat") == "trial"]
    for e in slices.values():
        assert e["pid"] == trial["pid"] and e["tid"] == trial["tid"]
        assert trial["ts"] <= e["ts"] \
            and e["ts"] + e["dur"] <= trial["ts"] + trial["dur"]
    assert slices["init"]["dur"] == 1_000_000
    # A journal from before the spans still gets its sequential layout.
    old = [e["name"] for e in build_trace(_journal(False))["traceEvents"]
           if e.get("cat") == "compile"]
    assert old and not [e for e in build_trace(_journal(False))["traceEvents"]
                        if e.get("cat") == "span"]
