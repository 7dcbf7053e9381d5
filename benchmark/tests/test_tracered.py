"""The trace reduction: on synthetic events, on the recorded v5e cut under
``fixtures/``, and the xplane loader on a trace recorded here."""

import json
import os

import pytest

from benchmark.harness import tracered

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_bert_steps.json")


def trace_of(events, span_ns=1000.0):
    return {"start_ns": 5_000_000_000, "stop_ns": 5_000_000_000 + int(span_ns),
            "planes": {"/device:TPU:0": {"XLA Ops": events}}}


def test_busy_is_a_union_not_a_sum():
    events = [["a", 100.0, 200.0], ["b", 250.0, 100.0],   # overlap: 100-350
              ["c", 600.0, 100.0], ["zero", 800.0, 0.0]]
    got = tracered.reduce_trace(trace_of(events))
    assert got["busy_s"] == pytest.approx(350e-9)
    # The span begins at the first recorded operation: 100-1000.
    assert got["window_s"] == pytest.approx(900e-9)
    assert got["idle_pct"] == pytest.approx(100.0 * (1 - 350 / 900))
    assert got["device_ops"][0] == ["a", pytest.approx(200e-9)]
    # Gaps: 350-600 (before c), 700-1000 (to the end); none before a.
    assert got["idle_gaps"] == [["before end of trace", pytest.approx(300e-9)],
                                ["before c", pytest.approx(250e-9)]]


def test_gaps_are_labelled_on_the_journals_clock():
    seen = []

    def label(t0, t1):
        seen.append((t0, t1))
        return "ckpt_save"

    got = tracered.reduce_trace(trace_of([["a", 0.0, 400.0]]), label)
    assert got["idle_gaps"] == [["ckpt_save", pytest.approx(600e-9)]]
    assert seen == [(pytest.approx(5.0 + 400e-9), pytest.approx(5.0 + 1e-6))]


def test_the_profilers_own_start_and_stop_are_cut_off():
    """Starting the session stalls the host and the device runs dry behind
    it: the span begins at the first recorded operation. The session records
    on while it serialises: the span ends when the host asked it to stop, and
    an operation across that moment is cut."""
    events = [["a", 100.0, 200.0], ["b", 450.0, 200.0], ["late", 900.0, 50.0]]
    got = tracered.reduce_trace(trace_of(events), stop_epoch_s=5.0 + 500e-9)
    assert got["window_s"] == pytest.approx(400e-9)
    assert got["busy_s"] == pytest.approx(250e-9)
    assert [n for n, _s in got["device_ops"]] == ["a", "b"]


def test_no_device_operation_is_nothing():
    assert tracered.reduce_trace({"start_ns": 0, "stop_ns": 10,
                                  "planes": {}}) is None
    assert tracered.merge_reductions([None]) is None


def test_merge_averages_over_chips():
    a = tracered.reduce_trace(trace_of([["k", 0.0, 500.0]]))
    b = tracered.reduce_trace(trace_of([["k", 0.0, 100.0]]))
    got = tracered.merge_reductions([a, b])
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx(300e-9)
    assert got["idle_pct"] == pytest.approx(70.0)
    assert got["device_ops"] == [["k", pytest.approx(300e-9)]]


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_v5e_trace():
    with open(FIXTURE) as f:
        trace = json.load(f)
    got = tracered.reduce_trace(trace)
    ops = trace["planes"]["/device:TPU:0"]["XLA Ops"]
    assert got["devices"] == 1 and len(ops) > 100
    first = min(e[1] for e in ops)
    assert 0 < got["busy_s"] <= got["window_s"] == pytest.approx(
        0.25 - first / 1e9)
    assert got["busy_s"] <= sum(e[2] for e in ops) / 1e9 + 1e-12
    expected = json.load(open(FIXTURE.replace(".json", ".expected.json")))
    assert got["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert [n for n, _s in got["device_ops"][:3]] == expected["top3"]


def test_loader_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    x = jnp.ones((256, 256))
    for _ in range(3):
        x = (x @ x) / 256.0
    x.block_until_ready()
    jax.profiler.stop_trace()
    path = tracered.find_xplane(str(tmp_path))
    trace = tracered.load_xplane(path, tracered.wanted_line)
    assert trace["stop_ns"] > trace["start_ns"] > 1e18  # epoch nanoseconds
    got = tracered.reduce_trace(trace, rehearse=True)
    assert got is not None and got["busy_s"] > 0
    assert tracered.reduce_trace(trace) is None  # no TPU plane on the CPU
