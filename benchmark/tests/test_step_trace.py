"""`harness/step_trace.py`: a whole step by part on a synthetic trace (a
``while``, a mixed fusion, an unnoted event, a span shorter than two
periods), the four readers it feeds (None, never 0, on a program without
``step_ops``), their entries in ``BENCHMARK.json`` by name, and a traced
rehearsal of a steady cell."""

import json
import types

import pytest

from benchmark.harness import annotated, moe_trace, peaks, spec, step_trace
from benchmark.tests.test_ouro_cell import synthetic_trace
from benchmark.tests.test_run import check_last_line, run_cell

CELLS = ["bert-base.steady-s512", "vit-base-16.rs-short",
         "sdar-30b-a3b.bd-steady-s4096",
         "nemotron-3-nano-30b-a3b.ntp-steady-s8192",
         "ouro-2.6b.loop4-steady-s4096"]
#: name: (unit, better, layer, cells)
NEW_METRICS = {
    "step_scoped_pct": ("%", "higher", "harness", CELLS),
    "optimizer_ms": ("ms", "lower", "trainer", CELLS),
    "remat_ms": ("ms", "lower", "model", CELLS[2:]),
    "head_ms": ("ms", "lower", "model", CELLS[2:4]),
}
FLOPS, BYTES = 197e12, 819e9

#: The synthetic trace's step: four passes of attention (a projection and a
#: kernel), mlp and the closing norm, the gate, the head's loop of three
#: chunks, and one fusion of the last weight gradient with the update.
STEP_OPS = {
    "loop_attn:fwd": ["fusion.{}".format(10 + t) for t in range(4)],
    "loop_attn/attention:fwd": ["flash_fwd.{}".format(t) for t in range(4)],
    "loop_mlp:remat": ["fusion.{}".format(20 + t) for t in range(4)],
    "exit_norm:fwd": ["fusion.30", "fusion.31"],
    "unscoped>exit_norm:fwd": ["fusion.32"],  # XLA's own, at work for it
    "unscoped:fwd": ["fusion.33"],
    "exit_gate:fwd": ["fusion.40"],
    "head/chunked_ce:fwd": ["fusion.50"],  # the loop itself is no leaf
}
#: 1 ms of bytes under the optimizer's name, 3 ms of FLOPs in the product.
STEP_MIXED = {"fusion.60": [["optimizer:update", 0, BYTES * 1e-3],
                            ["loop_mlp:bwd", FLOPS * 3e-3, BYTES * 1e-4]]}
PARTS = {"exit_gate:fwd": 0.5, "exit_norm:fwd": 0.75,
         "head/chunked_ce:fwd": 9.0, "loop_attn/attention:fwd": 8.0,
         "loop_attn:fwd": 4.0, "loop_mlp:bwd": 3.75, "loop_mlp:remat": 10.0,
         "optimizer:update": 1.25, "unscoped:fwd": 0.25}


def reduce(trace, stop=None):
    return step_trace.reduce_step(trace, STEP_OPS, STEP_MIXED, FLOPS, BYTES,
                                  stop)


def read(name, w):
    return spec.load_module("metrics", name).read(w)


def test_a_step_by_part_on_a_synthetic_trace():
    found = reduce(synthetic_trace())
    assert found["period_ms"] == 41.0
    # The fusion XLA made across two parts is whole under its own name's
    # part for a reader of roots, and divided 1 : 3 by least time.
    # ... and an instruction without a path is unscoped there, and here
    # the part's that uses it.
    by_root = dict(PARTS, **{"optimizer:update": 5.0, "exit_norm:fwd": 0.5,
                             "unscoped:fwd": 0.5})
    del by_root["loop_mlp:bwd"]
    assert found["by_root_ms"] == by_root
    assert found["parts_ms"] == pytest.approx(PARTS)
    assert found["mixed_ms"] == 5.0
    assert found["moved_ms"] == {"optimizer:update": {"loop_mlp:bwd": 3.75},
                                 "unscoped:fwd": {"exit_norm:fwd": 0.25}}
    # The first operation recorded is another program's: in neither field.
    assert found["unnoted_ms"] == 1.0
    assert found["unnoted_top"] == [["copy.1", 1.0]]
    assert found["unscoped_top"] == [["fusion.33", "fusion", 0.25]]
    # The reading closes: the leaves, the loop's body for the loop, sum to
    # the period's busy time, and the loop holds nothing but its body.
    assert sum(found["parts_ms"].values()) + found["unnoted_ms"] \
        == pytest.approx(found["busy_ms"]) == pytest.approx(38.5)
    assert found["loop_gap_ms"] == 0.0
    for part, ms in found["by_root_ms"].items():
        came_in = sum(to.get(part, 0.0) for to in found["moved_ms"].values())
        went_out = sum(found["moved_ms"].get(part, {}).values())
        assert found["parts_ms"][part] - ms \
            == pytest.approx(came_in - went_out)


@pytest.mark.parametrize("cut_ms", [62.0, 70.0, 80.0])
def test_a_span_shorter_than_two_periods_is_read_over_one(cut_ms):
    ms = 1e6
    trace = synthetic_trace()
    lines = trace["devices"]["/device:TPU:0"]
    lines["ops"] = [[n, s, d] for n, s, d in lines["ops"] if s >= 20 * ms]
    lines["modules"] = [m for m in lines["modules"] if m[1] >= 20 * ms] \
        + [["jit_train_step(1)", 20 * ms, 25 * ms]]  # cut by the start
    trace["stop_ns"] = int(cut_ms * ms)
    found = reduce(trace)
    assert found["period_ms"] == 41.0
    assert found["parts_ms"] == pytest.approx(PARTS)
    assert found["unnoted_ms"] == 0.0
    assert reduce(trace, cut_ms / 1e3) == found
    # Shorter than a step: only the loop's body starts twice.
    assert reduce(trace, 0.055) is None


def test_another_programs_operation_is_unnoted_whatever_its_name():
    """Instruction names repeat from program to program (``%fusion.10`` of
    an init program): an operation outside every `train_step` program is
    in no part."""
    ms = 1e6
    trace = synthetic_trace()
    lines = trace["devices"]["/device:TPU:0"]
    lines["modules"].append(["jit_init_variables(2)", 45.1 * ms, 0.8 * ms])
    lines["ops"].append(["%fusion.10 fusion", 45.2 * ms, 0.5 * ms])
    lines["ops"] = [op for op in lines["ops"] if op[1] >= 5 * ms]
    found = reduce(trace)
    assert found["unnoted_top"] == [["fusion.10", 0.5]]
    assert found["parts_ms"] == pytest.approx(PARTS)


def test_a_fusion_the_text_gave_no_cost_stays_with_its_own_name():
    assert step_trace.divide(
        2.0, [["unscoped:fwd", 0, 0], ["attn:fwd", 0, 0]], FLOPS, BYTES) \
        == {"unscoped:fwd": 2.0}
    assert step_trace.divide(
        2.0, [["optimizer:update", 0, 10], ["attn:bwd", 0, 30]], FLOPS,
        BYTES) == pytest.approx({"optimizer:update": 0.5, "attn:bwd": 1.5})
    # A nameless relayout around named work: all of it the body's part's.
    assert step_trace.divide(
        2.0, [["unscoped:fwd", 0, 0], ["attn:fwd", 0, 64]], FLOPS, BYTES) \
        == {"unscoped:fwd": 0.0, "attn:fwd": 2.0}


def window(trace=None, noted=True):
    cell = spec.load_cell(CELLS[-1])
    runners = {0: {"trace": {"dir": "d", "t_stop": None}}} \
        if trace is not None else {}
    compiled = {"step_ops": STEP_OPS, "step_mixed": STEP_MIXED} \
        if noted else {}
    return types.SimpleNamespace(
        cell=cell, trace={}, device_kind="TPU v5 lite", runners=runners,
        # A warm trial after the cold one traces and notes nothing.
        trials=[{"compiled": compiled}, {"compiled": {}}],
        peak=peaks.chip_peaks("TPU v5 lite"))


def test_the_four_readers(monkeypatch):
    trace = synthetic_trace()
    monkeypatch.setattr(moe_trace.tracered, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(annotated, "load_annotated", lambda path: trace)
    w = window(trace)
    assert read("optimizer_ms", w) == pytest.approx(1.25)
    assert read("remat_ms", w) == pytest.approx(10.0)
    assert read("head_ms", w) == pytest.approx(9.0)
    # All but the unscoped norm and the other program's copy, of 38.5 ms.
    assert read("step_scoped_pct", w) == pytest.approx(
        100 * (38.5 - 0.25 - 1.0) / 38.5)
    assert w.trace["annotated"]["step"]["period_ms"] == 41.0
    # A program that notes no ``step_ops`` (the parent), and an untraced or
    # CPU run, give nothing and raise nothing.
    for w in (window(trace, noted=False), window(None)):
        for name in NEW_METRICS:
            assert read(name, w) is None
        assert w.trace["annotated"]["step"] is None


def test_the_benchmark_holds_the_four_metrics():
    """By name and never by position: a later PR appends its own."""
    bench = spec.load_benchmark()
    assert [w["name"] for w in bench["workloads"]][:5] == CELLS
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, layer, cells) in NEW_METRICS.items():
        assert metrics[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": "train_tput",
            "workloads": cells}
    assert not {m["name"] for m in bench["per_layer"]
                if "roofline" in m["name"] or "mfu" in m["name"]} \
        & set(NEW_METRICS)


def test_a_steady_cell_still_rehearses_traced():
    bench = spec.load_benchmark()
    rc, out, err = run_cell(
        spec.ROOT, "--workload", CELLS[0], "--seed", "2147483999",
        "--seconds", "4", "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    result = check_last_line(out, bench["per_layer"], 1)
    # The CPU's trace has no device plane: the readers leave their metrics
    # out, and the report says the reduction found nothing.
    assert not set(NEW_METRICS) & set(result["metrics"])
    full = json.loads(out.strip().splitlines()[-2])
    assert full["trace_reduced"]["annotated"]["step"] is None
