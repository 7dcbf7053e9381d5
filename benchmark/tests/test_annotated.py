"""The readers of the program's own names: on a cut recorded on the v5e
(``fixtures/v5e_bert_annotated.json``, two whole steps of
``bert-base.steady-s512``) against numbers worked out by hand, on synthetic
traces and journals, `attention_work` against ``flops.py``, and a CPU
rehearsal, whose trace holds nothing for the trace-fed readers."""

import json
import os
import types

import pytest

from benchmark.harness import annotated, attention_work, peaks, spec
from benchmark.tests.test_run import run_cell

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_bert_annotated.json")
TRACE_FED = ["flash_ms", "flash_fwd_roofline_pct", "flash_bwd_roofline_pct",
             "place_batch_ms", "input_wait_pct"]


def read(name, w):
    return spec.load_module("metrics", name).read(w)


def cell_of(workload="bert-base.steady-s512"):
    cell = spec.load_cell(workload)
    for part in ("config", "mix"):
        cell[part].pop("rehearse", None)
    return cell


def window(reduction=None, **fields):
    """What a reader uses of a `Window`, with the trace already reduced."""
    return types.SimpleNamespace(**dict({
        "annotated": reduction, "trace": {}, "cell": cell_of(),
        "device_kind": "TPU v5 lite", "runners": {}, "trials": [],
        "events": [], "peak": peaks.chip_peaks("TPU v5 lite")}, **fields))


# ------------------------------------------------------ the recorded cut


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        trace = json.load(f)
    return annotated.reduce_annotated(trace, trace["t_stop_s"])


def test_the_kernels_are_found_by_name():
    assert annotated.kernel_of(
        "%flash_fwd.13 custom-call tpu_custom_call") == "flash_fwd"
    assert annotated.kernel_of(
        "%flash_bwd_dkdv.14 custom-call tpu_custom_call") == "flash_bwd_dkdv"
    # As autodiff names them outside a flax module's scope.
    assert annotated.kernel_of("%transpose_jvp_flash_bwd_dq__.1 custom-call "
                               "tpu_custom_call") == "flash_bwd_dq"
    assert annotated.kernel_of("%jvp_flash_fwd_.1 custom-call "
                               "tpu_custom_call") == "flash_fwd"
    # Position says nothing: the parent's names, and XLA's own operations.
    assert annotated.kernel_of(
        "%layer_7.4 custom-call tpu_custom_call") is None
    assert annotated.kernel_of("%flash_fwd_fusion.2 fusion") is None
    assert annotated.kernel_of("%custom-call.85 custom-call") is None


def test_flash_ms_on_the_recorded_steps(recorded):
    """By hand (a plain loop over the fixture, PR 23): the programs of run
    872 and 873 ran whole, 243.401-624.627 ms and 624.636-1005.785 ms; in
    them 24 events of each kernel, summing to 189,994,178 ns forward,
    161,332,665 ns dK/dV and 132,531,827 ns dQ."""
    assert recorded["steps"] == 2
    assert recorded["kernels_ms"] == {
        "flash_bwd_dkdv": pytest.approx(80.6663325),
        "flash_bwd_dq": pytest.approx(66.2659135),
        "flash_fwd": pytest.approx(94.997089)}
    w = window(recorded)
    flash_ms = read("flash_ms", w)
    assert flash_ms == pytest.approx(241.929335)
    assert 0.4 * 381.2 < flash_ms < 0.8 * 381.2  # 63 % of the step


def test_roofline_shares_on_the_recorded_steps(recorded):
    """B=64, H=12, S=512, D=64, bf16, 12 layers. Forward: 12 x 4 B H S^2 D
    = 618,475,290,624 FLOP = 3.139468 ms at 197 TFLOP/s; 12 x 4 tensors of
    50,331,648 B = 2,415,919,104 B = 2.949840 ms at 819 GB/s: FLOPs bind.
    Backward: twice the FLOPs, 6.278937 ms, and 8 tensors, 5.899680 ms."""
    w = window(recorded)
    assert read("flash_fwd_roofline_pct", w) == pytest.approx(
        100 * 3.139468 / 94.997089, rel=1e-6)
    assert read("flash_bwd_roofline_pct", w) == pytest.approx(
        100 * 6.278937 / (80.6663325 + 66.2659135), rel=1e-6)
    noted = w.trace["annotated"]
    assert noted["forward_roofline"]["bound"] == "flops"
    assert noted["backward_roofline"]["least_ms"] == pytest.approx(6.278937)


def test_the_loops_annotations_on_the_recorded_steps(recorded):
    """Two ``place_batch`` annotations lie inside the span (1,532,570 and
    1,189,980 ns; the third begins 2.4 ms past its end), and the device was
    busy through both: the host runs 32 steps ahead in this cell."""
    assert recorded["place_batch_ms"] == [pytest.approx(1.53257),
                                          pytest.approx(1.18998)]
    w = window(recorded)
    assert read("place_batch_ms", w) == pytest.approx(1.361275)
    assert read("input_wait_pct", w) == 0.0
    idle = recorded["idle_by_annotation_s"]
    assert set(idle) <= {"none", "train_step", "report", "place_batch"}
    # All of the span's idle time is under some label, and it is little.
    assert 0 < sum(idle.values()) < 0.001 * recorded["span_s"]


# ------------------------------------------------------- synthetic traces


def synthetic(ops, modules=(), host=None, span_ns=100):
    return {"start_ns": 7_000_000_000, "stop_ns": 7_000_000_000 + span_ns,
            "devices": {"/device:TPU:0": {"ops": ops,
                                          "modules": list(modules)}},
            "host": host or {}}


def test_idle_time_goes_to_the_innermost_annotation():
    """Busy 0-12, 20-45, 48-60, 95-100. The loop's thread: trial 0-100
    around init 10-30, place_batch 40-50, train_step 50-90. Idle 12-20 is
    init's (8), 45-48 place_batch's (3), 60-95 train_step's to 90 (30) and
    the trial's after it (5)."""
    host = {"python3#1": [["trial", 0, 100, None], ["init", 10, 20, None],
                          ["place_batch", 40, 10, None],
                          ["train_step", 50, 40, 0]]}
    got = annotated.reduce_annotated(synthetic(
        [["a", 0, 12], ["b", 20, 25], ["c", 48, 12], ["d", 95, 5]],
        host=host))
    assert got["idle_by_annotation_s"] == {
        "init": pytest.approx(8e-9), "place_batch": pytest.approx(3e-9),
        "train_step": pytest.approx(30e-9), "trial": pytest.approx(5e-9)}
    assert got["input_wait_pct"] == pytest.approx(3.0)
    assert got["place_batch_ms"] == [pytest.approx(10e-6)]
    assert got["kernels_ms"] is None and got["steps"] == 0


def test_idle_time_outside_every_annotation_is_nobodys():
    host = {"python3#1": [["place_batch", 10, 10, None],
                          ["train_step", 20, 10, 0]]}
    got = annotated.reduce_annotated(synthetic(
        [["a", 0, 5], ["b", 15, 30], ["c", 60, 40]], host=host))
    # Idle 5-15: 5 of it before place_batch began; 45-60: nobody's.
    assert got["idle_by_annotation_s"] == {
        "none": pytest.approx(20e-9), "place_batch": pytest.approx(5e-9)}


def test_only_whole_programs_count_as_steps():
    """The first program was running when the session began (it begins with
    the first recorded operation), the last is cut by the stop; of the two
    between, each holds one forward of 10 and one backward pair of 6 + 4."""
    fwd, dkdv, dq = ("%flash_fwd.1 custom-call tpu_custom_call",
                     "%flash_bwd_dkdv.1 custom-call tpu_custom_call",
                     "%flash_bwd_dq.1 custom-call tpu_custom_call")
    ops = [[fwd, 0, 10]]
    for start in (100, 200, 300):
        ops += [[fwd, start, 10], ["%fusion.3 fusion", start + 10, 50],
                [dkdv, start + 60, 6], [dq, start + 66, 4]]
    modules = [["jit_train_step(1)", 0, 20], ["jit_train_step(1)", 100, 90],
               ["jit_reinit(2)", 190, 5], ["jit_train_step(1)", 200, 90],
               ["jit_train_step(1)", 300, 90]]
    got = annotated.reduce_annotated(
        synthetic(ops, modules, span_ns=1000), stop_epoch_s=7.0 + 350e-9)
    assert got["steps"] == 2
    assert got["kernels_ms"] == {"flash_bwd_dkdv": pytest.approx(6e-6),
                                 "flash_bwd_dq": pytest.approx(4e-6),
                                 "flash_fwd": pytest.approx(10e-6)}
    w = window(got)
    assert annotated.kernel_ms(w, "flash_") == pytest.approx(20e-6)
    assert annotated.kernel_ms(w, "flash_bwd") == pytest.approx(10e-6)


def test_a_program_without_the_names_gives_nothing():
    """The parent's trace: the step is ``jit_step``, the kernels are
    ``%layer_<n>.3``, no thread carries an annotation."""
    ops = [["%layer_0.3 custom-call tpu_custom_call", s, 10]
           for s in (0, 100, 200)]
    got = annotated.reduce_annotated(synthetic(
        ops, [["jit_step(1)", 100, 50]], span_ns=300))
    assert got["steps"] == 0 and got["kernels_ms"] is None
    w = window(got)
    for name in TRACE_FED:
        assert read(name, w) is None
    assert annotated.reduce_annotated(synthetic([])) is None
    w = window(None)
    for name in TRACE_FED:
        assert read(name, w) is None


def test_several_runners_traces_merge_as_one():
    def part(steps, fwd, placed, wait, idle):
        return {"span_s": 1.5, "steps": steps,
                "kernels_ms": fwd and {"flash_fwd": fwd},
                "place_batch_ms": placed, "input_wait_pct": wait,
                "idle_by_annotation_s": idle}

    a = part(3, 90.0, [1.0, 2.0], 0.5, {"place_batch": 0.01, "none": 0.02})
    b = part(4, 100.0, [3.0], 1.5, {"place_batch": 0.03})
    dark = part(0, None, None, None, None)  # a runner that traced nothing
    assert annotated.merge_annotated([None]) is None
    assert annotated.merge_annotated([a, None]) is a
    got = annotated.merge_annotated([a, b, dark])
    assert got["steps"] == 7 and got["kernels_ms"] == {"flash_fwd": 95.0}
    assert got["place_batch_ms"] == [1.0, 2.0, 3.0]
    assert got["input_wait_pct"] == 1.0
    assert got["idle_by_annotation_s"] == {
        "none": pytest.approx(0.01), "place_batch": pytest.approx(0.02)}


def test_loader_keeps_the_annotations_of_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as `trialfn._trace_worker` sets it
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    x = jnp.ones((64, 64))
    for i in range(2):
        with jax.profiler.TraceAnnotation("place_batch"):
            x = x + 1.0
        with jax.profiler.StepTraceAnnotation("train_step", step_num=i):
            x = (x @ x) / 64.0
    x.block_until_ready()
    jax.profiler.stop_trace()
    trace = annotated.load_annotated(
        annotated.tracered.find_xplane(str(tmp_path)))
    assert trace["stop_ns"] > trace["start_ns"] > 1e18
    (events,) = trace["host"].values()  # one thread opened them
    assert [(n, num) for n, _s, _d, num in events] == [
        ("place_batch", None), ("train_step", 0),
        ("place_batch", None), ("train_step", 1)]
    # No TPU plane on the CPU: nothing to reduce, and no reader reads 0.
    assert trace["devices"] == {}
    assert annotated.reduce_annotated(trace) is None


# ------------------------------------------------------ attention's work


def test_attention_work_is_the_count_mfu_rests_on():
    model = cell_of()["config"]["model"]
    batch, seq = 64, 512
    work = attention_work.of_cell(model, batch, seq)
    assert work["layers"] == 12
    assert work["forward"]["flops"] == 4 * 64 * 12 * 512 * 512 * 64
    assert work["backward"]["flops"] == 2 * work["forward"]["flops"]
    # ``flops.py``'s ``attention`` term, which is per token and holds
    # forward plus backward (3 x forward), over all twelve layers.
    per_token = spec.load_module("families", "bert").flops_per_token(
        model, seq)["attention"]
    assert per_token * batch * seq == pytest.approx(
        work["layers"] * (work["forward"]["flops"]
                          + work["backward"]["flops"]))
    tensor = 64 * 12 * 512 * 64 * 2  # bf16
    assert work["forward"]["bytes"] == 4 * tensor   # q, k, v in; o out
    assert work["backward"]["bytes"] == 8 * tensor  # + o, dO in; dQ, dK, dV


def test_least_seconds_says_which_bound_binds():
    assert attention_work.least_seconds(197e12, 1.0, 197e12, 819e9) \
        == (1.0, "flops")
    assert attention_work.least_seconds(1.0, 819e9 * 2, 197e12, 819e9) \
        == (2.0, "hbm")
    assert attention_work.hbm_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(ValueError):
        attention_work.hbm_bytes_per_s("TPU v9")


# -------------------------------------------------- journal and counters


def trial(partition, enter, leave, dispatch=None, spans=True):
    compiled = {"init_ms": 40.0}
    if spans:
        compiled["spans"] = [["trial", enter, leave],
                             ["init", enter + 0.001, enter + 0.041]]
        if dispatch is not None:
            compiled["first_dispatch"] = dispatch
    return {"partition": partition, "compiled": compiled}


def test_boundaries_inside_the_window_from_the_trial_spans():
    """Runner 0's window is 100-200. Boundaries: 99.9 -> 100.0 begins
    before t0 (out); 110.000 -> 110.010, dispatch 110.050 (in: gap 10 ms,
    turnaround 50 ms); 120.000 -> 120.030, dispatch 120.090 (in: 30, 90);
    the last trial's first dispatch is past t1 (out). Runner 1 has one
    boundary (gap 20, turnaround 40)."""
    trials = [trial(0, 90.0, 99.9, 90.05), trial(0, 100.0, 110.0, 100.05),
              trial(0, 110.01, 120.0, 110.05), trial(0, 120.03, 199.99,
                                                     120.09),
              trial(0, 199.995, 205.0, 200.02),
              trial(1, 100.0, 150.0, 100.05), trial(1, 150.02, 190.0, 150.04),
              trial(2, 100.0, 150.0, 100.05)]  # a runner with no window
    runners = {0: {"t0": 100.0, "t1": 200.0}, 1: {"t0": 100.0, "t1": 200.0}}
    w = window(trials=trials, runners=runners)
    assert read("handoff_gap_ms", w) == pytest.approx(20.0)
    assert read("turnaround_ms", w) == pytest.approx(50.0)
    assert w.trace["annotated"]["boundaries"] == 3
    # The parent's journal has no ``trial`` span: nothing, not 0.
    bare = window(trials=[trial(0, 0, 0, spans=False) for _ in range(3)],
                  runners=runners)
    assert read("handoff_gap_ms", bare) is None
    assert read("turnaround_ms", bare) is None


def test_hb_fresh_pct_is_over_the_beats_inside_the_window():
    def stats(t, partition=0, **fields):
        return dict({"t": t, "ev": "runner_stats", "partition": partition},
                    **fields)

    # Deltas of cumulative counters: a field rides only where it changed.
    events = [stats(90.0, hb_beats=4, hb_fresh=1), stats(99.0, hb_beats=5),
              stats(101.0, hb_beats=6, metric_lag_steps=8),
              stats(102.0, hb_beats=7, hb_fresh=2, metric_lag_steps=1),
              stats(150.0, hb_beats=15, metric_lag_steps=30),
              stats(199.0, hb_beats=25, hb_fresh=3, metric_lag_steps=2),
              stats(201.0, hb_beats=26, hb_fresh=4),  # past t1
              stats(150.0, partition=1, hb_beats=10, hb_fresh=0),
              {"t": 120.0, "ev": "trial", "phase": "running"}]
    runners = {0: {"t0": 100.0, "t1": 200.0}, 1: {"t0": 100.0, "t1": 200.0}}
    w = window(events=events, runners=runners)
    # Runner 0: 25 - 5 = 20 beats, 3 - 1 = 2 fresh; runner 1: 10 and 0.
    assert read("hb_fresh_pct", w) == pytest.approx(100.0 * 2 / 30)
    assert w.trace["annotated"]["heartbeats"] == {
        "beats": 30, "fresh": 2, "median_lag_steps": 5.0}
    # A journal without the counters (the parent's): nothing, not 0.
    assert read("hb_fresh_pct", window(
        events=[stats(150.0, hb_rtt_ms=1.5)], runners=runners)) is None


# --------------------------------------------------------- the rehearsal


def rehearse(workload, seed):
    rc, out, err = run_cell(
        spec.ROOT, "--workload", workload, "--seed", str(seed), "--seconds",
        "4", "--trace", "1", "--rehearse")
    assert rc == 0, err[-2000:]
    lines = out.strip().splitlines()
    return json.loads(lines[-1])["metrics"], \
        json.loads(lines[-2])["trace_reduced"]["annotated"]


def test_the_trace_fed_readers_give_nothing_on_the_cpu_rehearsal():
    """A CPU trace has no device plane and no Pallas kernel: the five
    trace-fed metrics are left out of the line, and nothing reads 0."""
    metrics, noted = rehearse("bert-base.steady-s512", 11)
    assert not set(TRACE_FED) & set(metrics)
    assert noted["trace"] is None
    assert 0 <= metrics["hb_fresh_pct"]["value"] <= 100
    assert noted["heartbeats"]["beats"] >= 1


def test_the_journal_fed_readers_read_a_real_journal():
    """The program's spans and counters do not depend on the device: a
    rehearsed sweep has trial boundaries, each with its first dispatch."""
    metrics, noted = rehearse("vit-base-16.rs-short", 12)
    assert not set(TRACE_FED) & set(metrics)
    assert metrics["turnaround_ms"]["value"] \
        >= metrics["handoff_gap_ms"]["value"] > 0
    assert noted["boundaries"] >= 2
