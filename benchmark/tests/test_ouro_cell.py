"""The cell ``ouro-2.6b.loop4-steady-s4096``: its files against what ISSUE
32 fixes, a rehearsal traced and untraced, its controls, and each reader
this cell brought on a synthetic trace (and on a program that has none of
the names: None, never 0)."""

import json
import types

import pytest

from benchmark.harness import (annotated, loop_trace, moe_trace, ouro_work,
                               peaks, spec)
from benchmark.tests.test_run import check_last_line, run_cell

CELL = "ouro-2.6b.loop4-steady-s4096"
CONFIG = "ouro-2.6b"
NEW_METRICS = ["loop_ms", "exit_ms", "exit_head_roofline_pct",
               "loop_flash_ms"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, w):
    return spec.load_module("metrics", name).read(w)


def test_the_files_hold_what_the_issue_fixes():
    cell = spec.load_cell(CELL)
    mix, config = cell["mix"], cell["config"]
    assert cell["workload"]["chips"] == 1
    assert (mix["batch"], mix["seq"], mix["lr"]) == (2, 4096, 3e-05)
    assert mix["trial_steps"] == "until_deadline" and not mix["checkpoint"]
    assert mix["warmup"] == {"steps": 8} and mix["optimizer"] == "none"
    assert mix["experiment"]["num_trials"] == 1
    family = spec.load_module("families", "ouro")
    small = dict(config["model"], **config["rehearse"]["model"])
    cycled = family.batches(small, 2, 32, 7)
    assert len(cycled) == 4  # 4 seeded host batches, cycled
    (tokens, targets), labels = cycled[0]["inputs"], cycled[0]["labels"]
    assert tokens.shape == targets.shape == (2, 32)
    assert tokens.max() < small["vocab_size"]
    # The next-token step: position i is scored against token i + 1, the
    # mean over the B (S - 1) pairs; the last position weighs nothing; beta
    # rides in the labels.
    assert (targets[:, :-1] == tokens[:, 1:]).all()
    assert labels["weights"][:, -1].sum() == 0
    assert labels["weights"].sum() == pytest.approx(1.0)
    assert labels["beta"].shape == (2, 1)
    assert labels["beta"].ravel().tolist() == [pytest.approx(0.05)] * 2
    model = config["model"]
    assert (model["num_hidden_layers"], model["published_layers"],
            model["total_ut_steps"], model["vocab_size"],
            model["exit_entropy_beta"]) == (6, 48, 4, 49152, 0.05)
    assert (model["hidden_size"], model["intermediate_size"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], model["rope_theta"],
            model["rms_norm_eps"]) == (2048, 5632, 16, 16, 128, 1e6, 1e-6)
    assert model["layer_types"] == 6 * ["full_attention"]
    assert set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "max_window_layers"}
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["max_window_layers"] == 48
    for key in ("sandwich_norms", "normed_state_feeds_the_next_pass",
                "projection_bias", "objective", "sequence_and_batch",
                "initial_values", "activation_dtype", "remat", "loop_layout"):
        assert key in config["assumed"], key
    built = family.build(model)[1]
    assert (built.layers, built.num_layers, built.total_ut_steps,
            built.remat) == (6, 48, 4, True)
    assert "loop" not in model  # one layout of the passes, no option
    assert config["attention"] == "pallas"
    assert config["deployment"]["pool"] == "tpu"
    assert config["deployment"]["num_workers"] == 1
    assert "8 pipeline stages of 6" in config["deployment"]["what"]
    assert config["check"]["sequences"] == 2
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    # The published keys sit at the top level as the run has them, and the
    # family's ``model`` dict says the same.
    for key, value in config.items():
        if key in model and not isinstance(value, dict):
            assert model[key] == value, key


def test_every_published_key_is_the_catalogs_but_the_reduced():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in rows if r["name"] == "Ouro-2.6B")
    config = spec.load_cell(CELL)["config"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert config["layer_types"] == row["config"]["layer_types"][:6]


def test_the_benchmark_holds_the_cells_entries():
    """By name and never by position: a later PR appends its own."""
    bench = spec.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "loop4-steady-s4096", 1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [
        "num_hidden_layers", "layer_types", "max_window_layers"]
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert [metrics[n]["layer"] for n in NEW_METRICS] == [
        "looped stack", "exits", "kernels", "kernels"]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "train_tput"
    for name in ("step_ms", "place_batch_ms", "input_wait_pct",
                 "hb_fresh_pct"):
        assert CELL in metrics[name]["workloads"]
    # `attention_work.of_cell` counts all S^2 pairs, which would double a
    # causal kernel's share; and `flash_ms` reads whole `train_step`
    # programs only, of which the 1.5 s span holds none in one run of three
    # at this cell's 0.9 s step (the kernels' time is in the report of
    # `loop_ms`, read over one period where no whole step is).
    for name in ("flash_fwd_roofline_pct", "flash_bwd_roofline_pct",
                 "flash_ms", "moe_ms", "ssm_ms"):
        assert CELL not in metrics[name]["workloads"]


def test_the_parameters_are_the_cut_table():
    import jax

    cell = spec.load_cell(CELL)
    family = spec.load_module("families", "ouro")
    module, _ = family.build(cell["config"]["model"])
    tokens = jax.ShapeDtypeStruct((1, 128), "int32")
    shapes = jax.eval_shape(module.init, jax.random.key(0), tokens,
                            tokens)["params"]
    count = lambda t: sum(  # noqa: E731
        int(x.size) for x in jax.tree_util.tree_leaves(t))
    layer = shapes["stack"]["layer_0"]
    assert sum(count(layer[k]) for k in (
        "q_proj", "k_proj", "v_proj", "o_proj")) == 16_777_216
    assert sum(count(layer[k]) for k in (
        "gate_proj", "up_proj", "down_proj")) == 34_603_008
    assert count(layer) == 51_388_416
    assert sorted(shapes["stack"]) == ["final_norm"] + [
        "layer_{}".format(i) for i in range(6)]  # 6 layers, not 4 x 6
    assert count(shapes["stack"]) - 2048 == 308_330_496
    assert count(shapes["embedding"]) + count(shapes["lm_head"]) \
        == 201_326_592
    assert count(shapes["stack"]["final_norm"]) \
        + count(shapes["exit_gate"]) == 4_097
    assert count(shapes) == 509_661_185  # x 16 B = 8.15 GB
    assert ouro_work.parameters(cell["config"]["model"])["all"] \
        == count(shapes)


@pytest.mark.parametrize("control, passes", [
    ("bits23", True), ("bits7", True), ("bits3", False),
    ("three_passes", False), ("no_norm_between", False),
    ("no_after_norms", False), ("no_rope", False), ("uncausal", False),
    ("last_exit", False), ("beta_zero", False),
    ("first_passes_stopped", False), ("unshifted", False)])
def test_the_check_fails_its_controls_at_the_rehearsal_size(control, passes):
    """The reference's equations with a knob turned, in the program's place
    in the harness's own comparison: float32 without a fault reads nothing,
    bfloat16's bits pass, three mantissa bits and each fault fail a limit."""
    from benchmark.harness import ouro_controls

    cell = spec.load_cell(CELL)
    preset = cell["config"]["rehearse"]
    config = dict(cell["config"], check=preset["check"],
                  model=dict(cell["config"]["model"], **preset["model"]))
    got = ouro_controls.reading(
        config, cell["mix"]["rehearse"]["seq"], 3, control)
    assert got["ok"] is passes, got
    if control == "bits23":
        assert max(got["errors"].values()) < 1e-5, got


@pytest.mark.parametrize("traced", [0, 1])
def test_the_cell_rehearses(traced):
    bench = spec.load_benchmark()
    rc, out, err = run_cell(
        spec.ROOT, "--workload", CELL, "--seed", "2147483999", "--seconds",
        "4", "--trace", str(traced), "--rehearse")
    assert rc == 0, err[-3000:]
    result = check_last_line(
        out, bench["per_layer"] if traced else bench["end_to_end"], traced)
    full = json.loads(out.strip().splitlines()[-2])
    if traced:
        # The CPU's trace holds none of the names: the readers leave their
        # metrics out and do not raise.
        assert not set(NEW_METRICS) & set(result["metrics"])
        assert {"step_ms", "window_s"} <= set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"setup_s", "train_tput"}
        assert result["metrics"]["train_tput"]["value"] > 0
    assert full["tokens"] == full["first_run_steps"] * 2 * 32
    assert set(full["reference"]["errors"]) == {"logits", "loss", "grad"}


# ------------------------------------------------- readers, synthetic trace


def window(trace=None, loop_ops=None):
    cell = spec.load_cell(CELL)
    for part in ("config", "mix"):
        cell[part].pop("rehearse", None)
    runners = {}
    if trace is not None:
        runners = {0: {"trace": {"dir": "d", "t_stop": None}}}
    return types.SimpleNamespace(
        cell=cell, trace={}, device_kind="TPU v5 lite", runners=runners,
        trials=[{"compiled": {"loop_ops": loop_ops} if loop_ops else {}}],
        peak=peaks.chip_peaks("TPU v5 lite"))


def synthetic_trace():
    """Two whole `train_step` programs of 40 ms; the first operation, the
    program it belongs to and the last program are cut by the span. The
    passes are unrolled, so a layer's operations come under four names; the
    head's chunks are a ``while`` whose body runs three times a step."""
    ms = 1e6
    ops, modules = [["%copy.1 copy", 0.0, 1 * ms]], [
        ["jit_train_step(1)", 0.0, 4 * ms]]
    for t0 in (5 * ms, 46 * ms):
        modules.append(["jit_train_step(1)", t0, 40 * ms])
        for t in range(4):  # one pass: attention, mlp, the final norm
            at = t0 + t * 6 * ms
            ops += [
                ["%fusion.{} fusion".format(10 + t), at, 1 * ms],
                ["%flash_fwd.{} custom-call tpu_custom_call".format(t),
                 at + 1 * ms, 2 * ms],
                ["%fusion.{} fusion".format(20 + t), at + 3 * ms, 2.5 * ms],
                ["%fusion.{} fusion".format(30 + t), at + 5.5 * ms,
                 0.25 * ms],
            ]
        ops += [["%fusion.40 fusion", t0 + 24 * ms, 0.5 * ms],  # the gate
                ["%while.7 while", t0 + 25 * ms, 9 * ms]]   # spans its body
        for chunk in range(3):
            ops.append(["%fusion.50 fusion", t0 + (25 + 3 * chunk) * ms,
                        3 * ms])
        ops.append(["%fusion.60 fusion", t0 + 34 * ms, 5 * ms])  # optimizer
    modules.append(["jit_train_step(1)", 87 * ms, 40 * ms])
    ops.append(["%fusion.10 fusion", 87 * ms, 1 * ms])
    return {"start_ns": 0, "stop_ns": int(90 * ms),
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


LOOP_OPS = {
    "loop_attn": ["fusion.{}".format(10 + t) for t in range(4)]
    + ["flash_fwd.{}".format(t) for t in range(4)],
    "loop_mlp": ["fusion.{}".format(20 + t) for t in range(4)],
    "exit_norm": ["fusion.{}".format(30 + t) for t in range(4)],
    "exit_gate": ["fusion.40"],
    "exit_head": ["fusion.50"],  # the loop itself is not an instruction read
}


BY_SCOPE = {"exit_gate": 0.5, "exit_head": 9.0, "exit_norm": 1.0,
            "loop_attn": 12.0, "loop_mlp": 10.0}


def test_loop_and_exit_time_by_scope_on_a_synthetic_trace():
    found = loop_trace.reduce_loop(synthetic_trace(), LOOP_OPS)
    # The period is the trace's own: from one start of an instruction to
    # its next, the head's loop body (3 ms apart) left out.
    assert found["period_ms"] == 41.0
    # Sums over the four passes, and over the head's three chunks.
    assert found["scopes_ms"] == BY_SCOPE
    assert found["kernels_ms"] == {"flash_fwd": 8.0}


@pytest.mark.parametrize("cut_ms", [62.0, 70.0, 80.0])
def test_a_span_of_a_step_and_a_half_is_read_over_one_period(cut_ms):
    """The cell's step is longer than half the traced span: cut anywhere, a
    span of a step and a half holds no whole program, and one period of it
    holds every operation of a step once."""
    ms = 1e6
    trace = synthetic_trace()
    lines = trace["devices"]["/device:TPU:0"]
    # From 20 ms on (mid-way through the first whole step) to ``cut_ms``.
    lines["ops"] = [[n, s, d] for n, s, d in lines["ops"] if s >= 20 * ms]
    lines["modules"] = [m for m in lines["modules"] if m[1] >= 20 * ms]
    trace["stop_ns"] = int(cut_ms * ms)
    found = loop_trace.reduce_loop(trace, LOOP_OPS)
    assert found["period_ms"] == 41.0
    assert found["scopes_ms"] == pytest.approx(BY_SCOPE)
    assert found["kernels_ms"] == {"flash_fwd": pytest.approx(8.0)}
    # The host's stop, where it came before the session's, ends the span.
    assert loop_trace.reduce_loop(trace, LOOP_OPS, cut_ms / 1e3) == found
    # A span shorter than a step reads nothing: only the head's loop body
    # starts twice in it.
    assert loop_trace.reduce_loop(trace, LOOP_OPS, 0.055) is None


def test_the_three_readers(monkeypatch):
    trace = synthetic_trace()
    monkeypatch.setattr(moe_trace.tracered, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(annotated, "load_annotated", lambda path: trace)
    w = window(trace, LOOP_OPS)
    assert read("loop_ms", w) == pytest.approx(22.0)
    assert read("exit_ms", w) == pytest.approx(10.5)
    assert read("loop_flash_ms", w) == pytest.approx(8.0)
    heads = ouro_work.exit_heads(w.cell["config"]["model"], 2, 4096)
    least = max(heads["flops"] / 197e12, heads["bytes"] / 819e9) * 1e3
    assert least == pytest.approx(100.5, rel=1e-3)  # ISSUE 32's 100.5 ms
    assert read("exit_head_roofline_pct", w) == pytest.approx(
        100 * least / 9.0)
    assert w.trace["annotated"]["exit_head_roofline"] == {
        "bound": "flops", "least_ms": pytest.approx(least), "took_ms": 9.0}
    assert w.trace["annotated"]["loop"]["scopes_ms"]["loop_attn"] == 12.0
    # A program that notes no ``loop_ops`` (the parent), and an untraced or
    # CPU run, give nothing and raise nothing.
    for w in (window(trace, None), window(None, None)):
        for name in NEW_METRICS:
            assert read(name, w) is None
