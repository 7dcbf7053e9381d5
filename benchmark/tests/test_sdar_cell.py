"""The cell ``sdar-30b-a3b.bd-steady-s4096``: its files against what ISSUE
26 fixes, a rehearsal traced and untraced, and each reader this cell
brought on a synthetic trace (and on a program that has none of the names:
None, never 0)."""

import json
import os
import types

import pytest

from benchmark.harness import annotated, moe_trace, peaks, sdar_work, spec
from benchmark.tests.test_run import check_last_line, run_cell

CELL = "sdar-30b-a3b.bd-steady-s4096"
NEW_METRICS = ["bd_flash_fwd_roofline_pct", "bd_flash_bwd_roofline_pct",
               "moe_ms", "moe_gmm_roofline_pct"]


def read(name, w):
    return spec.load_module("metrics", name).read(w)


def test_the_files_hold_what_the_issue_fixes():
    cell = spec.load_cell(CELL)
    mix, config = cell["mix"], cell["config"]
    assert (mix["batch"], mix["seq"], mix["lr"]) == (2, 4096, 3e-05)
    assert mix["trial_steps"] == "until_deadline" and not mix["checkpoint"]
    assert mix["warmup"] == {"steps": 8} and mix["optimizer"] == "none"
    assert mix["experiment"]["num_trials"] == 1
    family = spec.load_module("families", "sdar_moe")
    cycled = family.batches(dict(config["model"], **config["rehearse"]["model"]),
                            2, 32, 7)
    assert len(cycled) == 4  # ISSUE 26: 4 seeded host batches, cycled
    assert cycled[0]["inputs"][0].shape == (2, 64)
    model = config["model"]
    assert (model["hidden_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"],
            model["moe_intermediate_size"], model["num_experts_per_tok"],
            model["num_experts_routed"]) == (2048, 32, 4, 128, 768, 8, 128)
    assert (model["num_hidden_layers"], model["num_experts"],
            model["vocab_size"]) == (6, 16, 18992)
    assert model["block_length"] == 4 and model["mask_token_id"] == 18991
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts",
                                      "vocab_size"}
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    assert config["attention"] == "pallas"
    assert config["deployment"]["pool"] == "tpu"
    assert "expert-parallel 8" in config["deployment"]["what"]
    # The published keys sit at the top level as the run has them, and the
    # family's ``model`` dict says the same.
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "sdar-30b-a3b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    for key, value in config.items():
        if key in model and not isinstance(value, dict):
            assert model[key] == value, key
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "rope_theta", "rms_norm_eps"):
        assert key in config, key


@pytest.mark.parametrize("control, passes", [
    ("bits23", True), ("bits3", False), ("mask", False), ("weights", False)])
def test_the_check_fails_its_controls_at_the_rehearsal_size(control, passes):
    """The reference's equations once more, rounded or with a fault, in the
    program's place in the harness's own comparison: float32 without a
    fault reads nothing, three mantissa bits and each fault fail a limit."""
    from benchmark.harness import sdar_controls

    cell = spec.load_cell(CELL)
    preset = cell["config"]["rehearse"]
    config = dict(cell["config"], check=preset["check"],
                  model=dict(cell["config"]["model"], **preset["model"]))
    got = sdar_controls.reading(config, cell["mix"]["rehearse"]["seq"], 5,
                                control)
    assert got["ok"] is passes, got
    if control == "bits23":
        assert max(got["errors"].values()) < 1e-5, got


def test_the_parameters_are_the_cut_table():
    import jax

    cell = spec.load_cell(CELL)
    family = spec.load_module("families", "sdar_moe")
    module, _ = family.build(cell["config"]["model"])
    shapes = jax.eval_shape(module.init, jax.random.key(0),
                            jax.ShapeDtypeStruct((1, 8), "int32"))
    count = lambda t: sum(  # noqa: E731
        int(x.size) for x in jax.tree_util.tree_leaves(t))
    layer = shapes["params"]["layer_0"]
    assert count(layer["attn"]) == 18_874_624
    assert count(layer["moe"]) - 2048 * 128 == 16 * 3 * 2048 * 768
    assert count(layer) == 94_638_336
    assert count(shapes) == 645_623_296  # x 16 B = 10.33 GB


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
    assert full["tokens"] == full["first_run_steps"] * 2 * 32  # L, not 2 L
    assert set(full["reference"]["errors"]) == {"logits", "loss", "grad"}


# ------------------------------------------------- readers, synthetic trace


def window(trace=None, moe_ops=None, kernels_ms=None):
    cell = spec.load_cell(CELL)
    for part in ("config", "mix"):
        cell[part].pop("rehearse", None)
    runners, paths = {}, {}
    if trace is not None:
        runners = {0: {"trace": {"dir": "d", "t_stop": None}}}
    w = types.SimpleNamespace(
        cell=cell, trace={}, device_kind="TPU v5 lite", runners=runners,
        trials=[{"compiled": {"moe_ops": moe_ops} if moe_ops else {}}],
        peak=peaks.chip_peaks("TPU v5 lite"),
        annotated=None if kernels_ms is None else {"kernels_ms": kernels_ms})
    return w, trace


def synthetic_trace():
    """Two whole `train_step` programs of 10 ms; the first operation, the
    program it belongs to and the last program are cut by the span."""
    ms = 1e6
    ops, modules = [["%copy.1 copy", 0.0, 1 * ms]], [
        ["jit_train_step(1)", 0.0, 4 * ms]]
    for step, t0 in enumerate((5 * ms, 16 * ms)):
        modules.append(["jit_train_step(1)", t0, 10 * ms])
        ops += [
            ["%fusion.7 fusion", t0, 1 * ms],                       # routing
            ["%fusion.8 fusion", t0 + 1 * ms, 0.5 * ms],            # dispatch
            ["%moe_gmm_fwd.3 custom-call tpu_custom_call", t0 + 2 * ms, 2 * ms],
            ["%moe_gmm_dlhs.4 custom-call tpu_custom_call", t0 + 4 * ms, ms],
            ["%moe_gmm_drhs.5 custom-call tpu_custom_call", t0 + 5 * ms, ms],
            ["%fusion.9 fusion", t0 + 6 * ms, 0.5 * ms],            # combine
            ["%fusion.10 fusion", t0 + 7 * ms, 3 * ms],             # not MoE
        ]
    modules.append(["jit_train_step(1)", 27 * ms, 10 * ms])
    ops.append(["%moe_gmm_fwd.3 custom-call tpu_custom_call", 27 * ms, 2 * ms])
    return {"start_ns": 0, "stop_ns": int(30 * ms),
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


MOE_OPS = {"moe_routing": ["fusion.7"], "moe_dispatch": ["fusion.8"],
           "moe_experts": ["moe_gmm_fwd.3", "moe_gmm_dlhs.4",
                           "moe_gmm_drhs.5"],
           "moe_combine": ["fusion.9"]}


def test_moe_time_by_scope_and_by_kernel_on_a_synthetic_trace():
    found = moe_trace.reduce_moe(synthetic_trace(), MOE_OPS)
    assert found["steps"] == 2
    assert found["scopes_ms"] == {"moe_combine": 0.5, "moe_dispatch": 0.5,
                                  "moe_experts": 4.0, "moe_routing": 1.0}
    assert found["gmm_ms"] == {"moe_gmm_dlhs": 1.0, "moe_gmm_drhs": 1.0,
                               "moe_gmm_fwd": 2.0}


def test_moe_readers(monkeypatch):
    trace = synthetic_trace()
    monkeypatch.setattr(moe_trace.tracered, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(annotated, "load_annotated", lambda path: trace)
    w, _ = window(trace, MOE_OPS)
    assert read("moe_ms", w) == pytest.approx(6.0)
    cell = w.cell
    work = sdar_work.grouped_products(cell["config"]["model"], 2, 4096)
    least_ms = max(6 * work["flops"] / 197e12, 6 * work["bytes"] / 819e9) * 1e3
    assert read("moe_gmm_roofline_pct", w) == pytest.approx(
        100 * least_ms / 4.0)
    assert w.trace["annotated"]["moe_gmm_roofline"]["bound"] == "flops"
    # A program that notes no ``moe_ops`` (the parent) has its kernels'
    # names, if it has any, but no scopes; one with neither gives nothing.
    w, _ = window(trace, None)
    assert read("moe_ms", w) is None
    bare = dict(trace, devices={"/device:TPU:0": {
        "ops": [[n, s, d] for n, s, d in
                trace["devices"]["/device:TPU:0"]["ops"] if "moe" not in n],
        "modules": trace["devices"]["/device:TPU:0"]["modules"]}})
    monkeypatch.setattr(annotated, "load_annotated", lambda path: bare)
    w, _ = window(bare, None)
    assert read("moe_ms", w) is None
    assert read("moe_gmm_roofline_pct", w) is None
    w, _ = window(None, None)  # an untraced or CPU run
    assert read("moe_ms", w) is None
    assert read("moe_gmm_roofline_pct", w) is None


def test_block_diffusion_roofline_readers():
    w, _ = window(kernels_ms={"flash_fwd": 100.0, "flash_bwd_dkdv": 60.0,
                              "flash_bwd_dq": 40.0})
    work = sdar_work.attention(w.cell["config"]["model"], 2, 4096)
    fwd_ms = 6 * work["forward"]["flops"] / 197e12 * 1e3
    assert fwd_ms == pytest.approx(16.76, rel=1e-3)  # 3.30 TFLOP of 8.63
    assert read("bd_flash_fwd_roofline_pct", w) == pytest.approx(fwd_ms)
    assert read("bd_flash_bwd_roofline_pct", w) == pytest.approx(2 * fwd_ms)
    assert w.trace["annotated"]["bd_forward_roofline"]["bound"] == "flops"
    # The accepted BERT readers count all S^2 pairs at hidden // heads: they
    # are not this cell's, and this cell's are not the BERT cell's.
    bench = spec.load_benchmark()
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert lists[name] == [CELL]
    for name in ("flash_fwd_roofline_pct", "flash_bwd_roofline_pct"):
        assert lists[name] == ["bert-base.steady-s512"]
    for name in ("step_ms", "flash_ms", "place_batch_ms", "input_wait_pct",
                 "hb_fresh_pct"):
        assert lists[name][-1] == CELL
    w, _ = window(kernels_ms=None)
    assert read("bd_flash_fwd_roofline_pct", w) is None
    assert read("bd_flash_bwd_roofline_pct", w) is None
