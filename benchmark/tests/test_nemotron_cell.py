"""The cell ``nemotron-3-nano-30b-a3b.ntp-steady-s8192``: its files against
what ISSUE 30 fixes, a rehearsal traced and untraced, its controls, and each
reader this cell brought on a synthetic trace (and on a program that has
none of the names: None, never 0)."""

import json
import types

import pytest

from benchmark.harness import (annotated, moe_trace, nemotron_h_work, peaks,
                               spec, ssm_trace)
from benchmark.tests.test_run import check_last_line, run_cell

CELL = "nemotron-3-nano-30b-a3b.ntp-steady-s8192"
CONFIG = "nemotron-3-nano-30b-a3b"
NEW_METRICS = ["ssm_ms", "ssd_roofline_pct", "nh_moe_gmm_roofline_pct"]
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def read(name, w):
    return spec.load_module("metrics", name).read(w)


def test_the_files_hold_what_the_issue_fixes():
    cell = spec.load_cell(CELL)
    mix, config = cell["mix"], cell["config"]
    assert cell["workload"]["chips"] == 1
    assert (mix["batch"], mix["seq"], mix["lr"]) == (2, 8192, 3e-05)
    assert mix["trial_steps"] == "until_deadline" and not mix["checkpoint"]
    assert mix["warmup"] == {"steps": 8} and mix["optimizer"] == "none"
    assert mix["experiment"]["num_trials"] == 1
    family = spec.load_module("families", "nemotron_h")
    small = dict(config["model"], **config["rehearse"]["model"])
    cycled = family.batches(small, 2, 32, 7)
    assert len(cycled) == 4  # 4 seeded host batches, cycled
    tokens, labels = cycled[0]["inputs"][0], cycled[0]["labels"]
    assert tokens.shape == (2, 32) and tokens.max() < small["vocab_size"]
    # The next-token step: position i is scored against token i + 1, the
    # mean over the B (S - 1) pairs; the last position weighs nothing.
    assert (labels["targets"][:, :-1] == tokens[:, 1:]).all()
    assert labels["weights"][:, -1].sum() == 0
    assert labels["weights"].sum() == pytest.approx(1.0)
    assert set(labels["weights"][:, :-1].ravel()) == {
        labels["weights"][0, 0]}
    model = config["model"]
    assert model["hybrid_override_pattern"] == "EMEMEMEM*" \
        == PUBLISHED_PATTERN[34:43]
    assert (model["num_hidden_layers"], model["n_routed_experts"],
            model["num_experts_routed"], model["first_expert"],
            model["vocab_size"]) == (9, 8, 128, 0, 16384)
    assert (model["hidden_size"], model["mamba_num_heads"],
            model["mamba_head_dim"], model["n_groups"],
            model["ssm_state_size"], model["conv_kernel"],
            model["chunk_size"]) == (2688, 64, 64, 8, 128, 4, 128)
    assert (model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"]) == (32, 2, 128)
    assert (model["moe_intermediate_size"],
            model["moe_shared_expert_intermediate_size"],
            model["num_experts_per_tok"], model["routed_scaling_factor"],
            model["mlp_hidden_act"]) == (1856, 3712, 6, 2.5, "relu2")
    assert set(config["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    assert config["published"] == {
        "num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED_PATTERN,
        "n_routed_experts": 128, "vocab_size": 131072}
    for key in ("router_scoring", "router_bias", "positions", "gated_norm",
                "initial_values", "router_gradient", "remat"):
        assert key in config["assumed"], key
    # The routers' bias is moved by the balance rule, at the scale the
    # configuration states, and the layer the family builds says so.
    assert model["router_balance_scale"] == 512
    assert "512" in config["assumed"]["router_bias"]
    family = spec.load_module("families", config["family"])
    assert family.build(model)[1].balance_scale == 512
    assert config["attention"] == "pallas"
    assert config["deployment"]["pool"] == "tpu"
    assert config["deployment"]["num_workers"] == 1
    assert "expert-parallel 16" in config["deployment"]["what"]
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    # The published keys sit at the top level as the run has them, and the
    # family's ``model`` dict says the same.
    for key, value in config.items():
        if key in model and not isinstance(value, dict):
            assert model[key] == value, key
    for key in ("hidden_size", "mamba_num_heads", "ssm_state_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "time_step_min", "chunk_size"):
        assert key in config, key


def test_the_benchmark_gained_entries_and_nothing_else_moved():
    bench = spec.load_benchmark()
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-3:]] == NEW_METRICS
    for name in NEW_METRICS:
        assert lists[name] == [CELL]
    for name in ("step_ms", "flash_ms", "moe_ms", "place_batch_ms",
                 "input_wait_pct", "hb_fresh_pct"):
        assert lists[name][-1] == CELL
    # SDAR's count of three matrices is not this cell's.
    assert CELL not in lists["moe_gmm_roofline_pct"]


def test_the_parameters_are_the_cut_table():
    import jax

    cell = spec.load_cell(CELL)
    family = spec.load_module("families", "nemotron_h")
    module, _ = family.build(cell["config"]["model"])
    shapes = jax.eval_shape(module.init, jax.random.key(0),
                            jax.ShapeDtypeStruct((1, 128), "int32"))["params"]
    count = lambda t: sum(  # noqa: E731
        int(x.size) for x in jax.tree_util.tree_leaves(t))
    assert count(shapes["block_0"]) == 100_125_440       # E
    assert count(shapes["block_0"]["mixer"]) - 2688 * 128 - 128 \
        == 8 * 2 * 2688 * 1856 + 2 * 2688 * 3712
    assert count(shapes["block_1"]) == 38_744_896        # M
    assert count(shapes["block_8"]) == 23_399_040        # *
    assert count(shapes["embedding"]) + count(shapes["lm_head"]) \
        + count(shapes["final_norm"]) == 88_083_072
    assert count(shapes) == 666_963_456  # x 16 B = 10.67 GB


@pytest.mark.parametrize("control, passes", [
    ("bits23", True), ("bits7", True), ("bits3", False), ("no_skip", False),
    ("no_softplus", False), ("conv_ahead", False), ("no_shared", False),
    ("no_scale", False), ("uncausal", False)])
def test_the_check_fails_its_controls_at_the_rehearsal_size(control, passes):
    """The reference's equations with a knob turned, in the program's place
    in the harness's own comparison: float32 without a fault reads nothing,
    bfloat16's bits pass, three mantissa bits and each fault fail a limit."""
    from benchmark.harness import nemotron_h_controls

    cell = spec.load_cell(CELL)
    preset = cell["config"]["rehearse"]
    config = dict(cell["config"], check=preset["check"],
                  model=dict(cell["config"]["model"], **preset["model"]))
    got = nemotron_h_controls.reading(
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


def window(trace=None, ssm_ops=None, moe_ops=None):
    cell = spec.load_cell(CELL)
    for part in ("config", "mix"):
        cell[part].pop("rehearse", None)
    runners = {}
    if trace is not None:
        runners = {0: {"trace": {"dir": "d", "t_stop": None}}}
    compiled = {k: v for k, v in (("ssm_ops", ssm_ops), ("moe_ops", moe_ops))
                if v}
    return types.SimpleNamespace(
        cell=cell, trace={}, device_kind="TPU v5 lite", runners=runners,
        trials=[{"compiled": compiled}], peak=peaks.chip_peaks("TPU v5 lite"))


def synthetic_trace(kernel: bool = False):
    """Two whole `train_step` programs of 20 ms; the first operation, the
    program it belongs to and the last program are cut by the span."""
    ms = 1e6
    ops, modules = [["%copy.1 copy", 0.0, 1 * ms]], [
        ["jit_train_step(1)", 0.0, 4 * ms]]
    for t0 in (5 * ms, 26 * ms):
        modules.append(["jit_train_step(1)", t0, 20 * ms])
        ops += [
            ["%fusion.1 fusion", t0, 3 * ms],                     # ssm_proj
            ["%fusion.2 fusion", t0 + 3 * ms, 1 * ms],            # ssm_conv
            ["%fusion.3 fusion", t0 + 4 * ms, 2 * ms],            # ssm_scan
            ["%while.4 while", t0 + 4 * ms, 8 * ms],     # spans its body
            ["%fusion.5 fusion", t0 + 6 * ms, 6 * ms],            # ssm_scan
            ["%fusion.6 fusion", t0 + 12 * ms, 0.5 * ms],         # gate norm
            ["%moe_gmm_fwd.3 custom-call tpu_custom_call", t0 + 13 * ms,
             2 * ms],
            ["%moe_gmm_drhs.5 custom-call tpu_custom_call", t0 + 15 * ms,
             1 * ms],
            ["%fusion.10 fusion", t0 + 16 * ms, 3 * ms],          # neither
        ]
        if kernel:
            ops.append(["%ssd_chunk_fwd.2 custom-call tpu_custom_call",
                        t0 + 19 * ms, 0.25 * ms])
    modules.append(["jit_train_step(1)", 47 * ms, 20 * ms])
    ops.append(["%fusion.5 fusion", 47 * ms, 2 * ms])
    return {"start_ns": 0, "stop_ns": int(50 * ms),
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


SSM_OPS = {"ssm_proj": ["fusion.1"], "ssm_conv": ["fusion.2"],
           "ssm_scan": ["fusion.3", "fusion.5"],  # the loop itself is not
           "ssm_gate_norm": ["fusion.6"]}
MOE_OPS = {"moe_experts": ["moe_gmm_fwd.3", "moe_gmm_drhs.5"]}


def test_ssm_time_by_scope_and_by_kernel_on_a_synthetic_trace():
    found = ssm_trace.reduce_ssm(synthetic_trace(), SSM_OPS)
    assert found["steps"] == 2
    assert found["scopes_ms"] == {"ssm_conv": 1.0, "ssm_gate_norm": 0.5,
                                  "ssm_proj": 3.0, "ssm_scan": 8.0}
    assert found["kernels_ms"] is None  # the scan is XLA products
    found = ssm_trace.reduce_ssm(synthetic_trace(kernel=True), dict(
        SSM_OPS, ssm_scan=SSM_OPS["ssm_scan"] + ["ssd_chunk_fwd.2"]))
    assert found["scopes_ms"]["ssm_scan"] == 8.25
    assert found["kernels_ms"] == {"ssd_chunk_fwd": 0.25}


def test_the_three_readers(monkeypatch):
    trace = synthetic_trace()
    monkeypatch.setattr(moe_trace.tracered, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(annotated, "load_annotated", lambda path: trace)
    w = window(trace, SSM_OPS, MOE_OPS)
    assert read("ssm_ms", w) == pytest.approx(12.5)
    model = w.cell["config"]["model"]
    scan = nemotron_h_work.scan(model, 2, 8192)
    least = max(4 * scan["flops"] / 197e12, 4 * scan["bytes"] / 819e9) * 1e3
    assert least == pytest.approx(4.32, rel=1e-2)
    assert read("ssd_roofline_pct", w) == pytest.approx(100 * least / 8.0)
    assert w.trace["annotated"]["ssd_roofline"]["bound"] == "hbm"
    assert w.trace["annotated"]["ssm"]["scopes_ms"]["ssm_scan"] == 8.0
    gmm = nemotron_h_work.grouped_products(model, 2, 8192)
    least = max(4 * gmm["flops"] / 197e12, 4 * gmm["bytes"] / 819e9) * 1e3
    assert read("nh_moe_gmm_roofline_pct", w) == pytest.approx(
        100 * least / 3.0)
    assert w.trace["annotated"]["nh_moe_gmm_roofline"] == {
        "bound": "flops", "least_ms": pytest.approx(least), "took_ms": 3.0,
        "rows_expected": 6144.0}
    # A program that notes no ``ssm_ops`` (the parent), and an untraced or
    # CPU run, give nothing and raise nothing.
    for w in (window(trace, None, None), window(None, None, None)):
        assert read("ssm_ms", w) is None
        assert read("ssd_roofline_pct", w) is None
    assert read("nh_moe_gmm_roofline_pct", window(None, None, None)) is None
