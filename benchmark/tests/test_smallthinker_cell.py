"""The cell ``smallthinker-21b-a3b.ntp-steady-s16384``: its files against the
cut and the mix they state, the work count against a brute count of visible pairs, a
rehearsal traced and untraced, its controls, and each reader this cell
brought on a synthetic ``step_ops`` period (and on a program that has none of
the names: None, never 0)."""

import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import (annotated, moe_trace, peaks, smallthinker_work,
                               spec)
from benchmark.tests.test_run import check_last_line, run_cell

CELL = "smallthinker-21b-a3b.ntp-steady-s16384"
CONFIG = "smallthinker-21b-a3b"
NEW_METRICS = ["window_attn_ms", "window_flash_roofline_pct"]
CATALOG = os.environ.get("MODEL_CATALOG", "")  # a JSONL of published configs
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size",
           "rope_layout", "sliding_window_layout"]


def read(name, w):
    return spec.load_module("metrics", name).read(w)


def test_the_files_hold_the_cut_and_the_mix():
    cell = spec.load_cell(CELL)
    mix, config = cell["mix"], cell["config"]
    assert cell["workload"]["chips"] == 1
    assert (mix["batch"], mix["seq"], mix["lr"]) == (1, 16384, 3e-05)
    assert mix["trial_steps"] == "until_deadline" and not mix["checkpoint"]
    assert mix["warmup"] == {"steps": 8} and mix["optimizer"] == "none"
    assert mix["experiment"]["num_trials"] == 1
    family = spec.load_module("families", "smallthinker")
    small = dict(config["model"], **config["rehearse"]["model"])
    cycled = family.batches(small, 1, 32, 7)
    assert len(cycled) == 4  # 4 seeded host batches, cycled
    (tokens, targets), labels = cycled[0]["inputs"], cycled[0]["labels"]
    assert tokens.shape == targets.shape == (1, 32)
    assert tokens.max() < small["vocab_size"]
    assert (targets[:, :-1] == tokens[:, 1:]).all()
    assert labels["weights"][:, -1].sum() == 0
    assert labels["weights"].sum() == pytest.approx(1.0)
    model = config["model"]
    assert (model["num_hidden_layers"], model["moe_num_primary_experts"],
            model["num_experts_routed"], model["first_expert"],
            model["vocab_size"], model["residual_layers"]) == (
                8, 8, 64, 0, 18992, 52)
    assert (model["hidden_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"],
            model["moe_ffn_hidden_size"],
            model["moe_num_active_primary_experts"],
            model["sliding_window_size"], model["rope_theta"],
            model["rms_norm_eps"]) == (2560, 28, 4, 128, 768, 6, 4096, 1.5e6,
                                       1e-6)
    assert model["sliding_window_layout"] == model["rope_layout"] \
        == [0, 1, 1, 1] * 2
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert config["published"]["num_hidden_layers"] == 52
    assert config["published"]["moe_num_primary_experts"] == 64
    assert config["published"]["vocab_size"] == 151936
    for layout in ("rope_layout", "sliding_window_layout"):
        assert config["published"][layout] == [0, 1, 1, 1] * 13
    for key in ("window", "layouts", "router_input", "router_scoring",
                "experts", "projection_bias", "initial_values", "remat",
                "head_and_loss", "sequence_and_batch", "sizes"):
        assert key in config["assumed"], key
    built = family.build(model)[1]
    assert (built.num_layers, built.experts_held, built.num_experts,
            built.top_k, built.remat) == (8, 8, 64, 6, True)
    assert config["attention"] == "pallas"
    assert config["deployment"]["pool"] == "tpu"
    assert config["deployment"]["num_workers"] == 1
    assert "expert-parallel 8" in config["deployment"]["what"]
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED
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
        pytest.skip("no model catalog named by MODEL_CATALOG")
    row = next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")
    config = spec.load_cell(CELL)["config"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    for layout in ("rope_layout", "sliding_window_layout"):
        assert config[layout] == row["config"][layout][:8]


def test_the_benchmark_holds_the_cells_entries():
    """By name and never by position: a later PR appends its own."""
    bench = spec.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ntp-steady-s16384", 1)
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert [metrics[n]["layer"] for n in NEW_METRICS] == [
        "windowed attention", "kernels"]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "train_tput"
    for name in ("step_ms", "place_batch_ms", "input_wait_pct",
                 "hb_fresh_pct", "step_scoped_pct", "optimizer_ms",
                 "remat_ms", "head_ms"):
        assert CELL in metrics[name]["workloads"]
    # Whole programs: the 1.5 s span holds none at this cell's step (PERF.md
    # section 7); the dense roofline counts all S^2 pairs.
    for name in ("flash_ms", "moe_ms", "flash_fwd_roofline_pct",
                 "flash_bwd_roofline_pct", "ssm_ms", "loop_ms"):
        assert CELL not in metrics[name]["workloads"]


def test_the_parameters_are_the_cut_table():
    import jax

    cell = spec.load_cell(CELL)
    family = spec.load_module("families", "smallthinker")
    module, _ = family.build(cell["config"]["model"])
    tokens = jax.ShapeDtypeStruct((1, 128), "int32")
    shapes = jax.eval_shape(module.init, jax.random.key(0), tokens,
                            tokens)["params"]
    count = lambda t: sum(  # noqa: E731
        int(x.size) for x in jax.tree_util.tree_leaves(t))
    layer = shapes["layer_0"]
    assert sum(count(layer[k]) for k in (
        "q_proj", "k_proj", "v_proj", "o_proj")) == 20_971_520
    assert count(layer["moe"]["router"]) == 163_840
    assert count(layer) == 68_326_400
    assert sorted(k for k in shapes if k.startswith("layer_")) == [
        "layer_{}".format(i) for i in range(8)]
    assert count(shapes["embedding"]) + count(shapes["lm_head"]) \
        == 97_239_040
    assert count(shapes) == 643_852_800  # x 16 B = 10.30 GB
    work = smallthinker_work.parameters(cell["config"]["model"])
    assert (work["layer"], work["all"]) == (68_326_400, count(shapes))


@pytest.mark.parametrize("S,window", [(32, 8), (40, 5), (64, 64), (16, 100),
                                      (128, 1)])
def test_visible_pairs_are_a_brute_count(S, window):
    i, j = np.meshgrid(np.arange(S), np.arange(S), indexing="ij")
    assert smallthinker_work.visible_pairs(S, window) == int(
        ((j <= i) & (j > i - window)).sum())
    assert smallthinker_work.visible_pairs(S) == int((j <= i).sum())


def test_the_work_is_the_cuts_arithmetic():
    """Forward MFLOP a token: projections 8 x 41.9, the held experts' rows
    70.8, two global layers 117.4 each and six windowed 51.4 each, the head
    97.2, the router 8 x 0.33: 1,049 in all; the windowed attention's least time 77 ms by its FLOPs."""
    model = spec.load_cell(CELL)["config"]["model"]
    per_token = smallthinker_work.train_flops_per_token(model, 16384)
    forward = {k: v / 3e6 for k, v in per_token.items()}
    assert forward["attention_projections"] == pytest.approx(8 * 41.94, 1e-3)
    assert forward["experts"] == pytest.approx(70.78, 1e-3)
    assert forward["attention_global"] == pytest.approx(2 * 117.45, 1e-3)
    assert forward["attention_window"] == pytest.approx(6 * 51.38, 1e-3)
    assert forward["head"] == pytest.approx(97.24, 1e-3)
    assert forward["router"] == pytest.approx(8 * 0.328, 1e-3)
    assert sum(forward.values()) == pytest.approx(1049.4, 1e-4)
    work = smallthinker_work.window_attention(model, 1, 16384)
    assert work["pairs"] == 58_722_304
    assert work["flops"] / 197e12 * 1e3 == pytest.approx(76.9, 1e-3)
    assert work["bytes"] / 819e9 * 1e3 == pytest.approx(5.90, 1e-2)
    assert smallthinker_work.least_ms(work, 197e12, "TPU v5 lite")[1] \
        == "flops"


@pytest.mark.parametrize("control, passes", [
    ("bits23", True), ("bits7", True), ("bits3", False),
    ("no_window", False), ("route_after_attn", False),
    ("rope_global", False), ("no_rope", False)])
def test_the_check_fails_its_controls_at_the_rehearsal_size(control, passes):
    """The reference's equations with a knob turned, in the program's place
    in the harness's own comparison: float32 without a fault reads nothing,
    bfloat16's bits pass, three mantissa bits and each fault fail a limit."""
    from benchmark.harness import smallthinker_controls

    cell = spec.load_cell(CELL)
    preset = cell["config"]["rehearse"]
    config = dict(cell["config"], check=preset["check"],
                  model=dict(cell["config"]["model"], **preset["model"]))
    got = smallthinker_controls.reading(
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
    assert full["tokens"] == full["first_run_steps"] * 32
    assert set(full["reference"]["errors"]) == {"logits", "loss", "grad"}


# ------------------------------------------------- readers, synthetic trace

MS = 1e6


def synthetic_trace():
    """Three steps of 40 ms, one program each; the span ends mid-way through
    the third. A step: the global layer's attention (a fusion and the
    forward kernel), the windowed layer's (a fusion, the forward kernel),
    the experts, then backward the windowed layer's two kernels and a
    fusion, the global layer's two kernels, and the optimizer."""
    ops, modules = [], []
    for t0 in (0.0, 40 * MS, 80 * MS):
        modules.append(["jit_train_step(1)", t0, 40 * MS])
        at = t0
        for name, ms in (("fusion.1", 1), ("flash_fwd.1", 3), ("fusion.2", 1),
                         ("flash_fwd.2", 2), ("fusion.3", 5),
                         ("flash_bwd_dkdv.2", 4), ("flash_bwd_dq.2", 3),
                         ("fusion.4", 2), ("flash_bwd_dkdv.1", 6),
                         ("flash_bwd_dq.1", 5), ("fusion.5", 4)):
            kind = "custom-call tpu_custom_call" if name.startswith("flash") \
                else "fusion"
            ops.append(["%{} {}".format(name, kind), at, ms * MS])
            at += ms * MS
    return {"start_ns": 0, "stop_ns": int(100 * MS),
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {}}


STEP_OPS = {
    "attn_global:fwd": ["fusion.1"],
    "attn_global/attention:fwd": ["flash_fwd.1"],
    "attn_window:fwd": ["fusion.2"],
    "attn_window/attention:fwd": ["flash_fwd.2"],
    "block/moe_experts:fwd": ["fusion.3"],
    "attn_window/attention:bwd": ["flash_bwd_dkdv.2", "flash_bwd_dq.2"],
    "unscoped>attn_window:bwd": ["fusion.4"],
    "attn_global/attention:bwd": ["flash_bwd_dkdv.1", "flash_bwd_dq.1"],
    "optimizer:update": ["fusion.5"],
}


def window(trace=None, step_ops=None):
    cell = spec.load_cell(CELL)
    for part in ("config", "mix"):
        cell[part].pop("rehearse", None)
    runners = {}
    if trace is not None:
        runners = {0: {"trace": {"dir": "d", "t_stop": None}}}
    return types.SimpleNamespace(
        cell=cell, trace={}, device_kind="TPU v5 lite", runners=runners,
        trials=[{"compiled": {"step_ops": step_ops} if step_ops else {}}],
        peak=peaks.chip_peaks("TPU v5 lite"))


def test_the_two_readers(monkeypatch):
    trace = synthetic_trace()
    monkeypatch.setattr(moe_trace.tracered, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(annotated, "load_annotated", lambda path: trace)
    w = window(trace, STEP_OPS)
    # One period: 1 + 2 (forward) + 4 + 3 + 2 (backward, the pathless
    # fusion lent to the part it works for).
    assert read("window_attn_ms", w) == pytest.approx(12.0)
    assert w.trace["annotated"]["window_attn"] == {
        "attn_window": pytest.approx(12.0), "attn_global": pytest.approx(15.0)}
    work = smallthinker_work.window_attention(w.cell["config"]["model"], 1,
                                              16384)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9) * 1e3
    # The windowed layers' three kernels: 2 + 4 + 3 ms.
    assert read("window_flash_roofline_pct", w) == pytest.approx(
        100 * least / 9.0)
    assert w.trace["annotated"]["window_flash_roofline"] == {
        "bound": "flops", "least_ms": pytest.approx(least), "took_ms": 9.0}
    # A program that notes no ``step_ops`` or no such scope (the parent),
    # and an untraced or CPU run, give nothing and raise nothing.
    no_scope = {k: v for k, v in STEP_OPS.items() if "attn_" not in k}
    for w in (window(trace, None), window(trace, no_scope),
              window(None, None)):
        for name in NEW_METRICS:
            assert read(name, w) is None
