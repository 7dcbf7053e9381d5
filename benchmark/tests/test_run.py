"""`run.py` end to end under ``--rehearse``: the last line's schema, and a
cell that a later PR adds as files plus entries, never by editing a file."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import spec

UNITS = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_/%.-"


def run_cell(root, *args, env=None):
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})), timeout=600)
    return proc.returncode, proc.stdout.decode(), proc.stderr.decode()


def check_last_line(stdout, entries, traced):
    lines = stdout.strip().splitlines()
    result = json.loads(lines[-1])
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result) == want | ({"breakdown"} if traced else set())
    checks = json.loads(lines[-2])["checks"]
    assert result["correct"] is True and result["failed"] == 0, checks
    assert result["attempted"] >= 1
    known = {e["name"]: e["unit"] for e in entries}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == known[name]
        assert isinstance(m["value"], (int, float))
        assert set(m["unit"]) <= set(UNITS)
    device = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(device)
    assert device["platform"] == "cpu"  # a rehearsal says what it ran on
    if traced:
        assert device["busy_s"] > 0 and device["window_s"] > 0
        for key in ("device_ops", "idle_gaps"):
            assert len(result["breakdown"][key]) <= 10
            assert all(isinstance(n, str) and s >= 0
                       for n, s in result["breakdown"][key])
    full = json.loads(lines[-2])  # the report, on the line before
    assert full["result"] == result and full["rehearse"] is True
    return result


@pytest.mark.parametrize("traced", [0, 1])
def test_last_line_schema(traced):
    bench = spec.load_benchmark()
    rc, out, err = run_cell(
        spec.ROOT, "--workload", "vit-base-16.rs-short", "--seed", "3",
        "--seconds", "4", "--trace", str(traced), "--rehearse")
    assert rc == 0, err[-2000:]
    result = check_last_line(
        out, bench["per_layer"] if traced else bench["end_to_end"], traced)
    if not traced:
        assert set(result["metrics"]) == {"setup_s", "train_tput",
                                          "trials_per_hour"}
        full = json.loads(out.strip().splitlines()[-2])
        assert full["held_chip_s"] >= 4.0
        if straddled(full):  # then a trial trained across the deadline
            assert any(t["window_steps"] and not t["finalized_in"]
                       for t in full["trials"])


KEPT_METRICS = [  # readers kept with the two ASHA cells' files
    ("fork_stage_ms", "ms", "executor"), ("ckpt_pct", "%", "checkpoints"),
    ("ckpt_save_s", "s", "checkpoints"),
    ("min_runner_goodput_pct", "%", "pools")]


def tree_with_the_kept_cells(root):
    """A copy of the tree in which the two ASHA-with-forks cells, kept as
    files without entries (PERF.md section 7), have their entries back."""
    copy_of_the_tree(root)
    bench = spec.load_benchmark()
    bert = bench["configs"][0]
    assert bert["name"] == "bert-base"
    bench["configs"].append(dict(
        bert, name="bert-base-host4",
        file="benchmark/configs/bert-base-host4.json"))
    cells = ["bert-base.glue-asha-fork", "bert-base-host4.glue-asha-fork"]
    for name, chips in zip(cells, (1, 4)):
        bench["workloads"].append({
            "name": name, "config": name.split(".")[0],
            "traffic": "glue-asha-fork", "chips": chips, "why": "kept"})
    for metric in bench["per_layer"]:
        if "vit-base-16.rs-short" in metric.get("workloads", []):
            metric["workloads"] += cells  # the sweep layers' metrics
    for name, unit, layer in KEPT_METRICS:
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": layer, "moves": "train_tput",
            "workloads": cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench


def straddled(full):
    """Runners whose window a trial's last loss closed past the deadline."""
    return [p for p, r in full["runners"].items()
            if r["t1"] > r["t0"] + full["seconds"]]


def test_the_kept_one_chip_asha_cell_still_rehearses(tmp_path):
    """``bert-base`` x ``glue-asha-fork``: saves, forks and promotions in a
    window cut at the deadline; no runner's window is shorter than asked."""
    root = str(tmp_path)
    bench = tree_with_the_kept_cells(root)
    rc, out, err = run_cell(
        root, "--workload", "bert-base.glue-asha-fork", "--seed", "3",
        "--seconds", "6", "--trace", "0", "--rehearse")
    assert rc == 0, err[-2000:]
    check_last_line(out, bench["end_to_end"], traced=0)
    full = json.loads(out.strip().splitlines()[-2])
    assert full["held_chip_s"] >= 6.0
    assert sum(full["rungs_finalized_in_window"].values()) >= 2
    if straddled(full):  # then some trial trained across the deadline
        assert any(t["window_steps"] and not t["finalized_in"]
                   for t in full["trials"])


def test_no_result_without_rehearse_flag_on_cpu():
    rc, out, err = run_cell(
        spec.ROOT, "--workload", "bert-base.steady-s512", "--seconds", "2")
    assert rc != 0 and out.strip() == ""
    assert "--rehearse" in err


def test_no_result_without_the_repository(tmp_path):
    root = str(tmp_path)
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    rc, out, _err = run_cell(
        root, "--workload", "bert-base.steady-s512", "--seconds", "2",
        "--rehearse", env={"PYTHONPATH": ""})
    assert rc != 0 and out.strip() == ""


def copy_of_the_tree(root):
    """The benchmark's files and BENCHMARK.json under ``root``, with the
    repository's ``maggy_tpu`` linked in. Returns the files' contents."""
    bench_dir = os.path.join(root, "benchmark")
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(spec.ROOT, "maggy_tpu"),
               os.path.join(root, "maggy_tpu"))
    before = {}
    for folder, _dirs, files in os.walk(bench_dir):
        for name in files:
            path = os.path.join(folder, name)
            before[path] = open(path, "rb").read()
    return before


def test_the_kept_four_chip_cell_still_rehearses(tmp_path):
    """``bert-base-host4`` x ``glue-asha-fork``: pinned runner processes that
    agree on the window through the marker files, the parent off the JAX
    backend, the check in a child."""
    root = str(tmp_path)
    bench = tree_with_the_kept_cells(root)
    rc, out, err = run_cell(
        root, "--workload", "bert-base-host4.glue-asha-fork", "--seed", "7",
        "--seconds", "4", "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    result = check_last_line(out, bench["per_layer"], traced=1)
    assert result["device"]["count"] == 2  # the preset's two runner processes
    for name, _unit, _layer in KEPT_METRICS:
        assert name in result["metrics"]


# ------------------------------------------------ a cell added as data

TOY_FAMILY = '''
"""Throw-away family: a two-layer perceptron on random vectors."""
import numpy as np


def build(model):
    import flax.linen as nn

    class Toy(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.tanh(nn.Dense(model["hidden_size"], name="layer_0")(x))
            return nn.Dense(model["num_labels"], name="head")(h)

    return Toy(), model


def positions(model, seq=None):
    return 1


def batches(model, batch, seq, seed, n=4):
    rng = np.random.default_rng(seed)
    return [{"inputs": (rng.standard_normal(
        (batch, model["input_size"]), dtype=np.float32),),
        "labels": rng.integers(0, model["num_labels"], size=(batch,)).astype(
            np.int32)} for _ in range(n)]


def init_args(batch):
    return batch["inputs"], {}


def loss(logits, batch):
    from maggy_tpu.train import cross_entropy_loss

    return cross_entropy_loss(logits, batch["labels"])


def checked_grads(grads):
    return grads["layer_0"]


def flops_per_token(model, seq=None):
    return {"matmul": 6.0 * model["hidden_size"] * (
        model["input_size"] + model["num_labels"]), "attention": 0.0}
'''

TOY_REFERENCE = '''
"""Throw-away reference of the toy family."""
import jax
import jax.numpy as jnp


def forward(params, inputs, model):
    x, = inputs
    with jax.default_matmul_precision("highest"):
        h = jnp.tanh(x @ params["layer_0"]["kernel"] + params["layer_0"]["bias"])
        return h @ params["head"]["kernel"] + params["head"]["bias"]


def loss_from_logits(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
'''

TOY_METRIC = '''
"""Throw-away layer metric: trials that trained in the window."""


def read(w):
    return float(len(w.active()))
'''

TOY_CONFIG = {
    "name": "toy", "family": "toy", "source": "https://example.org/toy",
    "model": {"input_size": 8, "hidden_size": 16, "num_labels": 3},
    "reduced": {}, "assumed": {},
    "deployment": {"what": "test", "pool": "thread", "num_workers": 2},
    "attention": "xla",
    "check": {"sequences": 4, "tolerance": 1e-4, "grad_tolerance": 1e-4},
}

TOY_MIX = {
    "name": "toy-rs", "what": "test", "optimizer": "randomsearch",
    "searchspace": {"lr": ["DOUBLE_LOG", [1e-4, 1e-2]]},
    "experiment": {"num_trials": 3000, "direction": "min",
                   "es_policy": "none", "prefetch": True, "warm_start": True,
                   "fork": True, "telemetry": True, "health": True,
                   "vmap_lanes": 1},
    "batch": 4, "seq": None, "trial_steps": {"fixed": 5},
    "checkpoint": False, "warmup": {"trials": 2},
}


def test_a_cell_is_added_as_files_and_entries(tmp_path):
    """A throw-away family, reference, configuration, mix, metric and cell,
    added to a copy of the tree without touching a file that is there (two
    thread runners on the one device, which no shipped cell has)."""
    root = str(tmp_path)
    bench_dir = os.path.join(root, "benchmark")
    before = copy_of_the_tree(root)

    def add(rel, text):
        path = os.path.join(bench_dir, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)

    add("families/toy.py", TOY_FAMILY)
    add("reference/toy.py", TOY_REFERENCE)
    add("metrics/toy_active_trials.py", TOY_METRIC)
    add("configs/toy.json", json.dumps(TOY_CONFIG))
    add("traffic/toy-rs.json", json.dumps(TOY_MIX))
    bench = spec.load_benchmark()
    bench["configs"].append({
        "name": "toy", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/toy.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.toy-rs", "config": "toy",
                               "traffic": "toy-rs", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "toy_active_trials", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "harness",
        "moves": "trials_per_hour", "workloads": ["toy.toy-rs"]})
    for metric in bench["end_to_end"]:
        if metric["name"] == "trials_per_hour":
            metric["workloads"].append("toy.toy-rs")  # an entry, not a file
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    for traced in (0, 1):
        rc, out, err = run_cell(
            root, "--workload", "toy.toy-rs", "--seed", "5", "--seconds",
            "3", "--trace", str(traced), "--rehearse")
        assert rc == 0, err[-3000:]
        result = check_last_line(
            out, bench["per_layer"] if traced else bench["end_to_end"],
            traced)
        if traced:
            assert result["metrics"]["toy_active_trials"]["value"] \
                == result["attempted"]
        else:
            assert result["metrics"]["train_tput"]["value"] > 0
    for path, content in before.items():
        assert open(path, "rb").read() == content, path
