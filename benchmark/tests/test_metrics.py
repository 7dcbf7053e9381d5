"""`train_tput`, `trials_per_hour` and the layer readers on a synthetic
experiment directory."""

import json
import os
import time

import pytest

from benchmark.harness import spec, trialfn
from benchmark.harness.window import Window
from benchmark.tests.journals import T, sweep_journal, write_experiment


def rec(first_step, steps_run, opened_at, target, **extra):
    return dict({"metric": 0.5, "first_loss": 0.7, "first_step": first_step,
                 "steps_run": steps_run, "opened_at_step": opened_at,
                 "target_steps": target, "drained": False,
                 "pallas_calls": 0}, **extra)


def make_window(tmp_path, windows, **outputs):
    exp = str(tmp_path / "exp")
    outputs = dict({
        "a": rec(0, 78, 78, 78),      # warm-up: the window opened at its end
        "b": rec(0, 78, 78, 78),
        "c": rec(78, 78, 0, 156),     # served fork: steps 78..155
        "d": rec(0, 78, 0, 78),
        "e": rec(0, 156, 0, 156),     # no fork: re-trains b's 78 steps
        "f": rec(0, 60, 0, 78),       # cut by the deadline at 60 steps
    }, **outputs)
    write_experiment(exp, sweep_journal(), windows, outputs)
    # The ASHA mix is kept as files without a ``workloads`` entry.
    cell = {"name": "bert-base.glue-asha-fork", "seconds": 20.0}
    for part, path in (("config", "configs/bert-base.json"),
                       ("mix", "traffic/glue-asha-fork.json")):
        with open(os.path.join(spec.BENCH_DIR, path)) as f:
            cell[part] = json.load(f)
    events = [json.loads(line) for line in
              open(os.path.join(exp, "telemetry.jsonl"))]
    return Window(cell, exp, events, process_start=T - 30.0, rehearse=True)


@pytest.fixture
def window(tmp_path):
    return make_window(tmp_path, {0: (21.0, 41.0), 1: (19.0, 39.0)})


def read(name, w):
    return spec.load_module("metrics", name).read(w)


def test_setup_and_held(window):
    assert window.setup_s == pytest.approx(30.0 + 21.0)
    assert window.held_s == pytest.approx(40.0)
    assert read("window_s", window) == pytest.approx(20.0)


def test_train_tput_counts_first_run_steps_only(window):
    steps = {t["id"]: (t["window_steps"], t["first_run_steps"])
             for t in window.trials}
    assert steps == {"a": (0, 0), "b": (0, 0), "c": (78, 78), "d": (78, 78),
                     "e": (156, 78), "f": (60, 60)}
    mix = window.cell["mix"]
    tokens = (78 + 78 + 78 + 60) * mix["batch"] * mix["seq"]
    assert window.tokens == tokens
    assert read("train_tput", window) == pytest.approx(tokens / 40.0)
    assert read("step_ms", window) == pytest.approx(1e3 * 40.0 / 294)


def test_trials_per_hour_is_over_whole_trials(window):
    # Started and finalised inside their runner's window: c (21.1-31.0 on 0),
    # d (19.3-29.0 on 1). e ends at 45 > 39; f's second attempt ends at 44.
    done = sorted(t["id"] for t in window.in_window(finalized=True))
    assert done == ["c", "d"]
    assert read("trials_per_hour", window) == pytest.approx(
        3600.0 * 2 / (31.0 - 19.3))


def test_layer_readers(window):
    assert read("goodput_pct", window) == pytest.approx(
        100.0 * window.fold["buckets"]["train"] / 40.0)
    assert read("ckpt_save_s", window) == pytest.approx(4.25)  # c, d, e, f
    assert read("fork_stage_ms", window) == pytest.approx(1500.0)
    assert read("warm_init_ms", window) == pytest.approx(225.0)
    assert read("compiles_in_window", window) == 0
    assert read("mfu_pct", window) is None        # never off the chip
    assert read("device_idle_pct", window) is None  # no trace in this run
    assert read("pallas_calls", window) == 0


# ------------------------------------------- the deadline and steps in flight

def test_steps_in_flight_at_the_deadline_count_over_the_time_they_took(
        tmp_path):
    """f had dispatched all 78 steps when the deadline (41.0) passed and got
    its last loss back at 43.5: the runner's window ends there, and the rate
    is over 22.5 s, not over the 20 s the deadline would give."""
    w = make_window(tmp_path, {0: (21.0, 43.5, 41.0), 1: (19.0, 39.0)},
                    f=rec(0, 78, 0, 78))
    assert w.held_s == pytest.approx(22.5 + 20.0)
    mix = w.cell["mix"]
    tokens = 4 * 78 * mix["batch"] * mix["seq"]
    assert read("train_tput", w) == pytest.approx(tokens / 42.5)
    assert read("window_s", w) == pytest.approx(42.5 / 2)
    # f finalises at 44.0, after its runner's t1: not a whole trial of the
    # window, as before.
    assert sorted(t["id"] for t in w.in_window(finalized=True)) == ["c", "d"]
    assert sum(w.fold["buckets"].values()) == pytest.approx(42.5, abs=1e-6)


def open_runner(tmp_path, seconds):
    run = trialfn._Runner({"seconds": seconds, "n_runners": 1, "trace": False},
                          str(tmp_path), 0)
    run.mark_ready()
    assert run.poll() == "opened"
    return run


def test_a_trial_that_straddles_the_deadline_closes_the_window_at_its_sync(
        tmp_path):
    """A short trial's steps are all dispatched before the deadline and
    finish after it: the host notices when the last loss is back, and that
    moment, not the deadline, is ``t1``."""
    import jax.numpy as jnp

    loss = jnp.ones(()) * 2.0
    run = open_runner(tmp_path, 0.2)
    assert run.poll(loss) is None and run.phase == trialfn.WINDOW
    time.sleep(0.3)  # the host inside float(loss), the device working
    before = time.time()
    assert run.poll(loss) == "closed" and run.phase == trialfn.DONE
    assert run.deadline + 0.05 <= before <= run.t1 <= time.time()
    with open(os.path.join(trialfn.bench_dir(str(tmp_path)),
                           "window.0.json")) as f:
        assert json.load(f)["t1"] == run.t1
    assert run.poll(loss) is None  # closed once


def test_a_runner_with_no_step_in_flight_closes_at_the_deadline(tmp_path):
    run = open_runner(tmp_path, 0.05)
    time.sleep(0.1)
    assert run.poll() == "closed"
    assert run.t1 == run.deadline == run.t0 + 0.05
