"""Synthetic journals and experiment directories for the tests."""

import json
import os

T = 1000.0  # the experiment's start on the journal's clock


def trial_events(trial, partition, running, finalized, *, rung=0, parent=None,
                 forked_step=None, compiled=None, ckpt=None, terminal=None):
    """The events of one attempt, times relative to ``T``."""
    info = {"rung": rung}
    if parent:
        info["parent"] = parent
    ev = [{"t": T + running, "ev": "trial", "trial": trial, "phase": "queued",
           "info": info},
          {"t": T + running, "ev": "trial", "trial": trial,
           "phase": "assigned", "partition": partition}]
    if forked_step is not None:
        ev.append({"t": T + running, "ev": "trial", "trial": trial,
                   "phase": "forked_from", "partition": partition,
                   "parent": parent, "step": forked_step})
    ev.append({"t": T + running, "ev": "trial", "trial": trial,
               "phase": "running", "partition": partition})
    ev.append(dict({"t": T + finalized, "ev": "trial", "trial": trial,
                    "phase": "finalized", "partition": partition,
                    "error": False}, **(terminal or {})))
    if compiled:
        ev.append(dict({"t": T + finalized + 0.5, "ev": "trial",
                        "trial": trial, "phase": "compiled",
                        "partition": partition}, **compiled))
    if ckpt:
        ev.append(dict({"t": T + finalized + 0.5, "ev": "trial",
                        "trial": trial, "phase": "ckpt_saved",
                        "partition": partition}, **ckpt))
    return ev


def sweep_journal():
    """Two runners; a cold trial, warm trials, a served fork, a promotion
    that was served no fork (re-trains), a requeued (dead) attempt, gaps
    below and above the hand-off cap."""
    ev = [{"t": T, "ev": "experiment", "phase": "start"},
          {"t": T + 0.5, "ev": "runner", "phase": "registered", "partition": 0},
          {"t": T + 0.7, "ev": "runner", "phase": "registered", "partition": 1}]
    ev += trial_events("a", 0, 1.0, 21.0, compiled={
        "warm": False, "init_ms": 6000.0, "trace_ms": 2000.0,
        "compile_ms": 3000.0}, ckpt={"save_ms": 5000.0, "saves": 1})
    ev += trial_events("b", 1, 1.2, 19.0, compiled={
        "warm": False, "init_ms": 7000.0, "trace_ms": 2000.0,
        "compile_ms": 2500.0}, ckpt={"save_ms": 4000.0, "saves": 1})
    ev += trial_events("c", 0, 21.1, 31.0, rung=1, parent="a", forked_step=77,
                       compiled={"warm": True, "init_ms": 200.0,
                                 "fork_load_ms": 1500.0, "forked": True},
                       ckpt={"save_ms": 5000.0, "saves": 1,
                             "restore_ms": 1200.0, "restores": 1})
    ev += trial_events("d", 1, 19.3, 29.0, compiled={
        "warm": True, "init_ms": 300.0}, ckpt={"save_ms": 4500.0, "saves": 1})
    # e: promoted from b, no forked_from edge: re-trains b's prefix.
    ev += trial_events("e", 1, 32.5, 45.0, rung=1, parent="b", compiled={
        "warm": True, "init_ms": 250.0}, ckpt={"save_ms": 4000.0, "saves": 1})
    # f: an attempt that died (requeued) and ran again.
    ev += trial_events("f", 0, 31.2, 35.0, terminal={
        "phase": "requeued", "reason": "runner_lost"})
    ev += trial_events("f", 0, 35.4, 44.0, compiled={
        "warm": True, "init_ms": 100.0}, ckpt={"save_ms": 3000.0, "saves": 1})
    ev.append({"t": T + 46.0, "ev": "experiment", "phase": "finalized"})
    ev.sort(key=lambda e: e["t"])
    return ev


def write_experiment(exp_dir, events, windows, outputs):
    """An experiment directory as `lagom` and the trial function leave it:
    the journal, ``bench/window.<p>.json`` and each ``.outputs.json``.
    ``windows`` is ``{partition: (t0, t1)}`` or ``(t0, t1, deadline)`` where
    a trial's steps were in flight at the deadline."""
    os.makedirs(os.path.join(exp_dir, "bench"), exist_ok=True)
    with open(os.path.join(exp_dir, "telemetry.jsonl"), "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    for partition, (t0, t1, *deadline) in windows.items():
        with open(os.path.join(exp_dir, "bench",
                               "window.{}.json".format(partition)), "w") as f:
            json.dump({"partition": partition, "pid": 100 + partition,
                       "t0": T + t0, "deadline": T + (deadline or [t1])[0],
                       "t1": T + t1,
                       "counters0": {"hits": 3, "misses": 0},
                       "counters1": {"hits": 5, "misses": 0},
                       "platform": "cpu", "device_kind": "cpu",
                       "n_devices": 1, "memory_stats": {},
                       "pallas_calls": 0, "trace": None}, f)
    for trial, out in outputs.items():
        os.makedirs(os.path.join(exp_dir, trial), exist_ok=True)
        with open(os.path.join(exp_dir, trial, ".outputs.json"), "w") as f:
            json.dump(out, f)
