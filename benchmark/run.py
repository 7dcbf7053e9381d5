"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It drives ONE `maggy_tpu.experiment.lagom` call whose
trial function is the benchmark's own (``harness/trialfn.py``), over the
configuration, mix and metrics that BENCHMARK.json names, all found as files
under ``benchmark/``. The last line of stdout is the result the driver
reads; the line before it is the full report, which also goes to
``chiprun_out/benchmark/<cell>/result.json``.

It needs a TPU. ``--rehearse`` (with ``JAX_PLATFORMS=cpu``) swaps in each
file's tiny ``rehearse`` preset to exercise the control flow on the CPU; its
output names the CPU as the device and is never a device number. Any failure
is a non-zero exit with the reason on stderr and no result line.
"""

from __future__ import annotations

import time

_PROCESS_START = time.time()

import argparse  # noqa: E402
import functools  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: The contract allows a run 360 s (its first, compiling run 1200 s).
RUN_LIMIT_S = 1150


class BenchFailure(Exception):
    """The run cannot give a result."""


def require(cond, reason: str) -> None:
    if not cond:
        raise BenchFailure(reason)


def merged(base: dict, override: dict) -> dict:
    """``base`` with ``override``'s keys; nested dicts merge key by key."""
    out = dict(base)
    for k, v in override.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(args) -> dict:
    """The cell as the plain data the trial function gets."""
    from benchmark.harness import spec

    cell = spec.load_cell(args.workload)
    for part in ("config", "mix"):
        preset = cell[part].pop("rehearse", {})
        if args.rehearse:
            cell[part] = merged(cell[part], preset)
    deployment = cell["config"]["deployment"]
    workers = deployment["num_workers"]
    cell.update(
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        platform="cpu" if args.rehearse else "tpu",
        # "auto" is one pinned runner per chip of the host the cell asks for;
        # the driver clamps the pool to the number of trials.
        n_runners=min(cell["workload"]["chips"] if workers == "auto"
                      else int(workers),
                      cell["mix"]["experiment"]["num_trials"]))
    return cell


def experiment_config(cell: dict, base_dir: str):
    from maggy_tpu import OptimizationConfig, Searchspace, optimizers

    mix, deployment = cell["mix"], cell["config"]["deployment"]
    optimizer = mix["optimizer"]
    if isinstance(optimizer, dict):
        optimizer = getattr(optimizers, optimizer["class"])(
            seed=cell["seed"], **optimizer["args"])
    space = mix["searchspace"]
    return OptimizationConfig(
        name=cell["name"], optimizer=optimizer,
        searchspace=Searchspace(**{k: tuple(v) for k, v in space.items()})
        if space else None,
        seed=cell["seed"], pool=deployment["pool"],
        num_workers=deployment["num_workers"], chips_per_trial=1,
        experiment_dir=base_dir, **mix["experiment"])


def check_model(cell: dict, in_process: bool, work_dir: str) -> dict:
    """The model against the plain reference, outside the window: here where
    this process holds the chip, in a child where runner processes did."""
    payload = {"config": cell["config"], "seq": cell["mix"]["seq"],
               "seed": cell["seed"]}
    if in_process:
        from benchmark.harness import checks

        return checks.model_vs_reference(**payload)
    path = os.path.join(work_dir, "check_payload.json")
    with open(path, "w") as f:
        json.dump(payload, f)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.checks", path], cwd=ROOT,
        stdout=subprocess.PIPE, timeout=600)
    require(proc.returncode == 0, "the reference check's child exited with "
            "code {}".format(proc.returncode))
    return json.loads(proc.stdout.decode().strip().splitlines()[-1])


def window_checks(w, cell: dict) -> dict:
    """The checks on what ran inside the window, each True or a reason."""
    checks = {}
    started = w.active()
    bad = [t["id"] for t in started if t["error"] or t["t_finalized"] is None]
    checks["trials_finalized"] = not bad or \
        "trials failed or never finalised: {}".format(bad)

    def moved(t):
        out = t["out"] or {}
        return out.get("steps_run", 0) < 2 or (
            math.isfinite(out["metric"]) and math.isfinite(out["first_loss"])
            and out["metric"] != out["first_loss"])

    stuck = [t["id"] for t in started if not moved(t)]
    checks["loss_finite_and_changed"] = not stuck or \
        "trials whose loss is not finite or did not change: {}".format(stuck)

    by_id = {t["id"]: t for t in w.trials}
    unforked = []
    for t in started:
        out = t["out"] or {}
        if not t["parent"] or not cell["mix"]["experiment"]["fork"] \
                or not out.get("steps_run"):
            continue
        if (by_id[t["parent"]]["out"] or {}).get("drained"):
            continue  # its parent ran past another runner's deadline
        # First executed step = the parent's last + 1, after one restore
        # (a trial cut by the deadline may not have shipped its record).
        served = t["forked"] is not None \
            and out["first_step"] == int(t["forked"]["step"]) + 1 \
            and (not t["ckpt"] or t["ckpt"].get("restores", 0) >= 1)
        if not served:
            unforked.append(t["id"])
    checks["promotions_forked"] = not unforked or \
        "promotions not served from a fork: {}".format(unforked)

    total = sum(w.fold["buckets"].values())
    checks["fold_closes"] = math.isclose(
        total, w.fold["held_chip_s"], rel_tol=0, abs_tol=1e-6) or \
        "sum(buckets)={} held={}".format(total, w.fold["held_chip_s"])

    if cell["platform"] == "tpu":
        calls = {r["pallas_calls"] for r in w.runners.values()}
        want = cell["config"]["attention"]
        ok = None not in calls and (
            min(calls) > 0 if want == "pallas" else max(calls) == 0)
        checks["attention_path"] = ok or \
            "the configuration says attention runs in {!r}; the step " \
            "executables hold {} tpu_custom_call".format(want, sorted(
                calls, key=str))
    return checks


def run(args) -> dict:
    require(os.path.isdir(os.path.join(ROOT, "maggy_tpu")),
            "no maggy_tpu/ next to benchmark/: the benchmark measures the "
            "repository it is part of")
    on_cpu = os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
    require(args.rehearse == on_cpu,
            "--rehearse goes with JAX_PLATFORMS=cpu and only with it: a "
            "measurement needs the TPU, a rehearsal must not hold it")
    cell = load_cell(args)
    deployment = cell["config"]["deployment"]
    in_process = deployment["pool"] == "thread"

    from maggy_tpu import experiment, util
    from maggy_tpu.telemetry import JOURNAL_NAME, read_events

    from benchmark.harness import trialfn
    from benchmark.harness.window import Window

    if in_process:
        # This process holds the chip and its runner threads share it. With
        # runner processes it stays off the backend: they need the chips.
        import jax

        util.enable_compile_cache()
        devices = jax.devices()
        require(devices[0].platform == cell["platform"],
                "JAX found no accelerator: devices are {}".format(devices))
        require(args.rehearse or len(devices) >= cell["workload"]["chips"],
                "the cell asks for {} chip(s), JAX found {}".format(
                    cell["workload"]["chips"], len(devices)))

    work_dir = os.path.join(ROOT, ".bench_runs", cell["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    try:
        experiment.lagom(functools.partial(trialfn.trial, cell=cell),
                         experiment_config(cell, work_dir))
        lagom_end = time.time()
        exp_dir, = [d for d in glob.glob(os.path.join(work_dir, "*"))
                    if os.path.isdir(d)]
        events = read_events(os.path.join(exp_dir, JOURNAL_NAME))
        w = Window(cell, exp_dir, events, _PROCESS_START, args.rehearse)
        window_end = time.time()

        device = w.device
        require(device["platform"] == cell["platform"],
                "the trials ran on {!r}".format(device["platform"]))
        require(args.rehearse
                or device["count"] >= cell["workload"]["chips"],
                "the cell asks for {} chip(s), the runners held {}".format(
                    cell["workload"]["chips"], device["count"]))

        checks = window_checks(w, cell)
        reference = check_model(cell, in_process, work_dir)
        checks["model_vs_reference"] = reference["ok"] or \
            "errors {} beyond {}".format(reference["errors"],
                                         reference["tolerances"])
        # What a run costs after its window, which every check pays too.
        after = {"drain_and_shutdown": lagom_end - max(
                     r["t1"] for r in w.runners.values()),
                 "window_assembly": window_end - lagom_end,
                 "checks": time.time() - window_end}
        return report(w, cell, args, checks, reference, exp_dir, after)
    finally:
        # Gigabytes of checkpoints have served; the journal stays.
        for d in glob.glob(os.path.join(work_dir, "*", "*", "checkpoints")):
            shutil.rmtree(d, ignore_errors=True)


def report(w, cell: dict, args, checks: dict, reference: dict,
           exp_dir: str, after_window_s: dict) -> dict:
    from benchmark.harness import spec

    entries = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for entry in entries:
        value = spec.load_module("metrics", entry["name"]).read(w)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    missing = [e["name"] for e in cell["end_to_end"]
               if not args.trace and e["name"] not in metrics]
    require(not missing, "the window gave no value for {}".format(missing))

    started = w.active()
    device = dict(w.device)
    result = {
        "correct": all(v is True for v in checks.values()),
        "attempted": len(started),
        "failed": sum(1 for t in started
                      if t["error"] or t["t_finalized"] is None),
        "metrics": metrics,
        "device": device,
    }
    rungs = {}
    for t in w.in_window(finalized=True):
        rungs[str(t["rung"])] = rungs.get(str(t["rung"]), 0) + 1
    full = {
        "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "rehearse": args.rehearse,
        "result": result, "checks": checks, "reference": reference,
        "setup_s": w.setup_s, "held_chip_s": w.held_s,
        "after_window_s": after_window_s,
        "first_run_steps": w.first_run_steps, "tokens": w.tokens,
        "buckets": w.fold["buckets"],
        "rungs_finalized_in_window": rungs,
        "runners": {str(p): {k: r[k] for k in (
            "pid", "t0", "t1", "counters0", "counters1", "pallas_calls",
            "memory_stats", "trace")}
            for p, r in w.runners.items()},
        "trials": [{
            "id": t["id"], "rung": t["rung"], "partition": t["partition"],
            "started_in": t["started_in"], "finalized_in": t["finalized_in"],
            "window_steps": t["window_steps"],
            "first_run_steps": t["first_run_steps"],
            "s": None if None in (t["t_running"], t["t_finalized"])
            else t["t_finalized"] - t["t_running"],
            "compiled": {k: v for k, v in t["compiled"].items()
                         if k.endswith("_ms") or k in ("warm", "forked")},
            "ckpt": {k: v for k, v in t["ckpt"].items()
                     if k.endswith("_ms") or k in ("saves", "restores")},
        } for t in w.trials if t["t_running"] is not None
            and not (t["out"] or {}).get("drained")],
        "trace_reduced": w.trace,
        "journal": os.path.relpath(exp_dir, ROOT),
    }
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    for path in glob.glob(os.path.join(exp_dir, "*.log")) + glob.glob(
            os.path.join(exp_dir, "bench", "window.*.json")) + [
            os.path.join(exp_dir, "telemetry.jsonl")]:
        shutil.copy(path, out_dir)

    def write_report():
        with open(os.path.join(out_dir, "result.json"), "w") as f:
            json.dump(full, f, indent=1)

    write_report()  # kept even where the run is then refused
    if args.trace:
        require(w.trace is not None and w.trace["busy_s"] > 0,
                "the traced run recorded no device operation")
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
        result["breakdown"] = {"device_ops": w.trace["device_ops"],
                               "idle_gaps": w.trace["idle_gaps"]}
        write_report()
    return {"full": full, "result": result}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the tiny presets; never a "
                    "device number")
    args = ap.parse_args()

    def on_alarm(signum, frame):
        raise BenchFailure("run limit of {} s reached".format(RUN_LIMIT_S))

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(RUN_LIMIT_S)
    try:
        out = run(args)
    except Exception as e:  # noqa: BLE001 - any failure: no result line
        import traceback

        traceback.print_exc()
        print("benchmark: FAILED: {}".format(e), file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
        for proc in multiprocessing.active_children():
            proc.kill()
            proc.join()
    print(json.dumps(out["full"]), flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
