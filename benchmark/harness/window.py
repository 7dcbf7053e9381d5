"""What happened inside the measured window, assembled after `lagom` returns
from the journal, each trial's ``.outputs.json``, the runners' window files
and, in a traced run, their profiler traces. One `Window` is what every
metric reader gets.
"""

from __future__ import annotations

import json
import math
import os
import statistics
from typing import Any, Dict, List, Optional

from benchmark.harness import fold, peaks, spec, tracered, trialfn


def median(values: List[float]) -> Optional[float]:
    """The median of the values that are there, or None of none."""
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


class Window:
    """The measured window of one run. Attributes a reader may use:

    ``cell`` (workload, config, mix as loaded), ``seconds`` asked for,
    ``setup_s``, ``runners`` ({partition: window file + ``t0``/``t1``}),
    ``held_s`` (sum over runners of ``t1 - t0``), ``fold`` (the windowed
    fold), ``trials`` (one dict per trial, see `_trials`), ``events`` (the
    journal), ``first_run_steps``, ``tokens``, ``flops_per_token``,
    ``peak`` (the chip's peak rates), ``trace`` (reduced, or None).
    """

    def __init__(self, cell: dict, exp_dir: str, events: List[dict],
                 process_start: float, rehearse: bool = False):
        self.cell = cell
        self.seconds = cell["seconds"]
        self.events = events
        self.runners = self._runners(exp_dir)
        if not self.runners:
            raise RuntimeError("no runner opened the window: see " + exp_dir)
        self.setup_s = max(r["t0"] for r in self.runners.values()) \
            - process_start
        self.held_s = sum(r["t1"] - r["t0"] for r in self.runners.values())
        self.fold = fold.windowed_fold(
            events, {p: (r["t0"], r["t1"]) for p, r in self.runners.items()})
        self.trials = self._trials(exp_dir)
        family = spec.load_module("families", cell["config"]["family"])
        model, mix = cell["config"]["model"], cell["mix"]
        self.positions = family.positions(model, mix["seq"])
        self.first_run_steps = sum(t["first_run_steps"] for t in self.trials)
        self.tokens = self.first_run_steps * mix["batch"] * self.positions
        self.flops_per_token = family.flops_per_token(model, mix["seq"])
        kinds = {r["device_kind"] for r in self.runners.values()}
        self.device_kind = sorted(kinds)[0]
        self.platform = sorted({r["platform"]
                                for r in self.runners.values()})[0]
        self.peak = None if rehearse else peaks.chip_peaks(self.device_kind)
        self.trace = self._trace(rehearse)

    # ------------------------------------------------------------ runners
    def _runners(self, exp_dir: str) -> Dict[int, dict]:
        out = {}
        bench = trialfn.bench_dir(exp_dir)
        ends = [e["t"] for e in self.events if e.get("ev") == "experiment"
                and e.get("phase") == "finalized"]
        for name in sorted(os.listdir(bench) if os.path.isdir(bench) else []):
            if name.startswith("window.") and name.endswith(".json"):
                with open(os.path.join(bench, name)) as f:
                    r = json.load(f)
                if r["t0"] is None:
                    continue
                if r["t1"] is None:
                    # Idle from before the deadline to the end. A sweep that
                    # ran out of trials closes the window at its own end.
                    r["t1"] = min([r["deadline"]] + ends)
                out[int(r["partition"])] = r
        return out

    @property
    def device(self) -> dict:
        """The device as JAX reported it to the runners: a thread pool's
        runners share their process's devices, pinned runners have one
        process and one chip each."""
        by_pid = {r["pid"]: r["n_devices"] for r in self.runners.values()}
        # The allocator's peak of live buffers plus what it reserved for the
        # programs' temporaries: on the TPU a step's activations are in
        # ``peak_bytes_reserved`` and not in ``peak_bytes_in_use``.
        peaks_ = [r["memory_stats"].get("peak_bytes_in_use", 0)
                  + r["memory_stats"].get("peak_bytes_reserved", 0)
                  for r in self.runners.values()]
        return {"platform": self.platform, "kind": self.device_kind,
                "count": sum(by_pid.values()),
                "memory_peak_bytes": max(peaks_)}

    # ------------------------------------------------------------- trials
    def _trials(self, exp_dir: str) -> List[Dict[str, Any]]:
        """One dict per trial the journal queued: ``id``, ``rung``,
        ``parent``, ``partition``, ``t_running``, ``t_finalized``,
        ``error``, ``forked`` (journal edge), ``compiled`` and ``ckpt``
        (the runner's records), ``out`` (its ``.outputs.json``),
        ``started_in`` / ``finalized_in`` the window of its runner,
        ``window_steps``, ``first_run_steps`` and ``active_in`` (started or
        trained in the window)."""
        trials: Dict[str, Dict[str, Any]] = {}
        for e in self.events:
            if e.get("ev") != "trial" or not e.get("trial"):
                continue
            t = trials.setdefault(e["trial"], {
                "id": e["trial"], "rung": 0, "parent": None,
                "partition": None, "t_running": None, "t_finalized": None,
                "error": False, "forked": None, "compiled": {}, "ckpt": {},
                "out": None})
            phase = e.get("phase")
            if phase == "queued":
                info = e.get("info") or {}
                t["rung"], t["parent"] = info.get("rung", 0), info.get("parent")
            elif phase == "running" and t["t_running"] is None:
                t["t_running"], t["partition"] = e["t"], e.get("partition")
            elif phase == "finalized":
                t["t_finalized"], t["error"] = e["t"], bool(e.get("error"))
            elif phase == "forked_from":
                t["forked"] = {"parent": e.get("parent"), "step": e.get("step")}
            elif phase == "compiled":
                t["compiled"] = e
            elif phase == "ckpt_saved":
                t["ckpt"] = e
        for t in trials.values():
            path = os.path.join(exp_dir, t["id"], ".outputs.json")
            if os.path.isfile(path):
                with open(path) as f:
                    t["out"] = json.load(f)
        for t in trials.values():
            r = self.runners.get(t["partition"])
            t["started_in"] = bool(
                r and t["t_running"] is not None
                and r["t0"] <= t["t_running"] <= r["t1"])
            t["finalized_in"] = bool(
                t["started_in"] and t["t_finalized"] is not None
                and t["t_finalized"] <= r["t1"])
            out = t["out"] or {}
            opened = out.get("opened_at_step")
            t["window_steps"] = 0 if opened is None or out.get("drained") \
                else out["steps_run"] - opened
            # Steps below the parent's total that a promotion ran itself
            # (no fork was served) are re-training, not first-run work.
            rework = 0
            parent = trials.get(t["parent"]) if t["parent"] else None
            if t["window_steps"] and parent and parent["out"]:
                first = out["first_step"] + opened
                rework = max(0, min((parent["out"].get("target_steps") or 0)
                                    - first, t["window_steps"]))
            t["first_run_steps"] = t["window_steps"] - rework
            t["active_in"] = t["started_in"] or t["window_steps"] > 0
        return sorted(trials.values(), key=lambda t: t["t_running"] or math.inf)

    def in_window(self, finalized: bool = False) -> List[dict]:
        """Trials that started inside the window; with ``finalized`` those
        that finalised inside it as well."""
        key = "finalized_in" if finalized else "started_in"
        return [t for t in self.trials if t[key]]

    def active(self) -> List[dict]:
        """Trials that started or trained inside the window."""
        return [t for t in self.trials if t["active_in"]]

    # -------------------------------------------------------------- trace
    def label_gap(self, partition: int):
        """What the runner's host side was doing between two epoch times:
        the fold's bucket that covers most of the gap."""
        pieces = self.fold.get("timeline", {}).get(partition, [])

        def label(g0: float, g1: float) -> Optional[str]:
            best, best_s = None, 0.0
            for s, e, bucket, _trial in pieces:
                overlap = min(e, g1) - max(s, g0)
                if overlap > best_s:
                    best, best_s = bucket, overlap
            return best

        return label

    def _trace(self, rehearse: bool) -> Optional[dict]:
        parts = []
        for partition, r in self.runners.items():
            info = r.get("trace")
            path = info and tracered.find_xplane(info["dir"])
            if path:
                parts.append(tracered.reduce_trace(
                    tracered.load_xplane(path, tracered.wanted_line),
                    self.label_gap(partition), rehearse, info["t_stop"]))
        return tracered.merge_reductions(parts)
