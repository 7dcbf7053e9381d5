"""What the program's own names say in a traced run: the flash kernels'
device time by name, the host loop's annotations against the device's idle
time, and the trial boundaries of the journal's ``trial`` spans.

`tracered` reduces a trace to busy time and drops everything else. This
module opens the same ``.xplane.pb`` a second time and keeps what that
drops, again in two steps so that the arithmetic is checked on a recorded
cut without a chip (``benchmark/tests/fixtures/v5e_bert_annotated.json``):
`load_annotated` turns the file into plain data and `reduce_annotated`
works on that.

    {"start_ns": epoch ns of the session's start, "stop_ns": ... of its stop,
     "devices": {plane: {"ops": [[short name, start_ns, dur_ns]],
                         "modules": [[program, start_ns, dur_ns]]}},
     "host": {thread: [[annotation, start_ns, dur_ns, step_num or None]]}}

Where each name lands in a trace of the v5e (looked at by hand, PERF.md
section 3): a Pallas kernel's ``name=`` is the NAME OF ITS HLO INSTRUCTION
(``%flash_fwd.13 = ... custom-call(...)``, and `tracered.short_name` keeps
it); the jitted function's name is the ``XLA Modules`` event
(``jit_train_step(<fingerprint>)``); a `TraceAnnotation` is an event of the
host thread that opened it, under its own name, and a
`StepTraceAnnotation` carries ``step_num`` as a stat. A kernel is found by
its name and by nothing positional. A program that has none of these names
(the parent of the PR that brought them, the CPU rehearsal) gives None from
every reader, never 0.
"""

from __future__ import annotations

import bisect
import re
import statistics
from typing import Dict, List, Optional

from benchmark.harness import attention_work, tracered
from benchmark.harness.tracered import busy_union

MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: The jitted step function's name (``train/trainer.py`` `build_step_fn`).
STEP_PROGRAM = "train_step"
#: Host annotations the program opens (``telemetry/vocab.py``
#: ANNOTATION_NAMES and SPAN_NAMES, copied: the yardstick stays here).
HOST_NAMES = ("place_batch", "train_step", "report", "trial", "init",
              "trace", "compile", "fork_stage", "ckpt_save", "ckpt_restore")
#: The names ``ops/attention.py`` gives its `pallas_call`s all begin so.
_KERNEL = re.compile(r"flash_[a-z]+(?:_[a-z]+)*")
FORWARD, BACKWARD = "flash_fwd", "flash_bwd"


def kernel_of(short: str) -> Optional[str]:
    """The flash kernel an ``XLA Ops`` event ran, from the name its
    `pallas_call` carries (``flash_fwd``, ``flash_bwd_dkdv``,
    ``flash_bwd_dq``; autodiff wraps it, ``%transpose_jvp_flash_bwd_dq__.1``),
    or None for any other operation."""
    if not short.endswith("tpu_custom_call"):
        return None
    found = _KERNEL.search(short.split(" ", 1)[0])
    return found.group(0) if found else None


def load_annotated(path: str) -> dict:
    """The trace's named parts as plain data (the schema above)."""
    from jax.profiler import ProfileData

    out = {"start_ns": None, "stop_ns": None, "devices": {}, "host": {}}
    for plane in ProfileData.from_file(path).planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            out["start_ns"] = int(stats["profile_start_time"])
            out["stop_ns"] = int(stats["profile_stop_time"])
        if plane.name.startswith(tracered.DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if tracered.OPS_LINE in lines:
                out["devices"][plane.name] = {
                    "ops": [[tracered.short_name(e.name), float(e.start_ns),
                             float(e.duration_ns)]
                            for e in lines[tracered.OPS_LINE].events],
                    "modules": [[e.name, float(e.start_ns),
                                 float(e.duration_ns)]
                                for e in lines[MODULES_LINE].events]
                    if MODULES_LINE in lines else []}
        elif plane.name == HOST_PLANE:
            # Thread names repeat ("python3"); the index tells them apart.
            for i, line in enumerate(plane.lines):
                kept = [[e.name, float(e.start_ns), float(e.duration_ns),
                         dict(e.stats).get("step_num")]
                        for e in line.events if e.name in HOST_NAMES]
                if kept:
                    out["host"]["{}#{}".format(line.name, i)] = kept
    return out


def flatten(annotations: List[list]) -> List[list]:
    """``[[start, end, name]]``, sorted and disjoint, from one thread's
    annotations: where they nest (``trial`` around ``init``), the innermost
    names the time."""
    edges = sorted({t for _n, s, d, *_ in annotations for t in (s, s + d)})
    ordered = sorted(annotations, key=lambda a: (a[1], -a[2]))
    out: List[list] = []
    for lo, hi in zip(edges, edges[1:]):
        inner = None
        for name, s, d, *_ in ordered:
            if s > lo:
                break
            if s + d >= hi:
                inner = name  # a later start inside an earlier: innermost
        if inner is not None:
            if out and out[-1][2] == inner and out[-1][1] == lo:
                out[-1][1] = hi
            else:
                out.append([lo, hi, inner])
    return out


def idle_by_annotation(gaps: List[list], segments: List[list]
                       ) -> Dict[str, float]:
    """Idle nanoseconds by the annotation the host was inside (``none``
    where it was inside none)."""
    starts = [s for s, _e, _n in segments]
    out: Dict[str, float] = {}
    for g0, g1 in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(segments) and segments[i][0] < g1:
            s, e, name = segments[i]
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap
                covered += overlap
            i += 1
        if g1 - g0 > covered:
            out["none"] = out.get("none", 0.0) + (g1 - g0) - covered
    return out


def reduce_annotated(trace: dict, stop_epoch_s: Optional[float] = None
                     ) -> Optional[dict]:
    """One process's trace, over the span `tracered.reduce_trace` cuts (the
    first device operation to the moment the host asked for the stop):

    - ``steps``: `train_step` programs that ran whole inside the span, and
      ``kernels_ms``: each flash kernel's device time per such step, by name
      (None where no program of that name ran whole);
    - ``place_batch_ms``: the durations of the loop thread's ``place_batch``
      annotations inside the span, ``idle_by_annotation_s`` and
      ``input_wait_pct`` (None where no thread carries ``train_step``, or
      several do: the idle time of one device is then nobody's).

    None where the trace holds no device operation."""
    if not trace["devices"] or trace.get("start_ns") is None:
        return None
    end_ns = float(trace["stop_ns"] - trace["start_ns"])
    if stop_epoch_s is not None:
        end_ns = min(end_ns, stop_epoch_s * 1e9 - trace["start_ns"])
    devices = {}
    for plane, lines in trace["devices"].items():
        ops = [[n, s, min(d, end_ns - s)] for n, s, d in lines["ops"]
               if s < end_ns and d > 0]
        if ops:
            devices[plane] = dict(lines, ops=ops)
    if not devices:
        return None
    begin_ns = min(s for lines in devices.values() for _n, s, _d in lines["ops"])
    out = {"span_s": (end_ns - begin_ns) / 1e9, "steps": 0,
           "kernels_ms": None, "place_batch_ms": None,
           "idle_by_annotation_s": None, "input_wait_pct": None}

    # A program that was running when the session began is recorded from
    # the session's first operation on, and one that the end of the
    # recording cut is short too: a step counts where its program began
    # after the first recorded operation and ended inside the span, before
    # the last one.
    kernel_ns: Dict[str, float] = {}
    for lines in devices.values():
        last_ns = max(s + d for _n, s, d in lines["ops"])
        whole = [[s, s + d] for name, s, d in lines["modules"]
                 if STEP_PROGRAM in name and s > begin_ns
                 and s + d <= end_ns and s + d < last_ns]
        out["steps"] += len(whole)
        starts = [s for s, _e in whole]
        for name, s, d in lines["ops"]:
            kernel = kernel_of(name)
            i = bisect.bisect_right(starts, s) - 1
            if kernel and i >= 0 and s < whole[i][1]:
                kernel_ns[kernel] = kernel_ns.get(kernel, 0.0) + d
    if out["steps"] and kernel_ns:
        out["kernels_ms"] = {k: ns / out["steps"] / 1e6
                             for k, ns in sorted(kernel_ns.items())}

    loops = [evs for evs in trace["host"].values()
             if any(e[0] == STEP_PROGRAM for e in evs)]
    if len(loops) == 1 and len(devices) == 1:
        placed = [d / 1e6 for n, s, d, _num in loops[0]
                  if n == "place_batch" and s >= begin_ns and s + d <= end_ns]
        if placed:
            (lines,) = devices.values()
            _busy, merged = busy_union(lines["ops"])
            edges = [[begin_ns, begin_ns]] + merged + [[end_ns, end_ns]]
            gaps = [[e0, s1] for (_s0, e0), (s1, _e1)
                    in zip(edges, edges[1:]) if s1 > e0]
            # An annotation the span cuts still names its part of it.
            by_name = idle_by_annotation(gaps, flatten([
                [n, max(s, begin_ns), min(s + d, end_ns) - max(s, begin_ns)]
                for n, s, d, _num in loops[0]
                if s < end_ns and s + d > begin_ns]))
            out["place_batch_ms"] = placed
            out["idle_by_annotation_s"] = {
                k: v / 1e9 for k, v in sorted(by_name.items())}
            out["input_wait_pct"] = 100.0 * by_name.get(
                "place_batch", 0.0) / (end_ns - begin_ns)
    return out


def _mean_by_key(dicts: List[dict]) -> Dict[str, float]:
    return {k: statistics.mean(d.get(k, 0.0) for d in dicts)
            for k in sorted(set().union(*dicts))}


def merge_annotated(parts: List[Optional[dict]]) -> Optional[dict]:
    """Reductions of several processes' traces (one pinned runner each) as
    one: kernel times averaged over the runners that ran whole steps,
    ``place_batch`` durations pooled, the idle shares averaged."""
    parts = [p for p in parts if p]
    if len(parts) < 2:
        return parts[0] if parts else None
    kernels = [p["kernels_ms"] for p in parts if p["kernels_ms"]]
    looped = [p for p in parts if p["place_batch_ms"]]
    return {
        "span_s": statistics.mean(p["span_s"] for p in parts),
        "steps": sum(p["steps"] for p in parts),
        "kernels_ms": _mean_by_key(kernels) if kernels else None,
        "place_batch_ms": [ms for p in looped
                           for ms in p["place_batch_ms"]] or None,
        "idle_by_annotation_s": _mean_by_key(
            [p["idle_by_annotation_s"] for p in looped]) if looped else None,
        "input_wait_pct": statistics.mean(
            p["input_wait_pct"] for p in looped) if looped else None}


def of_window(w) -> Optional[dict]:
    """The traced runners' reductions as one, read once per `Window` and
    kept on it; the full report gets it as ``trace_reduced.annotated``."""
    if hasattr(w, "annotated"):
        return w.annotated
    parts = []
    for r in w.runners.values():
        info = r.get("trace")
        path = info and tracered.find_xplane(info["dir"])
        if path:
            parts.append(reduce_annotated(load_annotated(path),
                                          info["t_stop"]))
    w.annotated = merge_annotated(parts)
    note(w, "trace", w.annotated)
    return w.annotated


def note(w, key: str, value) -> None:
    """Put what a reader worked from into the full report, beside the
    reduction it came with (``trace_reduced.annotated``)."""
    if isinstance(w.trace, dict):
        w.trace.setdefault("annotated", {})[key] = value


# ----------------------------------------------------------------- kernels


def kernel_ms(w, prefix: str) -> Optional[float]:
    """Device milliseconds a step spends in the kernels whose name begins
    with ``prefix`` (``flash_`` is all three, ``flash_bwd`` both backward
    kernels, or the one a fused backward would be)."""
    found = of_window(w)
    if not found or not found["kernels_ms"]:
        return None
    times = [ms for k, ms in found["kernels_ms"].items()
             if k.startswith(prefix)]
    return sum(times) if times else None


def roofline_pct(w, direction: str) -> Optional[float]:
    """The least time the chip could take for a step's attention in one
    direction (``forward`` or ``backward``, every layer: the larger of FLOPs
    over the peak and least HBM bytes over the bandwidth,
    `attention_work`) over the time its kernels took. The report says
    which bound binds."""
    took_ms = kernel_ms(w, FORWARD if direction == "forward" else BACKWARD)
    if not took_ms or w.peak is None:
        return None
    mix = w.cell["mix"]
    work = attention_work.of_cell(w.cell["config"]["model"], mix["batch"],
                                  mix["seq"])
    least_s, bound = attention_work.least_seconds(
        work["layers"] * work[direction]["flops"],
        work["layers"] * work[direction]["bytes"], w.peak["flops"],
        attention_work.hbm_bytes_per_s(w.device_kind))
    note(w, direction + "_roofline", {
        "bound": bound, "least_ms": least_s * 1e3, "took_ms": took_ms})
    return 100.0 * least_s * 1e3 / took_ms


# --------------------------------------------------------- trial boundaries


def trial_boundaries(w) -> List[dict]:
    """Each hand-over of a runner from one trial to the next that lies
    inside the runner's window, from the ``trial`` span (``fn_enter``,
    ``fn_exit`` on the runner's clock) and the ``first_dispatch`` stamp of
    the trials' ``compiled`` records: ``{"gap_ms": fn_exit to the next
    fn_enter, "turnaround_ms": fn_exit to the next trial's first step
    dispatch}``. Trials whose record has no ``trial`` span give none."""
    per_runner: Dict[int, List[list]] = {}
    for t in w.trials:
        spans = {s[0]: s for s in t["compiled"].get("spans") or ()}
        if "trial" in spans and t["partition"] in w.runners:
            _n, enter, leave = spans["trial"]
            per_runner.setdefault(t["partition"], []).append(
                [enter, leave, t["compiled"].get("first_dispatch")])
    out = []
    for partition, trials in per_runner.items():
        r = w.runners[partition]
        trials.sort()
        for (_e0, leave, _d0), (enter, _l1, dispatch) in zip(trials,
                                                             trials[1:]):
            if leave >= r["t0"] and (dispatch or enter) <= r["t1"]:
                out.append({
                    "gap_ms": (enter - leave) * 1e3,
                    "turnaround_ms": None if dispatch is None
                    else (dispatch - leave) * 1e3})
    note(w, "boundaries", len(out))
    return out
