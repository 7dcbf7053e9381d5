"""The looped stack's and the exits' device time in a traced run, by the
program's own names: every operation of the step that ran under one of the
model's `jax.named_scope`s (``loop_attn``, ``loop_mlp``; ``exit_norm``,
``exit_gate``, ``exit_head``).

As for the expert layers and the state-space blocks (``moe_trace.py`` and
``ssm_trace.py``), the scopes are not in a trace taken without HLO protos,
so the program says which instructions are which: ``loop_ops`` = ``{scope:
[instruction names]}`` of the trial's ``compiled`` record. An instruction
inside a ``while`` (the head's chunks) is an event every time the body
runs, so a step's time under a scope is the sum over the chunks. Unlike
those two readers this one counts over one period of the span and not over
whole `train_step` programs (`reduce_loop`). A program
without the record (the parent of the PR that brought it, the CPU
rehearsal) gives None from every reader, never 0.
"""

from __future__ import annotations

import bisect
import re
import statistics
from typing import Dict, Optional

from benchmark.harness import annotated, moe_trace, tracered

_KERNEL = re.compile(r"flash_[a-z]+(?:_[a-z]+)*")

#: Which scopes make up which layer's metric.
LOOP = ("loop_attn", "loop_mlp")
EXITS = ("exit_norm", "exit_gate", "exit_head")


def flash_kernels(trace: dict) -> Dict[str, list]:
    """``{kernel name: [instruction names]}`` of the trace's Mosaic calls
    whose name holds ``flash_``: the report lists their time beside the
    scopes', by the same count."""
    found: Dict[str, set] = {}
    for lines in trace["devices"].values():
        for short, _s, _d in lines["ops"]:
            name = moe_trace.instruction(short)
            kernel = _KERNEL.search(name)
            if kernel and short.endswith("tpu_custom_call"):
                found.setdefault(kernel.group(0), set()).add(name)
    return {k: sorted(v) for k, v in found.items()}


def step_period_ns(ops: list) -> Optional[float]:
    """The step's length by the device's own clock: the distance between two
    starts of the same instruction. An instruction outside every ``while``
    runs once a step, so the distance from one start of it to its next is
    the period; one inside a loop (found by the ``while`` event that spans
    it) runs as often as the body and is left out. The median over every
    such pair the span holds; None where it holds none: it is shorter than
    a step."""
    _, loops = tracered.busy_union(
        [op for op in ops if op[0].endswith(" while")])  # nested ones merged
    begins = [s for s, _e in loops]
    starts: Dict[str, list] = {}
    for short, s, _d in ops:
        i = bisect.bisect_left(begins, s) - 1  # a loop that began earlier
        if i < 0 or s >= loops[i][1]:
            starts.setdefault(short, []).append(s)
    apart = [b - a for v in starts.values()
             for a, b in zip(sorted(v), sorted(v)[1:])]
    return statistics.median(apart) if apart else None


def reduce_loop(trace: dict, scopes: Dict[str, list],
                stop_epoch_s: Optional[float] = None) -> Optional[dict]:
    """``{"period_ms", "scopes_ms": {scope: ms a step}, "kernels_ms": {flash
    kernel: ms a step}}`` from one process's `annotated.load_annotated`
    trace, over ONE PERIOD of the span: the operations that start within a
    step's length (`step_period_ns`) of the first one recorded. The steps of
    a steady trial are one program run back to back, so any stretch as long
    as a step holds each of its operations once, wherever in a step it
    starts. The other readers count `train_step` programs that ran whole in
    the span; this cell's step is 0.9 s and the traced span 1.5 s, and no
    traced run of it has held one. None where the span is shorter than a
    step."""
    if not trace["devices"] or trace.get("start_ns") is None:
        return None
    end_ns = float(trace["stop_ns"] - trace["start_ns"])
    if stop_epoch_s is not None:
        end_ns = min(end_ns, stop_epoch_s * 1e9 - trace["start_ns"])
    kernels = flash_kernels(trace)
    for lines in trace["devices"].values():
        ops = [[n, s, d] for n, s, d in lines["ops"] if s < end_ns and d > 0]
        period_ns = step_period_ns(ops)
        if not period_ns:
            continue
        until_ns = min(s for _n, s, _d in ops) + period_ns

        def took_ms(names_by_key):
            key_of = {name: key for key, names in names_by_key.items()
                      for name in names}
            took: Dict[str, float] = {}
            for short, s, d in ops:
                key = key_of.get(moe_trace.instruction(short))
                if key and s < until_ns:
                    took[key] = took.get(key, 0.0) + d / 1e6
            return dict(sorted(took.items())) or None

        return {"period_ms": period_ns / 1e6, "scopes_ms": took_ms(scopes),
                "kernels_ms": took_ms(kernels)}
    return None


def of_window(w) -> Optional[dict]:
    """The traced runner's reduction, read once per `Window` and kept on
    it; the full report gets it as ``trace_reduced.annotated.loop``."""
    if hasattr(w, "loop_trace"):
        return w.loop_trace
    w.loop_trace = None
    scopes = {}
    for t in w.trials:
        scopes = t["compiled"].get("loop_ops") or scopes
    for r in w.runners.values():
        info = r.get("trace")
        path = info and tracered.find_xplane(info["dir"])
        if path and scopes and w.loop_trace is None:
            w.loop_trace = reduce_loop(
                annotated.load_annotated(path), scopes, info["t_stop"])
    annotated.note(w, "loop", w.loop_trace)
    return w.loop_trace


def ms_under(w, scopes) -> Optional[float]:
    """Device ms a step under ``scopes`` together, or None where the trace
    holds none of them."""
    found = of_window(w)
    took = [ms for scope, ms in ((found or {}).get("scopes_ms") or {}).items()
            if scope in scopes]
    return sum(took) if took else None
