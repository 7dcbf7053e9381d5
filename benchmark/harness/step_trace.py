"""A whole training step's device time in a traced run, by the program's own
names: every device operation of one period of the span under exactly one
**part** ``"<scopes>:<pass>"`` (``block/moe_experts:bwd``,
``loop_mlp:remat``, ``optimizer:update``, ``unscoped:fwd``).

As for the expert layers, the state-space blocks and the looped stack
(``moe_trace.py``, ``ssm_trace.py``, ``loop_trace.py``), the scopes are not
in a trace taken without HLO protos, so the program says which instructions
are which: ``step_ops`` = ``{part: [instruction names]}`` of the trial's
``compiled`` record holds every operation of the step program whose body
resolves to one part, and ``step_mixed`` = ``{instruction: [[part, flops,
bytes], ...]}`` the fusions that XLA made across parts (a weight's gradient
product with that weight's optimizer update), first the part of the
fusion's own name, with what each part costs by the text's shapes
(``maggy_tpu/telemetry/hlo_scopes.py`` `Program.step_parts`). This reader
holds the chip's numbers: a mixed fusion's measured time is divided among
its parts in proportion to each part's least time, the larger of its FLOPs
over the peak and its bytes over the bandwidth.

Counted over ONE PERIOD of the span, as `loop_trace.reduce_loop` counts and
for its reason: one count for every cell, whether the span holds whole
`train_step` programs or not. A program without the fields (the parent of
the PR that brought them, the CPU rehearsal without a device plane) gives
None from every reader, never 0.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from benchmark.harness import (annotated, attention_work, loop_trace,
                               moe_trace, tracered)

#: Events that span their bodies' events: never a leaf.
CONTAINERS = ("while", "conditional", "call")
TOP = 10
UNSCOPED = "unscoped"
#: ``step_ops`` lists an instruction XLA made without a path (a relayout, a
#: weight's prefetch and the wait for it) under ``unscoped>`` and the part
#: of its first user: unscoped for a reader of roots, that part's here.
LENT, PATHLESS = UNSCOPED + ">", UNSCOPED + ":fwd"


def divide(took_ms: float, parts: List[list], flops_per_s: float,
           bytes_per_s: float) -> Dict[str, float]:
    """A mixed fusion's time by part, in proportion to each part's least
    time; all of it to the first (the fusion's own name's) where the text
    gave no part a cost."""
    least = {part: attention_work.least_seconds(
        flops, nbytes, flops_per_s, bytes_per_s)[0]
        for part, flops, nbytes in parts}
    total = sum(least.values())
    if not total:
        return {parts[0][0]: took_ms}
    return {part: took_ms * s / total for part, s in least.items()}


def _opcode(short: str) -> str:
    return short.split(" ")[1] if " " in short else "?"


def _add(into: Dict[str, float], key: str, ms: float) -> None:
    into[key] = into.get(key, 0.0) + ms


def _top(by_name: Dict[str, float]) -> List[list]:
    return [[name, ms] for name, ms in sorted(
        by_name.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce_step(trace: dict, step_ops: Dict[str, list],
                step_mixed: Dict[str, list], flops_per_s: float,
                bytes_per_s: float, stop_epoch_s: Optional[float] = None
                ) -> Optional[dict]:
    """One process's `annotated.load_annotated` trace over one period of its
    span (`loop_trace.step_period_ns`; the operations that start within a
    step's length of the first one recorded), ms a step:

    - ``by_root_ms``: every leaf whole under the part of its root, a mixed
      fusion under its first part and a pathless instruction under
      ``unscoped:fwd`` (what a reader of ``<kind>_ops`` sees);
    - ``parts_ms``: the same with each mixed fusion's time divided among
      its parts (`divide`) and each pathless instruction's given to the
      part that uses it; ``mixed_ms``: the fusions' time so divided, and
      ``moved_ms`` = ``{from part: {to part: ms}}`` for both;
    - ``unnoted_ms``: leaves whose instruction is in neither field or that
      ran outside every `train_step` program (another program, the
      runtime's copies), with ``unnoted_top``, the ten longest by name;
      ``unscoped_top``: the ten longest leaves of ``unscoped:*`` as
      ``[instruction, opcode, ms]``;
    - ``period_ms``; ``busy_ms``, the union of the leaves' intervals, which
      ``parts_ms`` and ``unnoted_ms`` sum to where no two leaves overlap;
      ``loop_gap_ms``, time inside a ``while`` that no leaf covers.

    A leaf is an event that is no ``while``, ``conditional`` or ``call``:
    those span their bodies' events, which are read themselves, an
    instruction inside a loop once each time its body runs. None where the
    span is shorter than a step."""
    if not trace["devices"] or trace.get("start_ns") is None:
        return None
    end_ns = float(trace["stop_ns"] - trace["start_ns"])
    if stop_epoch_s is not None:
        end_ns = min(end_ns, stop_epoch_s * 1e9 - trace["start_ns"])
    part_of = {name: part for part, names in step_ops.items()
               for name in names}
    for lines in trace["devices"].values():
        ops = [[n, s, d] for n, s, d in lines["ops"] if s < end_ns and d > 0]
        period_ns = loop_trace.step_period_ns(ops)
        if not period_ns:
            continue
        until_ns = min(s for _n, s, _d in ops) + period_ns
        inside = [op for op in ops if op[1] < until_ns]
        leaves = [op for op in inside if _opcode(op[0]) not in CONTAINERS]
        steps = sorted([s, s + d] for name, s, d in lines.get("modules", ())
                       if annotated.STEP_PROGRAM in name)
        starts = [s for s, _e in steps]

        def in_step(s: float) -> bool:
            i = bisect.bisect_right(starts, s) - 1
            return not steps or (i >= 0 and s < steps[i][1])

        by_root: Dict[str, float] = {}
        parts: Dict[str, float] = {}
        moved: Dict[str, Dict[str, float]] = {}
        unnoted: Dict[str, float] = {}
        unscoped: Dict[tuple, float] = {}
        mixed_ms = 0.0
        for short, s, d in leaves:
            name, ms = moe_trace.instruction(short), d / 1e6
            if name in part_of and in_step(s):
                root = part_of[name]
                shares = {root: ms}
                if root.startswith(LENT):
                    root, shares = PATHLESS, {root[len(LENT):]: ms}
            elif name in step_mixed and in_step(s):
                root = step_mixed[name][0][0]
                shares = divide(ms, step_mixed[name], flops_per_s,
                                bytes_per_s)
                mixed_ms += ms
            else:
                _add(unnoted, name, ms)
                continue
            _add(by_root, root, ms)
            for part, share in shares.items():
                _add(parts, part, share)
                if part != root:
                    _add(moved.setdefault(root, {}), part, share)
                if part.startswith(UNSCOPED):
                    _add(unscoped, (name, _opcode(short)), share)
        busy_ns, _ = tracered.busy_union(leaves)
        held_ns, _ = tracered.busy_union(inside)
        return {
            "period_ms": period_ns / 1e6, "busy_ms": busy_ns / 1e6,
            "loop_gap_ms": (held_ns - busy_ns) / 1e6,
            "by_root_ms": dict(sorted(by_root.items())),
            "parts_ms": dict(sorted(parts.items())),
            "mixed_ms": mixed_ms,
            "moved_ms": {k: dict(sorted(v.items()))
                         for k, v in sorted(moved.items())},
            "unnoted_ms": sum(unnoted.values()),
            "unnoted_top": _top(unnoted),
            "unscoped_top": [[name, opcode, ms] for (name, opcode), ms
                             in _top(unscoped)]}
    return None


def of_window(w) -> Optional[dict]:
    """The traced runner's reduction, read once per `Window` and kept on
    it; the full report gets it as ``trace_reduced.annotated.step``."""
    if hasattr(w, "step_trace"):
        return w.step_trace
    w.step_trace = None
    ops = mixed = None
    for t in w.trials:  # a warm trial traces nothing and notes nothing
        if t["compiled"].get("step_ops"):
            ops = t["compiled"]["step_ops"]
            mixed = t["compiled"].get("step_mixed") or {}
    for r in w.runners.values():
        info = r.get("trace")
        path = info and tracered.find_xplane(info["dir"])
        if path and ops and w.peak and w.step_trace is None:
            w.step_trace = reduce_step(
                annotated.load_annotated(path), ops, mixed, w.peak["flops"],
                attention_work.hbm_bytes_per_s(w.device_kind),
                info["t_stop"])
    annotated.note(w, "step", w.step_trace)
    return w.step_trace


def scopes_of(part: str) -> List[str]:
    """``block/moe_experts:bwd`` -> ``["block", "moe_experts"]``."""
    return part.rsplit(":", 1)[0].split("/")


def ms_where(w, wanted) -> Optional[float]:
    """``parts_ms`` summed over the parts ``wanted(part)`` holds for, or
    None where the run has no reading or no such part."""
    found = of_window(w)
    took = [ms for part, ms in ((found or {}).get("parts_ms") or {}).items()
            if wanted(part)]
    return sum(took) if took else None
