"""The expert layers' device time in a traced run, by the program's own
names: the Pallas grouped products (``moe_gmm_fwd``, ``moe_gmm_dlhs``,
``moe_gmm_drhs``: a `pallas_call`'s ``name=`` is its HLO instruction's
name, as for the flash kernels) and every operation of the step that ran
under one of the layer's `jax.named_scope`s (``moe_routing``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``).

The scopes are not in a trace taken without HLO protos, and XLA's gathers
and scatters are plain ``%fusion.<n>`` there. So the program says which
instructions are which: after compiling the step, `Trainer` notes
``moe_ops`` = ``{scope: [instruction names]}`` in the trial's ``compiled``
record (``maggy_tpu/telemetry/hlo_scopes.py``), and an ``XLA Ops`` event is
the layer's where its instruction name is in that record. A program without
the record (the parent of the PR that brought it, the CPU rehearsal) gives
None from every reader, never 0.

As `annotated.reduce_annotated` does, only `train_step` programs that ran
whole inside the traced span count, and times are per such step.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Optional

from benchmark.harness import annotated, tracered

_GMM = re.compile(r"moe_gmm_[a-z]+")


def instruction(short: str) -> str:
    """``%fusion.12 fusion`` -> ``fusion.12``."""
    return short.split(" ", 1)[0].lstrip("%")


def reduce_moe(trace: dict, scopes: Dict[str, list],
               stop_epoch_s: Optional[float] = None) -> Optional[dict]:
    """``{"steps", "scopes_ms": {scope: ms a step}, "gmm_ms": {kernel: ms a
    step}}`` from one process's `annotated.load_annotated` trace, or None
    where no `train_step` program ran whole in the span."""
    if not trace["devices"] or trace.get("start_ns") is None:
        return None
    end_ns = float(trace["stop_ns"] - trace["start_ns"])
    if stop_epoch_s is not None:
        end_ns = min(end_ns, stop_epoch_s * 1e9 - trace["start_ns"])
    scope_of = {name: scope for scope, names in scopes.items()
                for name in names}
    steps, scope_ns, gmm_ns = 0, {}, {}
    for lines in trace["devices"].values():
        ops = [[n, s, d] for n, s, d in lines["ops"] if s < end_ns and d > 0]
        if not ops:
            continue
        begin_ns = min(s for _n, s, _d in ops)
        last_ns = max(s + d for _n, s, d in ops)
        whole = sorted([s, s + d] for name, s, d in lines["modules"]
                       if annotated.STEP_PROGRAM in name and s > begin_ns
                       and s + d <= end_ns and s + d < last_ns)
        steps += len(whole)
        starts = [s for s, _e in whole]
        for short, s, d in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= whole[i][1]:
                continue
            name = instruction(short)
            if name in scope_of:
                scope = scope_of[name]
                scope_ns[scope] = scope_ns.get(scope, 0.0) + d
            kernel = _GMM.search(name)
            if kernel and short.endswith("tpu_custom_call"):
                gmm_ns[kernel.group(0)] = gmm_ns.get(kernel.group(0), 0.0) + d
    if not steps:
        return None
    return {"steps": steps,
            "scopes_ms": {k: v / steps / 1e6
                          for k, v in sorted(scope_ns.items())} or None,
            "gmm_ms": {k: v / steps / 1e6
                       for k, v in sorted(gmm_ns.items())} or None}


def of_window(w) -> Optional[dict]:
    """The traced runner's reduction, read once per `Window` and kept on
    it; the full report gets it as ``trace_reduced.annotated.moe``. One
    runner: the cells of this family hold one trial on one chip."""
    if hasattr(w, "moe_trace"):
        return w.moe_trace
    w.moe_trace = None
    scopes = {}
    for t in w.trials:
        scopes = t["compiled"].get("moe_ops") or scopes
    for r in w.runners.values():
        info = r.get("trace")
        path = info and tracered.find_xplane(info["dir"])
        if path and w.moe_trace is None:
            w.moe_trace = reduce_moe(annotated.load_annotated(path), scopes,
                                     info["t_stop"])
    annotated.note(w, "moe", w.moe_trace)
    return w.moe_trace
