"""The state-space blocks' device time in a traced run, by the program's
own names: every operation of the step that ran under one of the mixer's
`jax.named_scope`s (``ssm_proj``, ``ssm_conv``, ``ssm_scan``,
``ssm_gate_norm``), and whatever Pallas kernels carry a ``name=`` that
begins ``ssd_`` (none while the scan is XLA products).

As for the expert layers (``moe_trace.py``, whose reduction this reads
with), the scopes are not in a trace taken without HLO protos, so the
program says which instructions are which: ``ssm_ops`` = ``{scope:
[instruction names]}`` of the trial's ``compiled`` record. A program without
the record (the parent of the PR that brought it, the CPU rehearsal) gives
None from every reader, never 0.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmark.harness import annotated, moe_trace, tracered

_KERNEL = re.compile(r"ssd_[a-z]+(?:_[a-z]+)*")


def kernels_of(trace: dict) -> Dict[str, list]:
    """``{kernel name: [instruction names]}`` of the trace's Mosaic calls
    whose name holds ``ssd_``."""
    found: Dict[str, set] = {}
    for lines in trace["devices"].values():
        for short, _s, _d in lines["ops"]:
            name = moe_trace.instruction(short)
            kernel = _KERNEL.search(name)
            if kernel and short.endswith("tpu_custom_call"):
                found.setdefault(kernel.group(0), set()).add(name)
    return {k: sorted(v) for k, v in found.items()}


def reduce_ssm(trace: dict, scopes: Dict[str, list],
               stop_epoch_s: Optional[float] = None) -> Optional[dict]:
    """``{"steps", "scopes_ms": {scope: ms a step}, "kernels_ms": {kernel:
    ms a step}}`` from one process's `annotated.load_annotated` trace, or
    None where no `train_step` program ran whole in the span."""
    by_scope = moe_trace.reduce_moe(trace, scopes, stop_epoch_s)
    if by_scope is None:
        return None
    by_kernel = moe_trace.reduce_moe(trace, kernels_of(trace), stop_epoch_s)
    return {"steps": by_scope["steps"], "scopes_ms": by_scope["scopes_ms"],
            "kernels_ms": by_kernel and by_kernel["scopes_ms"]}


def of_window(w) -> Optional[dict]:
    """The traced runner's reduction, read once per `Window` and kept on
    it; the full report gets it as ``trace_reduced.annotated.ssm``."""
    if hasattr(w, "ssm_trace"):
        return w.ssm_trace
    w.ssm_trace = None
    scopes = {}
    for t in w.trials:
        scopes = t["compiled"].get("ssm_ops") or scopes
    for r in w.runners.values():
        info = r.get("trace")
        path = info and tracered.find_xplane(info["dir"])
        if path and scopes and w.ssm_trace is None:
            w.ssm_trace = reduce_ssm(annotated.load_annotated(path), scopes,
                                     info["t_stop"])
    annotated.note(w, "ssm", w.ssm_trace)
    return w.ssm_trace
