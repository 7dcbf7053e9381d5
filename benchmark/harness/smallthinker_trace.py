"""The windowed layers' flash kernels in a traced run: the device time of
the flash kernels (``flash_fwd``, ``flash_bwd_dkdv``, ``flash_bwd_dq``) that
ran under a scope, by the program's own names, over ONE PERIOD of the span
as ``step_trace.py`` counts it and through its `step_trace.reduce_step`.

``step_ops`` of the trial's ``compiled`` record says which instructions ran
under which part ``"<scopes>:<pass>"``; of those under a part that holds the
scope, the ones whose name is a flash kernel's (a `pallas_call`'s ``name=``
is its HLO instruction's) are the scope's kernels. A program without the
field or the scope (the parent of the PR that brought the scope, the CPU
rehearsal) gives None, never 0.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.harness import (annotated, attention_work, loop_trace,
                               step_trace, tracered)

#: The one part `reduce_step` is handed: the scope's kernels.
KERNELS = "flash:fwd"


def flash_instructions(step_ops: Dict[str, list], scope: str) -> List[str]:
    """Names of the flash kernels' instructions under parts that hold
    ``scope``."""
    return sorted(
        name for part, names in step_ops.items()
        if scope in step_trace.scopes_of(part.split(step_trace.LENT)[-1])
        for name in names if loop_trace._KERNEL.match(name))


def flash_ms(w, scope: str) -> Optional[float]:
    """Device ms a step of the flash kernels under ``scope``, read once per
    `Window` and scope and kept on it."""
    found = getattr(w, "scope_flash_ms", None)
    if found is None:
        found = w.scope_flash_ms = {}
    if scope in found:
        return found[scope]
    found[scope] = None
    names = []
    for t in w.trials:  # a warm trial traces nothing and notes nothing
        names = flash_instructions(t["compiled"].get("step_ops") or {},
                                   scope) or names
    for r in w.runners.values():
        info = r.get("trace")
        path = info and tracered.find_xplane(info["dir"])
        if path and names and w.peak and found[scope] is None:
            reduced = step_trace.reduce_step(
                annotated.load_annotated(path), {KERNELS: names}, {},
                w.peak["flops"], attention_work.hbm_bytes_per_s(w.device_kind),
                info["t_stop"])
            found[scope] = ((reduced or {}).get("parts_ms") or {}).get(
                KERNELS)
    return found[scope]
