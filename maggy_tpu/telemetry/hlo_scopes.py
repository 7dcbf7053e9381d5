"""Which instructions of a compiled program ran under which
`jax.named_scope`: the link between the program's own names and a profiler
trace that was taken without HLO protos.

A trace names a device operation by its HLO instruction (``%fusion.4242``,
``%moe_gmm_fwd.13``); the scopes are only in the ``op_name`` metadata of the
executable's text. `ops_by_scope` reads that text once, after the compile,
and gives ``{scope: [instruction names]}``, which `Trainer` notes in the
trial's ``compiled`` record (``moe_ops``) for a reader of the trace to match
events against.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = \S+ ([\w\-]+)\(.*op_name=\"([^\"]*)\"")
#: A kernel with several outputs: its type is a tuple, which holds spaces.
_KERNEL = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = \(.*?\) (custom-call)\(.*op_name=\"([^\"]*)\"")
#: Opcodes that move nothing on the device and are never a trace event.
_NO_EVENT = frozenset({"get-tuple-element", "constant", "bitcast",
                       "parameter", "tuple"})


def ops_by_scope(hlo_text: str, scopes: Iterable[str]) -> Dict[str, List[str]]:
    """Names of the instructions whose ``op_name`` path holds one of
    ``scopes`` as a component, by scope (the innermost where they nest).
    Instructions inside fused computations are left out: the fusion that
    calls them is the device's operation, and it carries the ``op_name`` of
    what it fused. Of the instructions whose result is a tuple only a
    custom call is read (a kernel that writes several arrays, `ssd_bwd`):
    a ``while`` is its body's instructions, which are read themselves."""
    wanted = tuple(scopes)
    found: Dict[str, List[str]] = {s: [] for s in wanted}
    fused = False
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            fused = "fus" in head.group(1)  # fused_computation.N, *_fusion
            continue
        if fused or "op_name=" not in line:
            continue
        inst = _INSTRUCTION.match(line) or _KERNEL.match(line)
        if not inst or inst.group(2) in _NO_EVENT:
            continue
        parts = inst.group(3).split("/")
        for part in reversed(parts):
            if part in found:
                found[part].append(inst.group(1))
                break
    return {s: sorted(names) for s, names in found.items() if names}
