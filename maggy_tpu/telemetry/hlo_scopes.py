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
#: A kernel, a fusion or a sort with several outputs (`ssd_bwd`; a norm that
#: keeps its statistics, a product that also reduces its rows; keys sorted
#: with their values): its type is a tuple, which holds spaces.
_SEVERAL_OUTPUTS = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = \(.*?\) (custom-call|fusion|sort)\("
    r".*op_name=\"([^\"]*)\"")
#: Opcodes that move nothing on the device and are never a trace event.
_NO_EVENT = frozenset({"get-tuple-element", "constant", "bitcast",
                       "parameter", "tuple"})


def ops_by_scope(hlo_text: str, scopes: Iterable[str]) -> Dict[str, List[str]]:
    """Names of the instructions whose ``op_name`` path holds one of
    ``scopes`` as a component, by scope (the innermost where they nest).
    Instructions inside fused computations are left out: the fusion that
    calls them is the device's operation, and it carries the ``op_name`` of
    what it fused. Of the instructions whose result is a tuple a custom call,
    a fusion and a sort are read, each one device operation (a kernel that
    writes several arrays, `ssd_bwd`; XLA gives a norm's statistics and a
    product's row reductions a second output; a router sorts keys with
    their values). A ``while`` is its body's instructions, which are read
    themselves."""
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
        inst = _INSTRUCTION.match(line) or _SEVERAL_OUTPUTS.match(line)
        if not inst or inst.group(2) in _NO_EVENT:
            continue
        parts = inst.group(3).split("/")
        for part in reversed(parts):
            if part in found:
                found[part].append(inst.group(1))
                break
    return {s: sorted(names) for s, names in found.items() if names}
