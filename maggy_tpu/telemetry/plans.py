"""What the traced parts of a program say of themselves, collected while
the program is traced and noted in the trial's ``compiled`` record.

A part that chooses something at trace time (the flash kernels' tiles, an
expert layer's share and row buffer) says it with `remember_plan` under a
kind of its own (``"flash"``, ``"moe"``); with it it may name the
`jax.named_scope`s it opens, so that the instructions of the compiled
program that ran under each can be told apart in a profiler trace
(`hlo_scopes.Program.ops_by_scope`). Whoever traces the program opens
`traced()` around the trace and gets every kind that spoke; it need not know
which parts the model is built from. `notes` turns that into the record's
fields: ``<kind>_plan`` and, for a kind that named scopes, ``<kind>_ops``;
and, whether a kind spoke or not, `STEP_FIELDS`: every device operation of
the program under one of the program's own scopes (`vocab.STEP_SCOPES`).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Iterable, List, Tuple

#: What `notes` says of every program whose text it can read: ``step_ops``
#: = ``{part: [instruction names]}`` and ``step_mixed`` = ``{instruction:
#: [[part, flops, bytes], ...]}`` (`hlo_scopes.Program.step_parts`).
STEP_FIELDS = ("step_ops", "step_mixed")

_tracing = threading.local()


class Traced:
    """``plans[kind]``: what the parts of that kind said, each once, in
    order; ``scopes[kind]``: the named scopes the kind opens."""

    def __init__(self):
        self.plans: Dict[str, List[str]] = {}
        self.scopes: Dict[str, Tuple[str, ...]] = {}


@contextlib.contextmanager
def traced():
    """Collects what is said on this thread while the body runs (a
    program's trace). Nested, the innermost hears it."""
    said, outer = Traced(), getattr(_tracing, "said", None)
    _tracing.said = said
    try:
        yield said
    finally:
        _tracing.said = outer


@contextlib.contextmanager
def plans_traced(kind: str = "flash"):
    """`traced()` for one kind: yields the list of its plans."""
    with traced() as said:
        yield said.plans.setdefault(kind, [])


def remember_plan(kind: str, said: str, scopes: Iterable[str] = ()) -> None:
    """Note ``said`` (and the scopes the kind opens) with the innermost
    open `traced()`; nothing where none is open."""
    heard = getattr(_tracing, "said", None)
    if heard is None:
        return
    plans = heard.plans.setdefault(kind, [])
    if said not in plans:
        plans.append(said)
    if scopes:
        heard.scopes[kind] = tuple(scopes)


def notes(said: Traced, compiled: Any) -> Dict[str, Any]:
    """The ``compiled`` record's fields for what a trace collected:
    ``<kind>_plan`` (several shapes in one program joined by `` | ``); for a
    kind that named scopes, ``<kind>_ops`` = ``{scope: [instruction
    names]}``; and `STEP_FIELDS` for the whole program. The last two are
    read from ``compiled.as_text()``, which is parsed once for all of them;
    an executable whose text cannot be read leaves them out and raises
    nothing."""
    from maggy_tpu.telemetry.hlo_scopes import Program
    from maggy_tpu.telemetry.vocab import STEP_SCOPES

    fields: Dict[str, Any] = {
        kind + "_plan": " | ".join(plans)
        for kind, plans in said.plans.items() if plans}
    try:
        program = Program(compiled.as_text())
        read = {kind + "_ops": program.ops_by_scope(scopes)
                for kind, scopes in said.scopes.items()
                if said.plans.get(kind)}
        read.update(zip(STEP_FIELDS, program.step_parts(STEP_SCOPES)))
    except Exception:  # noqa: BLE001 - a note, never a failure
        return fields
    fields.update(read)
    return fields
