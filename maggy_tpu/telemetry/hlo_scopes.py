"""Which instructions of a compiled program ran under which
`jax.named_scope`: the link between the program's own names and a profiler
trace that was taken without HLO protos.

A trace names a device operation by its HLO instruction (``%fusion.4242``,
``%moe_gmm_fwd.13``); the scopes are only in the ``op_name`` metadata of the
executable's text. `Program` reads that text once, after the compile, and
gives two readings of it, which `Trainer` notes in the trial's ``compiled``
record for a reader of the trace to match events against:

- `Program.ops_by_scope`: ``{scope: [instruction names]}`` for the scopes
  one kind of part named (``moe_ops``, ``ssm_ops``, ``loop_ops``);
- `Program.step_parts`: every device operation of the program under exactly
  one **part** ``"<scopes>:<pass>"`` (``step_ops``), a fusion read through
  its body, and the fusions whose bodies hold several parts with what each
  part costs by the text's own shapes (``step_mixed``).
"""

from __future__ import annotations

import math
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")
#: name, type, opcode, everything after the opening bracket. A type that is
#: a tuple holds spaces (a kernel, a fusion or a sort with several outputs:
#: `ssd_bwd`; a norm that keeps its statistics, a product that also reduces
#: its rows; keys sorted with their values; an asynchronous copy).
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
_OP_NAME = 'op_name="'
_CALLED = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%([\w.\-]+)|branch_computations=\{([^}]*)\}")
_NAME = re.compile(r"%([\w.\-]+)")
_SHAPE = re.compile(r"\b([a-z]+\d+\w*|pred)\[([\d,]*)\]")
_DIM_LABELS = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_CONTRACTING = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
#: A transformation wraps the outermost name under it: a scope opened right
#: under `value_and_grad` is ``jvp(loss)``, ``transpose(jvp(loss))``.
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
#: Opcodes that move nothing on the device and are never a trace event.
_NO_EVENT = frozenset({"get-tuple-element", "constant", "bitcast",
                       "parameter", "tuple"})
#: Of the instructions whose result is a tuple, `ops_by_scope` reads these.
_SEVERAL_OUTPUTS = frozenset({"custom-call", "fusion", "sort"})
#: An event that spans its body's events: never a leaf, its body's are.
_CONTAINERS = frozenset({"while", "conditional", "call"})
#: Inside a fused computation: what only hands values on. A part is not made
#: of these, and what a part reads or writes is found through them.
_PLUMBING = _NO_EVENT | {"broadcast", "reshape", "iota"}
_PRODUCTS = frozenset({"dot", "convolution"})
#: The frame around the model's passes, and the scope whose pass is its own.
FRAME, OPTIMIZER = "loss_and_grad", "optimizer"
UNSCOPED = "unscoped"
#: Before the part a pathless instruction works for.
LENT = UNSCOPED + ">"


class Instruction(NamedTuple):
    name: str
    type: str
    opcode: str
    rest: str  # operands, attributes, metadata
    root: bool
    op_name: Optional[str]  # the metadata's, the last where several

    def calls(self) -> List[str]:
        """The computations the instruction names (a fusion's body, a
        loop's body and condition, a conditional's branches)."""
        return [name for _key, one, listed in _CALLED.findall(self.rest)
                for name in ([one] if one else _NAME.findall(listed))]

    def operands(self) -> List[str]:
        """The names between the opcode's brackets."""
        depth = 0
        for i, c in enumerate(self.rest):
            if c in "({[":
                depth += 1
            elif c in ")}]":
                if depth == 0:
                    return _NAME.findall(self.rest[:i])
                depth -= 1
        return _NAME.findall(self.rest)


def _dims(shape: str) -> List[int]:
    found = _SHAPE.search(shape)
    return [int(d) for d in found.group(2).split(",") if d] if found else []


def _bytes(shape: str) -> int:
    """Bytes of a type as the text writes it (a tuple's elements summed)."""
    total = 0
    for dtype, dims in _SHAPE.findall(shape):
        bits = 8 if dtype == "pred" else int(re.search(r"\d+", dtype).group())
        total += math.prod(int(d) for d in dims.split(",") if d) * bits // 8
    return total


def part_of(op_name: Optional[str], scopes: frozenset) -> str:
    """``"<scopes>:<pass>"`` of an ``op_name`` path: its components that are
    known scopes, outermost first (one that repeats itself, a scope around
    a module of its own name, once; one a transformation wraps, by what is
    wrapped), or ``unscoped``; then ``update`` under the optimizer,
    ``remat`` where the path holds ``rematted_computation`` (a forward pass
    made again), ``bwd`` where a component is a ``transpose(...)``, else
    ``fwd``. Where XLA made one instruction of several and joined their
    paths with ``;``, the first speaks."""
    path = op_name.split(";")[0].split("/") if op_name else ()
    held: List[str] = []
    for c in path:
        wrapped = _WRAPPED.match(c)
        while wrapped:
            c = wrapped.group(1)
            wrapped = _WRAPPED.match(c)
        if c in scopes and c != FRAME and (not held or held[-1] != c):
            held.append(c)
    if OPTIMIZER in held:
        return OPTIMIZER + ":update"
    if "rematted_computation" in path:
        way = "remat"
    elif any(c.startswith("transpose(") for c in path):
        way = "bwd"
    else:
        way = "fwd"
    return ("/".join(held) or UNSCOPED) + ":" + way


class Program:
    """An executable's text, read once: its computations by name, each a
    list of `Instruction`s in the text's order."""

    def __init__(self, hlo_text: str):
        self.computations: Dict[str, List[Instruction]] = {}
        self.entry: Optional[str] = None
        body: List[Instruction] = []
        for line in hlo_text.splitlines():
            head = _COMPUTATION.match(line)
            if head:
                body = self.computations.setdefault(head.group(1), [])
                if line.startswith("ENTRY"):
                    self.entry = head.group(1)
                continue
            inst = _INSTRUCTION.match(line)
            if inst:
                root, name, shape, opcode, rest = inst.groups()
                at = rest.rfind(_OP_NAME) + len(_OP_NAME)
                body.append(Instruction(
                    name, shape, opcode, rest, bool(root),
                    rest[at:rest.index('"', at)] if at >= len(_OP_NAME)
                    else None))

    # ------------------------------------------------------- one kind's scopes
    def ops_by_scope(self, scopes: Iterable[str]) -> Dict[str, List[str]]:
        """Names of the instructions whose ``op_name`` path holds one of
        ``scopes`` as a component, by scope (the innermost where they nest).
        Instructions inside fused computations are left out: the fusion that
        calls them is the device's operation, and it carries the ``op_name``
        of what it fused. Of the instructions whose result is a tuple a
        custom call, a fusion and a sort are read, each one device operation
        (a kernel that writes several arrays, `ssd_bwd`; XLA gives a norm's
        statistics and a product's row reductions a second output; a router
        sorts keys with their values). A ``while`` is its body's
        instructions, which are read themselves."""
        found: Dict[str, List[str]] = {s: [] for s in scopes}
        for computation, body in self.computations.items():
            if "fus" in computation:  # fused_computation.N, *_fusion
                continue
            for inst in body:
                if inst.opcode in _NO_EVENT or (
                        " " in inst.type
                        and inst.opcode not in _SEVERAL_OUTPUTS):
                    continue
                for part in reversed((inst.op_name or "").split("/")):
                    if part in found:
                        found[part].append(inst.name)
                        break
        return {s: sorted(names) for s, names in found.items() if names}

    # ---------------------------------------------------------- the whole step
    def step_parts(self, scopes: Iterable[str]) -> Tuple[
            Dict[str, List[str]], Dict[str, List[list]]]:
        """``(step_ops, step_mixed)``: every instruction that is a device
        event, under exactly one part (`part_of`).

        An event is an instruction of the entry computation or of one that
        control flow runs (a ``while``'s body and condition, a branch, a
        call), but the opcodes that move nothing; a ``while``,
        ``conditional`` or ``call`` itself is a container and never a leaf.
        **A fusion is read through its body**: the instructions of a fused
        computation carry their own ``op_name``. One whose inner
        instructions (plumbing aside, and those without a name) all resolve
        to the part of the fusion's own ``op_name``, and one with no such
        instruction, is that part's: in ``step_ops`` = ``{part: [instruction
        names]}`` with the plain operations. One whose inner instructions
        resolve to several parts, or to one that is not its own name's (a
        relayout XLA left nameless around named work), is **mixed**:
        ``step_mixed`` = ``{instruction: [[part, flops, bytes], ...]}``,
        first the part of the fusion's own ``op_name`` (what a reader that
        takes a fusion whole counts it under, at no cost where the body
        holds nothing of it), then the others by name; ``flops`` are the
        part's products' (`_product_flops`), ``bytes`` those of the
        fusion's operands and results that the part's inner instructions
        read or write directly (through plumbing). The counts are raw: no
        peak is in the program.

        **An instruction without any path is XLA's own** (a relayout, a
        weight's prefetch into fast memory and the wait for it, a bitcast
        between them): it is listed under ``unscoped>`` and the part of
        the first instruction of its computation that uses it, through as
        many of its kind as lie between (``unscoped>attn:fwd``: no name of
        its own, at work for that part); where only the program's result
        uses it (an updated weight's copy out), the part of what it reads,
        or else of the loop or branch it runs in; and plainly ``unscoped``
        only where none of them has a path."""
        scopes = frozenset(scopes)
        known: Dict[Optional[str], str] = {}  # layers repeat their paths

        def part(inst: Instruction) -> str:
            path = inst.op_name
            if path not in known:
                known[path] = part_of(path, scopes)
            return known[path]

        ops: Dict[str, List[str]] = {}
        mixed: Dict[str, List[list]] = {}
        # The entry computation, then what its containers run, each once
        # with the part of the container that ran it.
        run, todo = {self.entry}, [(self.entry, UNSCOPED)]
        while todo:
            computation, around = todo.pop()
            body = self.computations.get(computation, ())
            # No name stack: no ``op_name``, or an argument's name alone
            # (an argument itself works for nobody).
            pathless = {inst.name for inst in body
                        if "/" not in (inst.op_name or "")
                        and inst.opcode != "parameter"}
            settled: Dict[str, str] = {}
            several: Dict[str, Dict[str, list]] = {}
            # Users follow their operands in the text: walked from the end,
            # a user's part is settled before it names its operands', and
            # the earliest user, walked last, is the one that stays.
            for inst in reversed(body):
                own = settled.get(inst.name) or part(inst)
                inner = self._fusion_parts(inst, part) \
                    if inst.opcode == "fusion" else {}
                named = [p for p in inner if not p.startswith(UNSCOPED)]
                if len(inner) > 1 or named and named != [part(inst)]:
                    several[inst.name] = inner
                if len(named) == 1:  # its body names it, for its operands
                    own = named[0]
                    pathless.discard(inst.name)
                settled[inst.name] = own
                if pathless and not own.startswith(UNSCOPED):
                    for operand in inst.operands():
                        if operand in pathless:
                            settled[operand] = own
            for inst in body:
                own = settled[inst.name]
                if inst.name in pathless and own.startswith(UNSCOPED):
                    own = settled[inst.name] = next(
                        (settled[o] for o in inst.operands() if not
                         settled.get(o, UNSCOPED).startswith(UNSCOPED)),
                        own if around.startswith(UNSCOPED) else around)
                if inst.opcode in _CONTAINERS:
                    for name in inst.calls():
                        if name not in run:
                            run.add(name)
                            todo.append((name, own))
                if inst.opcode in _NO_EVENT or inst.opcode in _CONTAINERS:
                    continue  # no leaf: it only hands its part on
                if inst.name in several:
                    inner, own = several[inst.name], part(inst)
                    inner.setdefault(own, [0, 0])
                    mixed[inst.name] = [[own] + inner.pop(own)] + [
                        [p] + cost for p, cost in sorted(inner.items())]
                    continue
                if inst.name in pathless and not own.startswith(UNSCOPED):
                    own = LENT + own
                ops.setdefault(own, []).append(inst.name)
        return {p: sorted(names) for p, names in sorted(ops.items())}, mixed

    def _fusion_parts(self, fusion: Instruction, part_of_inst
                      ) -> Dict[str, list]:
        """``{part: [flops, bytes]}`` of a fusion's body."""
        body = [inst for name in fusion.calls()
                for inst in self.computations.get(name, ())]
        by_name = {inst.name: inst for inst in body}
        part: Dict[str, str] = {}  # inner instruction -> its part
        for inst in body:
            if inst.opcode not in _PLUMBING and inst.op_name:
                part[inst.name] = part_of_inst(inst)
        costs: Dict[str, list] = {p: [0, 0] for p in part.values()}
        if set(costs) <= {part_of_inst(fusion)}:
            return costs  # one part, the fusion's own: nothing to divide

        def through_plumbing(wanted, stop):
            """``reach(name)``: the instructions of ``wanted`` whose values
            reach ``name`` through instructions of neither set."""
            found: Dict[str, frozenset] = {}

            def reach(name: str) -> frozenset:
                if name not in found:
                    if name in stop or name not in by_name:
                        found[name] = frozenset()
                    elif name in wanted:
                        found[name] = frozenset((name,))
                    else:
                        found[name] = frozenset().union(
                            *map(reach, by_name[name].operands()))
                return found[name]
            return reach

        arguments = {inst.name for inst in body if inst.opcode == "parameter"}
        # The fusion's operands a value is made of, and the parts'
        # instructions whose values leave under a name.
        parameters = through_plumbing(arguments, part)
        writers = through_plumbing(part, arguments)
        read: Dict[str, set] = {p: set() for p in costs}
        for name, p in part.items():
            inst = by_name[name]
            read[p].update(*map(parameters, inst.operands()))
            if inst.opcode in _PRODUCTS:
                costs[p][0] += _product_flops(inst, by_name)
        for p, names in read.items():
            costs[p][1] += sum(_bytes(by_name[n].type) for n in names)
        for inst in body:
            if inst.root:
                outs = inst.operands() if inst.opcode == "tuple" \
                    else [inst.name]
                for out in outs:
                    for w in writers(out):
                        costs[part[w]][1] += _bytes(by_name[out].type)
        return costs


def _product_flops(inst: Instruction, by_name: Dict[str, Instruction]) -> int:
    """FLOPs of a product from its operands' and result's shapes: twice the
    result's elements times what is contracted. A ``dot`` names the left
    operand's contracting dimensions; in the TPU's text a product is a
    ``convolution`` and ``dim_labels`` says which of the kernel's dimensions
    are contracted (its input features ``i`` and its window), each output
    element summing over all of them."""
    operands = [by_name[o] for o in inst.operands() if o in by_name]
    out = math.prod(_dims(inst.type))
    if inst.opcode == "dot":
        found = _CONTRACTING.search(inst.rest)
        if not found or not operands:
            return 0
        lhs = _dims(operands[0].type)
        return 2 * out * math.prod(
            lhs[int(d)] for d in found.group(1).split(",") if d)
    labels = _DIM_LABELS.search(inst.rest)
    if not labels or len(operands) < 2:
        return 0
    kernel = _dims(operands[1].type)
    return 2 * out * math.prod(
        size for label, size in zip(labels.group(2), kernel) if label != "o")


def ops_by_scope(hlo_text: str, scopes: Iterable[str]) -> Dict[str, List[str]]:
    """`Program.ops_by_scope` of a text read for this alone."""
    return Program(hlo_text).ops_by_scope(scopes)


def step_parts(hlo_text: str, scopes: Iterable[str]) -> Tuple[
        Dict[str, List[str]], Dict[str, List[list]]]:
    """`Program.step_parts` of a text read for this alone."""
    return Program(hlo_text).step_parts(scopes)
