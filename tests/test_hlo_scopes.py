"""`telemetry.hlo_scopes`: instruction names by named scope and the whole
program by part, from an executable's text read once; `telemetry.plans`:
what a trace's parts say of themselves, and the ``compiled`` record's fields
made of it."""

import re

import pytest

from maggy_tpu.telemetry import plans
from maggy_tpu.telemetry.hlo_scopes import (Program, ops_by_scope, part_of,
                                            step_parts)
from maggy_tpu.telemetry.vocab import STEP_SCOPES

TEXT = '''
%fused_computation.7 (p: f32[8]) -> f32[8] {
  %inner.1 = f32[8] add(%p, %p), metadata={op_name="jit(f)/moe_experts/add"}
}

%region_1.2 (a: f32[], b: f32[]) -> f32[] {
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(f)/other/add"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0), metadata={op_name="jit(f)/moe_routing/x"}
  %fusion.3 = f32[8] fusion(%x), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(f)/layer_0/moe/moe_experts/mul"}
  %moe_gmm_fwd.5 = f32[8] custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/layer_0/moe/while/body/moe_experts/moe_gmm_fwd"}
  %gte.1 = f32[8] get-tuple-element(%t), index=0, metadata={op_name="jit(f)/moe_dispatch/gte"}
  %ssd_bwd.4 = (bf16[2,8,64]{2,1,0:T(8,128)(2,1)}, /*index=1*/f32[2,8]{1,0:T(8,128)}) custom-call(%x, %gte.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp(M))/block_1/mixer/ssm_scan/ssd_bwd/pallas_call"}
  %while.6 = (s32[], f32[8]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(f)/ssm_scan/while"}
  %fusion.11 = (f32[8]{0:T(1024)S(1)}, bf16[8,4]{1,0:T(8,128)(2,1)}) fusion(%x, %gte.1), kind=kOutput, calls=%fused_computation.9, metadata={op_name="jit(f)/exit_head/while/body/dot_general"}
  %sort.3 = (f32[8]{0}, s32[8]{0}) sort(%x, %gte.1), dimensions={0}, is_stable=true, to_apply=%compare, metadata={op_name="jit(f)/sorted_pairs/sort"}
  %fusion.4 = f32[8] fusion(%x), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(f)/moe_dispatch/gather"}
  ROOT %copy.2 = f32[8] copy(%fusion.4), metadata={op_name="jit(f)/optimizer/copy"}
}
'''


def test_top_level_instructions_by_innermost_scope():
    found = ops_by_scope(TEXT, ("moe_routing", "moe_dispatch", "moe_experts",
                                "moe_combine"))
    # No fused-computation bodies, no parameters or tuple plumbing, and a
    # scope without an instruction is left out.
    assert found == {"moe_dispatch": ["fusion.4"],
                     "moe_experts": ["fusion.3", "moe_gmm_fwd.5"]}


def test_a_kernel_with_several_outputs_is_read_and_a_loop_is_not():
    """`ssd_bwd` writes six arrays, so its type is a tuple; a ``while``'s
    time is its body's instructions'."""
    assert ops_by_scope(TEXT, ("ssm_scan",)) == {"ssm_scan": ["ssd_bwd.4"]}


def test_a_fusion_or_a_sort_with_several_outputs_is_read():
    """A product that also reduces its rows, or keys sorted with their
    values, is one device operation with a tuple for a type: read like any
    other, for every kind."""
    assert ops_by_scope(TEXT, ("exit_head", "ssm_scan", "sorted_pairs")) == {
        "exit_head": ["fusion.11"], "ssm_scan": ["ssd_bwd.4"],
        "sorted_pairs": ["sort.3"]}
    with plans.traced() as said:
        plans.remember_plan("loop", "4 passes", ("exit_head",))
    assert plans.notes(said, Compiled(TEXT))["loop_ops"] == {
        "exit_head": ["fusion.11"]}


def test_no_scope_no_names():
    assert ops_by_scope(TEXT, ()) == {}
    assert ops_by_scope("", ("moe_experts",)) == {}


class Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        if self.text is None:
            raise RuntimeError("no text")
        return self.text


def test_notes_are_made_of_whatever_kinds_spoke():
    with plans.traced() as said:
        plans.remember_plan("flash", "fwd q128")
        plans.remember_plan("flash", "fwd q128")  # once
        plans.remember_plan("flash", "fwd q256")
        plans.remember_plan("moe", "experts 0+4/8",
                            ("moe_dispatch", "moe_experts"))
    plans.remember_plan("moe", "none open")
    noted = plans.notes(said, Compiled(TEXT))
    step = {field: noted.pop(field) for field in plans.STEP_FIELDS}
    assert noted == {
        "flash_plan": "fwd q128 | fwd q256", "moe_plan": "experts 0+4/8",
        "moe_ops": {"moe_dispatch": ["fusion.4"],
                    "moe_experts": ["fusion.3", "moe_gmm_fwd.5"]}}
    # The whole program by part goes with them, whoever spoke.
    assert step["step_ops"]["moe_experts:fwd"] == ["fusion.3", "moe_gmm_fwd.5"]
    assert step["step_mixed"] == {}
    # An executable without a text costs the ops, not the plans; a trace in
    # which nothing spoke and whose text cannot be read notes nothing.
    assert plans.notes(said, Compiled(None)) == {
        "flash_plan": "fwd q128 | fwd q256", "moe_plan": "experts 0+4/8"}
    # ... and so does a text that cannot be read through.
    assert plans.notes(said, Compiled(0)) == {
        "flash_plan": "fwd q128 | fwd q256", "moe_plan": "experts 0+4/8"}
    with plans.traced() as silent:
        pass
    assert plans.notes(silent, Compiled(None)) == {}


# ----------------------------------------------------- the whole step by part


def _former_ops_by_scope(hlo_text, scopes):
    """`ops_by_scope` as it stood before the text was parsed once for every
    reading (PR 33's tree), kept as the reference its fields are held to."""
    computation = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")
    instruction = re.compile(
        r"^\s*(?:ROOT )?%([\w.\-]+) = \S+ ([\w\-]+)\(.*op_name=\"([^\"]*)\"")
    several = re.compile(
        r"^\s*(?:ROOT )?%([\w.\-]+) = \(.*?\) (custom-call|fusion|sort)\("
        r".*op_name=\"([^\"]*)\"")
    no_event = {"get-tuple-element", "constant", "bitcast", "parameter",
                "tuple"}
    found = {s: [] for s in scopes}
    fused = False
    for line in hlo_text.splitlines():
        head = computation.match(line)
        if head:
            fused = "fus" in head.group(1)
            continue
        if fused or "op_name=" not in line:
            continue
        inst = instruction.match(line) or several.match(line)
        if not inst or inst.group(2) in no_event:
            continue
        for part in reversed(inst.group(3).split("/")):
            if part in found:
                found[part].append(inst.group(1))
                break
    return {s: sorted(names) for s, names in found.items() if names}


def _toy_step_text():
    """A compiled step with a rematerialised layer, a loop and an
    optimizer, on the CPU."""
    import jax
    import jax.numpy as jnp

    def layer(x, w):
        with jax.named_scope("loop_mlp"):
            return x + jnp.tanh(x @ w)

    def step(ws, x):
        def loss(ws):
            h = x
            for w in ws:
                h = jax.checkpoint(layer)(h, w)
            with jax.named_scope("exit_head"):
                h = jax.lax.fori_loop(0, 3, lambda i, h: h * 0.5 + i, h)
            return jnp.sum(h ** 2)

        with jax.named_scope("loss_and_grad"):
            value, grads = jax.value_and_grad(loss)(ws)
        with jax.named_scope("optimizer"):
            ws = [w - 0.1 * g for w, g in zip(ws, grads)]
        return ws, value

    ws = [jnp.ones((16, 16)) * 0.1] * 2
    return jax.jit(step).lower(ws, jnp.ones((8, 16))).compile().as_text()


@pytest.mark.parametrize("text", ["hand_written", "compiled"])
def test_the_kinds_fields_are_what_they_were(text):
    """``<kind>_ops`` is a projection of the one parse and byte for byte
    what the former line-by-line reading gave."""
    text = TEXT if text == "hand_written" else _toy_step_text()
    program = Program(text)
    for scopes in (("moe_routing", "moe_dispatch", "moe_experts",
                    "moe_combine"), ("ssm_scan",), ("loop_mlp", "exit_head"),
                   ("exit_head", "ssm_scan", "sorted_pairs"), ()):
        want = _former_ops_by_scope(text, scopes)
        assert program.ops_by_scope(scopes) == want
        assert repr(ops_by_scope(text, scopes)) == repr(want)


@pytest.mark.parametrize("op_name,part", [
    # The three forms a rematerialised layer under value_and_grad gives.
    ("jit(s)/loss_and_grad/jvp(M)/l0/loop_mlp/up/dot_general",
     "loop_mlp:fwd"),
    ("jit(s)/loss_and_grad/transpose(jvp(M))/loss_and_grad/jvp(M)/checkpoint"
     "/l0/loop_mlp/up/transpose", "loop_mlp:bwd"),
    ("jit(s)/loss_and_grad/transpose(jvp(M))/loss_and_grad/jvp(M)/checkpoint"
     "/rematted_computation/l1/loop_mlp/up/dot_general", "loop_mlp:remat"),
    # Nested scopes, outermost first; a scope around a module of its own
    # name once; the frame is no part.
    ("jit(s)/loss_and_grad/jvp(M)/l0/loop_attn/attention/flash_fwd",
     "loop_attn/attention:fwd"),
    ("jit(s)/loss_and_grad/jvp(M)/block_3/block/mixer/attn/attention/dot",
     "block/attn/attention:fwd"),
    ("jit(s)/loss_and_grad/jvp(M)/head/head/dot_general", "head:fwd"),
    ("jit(s)/loss_and_grad/transpose(jvp(M))/head/chunked_ce/while/body/mul",
     "head/chunked_ce:bwd"),
    # A scope right under the transformation is wrapped by it.
    ("jit(s)/loss_and_grad/jvp(loss)/weighted_ce/reduce_sum",
     "loss/weighted_ce:fwd"),
    ("jit(s)/loss_and_grad/transpose(jvp(loss))/mul", "loss:bwd"),
    ("jit(s)/loss_and_grad/transpose(loss_and_grad)/jvp(M)/l0/attn/attention"
     "/pallas_call", "attn/attention:bwd"),
    # The optimizer's pass is its own, whatever it was fused from.
    ("jit(s)/optimizer/mul", "optimizer:update"),
    # No known scope, no path, and paths XLA joined: the first speaks.
    ("jit(s)/loss_and_grad/transpose(jvp(M))/stack/add_any", "unscoped:bwd"),
    ("jit(s)/jit(tril)/ge", "unscoped:fwd"),
    (None, "unscoped:fwd"),
    ("jit(s)/loss_and_grad/jvp(M)/l0/attn/attention/reshape;"
     "jit(s)/loss_and_grad/jvp(M)/l0/attn/reshape", "attn/attention:fwd"),
])
def test_a_part_is_the_paths_scopes_and_its_pass(op_name, part):
    assert part_of(op_name, frozenset(STEP_SCOPES)) == part


MIXED = '''
%fused_computation.20 (p0: f32[64,32], p1: f32[], p2: bf16[128,64], p3: bf16[128,32]) -> (f32[64,32], f32[64,32]) {
  %p0 = f32[64,32]{1,0:T(8,128)S(1)} parameter(0)
  %p1 = f32[]{:T(128)S(6)} parameter(1)
  %lr = f32[64,32]{1,0} broadcast(%p1), dimensions={}, metadata={op_name="jit(s)/optimizer/mul"}
  %p2 = bf16[128,64]{1,0:T(8,128)(2,1)} parameter(2)
  %p3 = bf16[128,32]{1,0} parameter(3)
  %relaid = bf16[128,32]{1,0} fusion(%p3), kind=kLoop, calls=%bitcast_fusion.1
  %dot.5 = bf16[64,32]{1,0} dot(%p2, %relaid), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(s)/loss_and_grad/transpose(jvp(M))/l0/mlp/up/transpose" stack_frame_id=9}
  %wide = f32[64,32]{1,0} convert(%dot.5), metadata={op_name="jit(s)/loss_and_grad/transpose(jvp(M))/l0/mlp/up/convert_element_type"}
  %mul.1 = f32[64,32]{1,0} multiply(%lr, %wide), metadata={op_name="jit(s)/optimizer/mul"}
  %sub.1 = f32[64,32]{1,0} subtract(%p0, %mul.1), metadata={op_name="jit(s)/optimizer/sub"}
  ROOT %tuple.9 = (f32[64,32]{1,0}, f32[64,32]{1,0}) tuple(%sub.1, %wide)
}

%fused_computation.21 (q0: bf16[4,32,64], q1: bf16[4,32,32], q2: f32[64,32]) -> f32[64,32] {
  %q0 = bf16[4,32,64]{2,1,0:T(8,128)(2,1)} parameter(0)
  %q1 = bf16[4,32,32]{2,1,0:T(8,128)(2,1)} parameter(1)
  %q2 = f32[64,32]{1,0} parameter(2)
  %convolution.3 = bf16[64,32,1]{1,0,2:T(8,128)(2,1)} convolution(%q0, %q1), window={size=4}, dim_labels=0fb_0io->bf0, metadata={op_name="jit(s)/loss_and_grad/transpose(jvp(M))/head/dot_general"}
  %flat = bf16[64,32]{1,0} bitcast(%convolution.3), metadata={op_name="jit(s)/loss_and_grad/transpose(jvp(M))/head/dot_general"}
  %wide.1 = f32[64,32]{1,0} convert(%flat), metadata={op_name="jit(s)/loss_and_grad/transpose(jvp(M))/head/convert_element_type"}
  ROOT %sub.2 = f32[64,32]{1,0} subtract(%q2, %wide.1), metadata={op_name="jit(s)/optimizer/sub"}
}

%fused_computation.22 (r0: f32[8]) -> f32[8] {
  %r0 = f32[8]{0} parameter(0)
  %half = f32[8]{0} multiply(%r0, %r0), metadata={op_name="jit(s)/loss_and_grad/jvp(M)/l0/mlp/mul"}
  ROOT %out = f32[8]{0} add(%half, %r0), metadata={op_name="jit(s)/loss_and_grad/jvp(M)/l0/mlp/add"}
}

%bitcast_fusion.1 (b0: bf16[128,32]) -> bf16[128,32] {
  %b0 = bf16[128,32]{1,0} parameter(0)
  ROOT %b1 = bf16[128,32]{1,0} bitcast(%b0)
}

%add_region (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(s)/loss_and_grad/jvp(M)/head/reduce_sum"}
}

%chunk_body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %gte.3 = f32[8]{0} get-tuple-element(%t), index=1
  %fusion.30 = f32[8]{0} fusion(%gte.3), kind=kLoop, calls=%fused_computation.22, metadata={op_name="jit(s)/loss_and_grad/jvp(M)/exit_head/while/body/mul"}
  %copy-start.4 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%fusion.30)
  %copy-done.4 = f32[8]{0:S(1)} copy-done(%copy-start.4)
  %inner.7 = (s32[], f32[8]{0}) while(%t), condition=%chunk_cond, body=%inner_body, metadata={op_name="jit(s)/loss_and_grad/jvp(M)/exit_head/while/body/while"}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%gte.3, %copy-done.4)
}

%inner_body (u: (s32[], f32[8])) -> (s32[], f32[8]) {
  %u = (s32[], f32[8]{0}) parameter(0)
  ROOT %negate.2 = (s32[], f32[8]{0}) negate(%u), metadata={op_name="jit(s)/loss_and_grad/jvp(M)/exit_head/while/body/while/body/neg"}
}

%chunk_cond (c: (s32[], f32[8])) -> pred[] {
  %c = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%c, %c), direction=LT, metadata={op_name="jit(s)/loss_and_grad/jvp(M)/exit_head/while/cond/lt"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.20 = (f32[64,32]{1,0}, f32[64,32]{1,0}) fusion(%w, %s, %a, %g), kind=kOutput, calls=%fused_computation.20, metadata={op_name="jit(s)/optimizer/sub"}
  %while.6 = (s32[], f32[8]{0}) while(%t0), condition=%chunk_cond, body=%chunk_body, metadata={op_name="jit(s)/loss_and_grad/jvp(M)/exit_head/while"}
  %fusion.21 = f32[64,32]{1,0} fusion(%a2, %g2, %w2), kind=kOutput, calls=%fused_computation.21, metadata={op_name="jit(s)/loss_and_grad/transpose(jvp(M))/head/dot_general"}
  %copy.7 = f32[8]{0:S(1)} copy(%x)
  %bitcast.8 = f32[8]{0:S(1)} bitcast(%copy.7)
  %reduce.2 = f32[] reduce(%bitcast.8, %zero), dimensions={0}, to_apply=%add_region, metadata={op_name="jit(s)/loss_and_grad/jvp(M)/head/reduce_sum"}
  %copy.9 = f32[8]{0} copy(%bitcast.8), metadata={op_name="jit(s)/optimizer/copy"}
  ROOT %copy.2 = f32[8]{0} copy(%x)
}
'''


def test_every_event_has_one_part_and_a_loop_is_its_bodys():
    """A ``while`` is a container and no leaf; its body's and condition's
    instructions are, a loop inside it too. A reducer's instructions and a
    fused computation's are no events. A fusion whose body holds its own
    name's part is plainly that part's. An instruction without a path,
    XLA's own, is lent to
    its first user's part (through a bitcast), else to the part of what it
    reads, and is plainly ``unscoped`` where neither has one."""
    ops, mixed = step_parts(MIXED, STEP_SCOPES)
    assert ops == {
        "exit_head:fwd": ["lt.1", "negate.2"],
        "head:fwd": ["reduce.2"],
        "optimizer:update": ["copy.9"],
        # The relayout that the norm's sum reads first, the update later.
        "unscoped>head:fwd": ["copy.7"],
        # A fusion's result copied out, which only the loop's result
        # uses: by what it reads, and that fusion goes by its body.
        "unscoped>mlp:fwd": ["copy-done.4", "copy-start.4"],
        "unscoped:fwd": ["copy.2"]}  # a parameter's copy into the result
    assert sorted(mixed) == ["fusion.20", "fusion.21", "fusion.30"]
    named = [n for names in ops.values() for n in names] + list(mixed)
    assert len(named) == len(set(named))


def test_a_mixed_fusion_lists_its_parts_with_flops_and_bytes():
    """First the part of the fusion's own name. A product's FLOPs are twice
    its result times what it contracts: a ``dot``'s contracting dimensions,
    a TPU ``convolution``'s input features and window by ``dim_labels``.
    Bytes are the fusion's operands and results a part's inner instructions
    touch directly, plumbing (a broadcast, a nameless relayout) seen
    through."""
    _ops, mixed = step_parts(MIXED, STEP_SCOPES)
    product = 2 * 64 * 32 * 128
    assert mixed["fusion.20"] == [
        # p0 and the scalar p1 through its broadcast read, sub.1 written.
        ["optimizer:update", 0, 64 * 32 * 4 + 4 + 64 * 32 * 4],
        # p2 and p3 (through the nameless relayout) read, %wide written.
        ["mlp:bwd", product, 128 * 64 * 2 + 128 * 32 * 2 + 64 * 32 * 4]]
    assert mixed["fusion.21"] == [
        ["head:bwd", product, 4 * 32 * 64 * 2 + 4 * 32 * 32 * 2],
        ["optimizer:update", 0, 64 * 32 * 4 + 64 * 32 * 4]]
    # A body of one part that is not the fusion's own name's: whole under
    # the name for a reader of roots, all the body's part's by cost.
    assert mixed["fusion.30"] == [["exit_head:fwd", 0, 0],
                                  ["mlp:fwd", 0, 8 * 4 + 8 * 4]]


def test_a_compiled_step_is_partitioned_with_its_passes():
    """On a real text: forward, made again, backward and update all show,
    and the loop's body is read."""
    ops, mixed = step_parts(_toy_step_text(), STEP_SCOPES)
    parts = set(ops) | {p for listed in mixed.values() for p, _f, _b in listed}
    assert {"loop_mlp:fwd", "loop_mlp:bwd", "loop_mlp:remat",
            "exit_head:fwd", "optimizer:update"} <= parts
    for listed in mixed.values():
        assert len(listed) > 1 and all(f >= 0 and b >= 0
                                       for _p, f, b in listed)
