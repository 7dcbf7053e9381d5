"""`telemetry.hlo_scopes.ops_by_scope`: instruction names by named scope,
from an executable's text; `telemetry.plans`: what a trace's parts say of
themselves, and the ``compiled`` record's fields made of it."""

from maggy_tpu.telemetry import plans
from maggy_tpu.telemetry.hlo_scopes import ops_by_scope

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
    assert plans.notes(said, Compiled(TEXT)) == {
        "flash_plan": "fwd q128 | fwd q256", "moe_plan": "experts 0+4/8",
        "moe_ops": {"moe_dispatch": ["fusion.4"],
                    "moe_experts": ["fusion.3", "moe_gmm_fwd.5"]}}
    # An executable without a text costs the ops, not the plans; a trace in
    # which nothing spoke notes nothing and reads no text.
    assert plans.notes(said, Compiled(None)) == {
        "flash_plan": "fwd q128 | fwd q256", "moe_plan": "experts 0+4/8"}
    # ... and so does a text that cannot be read through.
    assert plans.notes(said, Compiled(0)) == {
        "flash_plan": "fwd q128 | fwd q256", "moe_plan": "experts 0+4/8"}
    with plans.traced() as silent:
        pass
    assert plans.notes(silent, Compiled(None)) == {}
