"""The Pallas flash kernels must COMPILE for the TPU at every shape class
auto-dispatch can reach. `multi_head_attention` has no fallback for a kernel
that fails to build (a compile error on the chip is an error), so the
guarantee lives here: libtpu compiles for a v5e without a chip, against a
topology description, through the real Mosaic/XLA:TPU compiler. Interpret
mode (tests/test_models.py) proves the algorithm; this proves the build.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from maggy_tpu.ops.attention import (FlashPlan, KernelTiles, flash_attention,
                                     flash_attention_planned, fused_fits,
                                     tile_plan)

#: The names the kernels' `pallas_call`s carry, by the plan's names for them.
KERNEL_NAMES = {"fwd": "flash_fwd", "bwd": "flash_bwd",
                "dkdv": "flash_bwd_dkdv", "dq": "flash_bwd_dq"}


def _plan_kernels(plan):
    """The names of the kernels ``plan`` runs, sorted."""
    return sorted(KERNEL_NAMES[name] for name, _ in plan.kernels())


@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu, or one without AOT
        pytest.skip("libtpu cannot describe a v5e:2x2 topology: {!r}".format(e))
    return SingleDeviceSharding(topo.devices[0])


#: The cell's own shape (`bert-base.steady-s512`: B 64, S 512, 12 heads of 64).
CELL_SHAPE = ("bert_b64_s512_h12_d64_masked", 64, 512, 512, 12, 12, 64, False,
              True, jnp.bfloat16)
#: name, B, Sq, Sk, H, Hkv, D, causal, ragged key mask, dtype: one of every
#: shape class dispatch can reach, and the cell's.
SHAPE_CLASSES = [
    ("bert_d64_masked", 2, 128, 128, 12, 12, 64, False, True, jnp.bfloat16),
    ("llama_gqa_d128_causal", 1, 2048, 2048, 32, 8, 128, True, False,
     jnp.bfloat16),
    ("sq_ne_sk_causal", 2, 128, 512, 4, 4, 128, True, False, jnp.bfloat16),
    ("float32", 2, 256, 256, 4, 2, 128, True, True, jnp.float32),
    ("d72", 2, 128, 128, 4, 4, 72, False, False, jnp.bfloat16),
    CELL_SHAPE,
]


@pytest.mark.parametrize("tiles", ["explicit_128", "planned"])
@pytest.mark.parametrize("shape", SHAPE_CLASSES, ids=lambda s: s[0])
def test_forward_and_both_backward_kernels_compile(v5e_device, shape, tiles):
    name, B, Sq, Sk, H, Hkv, D, causal, masked, dtype = shape
    q = jax.ShapeDtypeStruct((B, Sq, H, D), dtype, sharding=v5e_device)
    kv = jax.ShapeDtypeStruct((B, Sk, Hkv, D), dtype, sharding=v5e_device)
    keep = jax.ShapeDtypeStruct((B, Sk), jnp.bool_, sharding=v5e_device)
    itemsize = jnp.dtype(dtype).itemsize
    plan = FlashPlan.explicit(128, 128, fused_fits(
        Sq, Sk, D, H, Hkv, itemsize, KernelTiles(128, 128))) \
        if tiles == "explicit_128" else \
        tile_plan(Sq, Sk, D, H, Hkv, itemsize, causal, masked)

    def loss(q, k, v, keep):
        out = flash_attention_planned(
            q, k, v, keep if masked else None, causal, plan,
            False)  # compiled, never interpreted
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, kv, kv, keep).compile()
    # The forward and the fused backward: two Mosaic kernels in the
    # executable, under the names the benchmark's readers find them by;
    # where a row is no whole 128-lane tiles (D 72) the split pair, dK/dV
    # and dQ, in place of the fused kernel.
    assert _kernel_names(compiled.as_text()) == _plan_kernels(plan)
    assert (plan.bwd is None) == (name == "d72")


WALKED = {
    # name: B, S, H, Hkv, D, causal, description: the SDAR cell's own shape
    # under its mask, Llama's causal GQA, and the Nemotron cell's causal
    # attention block (GQA 32/2 at S 8192: 36 of 64 tiles of 1024 run), and
    # the Ouro cell's causal attention WITHOUT grouping (16 query and 16 K/V
    # heads of 128 at S 4096: 10 of 16 tiles of 1024 run, a head a step).
    "sdar_cell_under_its_description": (2, 8192, 32, 4, 128, False, (4096, 4)),
    "llama_gqa_d128_causal": (1, 2048, 32, 8, 128, True, None),
    "nemotron_cell_gqa_32_2_causal_s8192": (2, 8192, 32, 2, 128, True, None),
    "ouro_cell_mha_16_16_causal_s4096": (2, 4096, 16, 16, 128, True, None),
    # The SmallThinker cell's windowed layer: GQA 28/4, a group of 7, under
    # a window of 4096 at S 16384 (70 of 256 tiles of 1024 run).
    "smallthinker_cell_gqa_28_4_window_s16384": (1, 16384, 28, 4, 128, True,
                                                 "window 4096"),
    # Too long for the fused backward's accumulators (dK and dV alone are
    # 128 MiB of float32): the split pair, dK/dV and dQ.
    "llama_gqa_d128_causal_s131072": (1, 131072, 32, 8, 128, True, None),
}


@pytest.mark.parametrize("name", sorted(WALKED))
def test_the_kernels_that_walk_their_tiles_compile(v5e_device, name):
    """Under a mask that empties tiles the kernels take a `TileWalk`'s
    table as a scalar-prefetch operand (SMEM) and hold two bodies, one for
    partial and one for whole tiles: both must pass Mosaic before a chip
    call. The table is the custom call's first operand, four rows of the
    walk's steps. The fused backward runs wherever its accumulators fit,
    the split pair only where they do not."""
    from maggy_tpu.ops.attention import (_PARTIAL, _WHOLE, BlockDiffusionMask,
                                         CausalWindow, _head_blocks, tile_walk)

    B, S, H, Hkv, D, causal, described = WALKED[name]
    mask = described and (CausalWindow(int(described.split()[1]))
                          if isinstance(described, str)
                          else BlockDiffusionMask(*described))
    plan = tile_plan(S, S, D, H, Hkv, 2, causal, False, mask)
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=v5e_device)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16,
                              sharding=v5e_device)

    def loss(q, k, v):
        out = flash_attention_planned(q, k, v, None, causal, plan, False, mask)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile(
        ).as_text()
    assert _kernel_names(text) == _plan_kernels(plan)
    assert (plan.bwd is None) == (S > 16384)
    calls = {_kernel_names(line)[0]: line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    for kernel, tiles in plan.kernels():
        _, R, _, hr = _head_blocks(S, S, H, Hkv, D, tiles)
        reps = R // hr if kernel in ("dkdv", "bwd") else 1
        walk = tile_walk(kernel, S, S, tiles.blk_q, tiles.blk_k, causal, mask,
                         reps)
        assert walk.count(_WHOLE) and walk.count(_PARTIAL)
        call = calls[KERNEL_NAMES[kernel]]
        assert "operand_layout_constraints={{s32[{}]".format(
            4 * walk.steps) in call


def test_the_three_kernels_carry_their_names(v5e_device):
    """A trace names a kernel after its HLO instruction, which takes the
    `pallas_call`'s ``name=``: the benchmark's readers find the forward and
    the fused backward by these names and by nothing positional."""
    q = jax.ShapeDtypeStruct((2, 128, 12, 64), jnp.bfloat16,
                             sharding=v5e_device)

    def loss(q, k, v):
        out = flash_attention(q, k, v, None, False, 128, 128, False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    forward = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, None, False, 128, 128, False)).lower(q, q, q).compile()
    backward = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, q, q).compile()

    assert _kernel_names(forward.as_text()) == ["flash_fwd"]
    assert _kernel_names(backward.as_text()) == ["flash_bwd", "flash_fwd"]


def test_the_convolution_kernels_compile_at_the_cells_shape(v5e_device):
    """`ssd_conv_fwd` and `ssd_conv_bwd` through Mosaic at the Nemotron
    cell's own shape (B 2, S 8192, xBC the 6,144 columns from 4,096 of the
    in-projection's 10,304, bfloat16; taps and bias float32), at the block
    `conv_silu` takes there: forward alone, and the gradient with respect to
    all three, dx at the wider array's width."""
    from maggy_tpu.ops import ssd

    B, S, width, start, C, K = 2, 8192, 10304, 4096, 6144, 4
    rows, cols = ssd.conv_tile(S, C, start)

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_device)

    args = (of((B, S, width), jnp.bfloat16), of((K, C), jnp.float32),
            of((C,), jnp.float32))

    def conv(*a):
        return ssd.kernel_conv(*a, start, rows, cols, False)

    def loss(*a):
        return jnp.sum(conv(*a).astype(jnp.float32) ** 2)

    forward = jax.jit(conv).lower(*args).compile()
    assert _kernel_names(forward.as_text()) == ["ssd_conv_fwd"]
    backward = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*args)
    assert [a.shape for a in backward.out_info] == [
        (B, S, width), (K, C), (C,)]
    assert _kernel_names(backward.compile().as_text()) == [
        "ssd_conv_bwd", "ssd_conv_fwd"]


@pytest.mark.parametrize("keeps,forwards", [(False, 2), (True, 1)],
                         ids=["keeps_nothing", "keeps_the_names"])
def test_a_checkpointed_layer_compiles_with_one_forward_kernel(
        v5e_device, keeps, forwards):
    """Through XLA:TPU as through the jaxpr: a `jax.checkpoint` that keeps
    the forward rule's names (`REMAT_KEEP`) holds the forward kernel once,
    one that keeps nothing twice; the SDAR cell's heads (GQA, D 128) under
    its mask description."""
    from maggy_tpu.ops import attention
    from maggy_tpu.ops.attention import BlockDiffusionMask

    names = attention.REMAT_KEEP if keeps else ()
    q = jax.ShapeDtypeStruct((1, 1024, 8, 128), jnp.bfloat16,
                             sharding=v5e_device)
    kv = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16,
                              sharding=v5e_device)
    mask = BlockDiffusionMask(512, 4)
    plan = tile_plan(1024, 1024, 128, 8, 2, 2, False, False, mask)

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(*names))
    def layer(q, k, v):
        return flash_attention_planned(q * 2, k, v, None, False, plan, False,
                                       mask)

    def loss(q, k, v):  # what follows the layer needs its output
        return jnp.sum(jnp.tanh(layer(q, k, v).astype(jnp.float32)))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile(
        ).as_text()
    assert _kernel_names(text) == ["flash_bwd"] + ["flash_fwd"] * forwards


def _kernel_names(text):
    """The names the Mosaic kernels of a compiled program carry in their
    HLO instructions' names (autodiff wraps them: ``%jvp_flash_fwd_.1``)."""
    import re

    return sorted(
        re.search(r"(?:flash|moe_gmm|moe_combine|ssd)_[a-z]+(?:_[a-z]+)*",
                  line.split(" = ")[0]).group(0)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)


def test_a_bert_width_step_carries_the_scope_names(v5e_device, monkeypatch):
    """`train_step` at BERT-base width (two layers): the program is named
    after the step function, its kernels after their `pallas_call`s, and the
    ``op_name`` of its operations holds the scopes ``attention``, ``mlp``,
    ``loss_and_grad`` and ``optimizer``."""
    import re

    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from maggy_tpu.models import BertConfig, BertEncoder
    from maggy_tpu.ops import attention
    from maggy_tpu.train import cross_entropy_loss
    from maggy_tpu.train.trainer import make_train_step

    # Off the TPU dispatch picks XLA's attention; a rehearsal of the chip's
    # program steers it to the kernels (rehearsal only).
    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    device, = v5e_device.device_set
    mesh = Mesh([device], ("data",))
    here = NamedSharding(mesh, P())
    model = BertEncoder(BertConfig(num_layers=2, dropout=0.0))
    tx = optax.adamw(1e-4)
    B, S = 2, 128

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=here),
            tree)

    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=here)
    mask = jax.ShapeDtypeStruct((B, S), jnp.bool_, sharding=here)
    variables = jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda x: getattr(x, "value", x),
            model.init(jax.random.key(0), jnp.zeros((B, S), jnp.int32)),
            is_leaf=lambda x: hasattr(x, "value")))
    opt_state = jax.eval_shape(tx.init, variables["params"])
    batch = {"inputs": (tokens, mask),
             "labels": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=here)}
    step = make_train_step(
        model, tx, lambda logits, b: cross_entropy_loss(logits, b["labels"]),
        mesh)
    text = step.lower(abstract(variables), abstract(opt_state),
                      batch).compile().as_text()
    assert text.startswith("HloModule jit_train_step")
    assert _kernel_names(text) == (  # two layers x forward, backward
        ["flash_bwd"] * 2 + ["flash_fwd"] * 2)
    scopes = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        scopes.update(op_name.split("/"))
    assert {"attention", "mlp", "loss_and_grad", "optimizer"} <= scopes


def test_a_rematerialised_sdar_step_holds_each_kernel_once_a_layer(
        v5e_device, monkeypatch):
    """The model's own gradient through XLA:TPU, two layers at toy widths
    (heads of 128, GQA, a share of the experts): with ``remat=True`` the
    layers keep `models.sdar.REMAT_KEEP`, so the executable holds ONE
    `flash_fwd` a layer beside its fused backward (two where a layer
    keeps nothing);
    the grouped products are what the expert layer's own VJP asks for
    either way (three forward, two of them again in the backward pass), and
    the combine is one kernel a pass (`moe_combine_fwd`, `moe_combine_bwd`),
    the forward one not made again."""
    import collections

    import flax.linen as nn

    from maggy_tpu.models import SdarMoe, SdarMoeConfig, moe
    from maggy_tpu.ops import attention
    from maggy_tpu.ops.losses import weighted_token_xent

    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    cfg = SdarMoeConfig(
        vocab_size=512, hidden_dim=256, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=128, moe_intermediate_dim=128,
        num_experts=8, top_k=2, experts_held=4, mask_token_id=511)
    assert cfg.remat
    module, L = SdarMoe(cfg), 256

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=v5e_device), tree)

    tokens = jax.ShapeDtypeStruct((1, 2 * L), jnp.int32, sharding=v5e_device)
    targets = jax.ShapeDtypeStruct((1, L), jnp.int32, sharding=v5e_device)
    weights = jax.ShapeDtypeStruct((1, L), jnp.float32, sharding=v5e_device)
    params = abstract(nn.meta.unbox(jax.eval_shape(
        module.init, jax.random.key(0), tokens))["params"])

    def loss(p, tokens, targets, weights):
        return weighted_token_xent(module.apply({"params": p}, tokens),
                                   targets, weights)

    text = jax.jit(jax.grad(loss)).lower(
        params, tokens, targets, weights).compile().as_text()
    assert collections.Counter(_kernel_names(text)) == {
        "flash_fwd": 2, "flash_bwd": 2,
        "moe_gmm_fwd": 10, "moe_gmm_dlhs": 6, "moe_gmm_drhs": 6,
        "moe_combine_fwd": 2, "moe_combine_bwd": 2}


def test_the_scan_kernels_compile_at_the_cells_shape(v5e_device):
    """`ssd_fwd`, `ssd_states` and `ssd_bwd` through Mosaic at the Nemotron
    cell's own shape (B 2, S 8192, 64 heads of 64, 8 groups of 128 states,
    chunks of 128, bfloat16), at the chunks a grid step `ssd_scan` takes
    there: forward alone, and the gradient with respect to all six."""
    from maggy_tpu.ops import ssd

    B, S, H, P, G, N, chunk = 2, 8192, 64, 64, 8, 128, 128
    step = ssd.chunks_a_step(S, H, P, G, N, chunk)

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_device)

    args = (of((B, S, H, P), jnp.bfloat16), of((B, S, H), jnp.float32),
            of((H,), jnp.float32), of((B, S, G, N), jnp.bfloat16),
            of((B, S, G, N), jnp.bfloat16), of((H,), jnp.float32))

    def scan(*a):
        return ssd.kernel_scan(*a, chunk, step, False)  # never interpreted

    def loss(*a):
        return jnp.sum(scan(*a).astype(jnp.float32) ** 2)

    forward = jax.jit(scan).lower(*args).compile()
    assert _kernel_names(forward.as_text()) == ["ssd_fwd"]
    backward = jax.jit(jax.grad(loss, tuple(range(6)))).lower(*args).compile()
    assert _kernel_names(backward.as_text()) == [
        "ssd_bwd", "ssd_fwd", "ssd_states"]


@pytest.mark.parametrize("keeps,forwards", [(False, 2), (True, 1)],
                         ids=["keeps_no_ssd_out", "keeps_the_names"])
def test_a_rematerialised_nemotron_step_holds_each_kernel_once_a_block(
        v5e_device, monkeypatch, keeps, forwards):
    """The model's own gradient through XLA:TPU, one block of each kind at
    toy widths (heads of 128, GQA, a share of relu^2 experts, a scan the
    kernels tile): the blocks keep `models.nemotron_h.REMAT_KEEP`, so the
    executable holds ONE `flash_fwd` beside its fused backward and ONE
    `ssd_fwd` beside `ssd_states` and `ssd_bwd` (two with `ssd_out` out of
    what a block keeps: the rematerialised block runs it again), TWO
    `ssd_conv_fwd` beside `ssd_conv_bwd` (the convolution's output is made
    again, never kept), and the two-matrix experts ask for two grouped
    products forward (one of them again in the backward pass) where SwiGLU
    asks for three, one combine kernel a pass; the step's instructions
    carry the state-space and the
    shared expert's scopes, every scan kernel, forward and backward, is
    under ``ssm_scan`` and every convolution kernel under ``ssm_conv``."""
    import collections

    import flax.linen as nn

    from maggy_tpu.models import NemotronH, NemotronHConfig, moe, nemotron_h
    from maggy_tpu.ops import attention, ssd
    from maggy_tpu.ops.losses import weighted_token_xent
    from maggy_tpu.telemetry.hlo_scopes import ops_by_scope

    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssd, "_tpu_backend", lambda: True)
    if not keeps:
        monkeypatch.setattr(nemotron_h, "REMAT_KEEP",
                            attention.REMAT_KEEP + moe.REMAT_KEEP)
    cfg = NemotronHConfig(
        vocab_size=512, hidden_dim=256, pattern="EM*", num_heads=4,
        num_kv_heads=2, head_dim=128, mamba_heads=8, mamba_head_dim=64,
        ssm_groups=2, ssm_state=128, moe_intermediate_dim=128,
        shared_intermediate_dim=256, num_experts=8, top_k=2, experts_held=4)
    assert cfg.remat
    module, S = NemotronH(cfg), 256

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=v5e_device), tree)

    tokens = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=v5e_device)
    weights = jax.ShapeDtypeStruct((1, S), jnp.float32, sharding=v5e_device)
    params = abstract(nn.meta.unbox(jax.eval_shape(
        module.init, jax.random.key(0), tokens))["params"])

    def loss(p, tokens, targets, weights):
        return weighted_token_xent(module.apply({"params": p}, tokens),
                                   targets, weights)

    text = jax.jit(jax.grad(loss)).lower(
        params, tokens, tokens, weights).compile().as_text()
    assert collections.Counter(_kernel_names(text)) == {
        "flash_fwd": 1, "flash_bwd": 1,
        "moe_gmm_fwd": 3, "moe_gmm_dlhs": 2, "moe_gmm_drhs": 2,
        "moe_combine_fwd": 1, "moe_combine_bwd": 1,
        "ssd_fwd": forwards, "ssd_states": 1, "ssd_bwd": 1,
        "ssd_conv_fwd": 2, "ssd_conv_bwd": 1}
    scopes = ops_by_scope(text, ssd.SCOPES + moe.SCOPES + (moe.SHARED_SCOPE,))
    assert set(scopes) == set(ssd.SCOPES + moe.SCOPES + (moe.SHARED_SCOPE,))
    assert sorted(name.split(".")[0] for name in scopes["ssm_scan"]
                  if "ssd_" in name) == (
        ["ssd_bwd"] + ["ssd_fwd"] * forwards + ["ssd_states"])
    assert sorted(name.split(".")[0] for name in scopes["ssm_conv"]
                  if "ssd_" in name) == ["ssd_conv_bwd"] + ["ssd_conv_fwd"] * 2
    assert not [line for line in text.splitlines()  # no loop over groups
                if " while(" in line and "ssm_scan" in line]


def test_a_rematerialised_smallthinker_step_holds_each_kernel_once_a_layer(
        v5e_device, monkeypatch):
    """The model's own gradient through XLA:TPU, one global and one windowed
    layer at toy widths (heads of 128, a group of 7, a share of ReGLU
    experts routed before attention): each layer keeps its flash kernel's
    out and lse and its routing, so the executable holds ONE `flash_fwd` a
    layer beside its fused backward, and the experts' three matrices
    ask for three grouped products forward, two of them again in the
    backward pass, and one combine kernel a pass. Each layer's two flash
    kernels are under its kind's scope,
    ``attn_global`` or ``attn_window``, which the cell's readers take them
    by."""
    import collections

    import flax.linen as nn

    from maggy_tpu.models import SmallThinker, SmallThinkerConfig, moe
    from maggy_tpu.ops import attention
    from maggy_tpu.telemetry.hlo_scopes import ops_by_scope

    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    cfg = SmallThinkerConfig(
        vocab_size=512, hidden_dim=256, num_heads=7, num_kv_heads=1,
        head_dim=128, moe_intermediate_dim=128, num_experts=8, top_k=2,
        experts_held=4, sliding_window=128, sliding_window_layout=(0, 1),
        rope_layout=(0, 1), head_chunk=256)
    assert cfg.remat
    module, S = SmallThinker(cfg), 512

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=v5e_device), tree)

    tokens = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=v5e_device)
    params = abstract(nn.meta.unbox(jax.eval_shape(
        module.init, jax.random.key(0), tokens, tokens))["params"])

    def loss(p, tokens, targets):
        return jnp.mean(module.apply({"params": p}, tokens, targets))

    text = jax.jit(jax.grad(loss)).lower(params, tokens, tokens).compile() \
        .as_text()
    assert collections.Counter(_kernel_names(text)) == {
        "flash_fwd": 2, "flash_bwd": 2,
        "moe_gmm_fwd": 10, "moe_gmm_dlhs": 6, "moe_gmm_drhs": 6,
        "moe_combine_fwd": 2, "moe_combine_bwd": 2}
    scopes = ops_by_scope(text, ("attn_global", "attn_window"))
    for scope in ("attn_global", "attn_window"):
        assert sorted(name.split(".")[0] for name in scopes[scope]
                      if "flash_" in name) == ["flash_bwd", "flash_fwd"]


#: The expert cells' layers: width, held experts, pairs a token, matrices
#: into the width (SDAR's SwiGLU, Nemotron's relu^2, SmallThinker's ReGLU),
#: at the 16,384 positions each step puts through a layer.
COMBINE_CELLS = {"sdar": (2048, 16, 8, 2), "nemotron": (2688, 8, 6, 1),
                 "smallthinker": (2560, 8, 6, 2)}


def _combine_call(v5e_device, width, held, top_k, products, which):
    """The lowered combine kernel of one pass at a cell's shapes."""
    from maggy_tpu.models import moe

    N = 16384
    rows = moe.buffer_rows(N, top_k, held)
    tokens, _ = moe.combine_plan(N, width, moe.TILE_ROWS)

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_device)

    table = (of((held, N), jnp.int32),) + (
        of((N // tokens * held,), jnp.int32),) * 2
    rows_in = (of((rows, width), jnp.bfloat16),) * (
        1 if which == "fwd" else products)
    scale = of((held, N), jnp.float32) if which == "fwd" else None

    def combine(row_of, start, length, scale, *rows_in):
        return moe._combine(rows_in, scale, row_of, start, length,
                            tokens=tokens, out_dtype=jnp.bfloat16,
                            name="moe_combine_" + which, interpret=False)

    return jax.jit(combine).lower(*table, scale, *rows_in)


@pytest.mark.parametrize("cell", sorted(COMBINE_CELLS))
def test_the_combine_kernels_compile_at_the_cells_shapes(v5e_device, cell):
    """`moe_combine_fwd` (the gate-weighted rows) and `moe_combine_bwd`
    (the products of the gradient's rows, one a matrix into the width)
    through Mosaic at each expert cell's width, held experts and buffer,
    over its 16,384 positions."""
    for which in ("fwd", "bwd"):
        text = _combine_call(v5e_device, *COMBINE_CELLS[cell],
                             which).compile().as_text()
        assert _kernel_names(text) == ["moe_combine_" + which]


def _mosaic_module(lowered):
    """The Mosaic module of a lowered program's one kernel, as text."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    from jax.experimental.mosaic.dialects import tpu

    body, = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                       lowered.as_text())
    ctx = mlir.JaxIrContext()
    ctx.append_dialect_registry(mlir.upstream_dialects)
    ctx.load_all_available_dialects()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        return str(ir.Module.parse(base64.b64decode(body)))


def _mosaic_text(lowered):
    """`_mosaic_module` with every number masked: what its body holds,
    whatever its shapes."""
    import re

    return re.sub(r"\d+", "0", _mosaic_module(lowered))


def test_the_combine_kernel_does_not_grow_with_the_held_experts(v5e_device):
    """The set-up a layer costs is its program's: a body that walked the
    held experts in Python would lower once per expert and grow with them,
    on every run, cached or not. The forward kernel's Mosaic module is the
    same text at 4 held experts as at 16, numbers aside."""
    width, _, top_k, products = COMBINE_CELLS["sdar"]
    four, sixteen = (_mosaic_text(_combine_call(
        v5e_device, width, held, top_k, products, "fwd")) for held in (4, 16))
    assert len(four) == len(sixteen)
    assert four.count("\n") == sixteen.count("\n")


#: The forward at the claimed kernel cells' own shapes: B, S, H, Hkv, D,
#: causal, mask description.
FORWARD_CELLS = {
    "sdar_under_its_description": (2, 8192, 32, 4, 128, False, "bd"),
    "smallthinker_windowed": (1, 16384, 28, 4, 128, True, "window"),
    "smallthinker_global": (1, 16384, 28, 4, 128, True, None),
}


@pytest.mark.parametrize("name", sorted(FORWARD_CELLS))
def test_the_forward_reduces_over_sublanes_not_lanes(v5e_device, name):
    """The forward takes its scores keys-major, [keys, queries]: the
    running max and sum of every pass reduce over dimension 0 of a 2-d
    chunk, the sublanes, and no reduction in its Mosaic module runs over
    the last dimension, the lanes."""
    import re

    from maggy_tpu.ops.attention import BlockDiffusionMask, CausalWindow

    B, S, H, Hkv, D, causal, kind = FORWARD_CELLS[name]
    mask = {"bd": BlockDiffusionMask(S // 2, 4),
            "window": CausalWindow(4096)}.get(kind)
    plan = tile_plan(S, S, D, H, Hkv, 2, causal, False, mask)
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=v5e_device)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16,
                              sharding=v5e_device)
    lowered = jax.jit(lambda q, k, v: flash_attention_planned(
        q, k, v, None, causal, plan, False, mask)).lower(q, kv, kv)
    text = _mosaic_module(lowered)
    assert text.startswith("module @flash_fwd")
    reductions = re.findall(
        r"multi_reduction.*reduction_dims = array<i64: ([\d, ]+)>"
        r".*: \(vector<(\d+)x(\d+)xf32>", text)
    assert reductions  # the max and the sum of each pass and head
    assert {dims for dims, _, _ in reductions} == {"0"}
    blk_k = plan.fwd.blk_k
    assert {int(keys) for _, keys, _ in reductions} == {blk_k}
    assert plan.describe().startswith("fwd q{} k{} h{} kq;".format(
        *plan.fwd))


#: The kernel cells' shapes, as `tile_plan` takes them, and the tiles each
#: takes: the same in the forward and the fused backward, which every cell
#: runs (the forward's keys-major scratch changed what it reckons of VMEM,
#: not the tiles; the fused backward takes the tiles and heads the split
#: pair took).
CELL_PLANS = {
    "bert-base": ((512, 512, 64, 12, 12, 2, False, True), (512, 512, 4)),
    "sdar-30b-a3b": ((8192, 8192, 128, 32, 4, 2, False, False, "bd"),
                     (512, 512, 4)),
    "nemotron-3-nano-30b-a3b": ((8192, 8192, 128, 32, 2, 2, True, False),
                                (1024, 1024, 1)),
    "ouro-2.6b": ((4096, 4096, 128, 16, 16, 2, True, False), (1024, 1024, 1)),
    "smallthinker-21b-a3b_windowed": (
        (16384, 16384, 128, 28, 4, 2, True, False, "window"), (1024, 1024, 1)),
    "smallthinker-21b-a3b_global": (
        (16384, 16384, 128, 28, 4, 2, True, False), (1024, 1024, 1)),
}


@pytest.mark.parametrize("cell", sorted(CELL_PLANS))
def test_the_kernel_cells_plans_keep_their_tiles(cell):
    from maggy_tpu.ops.attention import (BlockDiffusionMask, CausalWindow,
                                         KernelTiles)

    shape, tiles = CELL_PLANS[cell]
    mask = {"bd": BlockDiffusionMask(4096, 4),
            "window": CausalWindow(4096)}.get(shape[8:] and shape[8])
    assert tile_plan(*shape[:8], mask) \
        == FlashPlan(KernelTiles(*tiles), bwd=KernelTiles(*tiles))


#: The kernels' operands and results by position (a walk's table, the first
#: operand where a kernel walks, aside): what each one reads and writes.
KERNEL_ROLES = {
    "flash_fwd": (("q", "k", "v"), ("o",)),
    "flash_bwd": (("q", "k", "v", "do", "lse", "o"), ("dq", "dk", "dv")),
}


def _boundary_relayouts(text):
    """({kernel: count}, {(kernel, role): relayouts}) of an optimised HLO
    module: the copies and transposes (and fusions of data movement alone)
    that stand between the flash kernels and what produces their operands
    or consumes their results, through bitcasts and tuple elements."""
    import collections
    import re

    inst, users = {}, collections.defaultdict(list)
    bodies, body = collections.defaultdict(set), None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            body = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+|\(.*?\)) ([a-z\-]+)\((.*?)\)"
                     r"(, .*)?$", line)
        if not m:
            continue
        name, typ, op, args, rest = m.groups()
        calls = re.search(r"calls=%([\w.\-]+)", rest or "")
        index = re.search(r"index=(\d+)", rest or "")
        inst[name] = (typ, op, re.findall(r"%([\w.\-]+)", args),
                      calls and calls.group(1), index and int(index.group(1)))
        bodies[body].add(op)
        for operand in inst[name][2]:
            users[operand].append(name)
    movement = {"parameter", "copy", "transpose", "bitcast", "reshape",
                "get-tuple-element", "tuple"}

    def relayout(name):
        _, op, _, calls, _ = inst[name]
        return op in ("copy", "transpose") or op == "fusion" and bool(
            bodies[calls] & {"copy", "transpose"}) \
            and bodies[calls] <= movement

    kernels, found = collections.Counter(), collections.Counter()
    for name, (typ, op, operands, _, _) in inst.items():
        kernel = re.match(r"(?:\w*?_)?(flash_(?:fwd|bwd))"
                          r"(?:\.\d+)?$", name)
        if op != "custom-call" or not kernel:
            continue
        kernel = kernel.group(1)
        kernels[kernel] += 1
        if inst[operands[0]][0].startswith("s32["):  # the walk's table
            operands = operands[1:]
        reads, writes = KERNEL_ROLES[kernel]
        todo = [(o, role, "in") for o, role in zip(operands, reads)]
        todo += [(u, writes[inst[u][4]], "out") for u in users[name]
                 if inst[u][4] is not None and inst[u][4] < len(writes)]
        seen = set()
        while todo:
            at, role, way = todo.pop()
            if (at, role) in seen:
                continue
            seen.add((at, role))
            if inst[at][1] in ("bitcast", "get-tuple-element"):
                todo += [(x, role, way)
                         for x in (inst[at][2] if way == "in" else users[at])]
            elif relayout(at):
                found[(kernel, role)] += 1
    return kernels, found


def _cell_layer_step(name):
    """(module, example arguments, loss, attention applications a step, what
    rope turns) of one kernel cell's model at its layer's width and its
    batch and sequence: its hidden size and heads, one period deep, with a
    share of experts and a small vocabulary. (XLA lays out what feeds the
    kernels by the sizes too: at B 1 and S 1,024 it puts Ouro's v
    projection S minor, at the cell's B 2 and S 4,096 it does not.)"""
    from maggy_tpu.models import (BertConfig, BertEncoder, NemotronH,
                                  NemotronHConfig, SdarMoe, SdarMoeConfig,
                                  SmallThinker, SmallThinkerConfig)
    from maggy_tpu.models.ouro import Ouro, OuroConfig

    experts = dict(moe_intermediate_dim=128, num_experts=8, top_k=2,
                   experts_held=4)

    def total(out):
        return jnp.sum(out.astype(jnp.float32))

    if name == "bert":
        module = BertEncoder(BertConfig(num_layers=1, dropout=0.0))
        return module, (((64, 512), jnp.int32), ((64, 512), jnp.bool_)), \
            total, 1, ()
    if name == "sdar":  # q and k: per-head norm, then rope
        module = SdarMoe(SdarMoeConfig(vocab_size=512, num_layers=1,
                                       mask_token_id=511, **experts))
        return module, (((2, 8192), jnp.int32),), total, 1, ("q", "k")
    if name == "ouro":  # two passes over one layer
        module = Ouro(OuroConfig(vocab_size=512, num_layers=1,
                                 intermediate_dim=256, total_ut_steps=2,
                                 head_chunk=256))
        return module, (((2, 4096), jnp.int32),) * 2, jnp.mean, 2, \
            ("q", "k")
    if name == "smallthinker":  # one global (no rope), one windowed layer
        module = SmallThinker(SmallThinkerConfig(
            vocab_size=512, sliding_window_layout=(0, 1), rope_layout=(0, 1),
            head_chunk=256, **experts))
        return module, (((1, 16384), jnp.int32),) * 2, jnp.mean, 2, \
            ("q", "k")
    module = NemotronH(NemotronHConfig(
        vocab_size=512, pattern="*", shared_intermediate_dim=256, **experts))
    return module, (((2, 8192), jnp.int32),), total, 1, ()


@pytest.mark.parametrize("name", ["bert", "sdar", "ouro", "smallthinker",
                                  "nemotron"])
def test_the_cells_steps_hold_no_relayout_at_the_kernels(
        v5e_device, monkeypatch, name):
    """Each kernel cell's model at its layer's width (hidden size, heads,
    head size), its gradient through XLA:TPU with its own remat: the two
    kernels read q, k, v and dO where the projections wrote them and write
    o, dQ, dK and dV where the model reads them, so no copy or transpose
    stands between them and the model on v, o, dO or dV, nor on the row
    statistics. Where rope turns q and k, XLA lays out its fusion on its
    own (S minor, for its halves) and copies at q, k, dQ and dK remain:
    those, and only those, are the model's (PERF.md section 7). Without
    rope nothing stands there. Each kernel runs once an application of the
    attention (forward once: the layers keep `REMAT_KEEP`)."""
    import flax.linen as nn

    from maggy_tpu.models import moe
    from maggy_tpu.ops import attention, ssd

    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssd, "_tpu_backend", lambda: True)
    module, shapes, reduce, applications, turned = _cell_layer_step(name)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_device)
            for shape, dtype in shapes]
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e_device),
        nn.meta.unbox(jax.eval_shape(module.init, jax.random.key(0),
                                     *args))["params"])

    def loss(p, *args):
        return reduce(module.apply({"params": p}, *args))

    text = jax.jit(jax.grad(loss)).lower(params, *args).compile().as_text()
    kernels, found = _boundary_relayouts(text)
    assert kernels == {kernel: applications for kernel in KERNEL_ROLES}
    model_side = {role for role in turned} | {"d" + role for role in turned}
    assert {role for _, role in found} <= model_side, found
