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

from maggy_tpu.ops.attention import (FlashPlan, flash_attention,
                                     flash_attention_planned, tile_plan)


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
    plan = FlashPlan.explicit(128, 128) if tiles == "explicit_128" else \
        tile_plan(Sq, Sk, D, H, Hkv, jnp.dtype(dtype).itemsize, causal, masked)

    def loss(q, k, v, keep):
        out = flash_attention_planned(
            q, k, v, keep if masked else None, causal, plan,
            False)  # compiled, never interpreted
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, kv, kv, keep).compile()
    # Forward, dK/dV and dQ: three Mosaic kernels in the executable, under
    # the names the benchmark's readers find them by.
    assert _kernel_names(compiled.as_text()) == [
        "flash_bwd_dkdv", "flash_bwd_dq", "flash_fwd"]


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
}


@pytest.mark.parametrize("name", sorted(WALKED))
def test_the_kernels_that_walk_their_tiles_compile(v5e_device, name):
    """Under a mask that empties tiles the three kernels take a `TileWalk`'s
    table as a scalar-prefetch operand (SMEM) and hold two bodies, one for
    partial and one for whole tiles: both must pass Mosaic before a chip
    call. The table is the custom call's first operand, four rows of the
    walk's steps."""
    from maggy_tpu.ops.attention import (_PARTIAL, _WHOLE, BlockDiffusionMask,
                                         tile_walk)

    B, S, H, Hkv, D, causal, described = WALKED[name]
    mask = described and BlockDiffusionMask(*described)
    plan = tile_plan(S, S, D, H, Hkv, 2, causal, False, mask)
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=v5e_device)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16,
                              sharding=v5e_device)

    def loss(q, k, v):
        out = flash_attention_planned(q, k, v, None, causal, plan, False, mask)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile(
        ).as_text()
    assert _kernel_names(text) == ["flash_bwd_dkdv", "flash_bwd_dq",
                                   "flash_fwd"]
    calls = {_kernel_names(line)[0]: line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line}
    for kernel, tiles in zip(plan._fields, plan):
        reps = (H // Hkv) // tiles.heads if kernel == "dkdv" else 1
        walk = tile_walk(kernel, S, S, tiles.blk_q, tiles.blk_k, causal, mask,
                         reps)
        assert walk.count(_WHOLE) and walk.count(_PARTIAL)
        call = calls[{"fwd": "flash_fwd"}.get(kernel, "flash_bwd_" + kernel)]
        assert "operand_layout_constraints={{s32[{}]".format(
            4 * walk.steps) in call


def test_the_three_kernels_carry_their_names(v5e_device):
    """A trace names a kernel after its HLO instruction, which takes the
    `pallas_call`'s ``name=``: the benchmark's readers find the forward,
    dK/dV and dQ kernels by these names and by nothing positional."""
    q = jax.ShapeDtypeStruct((2, 128, 12, 64), jnp.bfloat16,
                             sharding=v5e_device)

    def loss(q, k, v):
        out = flash_attention(q, k, v, None, False, 128, 128, False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    forward = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, None, False, 128, 128, False)).lower(q, q, q).compile()
    backward = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, q, q).compile()

    assert _kernel_names(forward.as_text()) == ["flash_fwd"]
    assert _kernel_names(backward.as_text()) == [
        "flash_bwd_dkdv", "flash_bwd_dq", "flash_fwd"]


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
    assert _kernel_names(text) == (
        ["flash_bwd_dkdv", "flash_bwd_dq"] + ["flash_fwd"] * forwards)


def _kernel_names(text):
    """The names the Mosaic kernels of a compiled program carry in their
    HLO instructions' names (autodiff wraps them: ``%jvp_flash_fwd_.1``)."""
    import re

    return sorted(
        re.search(r"(?:flash|moe_gmm|ssd)_[a-z]+(?:_[a-z]+)*",
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
    assert _kernel_names(text) == (  # two layers x forward, dK/dV, dQ
        ["flash_bwd_dkdv"] * 2 + ["flash_bwd_dq"] * 2 + ["flash_fwd"] * 2)
    scopes = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        scopes.update(op_name.split("/"))
    assert {"attention", "mlp", "loss_and_grad", "optimizer"} <= scopes


def test_a_rematerialised_sdar_step_holds_each_kernel_once_a_layer(
        v5e_device, monkeypatch):
    """The model's own gradient through XLA:TPU, two layers at toy widths
    (heads of 128, GQA, a share of the experts): with ``remat=True`` the
    layers keep `models.sdar.REMAT_KEEP`, so the executable holds ONE
    `flash_fwd` a layer beside its two backward kernels (two before PR 27);
    the grouped products are what the expert layer's own VJP asks for
    either way (three forward, two of them again in the backward pass)."""
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
        "flash_fwd": 2, "flash_bwd_dkdv": 2, "flash_bwd_dq": 2,
        "moe_gmm_fwd": 10, "moe_gmm_dlhs": 6, "moe_gmm_drhs": 6}


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
    executable holds ONE `flash_fwd` beside its two backward kernels and ONE
    `ssd_fwd` beside `ssd_states` and `ssd_bwd` (two with `ssd_out` out of
    what a block keeps: the rematerialised block runs it again), TWO
    `ssd_conv_fwd` beside `ssd_conv_bwd` (the convolution's output is made
    again, never kept), and the two-matrix experts ask for two grouped
    products forward (one of them again in the backward pass) where SwiGLU
    asks for three; the step's instructions carry the state-space and the
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
        "flash_fwd": 1, "flash_bwd_dkdv": 1, "flash_bwd_dq": 1,
        "moe_gmm_fwd": 3, "moe_gmm_dlhs": 2, "moe_gmm_drhs": 2,
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
