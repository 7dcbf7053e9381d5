"""The flash kernels at the tiles the plan chooses: values on the CPU in
interpret mode (the fast lane's only numeric check of the kernels;
tests/test_models.py is `slow`), and the plan itself as a pure function of
the shape. tests/test_flash_compile.py holds the build for the v5e."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.ops import attention as att
from maggy_tpu.ops.attention import (BWD_VMEM_LIMIT, FlashPlan, KernelTiles,
                                     VMEM_BUDGET, attention_reference,
                                     flash_attention, lane_pack,
                                     multi_head_attention, step_vmem_bytes,
                                     tile_plan)
from test_flash_compile import CELL_SHAPE, SHAPE_CLASSES

#: bfloat16 keeps 8 significant bits: one unit in the last place of a value
#: is 2**-8 of it at most. Forward and gradients may differ from the float32
#: reference, and from the same kernels at other tiles, by four such units of
#: the tensor's largest magnitude (what `chip_smoke.FLASH_TOL` allows on the
#: chip); a wrong mask, offset, scale or head is an error of order one.
BF16_ULPS = 4
BF16_TOL = BF16_ULPS * 2.0 ** -8

TENSORS = ("out", "lse", "dq", "dk", "dv")
CASES = {
    # name: B, Sq, Sk, H, Hkv, D, causal, masked, dtype
    "bert_s256_d64_masked": (2, 256, 256, 4, 4, 64, False, True, jnp.bfloat16),
    "bert_s512_d64_masked": (1, 512, 512, 4, 4, 64, False, True, jnp.bfloat16),
    "gqa_h4_kv2_d128_causal": (1, 256, 256, 4, 2, 128, True, False,
                               jnp.bfloat16),
    "sq128_sk384_causal": (1, 128, 384, 2, 2, 128, True, False, jnp.bfloat16),
    # D 64 with no pair to share a tile's lanes with: GQA, and an odd H.
    "gqa_h4_kv2_d64_masked": (1, 256, 256, 4, 2, 64, False, True,
                              jnp.bfloat16),
    "h3_d64_causal": (1, 256, 256, 3, 3, 64, True, False, jnp.bfloat16),
    "float32_gqa_causal_masked": (1, 256, 256, 4, 2, 128, True, True,
                                  jnp.float32),
    "gqa_sq256_sk512_causal": (1, 256, 512, 4, 2, 128, True, False,
                               jnp.bfloat16),
    "d72_causal_masked": (1, 256, 256, 2, 2, 72, True, True, jnp.bfloat16),
}


def reference_lse(q, k, causal, mask=None):
    """[B, H, Sq] float32 log-sum-exp of the scaled logits over the keys a
    query sees (``mask`` as `attention_reference` takes it)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    k = jnp.repeat(k.astype(jnp.float32), H // Hkv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k) / np.float32(np.sqrt(D))
    seen = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq) if causal \
        else jnp.ones((Sq, Sk), bool)
    if mask is not None:
        seen = seen & mask
    return jax.nn.logsumexp(jnp.where(seen, logits, -jnp.inf), axis=-1)


def flash_lse(q, k, v, keep, causal, tiles, structure=None):
    """The forward kernel's log-sum-exp at ``tiles``, as [B, H, Sq]."""
    _, lse = att._flash_fwd_4d(q, k, v, keep, causal, tiles, True, structure)
    return np.asarray(lse).reshape(q.shape[0], q.shape[2], q.shape[1])


@functools.lru_cache(maxsize=None)
def _results(name):
    """{"planned" | "explicit" | "split" | "reference": (out, lse, dq, dk,
    dv)} of one case, computed once for the tests that read it: "split" is
    the planned tiles with the backward as the split pair."""
    B, Sq, Sk, H, Hkv, D, causal, masked, dtype = CASES[name]
    rng = np.random.default_rng(Sq + Sk + D)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(B, Sk, Hkv, D)), dtype)
            for _ in range(2))
    w = jnp.asarray(rng.normal(size=(B, Sq, H, D)), jnp.float32)
    keep = None
    if masked:
        keep = jnp.asarray(np.arange(Sk)[None, :]
                           < rng.integers(Sk // 2, Sk + 1, size=(B, 1)))
    mask4 = None if keep is None else keep[:, None, None, :]

    def planned(q, k, v):  # the public entry; interpreted off the TPU
        return multi_head_attention(q, k, v, causal=causal, mask=mask4,
                                    force="flash")

    def explicit(q, k, v):
        return flash_attention(q, k, v, keep, causal, 128, 128, True)

    plan = tile_plan(Sq, Sk, D, H, Hkv, jnp.dtype(dtype).itemsize, causal,
                     masked)
    bwd = plan.bwd or plan.dkdv
    split_plan = FlashPlan(plan.fwd, bwd, bwd)

    def split(q, k, v):
        return att.flash_attention_planned(q, k, v, keep, causal, split_plan,
                                           True)

    def reference(q, k, v):
        return attention_reference(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32), causal=causal,
                                   mask=mask4)

    lse = {"planned": flash_lse(q, k, v, keep, causal, plan.fwd),
           "explicit": flash_lse(q, k, v, keep, causal, KernelTiles(128, 128)),
           "reference": reference_lse(q, k, causal, mask4)}
    lse["split"] = lse["planned"]
    found = {}
    for label, fn in (("planned", planned), ("explicit", explicit),
                      ("split", split), ("reference", reference)):
        def loss(q, k, v, fn=fn):
            out = fn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out

        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
            q, k, v)
        found[label] = [np.asarray(x, np.float32)
                        for x in (out, lse[label], *grads)]
    return found


def _limits(name, tensor):
    """(relative limit against the reference, against the explicit call).
    The log-sum-exp is float32 from the operands as they are, whatever
    their dtype: it differs from the reference's by float32 rounding."""
    if tensor == "lse":
        return 1e-5, 1e-5
    if CASES[name][-1] == jnp.bfloat16:
        return BF16_TOL, BF16_TOL
    return (1e-4 if tensor == "out" else 1e-3,) * 2  # as tests/test_models.py


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("name", sorted(CASES))
def test_planned_tiles_agree_with_the_reference(name, tensor):
    found, i = _results(name), TENSORS.index(tensor)
    got, want = found["planned"][i], found["reference"][i]
    assert np.isfinite(got).all()
    limit, _ = _limits(name, tensor)
    assert np.abs(got - want).max() <= limit * np.abs(want).max()


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("name", sorted(CASES))
def test_planned_tiles_agree_with_explicit_128(name, tensor):
    found, i = _results(name), TENSORS.index(tensor)
    got, want = found["planned"][i], found["explicit"][i]
    _, limit = _limits(name, tensor)
    assert np.abs(got - want).max() <= limit * np.abs(want).max()
    # And the explicit call is itself within the limit of the reference.
    ref = found["reference"][i]
    assert np.abs(want - ref).max() <= _limits(name, tensor)[0] \
        * np.abs(ref).max()


@pytest.mark.parametrize("tensor", ("dq", "dk", "dv"))
@pytest.mark.parametrize("name", sorted(CASES))
def test_the_fused_backward_agrees_with_the_split_pair(name, tensor):
    """The one kernel against the two it replaces, at the same tiles: the
    same products on the same operands, each accumulator taking its terms
    in the same order. A case whose rows are not whole 128-lane tiles runs
    the split pair in both."""
    found, i = _results(name), TENSORS.index(tensor)
    got, want = found["planned"][i], found["split"][i]
    B, Sq, Sk, H, Hkv, D, causal, masked, dtype = CASES[name]
    fused = tile_plan(Sq, Sk, D, H, Hkv, jnp.dtype(dtype).itemsize, causal,
                      masked).bwd is not None
    assert fused == (H * D % 128 == 0 and Hkv * D % 128 == 0)
    _, limit = _limits(name, tensor)
    assert np.abs(got - want).max() <= limit * np.abs(want).max()


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_cases_leave_128_by_128(name):
    """Each case runs tiles or heads that the explicit call does not."""
    B, Sq, Sk, H, Hkv, D, causal, masked, dtype = CASES[name]
    plan = tile_plan(Sq, Sk, D, H, Hkv, jnp.dtype(dtype).itemsize, causal,
                     masked)
    assert plan != FlashPlan.explicit(128, 128)
    assert all(t.blk_q * t.blk_k * t.heads > 128 * 128
               for _, t in plan.kernels())


def test_every_head_of_a_step_is_its_own():
    """Distinct tiles and head counts in the three kernels, more heads a
    step than the plan would take, against one head a step."""
    B, S, H, D = 1, 256, 4, 64
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))
    plan = FlashPlan(KernelTiles(128, 128, 2), KernelTiles(128, 256, 4),
                     KernelTiles(256, 128, 2))

    def grads(plan):
        return jax.grad(lambda q, k, v: jnp.sum(att.flash_attention_planned(
            q, k, v, None, False, plan, True) ** 2), (0, 1, 2))(q, k, v)

    for got, want in zip(grads(plan), grads(FlashPlan.explicit(128, 128))):
        assert float(jnp.abs(got - want).max()) < 1e-4


@pytest.mark.parametrize("name", ["bert_s512_d64_masked",
                                  "gqa_h4_kv2_d128_causal"])
def test_long_loops_that_are_not_unrolled_compute_the_same(name, monkeypatch):
    """Heads and row chunks beyond `_UNROLL` run as `fori_loop`s with traced
    indices (a 2048-wide tile has 16 chunks): the same values, bit for bit."""
    B, Sq, Sk, H, Hkv, D, causal, masked, dtype = CASES[name]
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(B, Sk, Hkv, D)), dtype)
            for _ in range(2))
    keep = jnp.asarray(np.arange(Sk)[None, :] < Sk - 100) if masked else None
    plan = tile_plan(Sq, Sk, D, H, Hkv, 2, causal, masked)

    def grads():
        att._flash_fwd.clear_cache()  # traced anew under this `_UNROLL`
        att._flash_bwd.clear_cache()
        return jax.grad(lambda q, k, v: jnp.sum(att.flash_attention_planned(
            q, k, v, keep, causal, plan, True).astype(jnp.float32) ** 2),
            (0, 1, 2))(q, k, v)

    unrolled = grads()
    monkeypatch.setattr(att, "_UNROLL", 0)
    looped = grads()
    monkeypatch.undo()
    att._flash_fwd.clear_cache()
    att._flash_bwd.clear_cache()
    for got, want in zip(looped, unrolled):
        assert jnp.array_equal(got, want)


@pytest.mark.parametrize("causal", [True, False])
def test_the_ring_block_backward_is_the_fused_kernel_in_float32(causal):
    """`flash_block_bwd` (ring attention's block) runs the fused backward
    at its explicit tiles, from the global log-sum-exp and output, and
    returns float32: the gradient of the whole attention here, where the
    block is the whole sequence, and what the split pair gives."""
    B, S, H, Hkv, D = 1, 256, 4, 2, 128
    rng = np.random.default_rng(11)
    q, do = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
             for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
            for _ in range(2))
    assert att._explicit_plan(q, k, 128, 128).bwd is not None
    out, lse = att.flash_block_fwd(q, k, v, causal, interpret=True)
    got = att.flash_block_bwd(q, k, v, do, lse, out, causal, interpret=True)
    assert all(g.dtype == jnp.float32 for g in got)
    split = att._flash_bwd(
        *(att._columns(x) for x in (q, k, v, do)),
        att._slab_stats(lse), att._columns(out), None, causal,
        FlashPlan.explicit(128, 128, fused=False), (H, Hkv), True)
    _, vjp = jax.vjp(lambda q, k, v: attention_reference(
        q, k, v, causal=causal), q, k, v)
    for g, pair, ref in zip(got, split, vjp(do)):
        scale = float(jnp.abs(ref).max())
        assert float(jnp.abs(g - ref).max()) <= 1e-4 * scale
        assert float(jnp.abs(g - pair.reshape(g.shape)).max()) <= 1e-5 * scale


# ------------------------------------------------------------- the walk


@contextlib.contextmanager
def every_tile_partial():
    """The kernels traced anew with no tile marked whole: every tile that
    runs does so under its index mask, as the dense grid ran them until
    PR 29."""
    def traced_anew():
        att.tile_walk.cache_clear()
        att._flash_fwd.clear_cache()
        att._flash_bwd.clear_cache()

    keep, att._tile_whole = att._tile_whole, lambda *a: False
    traced_anew()
    try:
        yield
    finally:
        att._tile_whole = keep
        traced_anew()


WALK_TENSORS = ("out", "lse", "dq", "dk", "dv")


def _walk_inputs(Sq, Sk, H, Hkv):
    """q and the cotangent w [2, Sq, H, 64], k and v [2, Sk, Hkv, 64]."""
    rng = np.random.default_rng(Sq + Sk + H)
    q, w = (jnp.asarray(rng.normal(size=(2, Sq, H, 64)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(2, Sk, Hkv, 64)), jnp.float32)
            for _ in range(2))
    return q, w, k, v


def walk_bits(Sq, Sk, H, Hkv, tiles, causal, masked=False, structure=None):
    """{"walk" | "all_partial": (out, lse, dq, dk, dv)} at D 64, float32, in
    interpret mode, with the backward as the split pair; the same under
    "fused_walk" and "fused_all_partial" with the fused backward (where
    the rows are whole lane tiles). D 64 because the scale is then 1/8: on
    the branch with
    no select between them XLA:CPU contracts ``s * scale - m`` into one
    fused multiply-add, which rounds once where the masked branch rounds
    twice, unless the product is exact (at D 128 the two branches differ in
    the last place on the CPU; Mosaic contracts nothing, and the chip read
    the same bits at D 128: PERF.md section 6, PR 29)."""
    q, w, k, v = _walk_inputs(Sq, Sk, H, Hkv)
    keep = jnp.asarray(np.arange(Sk)[None, :]
                       < np.array([[Sk - 40], [Sk // 2]])) if masked else None
    tiles = KernelTiles(*tiles)
    plans = {"": FlashPlan(tiles, tiles, tiles)}
    if H * 64 % 128 == 0 and Hkv * 64 % 128 == 0:
        plans["fused_"] = FlashPlan(tiles, bwd=tiles)

    def bits(plan):
        out, lse = att._flash_fwd_4d(q, k, v, keep, causal, plan.fwd, True,
                                     structure)
        _, vjp = jax.vjp(lambda q, k, v: att.flash_attention_planned(
            q, k, v, keep, causal, plan, True, structure), q, k, v)
        return tuple(np.asarray(x) for x in (out, lse) + vjp(w))

    found = {kind + "walk": bits(plan) for kind, plan in plans.items()}
    with every_tile_partial():
        found.update((kind + "all_partial", bits(plan))
                     for kind, plan in plans.items())
    return found


CAUSAL_WALKS = {
    # name: Sq, Sk, H, Hkv, (blk_q, blk_k, heads), key mask; the forward's
    # walk: tiles + empty steps (partial)
    "s512_gqa_two_heads_a_step": ((512, 512, 4, 2, (128, 128, 2), False),
                                  "10+0 (4 partial)"),
    "s512_mha_packed_q256_masked": ((512, 512, 4, 4, (256, 128, 4), True),
                                    "6+0 (4 partial)"),
    "sq128_sk384": ((128, 384, 2, 1, (128, 128, 1), False), "3+0 (1 partial)"),
    # The first two q-blocks see no key: one empty step each.
    "sq384_sk128": ((384, 128, 2, 2, (128, 128, 2), False), "1+2 (1 partial)"),
    # The same in GQA: a K/V group of two heads, one a step, so the fused
    # backward streams two rep blocks through dK/dV.
    "sq384_sk128_gqa": ((384, 128, 4, 2, (128, 128, 1), False),
                        "1+2 (1 partial)"),
}


@functools.lru_cache(maxsize=None)
def _causal_walk_bits(name):
    (Sq, Sk, H, Hkv, tiles, masked), _ = CAUSAL_WALKS[name]
    return walk_bits(Sq, Sk, H, Hkv, tiles, True, masked)


@pytest.mark.parametrize("name", sorted(CAUSAL_WALKS))
def test_a_causal_mask_walks_the_tiles_the_diagonal_reaches(name):
    (Sq, Sk, _, _, (blk_q, blk_k, _), _), said = CAUSAL_WALKS[name]
    walk = att.tile_walk("fwd", Sq, Sk, blk_q, blk_k, True, None)
    assert walk.describe() == said
    assert walk.steps - walk.count(att._EMPTY) \
        == att._blocks_run(Sq, Sk, blk_q, blk_k, True)
    # Every q-block is opened and closed once, whether a tile reaches it or
    # not, and every k-block is reached (dK/dV has no empty step).
    assert sorted(q for q, f in zip(walk.q_blk, walk.flags)
                  if f & att._FIRST) == list(range(Sq // blk_q))
    assert sorted(q for q, f in zip(walk.q_blk, walk.flags)
                  if f & att._LAST) == list(range(Sq // blk_q))
    assert att.tile_walk("dkdv", Sq, Sk, blk_q, blk_k, True,
                         None).count(att._EMPTY) == 0
    assert att.tile_walk("fwd", Sq, Sk, blk_q, blk_k, False, None) is None
    # The fused backward walks the same tiles and has no empty step.
    fused = att.tile_walk("bwd", Sq, Sk, blk_q, blk_k, True, None)
    assert fused.count(att._EMPTY) == 0
    assert fused.steps == walk.steps - walk.count(att._EMPTY)


@pytest.mark.parametrize("tensor", WALK_TENSORS)
@pytest.mark.parametrize("name", sorted(CAUSAL_WALKS))
def test_whole_causal_tiles_without_the_mask_are_bitwise_the_masked_ones(
        name, tensor):
    found, i = _causal_walk_bits(name), WALK_TENSORS.index(tensor)
    for kind in ("", "fused_")[:1 + ("fused_walk" in found)]:
        walk = found[kind + "walk"][i]
        assert np.isfinite(walk).all() and walk.any()
        np.testing.assert_array_equal(walk, found[kind + "all_partial"][i])


@pytest.mark.parametrize("name", ["sq128_sk384", "sq384_sk128",
                                  "sq384_sk128_gqa"])
def test_a_causal_walk_writes_every_block(name):
    """Sq < Sk: every row sees a key. Sq > Sk: the first Sq - Sk rows see
    none, their q-blocks are no tile's, and the walk's empty steps still
    write them: zeros in ``out`` and ``dq`` (the reference averages V
    there), the rest as the reference has it. The fused backward has no
    empty step: its dQ is written whole, zeros where no tile reached."""
    (Sq, Sk, H, Hkv, _, _), _ = CAUSAL_WALKS[name]
    q, w, k, v = _walk_inputs(Sq, Sk, H, Hkv)
    seen = np.arange(Sq) + Sk - Sq >= 0
    q_seen = jnp.asarray(seen, jnp.float32)[None, :, None, None]
    want, vjp = jax.vjp(lambda q, k, v: attention_reference(
        q, k, v, causal=True) * q_seen, q, k, v)
    found = _causal_walk_bits(name)
    out, lse, *grads = found["walk"]
    fused = list(found.get("fused_walk", ())[2:])
    for got, ref in zip([out] + grads + fused, (want,) + vjp(w) * 2):
        assert np.isfinite(got).all()
        assert np.abs(got - np.asarray(ref)).max() <= 1e-5 * np.abs(ref).max()
    assert np.isfinite(lse).all()
    for dq in [grads[0]] + fused[:1]:
        assert not dq[:, ~seen].any()
    assert not out[:, ~seen].any()
    assert (~seen).sum() == max(0, Sq - Sk)
    # The log-sum-exp of the rows that see a key is the reference's; a row
    # that sees none takes that of no key, NEG_INF + log(1e-30).
    lse = lse.reshape(2, H, Sq)
    want = np.asarray(reference_lse(q, k, True))
    assert np.abs(lse[..., seen] - want[..., seen]).max() \
        <= 1e-5 * np.abs(want[..., seen]).max()
    assert (lse[..., ~seen] == np.float32(att.NEG_INF)
            + np.log(np.float32(1e-30))).all()


FUSED_WALKS = {
    # name: Sq, Sk, blk_q, blk_k, causal, mask description, rep blocks
    "causal": (512, 512, 128, 128, True, None, 2),
    "causal_sq_gt_sk": (384, 128, 128, 128, True, None, 3),
    "causal_q256_k128": (512, 512, 256, 128, True, None, 1),
    "window": (1024, 1024, 128, 256, True, att.CausalWindow(300), 2),
    "block_diffusion": (512, 512, 128, 128, False,
                        att.BlockDiffusionMask(256, 4), 2),
}


@pytest.mark.parametrize("name", sorted(FUSED_WALKS))
def test_the_fused_walk_keeps_each_accumulators_order(name):
    """The fused backward walks (rep block, k-block, q-block): each q-block's
    dQ takes its k-blocks in ascending order, as the dQ kernel's walk gives
    them, and each k-block's dK/dV its (rep block, q-block) pairs in the
    dK/dV kernel's order; a rep block opens at its first step and closes at
    its last, no step is empty, delta is made where a rep block first
    reaches a q-block, and o's block is the last one so opened."""
    Sq, Sk, blk_q, blk_k, causal, structure, reps = FUSED_WALKS[name]
    walk = att.tile_walk("bwd", Sq, Sk, blk_q, blk_k, causal, structure,
                         reps)
    steps = list(zip(walk.q_blk, walk.k_blk, walk.flags, walk.rep))
    assert walk.count(att._EMPTY) == 0
    assert walk.steps == reps * att._blocks_run(Sq, Sk, blk_q, blk_k, causal,
                                                structure)
    assert [r for *_, r in steps] == sorted(r for *_, r in steps)
    dq = att.tile_walk("dq", Sq, Sk, blk_q, blk_k, causal, structure)
    dkdv = att.tile_walk("dkdv", Sq, Sk, blk_q, blk_k, causal, structure,
                         reps)
    for rep in range(reps):
        mine = [(q, k, f) for q, k, f, r in steps if r == rep]
        assert mine[0][2] & att._FIRST and mine[-1][2] & att._LAST
        assert not any(f & (att._FIRST | att._LAST) for *_, f in mine[1:-1])
        for qi in range(Sq // blk_q):
            want = [k for q, k, f in zip(*dq[:3])
                    if q == qi and f & att._KIND != att._EMPTY]
            assert [k for q, k, _ in mine if q == qi] == want
        held = None
        for n, (q, _, f) in enumerate(mine):
            opens = q not in [x for x, _, _ in mine[:n]]
            assert bool(f & att._OPEN) == opens
            held = q if opens else held
            assert f >> att._HELD == held
    for kb in range(Sk // blk_k):
        assert [(r, q) for q, k, _, r in steps if k == kb] == [
            (r, q) for q, k, _, r in zip(*dkdv) if k == kb]
    kinds = {(q, k): f & att._KIND for q, k, f in zip(*dq[:3])}
    assert all(kinds[q, k] == f & att._KIND for q, k, f, _ in steps)


# ------------------------------------------------------------- the plan


def _plan_of(shape):
    name, B, Sq, Sk, H, Hkv, D, causal, masked, dtype = shape
    return tile_plan(Sq, Sk, D, H, Hkv, jnp.dtype(dtype).itemsize, causal,
                     masked)


def _grid_steps(shape, tiles):
    name, B, Sq, Sk, H, Hkv, D, causal, masked, dtype = shape
    return B * (H // tiles.heads) * (Sq // tiles.blk_q) * (Sk // tiles.blk_k)


@pytest.mark.parametrize("shape", SHAPE_CLASSES, ids=lambda s: s[0])
def test_plan_tiles_divide_align_and_fit(shape):
    name, B, Sq, Sk, H, Hkv, D, causal, masked, dtype = shape
    plan = _plan_of(shape)
    assert plan == _plan_of(shape)  # pure
    pack = lane_pack(H, Hkv, D)
    itemsize = jnp.dtype(dtype).itemsize
    for kernel, tiles in plan.kernels():
        assert tiles.blk_q % 128 == 0 and Sq % tiles.blk_q == 0
        assert tiles.blk_k % 128 == 0 and Sk % tiles.blk_k == 0
        # Heads of a step: heads of a batch row, or of one K/V group; whole
        # tiles of them where two share a tile's lanes.
        assert (H // Hkv if H != Hkv else H) % tiles.heads == 0
        assert tiles.heads % pack == 0
        if kernel == "bwd":  # the dK/dV kernel's passes, one product more
            assert step_vmem_bytes("bwd", tiles, D, itemsize, H != Hkv, pack,
                                   H // Hkv, (Sq, Sk)) \
                <= BWD_VMEM_LIMIT - VMEM_BUDGET
            kernel = "dkdv"
        assert step_vmem_bytes(kernel, tiles, D, itemsize, H != Hkv,
                               pack) <= VMEM_BUDGET
    assert VMEM_BUDGET <= 16 * 2 ** 20 // 2  # well under a v5e's 16 MiB
    assert BWD_VMEM_LIMIT <= 128 * 2 ** 20  # a v5e core's VMEM


@pytest.mark.parametrize("shape", [
    CELL_SHAPE,
    ("bert_s256", 8, 256, 256, 12, 12, 64, False, True, jnp.bfloat16),
], ids=lambda s: s[0])
def test_the_bert_class_steps_the_grid_a_sixteenth_as_often(shape):
    for _, tiles in _plan_of(shape).kernels():
        assert 16 * _grid_steps(shape, tiles) \
            <= _grid_steps(shape, KernelTiles(128, 128))


def test_the_cell_takes_the_whole_sequence_in_one_tile():
    plan = _plan_of(CELL_SHAPE)
    assert all((t.blk_q, t.blk_k) == (512, 512) and t.heads > 1
               for _, t in plan.kernels())
    assert plan.describe() == "; ".join(
        "{} q512 k512 h{}{}".format(n, t.heads, " kq" * (n == "fwd"))
        for n, t in zip(("fwd", "bwd"), (plan.fwd, plan.bwd)))


def test_a_causal_mask_takes_smaller_tiles_as_the_sequence_grows():
    def area(S, causal):
        t = tile_plan(S, S, 128, 32, 8, 2, causal, False).fwd
        return t.blk_q * t.blk_k

    assert area(4096, True) < area(4096, False)
    assert area(512, True) <= area(2048, True) <= area(8192, True)


def test_step_vmem_grows_with_every_part_of_a_step():
    base = step_vmem_bytes("dkdv", KernelTiles(256, 256, 2), 64, 2, False)
    assert step_vmem_bytes("dkdv", KernelTiles(512, 256, 2), 64, 2, False) > base
    assert step_vmem_bytes("dkdv", KernelTiles(256, 512, 2), 64, 2, False) > base
    assert step_vmem_bytes("dkdv", KernelTiles(256, 256, 4), 64, 2, False) > base
    assert step_vmem_bytes("dkdv", KernelTiles(256, 256, 2), 64, 4, False) > base
    # A K/V tile that a group's heads share is held once.
    assert step_vmem_bytes("dkdv", KernelTiles(256, 256, 2), 64, 2, True) < base
    # D 64 pads to 128 lanes: no smaller than D 128, unless two heads
    # share the lanes.
    assert step_vmem_bytes("dkdv", KernelTiles(256, 256, 2), 128, 2, False) \
        == base
    assert step_vmem_bytes("dkdv", KernelTiles(256, 256, 2), 64, 2, False,
                           pack=2) < base
    with pytest.raises(ValueError, match="no kernel"):
        step_vmem_bytes("fused", KernelTiles(128, 128), 64, 2, False)


@pytest.mark.parametrize("plan,match", [
    (FlashPlan.explicit(384, 128), "do not divide Sq=256"),
    (FlashPlan(KernelTiles(128, 128, 3), KernelTiles(128, 128),
               KernelTiles(128, 128)), "3 heads a step do not divide H=4"),
])
def test_tiles_that_do_not_fit_the_shape_are_refused(plan, match):
    q = jnp.zeros((1, 256, 4, 128), jnp.float32)
    with pytest.raises(ValueError, match=match):
        att.flash_attention_planned(q, q, q, None, False, plan, True)


@pytest.mark.parametrize("H,Hkv,D,pack", [
    (12, 12, 64, 2),    # BERT-base: two heads to a tile's 128 lanes
    (3, 3, 64, 1),      # no pair for the third head
    (4, 2, 64, 1),      # a K/V group's heads share a K/V tile, not lanes
    (12, 12, 72, 1), (32, 32, 128, 1), (32, 8, 128, 1),
])
def test_heads_share_a_tiles_lanes_only_in_pairs_of_64(H, Hkv, D, pack):
    assert lane_pack(H, Hkv, D) == pack


def test_plans_traced_collects_each_plan_once_in_order():
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    with att.plans_traced() as outer:
        with att.plans_traced() as inner:
            for _ in range(2):
                multi_head_attention(q, q, q, causal=False, force="flash")
        multi_head_attention(q[:, :128], q[:, :128], q[:, :128], causal=True,
                             force="flash")
        multi_head_attention(q, q, q, causal=False, force="reference")
    short = tile_plan(128, 128, 64, 2, 2, 4, True, False).describe()
    assert inner == [tile_plan(256, 256, 64, 2, 2, 4, False, False).describe()]
    assert outer == [short] and short.startswith("fwd q128 k128 h2 kq; bwd ")
    multi_head_attention(q, q, q, causal=False, force="flash")  # none open
    assert len(inner) == 1 and len(outer) == 1
