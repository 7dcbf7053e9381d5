"""The flash kernels under a mask *description* (`BlockDiffusionMask`):
values in interpret mode against a dense-mask `attention_reference`, the
tiles the description empties against brute force, the build for the v5e,
and the key-padding and causal plans and predicates left as they were."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.ops import attention as att
from maggy_tpu.ops.attention import (BlockDiffusionMask, FlashPlan,
                                     KernelTiles, attention_reference,
                                     multi_head_attention, tile_plan)
from test_flash_tiles import (WALK_TENSORS, flash_lse, reference_lse,
                              walk_bits)

TENSORS = ("out", "lse", "dq", "dk", "dv")
GRADS = TENSORS[2:]
CASES = {
    # name: data length L, block, H, Hkv, D, explicit tiles or None (planned)
    "block4_gqa_d128": (128, 4, 4, 2, 128, (128, 128)),
    "block32_gqa_d128": (128, 32, 4, 2, 128, (128, 128)),
    # L 192: the halves meet inside the second 128-tile of 384 positions.
    "block4_straddles_a_tile": (192, 4, 2, 2, 64, (128, 128)),
    "block32_straddles_planned": (192, 32, 4, 1, 128, None),
    "block4_planned_256_tiles": (256, 4, 4, 1, 128, None),
}


def test_the_dense_mask_is_the_three_rules():
    """A noised query sees its own noised block and the clean blocks before
    it; a clean query the clean blocks up to its own; never clean -> noised."""
    L, b = 8, 4
    m = np.asarray(BlockDiffusionMask(L, b).dense())
    blk = lambda i: (i % L) // b  # noqa: E731
    for i in range(2 * L):
        for j in range(2 * L):
            qn, kn = i < L, j < L
            want = (qn and kn and blk(j) == blk(i)) \
                or (qn and not kn and blk(j) < blk(i)) \
                or (not qn and not kn and blk(j) <= blk(i))
            assert m[i, j] == want, (i, j)
    assert m.any(axis=1).all()  # no query row is empty


@functools.lru_cache(maxsize=None)
def _results(name):
    L, block, H, Hkv, D, tiles = CASES[name]
    S, mask = 2 * L, BlockDiffusionMask(L, block)
    rng = np.random.default_rng(L + block)
    q = jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, S, Hkv, D)), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)

    def flash(q, k, v):
        if tiles is None:
            return multi_head_attention(q, k, v, causal=False, mask=mask,
                                        force="flash")
        return att.flash_attention_planned(
            q, k, v, None, False, FlashPlan.explicit(*tiles), True, mask)

    plan = FlashPlan.explicit(*tiles) if tiles else tile_plan(
        S, S, D, H, Hkv, 4, False, False, mask)

    def split(q, k, v):  # the same tiles, the backward as the split pair
        return att.flash_attention_planned(
            q, k, v, None, False, FlashPlan(plan.fwd, plan.bwd, plan.bwd),
            True, mask)

    def reference(q, k, v):
        return attention_reference(q, k, v, causal=False, mask=mask.dense())

    def all_of(fn, lse):
        out, vjp = jax.vjp(fn, q, k, v)
        return tuple(np.asarray(t) for t in (out, lse) + vjp(w))

    fwd = KernelTiles(*tiles) if tiles else tile_plan(
        S, S, D, H, Hkv, 4, False, False, mask).fwd
    lse = flash_lse(q, k, v, None, False, fwd, mask)
    return {"flash": all_of(flash, lse), "split": all_of(split, lse),
            "reference": all_of(reference, reference_lse(
                q, k, False, mask.dense()))}


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("name", sorted(CASES))
def test_flash_under_the_description_matches_the_dense_mask(name, tensor):
    """The fused backward, and the split pair at the same tiles."""
    i = TENSORS.index(tensor)
    want = _results(name)["reference"][i]
    for kind in ("flash", "split"):
        got = _results(name)[kind][i]
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def _brute_force_tiles(L, block, blk_q, blk_k):
    m = np.asarray(BlockDiffusionMask(L, block).dense())
    return sum(bool(m[i:i + blk_q, j:j + blk_k].any())
               for i in range(0, 2 * L, blk_q) for j in range(0, 2 * L, blk_k))


@pytest.mark.parametrize("L,block,blk_q,blk_k", [
    (256, 4, 128, 128), (192, 32, 128, 128), (192, 4, 128, 128),
    (512, 4, 256, 128), (384, 64, 128, 256), (1024, 32, 512, 256),
    (2048, 4, 512, 512)])
def test_tiles_run_match_brute_force(L, block, blk_q, blk_k):
    mask = BlockDiffusionMask(L, block)
    assert att._blocks_run(2 * L, 2 * L, blk_q, blk_k, False, mask) \
        == _brute_force_tiles(L, block, blk_q, blk_k)


TILE_CASES = [
    (256, 4, 128, 128), (192, 32, 128, 128), (192, 4, 128, 128),
    (512, 4, 256, 128), (384, 64, 128, 256), (1024, 32, 512, 256),
    (2048, 4, 512, 512), (256, 128, 128, 128)]


def _dense_grid_walk(kernel, visible, blk_q, blk_k, reps):
    """What the dense grid with a skip did, by brute force over the dense
    [Sq, Sk] mask ``visible``: its steps in the grid's order, those whose
    tile holds a visible pair kept, as (q-block, k-block, rep block, whole,
    first of its accumulator, last of it)."""
    nq, nk = visible.shape[0] // blk_q, visible.shape[1] // blk_k
    tile = lambda qi, kb: visible[qi * blk_q:(qi + 1) * blk_q,  # noqa: E731
                                  kb * blk_k:(kb + 1) * blk_k]
    if kernel == "dkdv":  # grid (kb, r, qi)
        groups = [[(qi, kb, r) for r in range(reps) for qi in range(nq)]
                  for kb in range(nk)]
    elif kernel == "bwd":  # grid (r, kb, qi), dQ's accumulator a rep block
        groups = [[(qi, kb, r) for kb in range(nk) for qi in range(nq)]
                  for r in range(reps)]
    else:                 # grid (qi, kb), the rep blocks apart
        groups = [[(qi, kb, 0) for kb in range(nk)] for qi in range(nq)]
    steps = []
    for group in groups:
        kept = [(qi, kb, r) for qi, kb, r in group if tile(qi, kb).any()]
        steps += [(qi, kb, r, bool(tile(qi, kb).all()), n == 0,
                   n == len(kept) - 1) for n, (qi, kb, r) in enumerate(kept)]
    return steps


def _walk_steps(walk):
    return [(q, k, r, f & att._KIND == att._WHOLE, bool(f & att._FIRST),
             bool(f & att._LAST))
            for q, k, f, r in zip(*walk) if f & att._KIND != att._EMPTY]


@pytest.mark.parametrize("kernel", FlashPlan._fields)
@pytest.mark.parametrize("L,block,blk_q,blk_k", TILE_CASES)
def test_the_walk_is_the_dense_grid_without_its_empty_steps(
        L, block, blk_q, blk_k, kernel):
    """Same tiles, same order, whole and partial told apart exactly (a tile
    that straddles ``length`` among them: L 192 in 128-tiles), and each
    accumulator opened and closed once."""
    mask = BlockDiffusionMask(L, block)
    reps = 2 if kernel in ("dkdv", "bwd") else 1
    walk = att.tile_walk(kernel, 2 * L, 2 * L, blk_q, blk_k, False, mask, reps)
    want = _dense_grid_walk(kernel, np.asarray(mask.dense()), blk_q, blk_k,
                            reps)
    assert _walk_steps(walk) == want
    assert walk.count(att._EMPTY) == 0  # no query row and no key is unseen
    assert walk.steps == reps * att._blocks_run(2 * L, 2 * L, blk_q, blk_k,
                                                False, mask)
    # The table the kernel reads: the four rows one after the other.
    assert walk.table().dtype == np.int32
    assert walk.table().reshape(4, -1).tolist() == [list(r) for r in walk]


@pytest.mark.parametrize("kernel", FlashPlan._fields)
def test_the_cell_walks_80_steps_a_head_24_of_them_partial(kernel):
    """Of a head's 16 x 16 512-tiles: 28 clean-clean and 28 noised-clean
    ones below the diagonal are whole; the 8 + 8 diagonal ones and the 8
    noised-noised are partial; 176 are not steps at all."""
    walk = att.tile_walk(kernel, 8192, 8192, 512, 512, False,
                         BlockDiffusionMask(4096, 4))
    assert (walk.steps, walk.count(att._PARTIAL), walk.count(att._WHOLE),
            walk.count(att._EMPTY)) == (80, 24, 56, 0)
    assert walk.describe() == "80+0 (24 partial)"
    # dK/dV streams a K/V group's two blocks of four query heads through
    # each k-block's accumulator: 160 steps, 80 a block of heads; the fused
    # backward streams them through its one accumulator over all keys.
    both = att.tile_walk("dkdv", 8192, 8192, 512, 512, False,
                         BlockDiffusionMask(4096, 4), 2)
    assert both.steps == 160 and sum(
        bool(f & att._FIRST) for f in both.flags) == 16
    fused = att.tile_walk("bwd", 8192, 8192, 512, 512, False,
                          BlockDiffusionMask(4096, 4), 2)
    assert fused.steps == 160 and sum(
        bool(f & att._FIRST) for f in fused.flags) == 2


DESCRIBED_WALKS = {
    # name: L, block, H, Hkv, (blk_q, blk_k, heads); the forward's walk
    "block32_mha_packed": ((256, 32, 2, 2, (128, 128, 2)), "8+0 (6 partial)"),
    "block4_gqa_q256": ((512, 4, 4, 1, (256, 128, 4)), "16+0 (12 partial)"),
    # Blocks as long as a tile: noised on noised is whole too.
    "block128_gqa": ((256, 128, 4, 2, (128, 128, 2)), "6+0 (0 partial)"),
    # The halves meet inside the third of five tiles.
    "block32_straddles_a_tile": ((320, 32, 2, 1, (128, 128, 2)),
                                 "15+0 (14 partial)"),
}


@functools.lru_cache(maxsize=None)
def _described_walk_bits(name):
    (L, block, H, Hkv, tiles), _ = DESCRIBED_WALKS[name]
    return walk_bits(2 * L, 2 * L, H, Hkv, tiles, False,
                     structure=BlockDiffusionMask(L, block))


@pytest.mark.parametrize("tensor", WALK_TENSORS)
@pytest.mark.parametrize("name", sorted(DESCRIBED_WALKS))
def test_whole_tiles_without_the_mask_are_bitwise_the_masked_ones(name,
                                                                  tensor):
    """``out``, ``lse`` and the three gradients of the walk against the
    same walk with every tile under the description's mask, which is what
    the dense grid computed until PR 29 (against the parent's own kernels,
    once: PERF.md section 6, PR 29)."""
    (L, block, _, _, (blk_q, blk_k, _)), said = DESCRIBED_WALKS[name]
    assert att.tile_walk("fwd", 2 * L, 2 * L, blk_q, blk_k, False,
                         BlockDiffusionMask(L, block)).describe() == said
    found, i = _described_walk_bits(name), WALK_TENSORS.index(tensor)
    for kind in ("", "fused_")[:1 + ("fused_walk" in found)]:
        walk = found[kind + "walk"][i]
        assert np.isfinite(walk).all() and walk.any()
        np.testing.assert_array_equal(walk, found[kind + "all_partial"][i])


def test_the_cell_skips_176_of_256_tiles():
    """L 4096 in 512-tiles: 8 own-block tiles on the noised diagonal, 36 of
    noised queries on the clean past, 36 block-causal clean ones."""
    mask = BlockDiffusionMask(4096, 4)
    assert att._blocks_run(8192, 8192, 512, 512, False, mask) == 80
    plan = tile_plan(8192, 8192, 128, 32, 4, 2, False, False, mask)
    assert plan.describe() == "fwd q512 k512 h4 kq; bwd q512 k512 h4"


@pytest.mark.parametrize("Sq,Sk,blk_q,blk_k", [
    (512, 512, 128, 128), (128, 384, 128, 128), (384, 128, 128, 128),
    (2048, 2048, 512, 256), (1024, 2048, 256, 512)])
def test_causal_tiles_run_are_what_they_were(Sq, Sk, blk_q, blk_k):
    """The causal count as PR 24 wrote it, in closed form per row of tiles."""
    nq, nk, offset = Sq // blk_q, Sk // blk_k, Sk - Sq
    want = sum(min(nk, max(0, -(-((qi + 1) * blk_q + offset) // blk_k)))
               for qi in range(nq))
    assert att._blocks_run(Sq, Sk, blk_q, blk_k, True) == want
    assert att._blocks_run(Sq, Sk, blk_q, blk_k, False) == nq * nk


@pytest.mark.parametrize("shape,said", [
    # BERT-base's cell, Llama's causal GQA, the kept ASHA mix's S 128.
    ((512, 512, 64, 12, 12, 2, False, True),
     "fwd q512 k512 h4 kq; bwd q512 k512 h4"),
    ((2048, 2048, 128, 32, 8, 2, True, False),
     "fwd q512 k512 h4 kq; bwd q512 k512 h4"),
    ((128, 128, 64, 12, 12, 2, False, True),
     "fwd q128 k128 h12 kq; bwd q128 k128 h12"),
])
def test_plans_without_a_description_are_unchanged(shape, said):
    assert tile_plan(*shape).describe() == said
    assert tile_plan(*shape, None) == tile_plan(*shape)


def test_a_description_is_a_static_argument_not_a_branch():
    """Without a description the kernels are traced exactly as before: the
    predicate gives None (no `pl.when`) and no mask code is emitted."""
    assert att._tile_runs(0, 128, 0, 128, 0, False, None) is None
    assert att._tile_runs(0, 128, 128, 128, 0, True, None) is False
    assert att._tile_runs(128, 128, 0, 128, 0, True, None) is True


def test_the_plan_says_the_mask_and_the_tiles_run():
    q = jnp.zeros((1, 256, 2, 128), jnp.float32)
    with att.plans_traced() as plans:
        multi_head_attention(q, q, q, causal=False,
                             mask=BlockDiffusionMask(128, 4), force="flash")
    assert plans == ["fwd q256 k256 h2 kq; bwd q256 k256 h2; "
                     "block_diffusion b4 L128 tiles fwd 1/1 bwd 1/1; "
                     "walk fwd 1+0 (1 partial) bwd 1+0 (1 partial)"]


def test_the_plan_of_the_cell_says_its_walk(monkeypatch):
    said = []
    monkeypatch.setattr(att, "remember_plan",
                        lambda kind, text: said.append(text))
    mask = BlockDiffusionMask(4096, 4)
    att._remember(tile_plan(8192, 8192, 128, 32, 4, 2, False, False, mask),
                  8192, 8192, False, mask)
    assert said == [
        "fwd q512 k512 h4 kq; bwd q512 k512 h4; "
        "block_diffusion b4 L4096 tiles fwd 80/256 bwd 80/256; "
        "walk fwd 80+0 (24 partial) bwd 80+0 (24 partial)"]


def test_a_description_never_falls_back_silently_on_a_tpu(monkeypatch):
    monkeypatch.setattr(att, "_tpu_backend", lambda: True)
    seen = {}

    def stub(q, k, v, mask, causal, plan, interpret, structure):
        seen.update(mask=mask, structure=structure, interpret=interpret)
        return q

    monkeypatch.setattr(att, "flash_attention_planned", stub)
    monkeypatch.setattr(att, "attention_reference",
                        lambda *a, **k: pytest.fail("reference taken"))
    q = jnp.zeros((1, 256, 4, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    mask = BlockDiffusionMask(128, 4)
    multi_head_attention(q, kv, kv, causal=False, mask=mask)
    assert seen == {"mask": None, "structure": mask, "interpret": False}


@pytest.mark.parametrize("mask,match", [
    (BlockDiffusionMask(100, 4), "describes Sq = Sk = 200"),
    (BlockDiffusionMask(128, 3), "whole blocks")])
def test_a_description_that_does_not_fit_the_shape_is_refused(mask, match):
    q = jnp.zeros((1, 256, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match=match):
        multi_head_attention(q, q, q, causal=False, mask=mask)


def test_force_flash_says_what_the_kernels_take():
    q = jnp.zeros((1, 128, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="mask description"):
        multi_head_attention(q, q, q, mask=jnp.ones((128, 128), bool),
                             force="flash")


REMAT_MASKS = {
    "description": lambda S: (None, BlockDiffusionMask(S // 2, 4)),
    "key_padding": lambda S: (jnp.arange(S)[None, :] < S - 40, None),
}


@functools.lru_cache(maxsize=None)
def _under_checkpoint(kind):
    """dq, dk, dv and the gradient's `pallas_call`s of one attention between
    two products, with no `jax.checkpoint`, under one that keeps nothing and
    under one that keeps the names the forward rule gives."""
    from jaxpr_counts import primitives

    S, H, Hkv, D = 256, 4, 2, 64
    pad, structure = REMAT_MASKS[kind](S)
    rng = np.random.default_rng(S)
    q, w = (jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(1, S, Hkv, D)), jnp.float32)
            for _ in range(2))

    def loss(q, k, v):  # a layer's shape: work before and after the kernel
        out = att.flash_attention_planned(
            q * 0.5, k, jnp.tanh(v), pad, False,
            FlashPlan.explicit(128, 128), True, structure)
        return jnp.sum(jnp.tanh(out) * w)

    policy = jax.checkpoint_policies.save_only_these_names(*att.REMAT_KEEP)
    found = {}
    for name, fn in (("plain", loss), ("remat", jax.checkpoint(loss)),
                     ("policy", jax.checkpoint(loss, policy=policy))):
        grad = jax.grad(fn, (0, 1, 2))
        counts = primitives(grad, q, k, v)
        found[name] = (
            tuple(np.asarray(g) for g in grad(q, k, v)),
            {n: counts["pallas_call:" + n]
             for n in ("flash_fwd", "flash_bwd")})
    return found


@pytest.mark.parametrize("kind", sorted(REMAT_MASKS))
def test_a_checkpoint_that_keeps_the_names_runs_the_forward_kernel_once(kind):
    """Under `jax.checkpoint` with no policy the backward pass runs the
    forward kernel again for ``out`` and ``lse`` (what a rematerialised
    layer paid until PR 27); keeping `REMAT_KEEP` it does not."""
    found = _under_checkpoint(kind)
    once = {"flash_fwd": 1, "flash_bwd": 1}
    assert found["plain"][1] == once
    assert found["remat"][1] == dict(once, flash_fwd=2)
    assert found["policy"][1] == once


@pytest.mark.parametrize("tensor", GRADS)
@pytest.mark.parametrize("kind", sorted(REMAT_MASKS))
def test_the_kept_values_are_bitwise_what_a_second_run_gives(kind, tensor):
    i = GRADS.index(tensor)
    found = _under_checkpoint(kind)
    assert np.any(found["plain"][0][i] != 0)
    for name in ("remat", "policy"):
        np.testing.assert_array_equal(found[name][0][i], found["plain"][0][i])


def test_a_name_outside_a_checkpoint_lowers_to_nothing():
    """With no `jax.checkpoint` around it the forward rule's names are
    identities: the lowered program does not mention them."""
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)

    def loss(q):
        return jnp.sum(att.flash_attention_planned(
            q, q, q, None, False, FlashPlan.explicit(128, 128), True))

    text = jax.jit(jax.grad(loss)).lower(q).as_text()
    assert not any(name in text for name in att.REMAT_KEEP)
    assert "checkpoint" not in text and "optimization_barrier" not in text


def test_the_three_kernels_compile_for_the_v5e_under_a_description():
    """The cell's shape but for the batch (one sequence), through the real
    Mosaic compiler: tests/test_flash_compile.py has the other classes."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu, or one without AOT
        pytest.skip("libtpu cannot describe a v5e:2x2 topology: {!r}".format(e))
    dev = SingleDeviceSharding(topo.devices[0])
    L, H, Hkv, D = 4096, 32, 4, 128
    mask = BlockDiffusionMask(L, 4)
    plan = tile_plan(2 * L, 2 * L, D, H, Hkv, 2, False, False, mask)
    q = jax.ShapeDtypeStruct((1, 2 * L, H, D), jnp.bfloat16, sharding=dev)
    kv = jax.ShapeDtypeStruct((1, 2 * L, Hkv, D), jnp.bfloat16, sharding=dev)

    def loss(q, k, v):
        out = att.flash_attention_planned(q, k, v, None, False, plan, False,
                                          mask)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile() \
        .as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("flash_fwd", "flash_bwd"):
        assert "%{}".format(name) in text
