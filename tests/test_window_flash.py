"""The flash kernels under a sliding window (`CausalWindow`): values in
interpret mode against a dense-mask `attention_reference`, the tiles the
window empties against brute force, the walk against the dense grid without
its empty steps, the build for the v5e, and the plans and walks of the
accepted cells' shapes, which the window leaves as they were."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.ops import attention as att
from maggy_tpu.ops.attention import (BlockDiffusionMask, CausalWindow,
                                     FlashPlan, KernelTiles,
                                     attention_reference,
                                     multi_head_attention, tile_plan)
from test_block_diffusion_flash import _dense_grid_walk, _walk_steps
from test_flash_tiles import (WALK_TENSORS, flash_lse, reference_lse,
                              walk_bits)

TENSORS = ("out", "lse", "dq", "dk", "dv")
CASES = {
    # name: S, window, H, Hkv, D, explicit tiles or None (planned)
    # A window that is no multiple of the tile: its edge crosses tiles.
    "w200_gqa7_d128": (512, 200, 7, 1, 128, (128, 128)),
    "w200_gqa7_planned": (512, 200, 14, 2, 128, None),
    "w129_mha_packed": (512, 129, 2, 2, 64, (128, 128)),
    "w2_itself_and_one_before": (256, 2, 2, 1, 128, (128, 128)),
    # A window of S or more is the causal mask.
    "w512_is_causal": (512, 512, 7, 1, 128, (128, 128)),
    "w1000_is_causal": (512, 1000, 4, 2, 64, None),
}


def test_the_dense_mask_is_the_band():
    m = np.asarray(CausalWindow(5).dense(16))
    for i in range(16):
        for j in range(16):
            assert m[i, j] == (i - 5 < j <= i), (i, j)


@functools.lru_cache(maxsize=None)
def _results(name):
    S, window, H, Hkv, D, tiles = CASES[name]
    mask = CausalWindow(window)
    rng = np.random.default_rng(S + window + H)
    q, w = (jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(1, S, Hkv, D)), jnp.float32)
            for _ in range(2))

    def flash(q, k, v):
        if tiles is None:
            return multi_head_attention(q, k, v, causal=True, mask=mask,
                                        force="flash")
        return att.flash_attention_planned(
            q, k, v, None, True, FlashPlan.explicit(*tiles), True, mask)

    plan = FlashPlan.explicit(*tiles) if tiles else tile_plan(
        S, S, D, H, Hkv, 4, True, False, mask)

    def split(q, k, v):  # the same tiles, the backward as the split pair
        return att.flash_attention_planned(
            q, k, v, None, True, FlashPlan(plan.fwd, plan.bwd, plan.bwd),
            True, mask)

    def reference(q, k, v):
        return attention_reference(q, k, v, causal=True, mask=mask.dense(S))

    def causal(q, k, v):
        return attention_reference(q, k, v, causal=True)

    def all_of(fn, lse):
        out, vjp = jax.vjp(fn, q, k, v)
        return tuple(np.asarray(t) for t in (out, lse) + vjp(w))

    fwd = KernelTiles(*tiles) if tiles else tile_plan(
        S, S, D, H, Hkv, 4, True, False, mask).fwd
    lse = flash_lse(q, k, v, None, True, fwd, mask)
    return {"flash": all_of(flash, lse), "split": all_of(split, lse),
            "reference": all_of(reference, reference_lse(
                q, k, True, mask.dense(S))),
            "causal": all_of(causal, reference_lse(q, k, True))}


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("name", sorted(CASES))
def test_flash_under_the_window_matches_the_dense_mask(name, tensor):
    """The fused backward, and the split pair at the same tiles."""
    i = TENSORS.index(tensor)
    found = _results(name)
    want = found["reference"][i]
    for kind in ("flash", "split"):
        got = found[kind][i]
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    S, window = CASES[name][:2]
    apart = np.abs(found["causal"][i] - want).max() / np.abs(want).max()
    if window >= S:
        assert apart == 0.0
    else:  # the window leaves keys out: not the causal values
        assert apart > 1e-2


def _brute_force_tiles(S, window, blk_q, blk_k):
    m = np.asarray(CausalWindow(window).dense(S))
    return sum(bool(m[i:i + blk_q, j:j + blk_k].any())
               for i in range(0, S, blk_q) for j in range(0, S, blk_k))


WINDOW_TILES = [(512, 200, 128, 128), (512, 128, 128, 128),
                (512, 129, 128, 256), (1024, 300, 256, 128),
                (2048, 1, 512, 512), (2048, 5000, 256, 512),
                (16384, 4096, 1024, 1024), (16384, 4096, 512, 512)]


@pytest.mark.parametrize("S,window,blk_q,blk_k", WINDOW_TILES)
def test_tiles_run_match_brute_force(S, window, blk_q, blk_k):
    assert att._blocks_run(S, S, blk_q, blk_k, True, CausalWindow(window)) \
        == _brute_force_tiles(S, window, blk_q, blk_k)


@pytest.mark.parametrize("kernel", FlashPlan._fields)
@pytest.mark.parametrize("S,window,blk_q,blk_k", WINDOW_TILES[:6])
def test_the_walk_is_the_dense_grid_without_its_empty_steps(
        S, window, blk_q, blk_k, kernel):
    """Same tiles, same order, whole and partial told apart exactly, each
    accumulator opened and closed once, and as many steps as `_tile_runs`
    keeps."""
    mask = CausalWindow(window)
    reps = 7 if kernel in ("dkdv", "bwd") else 1
    walk = att.tile_walk(kernel, S, S, blk_q, blk_k, True, mask, reps)
    want = _dense_grid_walk(kernel, np.asarray(mask.dense(S)), blk_q, blk_k,
                            reps)
    assert _walk_steps(walk) == want
    assert walk.count(att._EMPTY) == 0  # every row sees itself
    assert walk.steps == reps * att._blocks_run(S, S, blk_q, blk_k, True,
                                                mask)


def test_the_cell_walks_70_of_the_causal_136_steps():
    """S 16,384 in 1024-tiles: 16 diagonal tiles and the 12 on the window's
    lower edge are partial, the 42 between them whole; the causal walk of the
    global layers holds 136 tiles."""
    mask = CausalWindow(4096)
    plan = tile_plan(16384, 16384, 128, 28, 4, 2, True, False, mask)
    assert plan.describe() == "fwd q1024 k1024 h1 kq; bwd q1024 k1024 h1"
    assert tile_plan(16384, 16384, 128, 28, 4, 2, True, False) == plan
    for kernel in FlashPlan._fields:
        walk = att.tile_walk(kernel, 16384, 16384, 1024, 1024, True, mask)
        assert (walk.steps, walk.count(att._PARTIAL),
                walk.count(att._WHOLE)) == (70, 28, 42)
    assert att._blocks_run(16384, 16384, 1024, 1024, True) == 136


def test_the_plan_says_the_window_and_its_walk(monkeypatch):
    said = []
    monkeypatch.setattr(att, "remember_plan",
                        lambda kind, text: said.append(text))
    mask = CausalWindow(4096)
    att._remember(tile_plan(16384, 16384, 128, 28, 4, 2, True, False, mask),
                  16384, 16384, True, mask)
    assert said == [
        "fwd q1024 k1024 h1 kq; bwd q1024 k1024 h1; "
        "window w4096 tiles fwd 70/256 bwd 70/256; "
        "walk fwd 70+0 (28 partial) bwd 70+0 (28 partial)"]


#: The flash plans and the forward walks of the accepted cells' shapes, as
#: they were before the window: BERT-base (key padding), SDAR (the
#: block-diffusion description), Nemotron-H and Ouro (causal). ViT's 197
#: positions do not tile. Since the fused backward, each runs it at the
#: tiles and heads its two split kernels took, over the same walk.
ACCEPTED = {
    "bert-base": ((512, 512, 64, 12, 12, 2, False, True, None),
                  "fwd q512 k512 h4 kq; bwd q512 k512 h4",
                  None),
    "sdar-30b-a3b": ((8192, 8192, 128, 32, 4, 2, False, False,
                      BlockDiffusionMask(4096, 4)),
                     "fwd q512 k512 h4 kq; bwd q512 k512 h4",
                     "80+0 (24 partial)"),
    "nemotron-3-nano-30b-a3b": (
        (8192, 8192, 128, 32, 2, 2, True, False, None),
        "fwd q1024 k1024 h1 kq; bwd q1024 k1024 h1",
        "36+0 (8 partial)"),
    "ouro-2.6b": ((4096, 4096, 128, 16, 16, 2, True, False, None),
                  "fwd q1024 k1024 h1 kq; bwd q1024 k1024 h1",
                  "10+0 (4 partial)"),
}


@pytest.mark.parametrize("cell", sorted(ACCEPTED))
def test_the_accepted_cells_plans_and_walks_are_what_they_were(cell):
    shape, said, walked = ACCEPTED[cell]
    plan = tile_plan(*shape)
    assert plan.describe() == said
    Sq, Sk, causal, structure = shape[0], shape[1], shape[6], shape[8]
    for kernel, tiles in plan.kernels():
        walk = att.tile_walk(kernel, Sq, Sk, tiles.blk_q, tiles.blk_k, causal,
                             structure)
        assert (walk and walk.describe()) == walked


DESCRIBED_WALKS = {
    # name: S, window, H, Hkv, (blk_q, blk_k, heads); the forward's walk
    # The band's edge inside the tiles: whole tiles between it and the
    # diagonal.
    "w400_gqa_two_heads": ((512, 400, 4, 2, (128, 128, 2)),
                           "10+0 (5 partial)"),
    "w300_mha_packed": ((768, 300, 4, 4, (128, 128, 4)), "18+0 (13 partial)"),
}


@functools.lru_cache(maxsize=None)
def _described_walk_bits(name):
    (S, window, H, Hkv, tiles), _ = DESCRIBED_WALKS[name]
    return walk_bits(S, S, H, Hkv, tiles, True,
                     structure=CausalWindow(window))


@pytest.mark.parametrize("tensor", WALK_TENSORS)
@pytest.mark.parametrize("name", sorted(DESCRIBED_WALKS))
def test_whole_tiles_without_the_mask_are_bitwise_the_masked_ones(name,
                                                                  tensor):
    (S, window, _, _, (blk_q, blk_k, _)), said = DESCRIBED_WALKS[name]
    assert att.tile_walk("fwd", S, S, blk_q, blk_k, True,
                         CausalWindow(window)).describe() == said
    found, i = _described_walk_bits(name), WALK_TENSORS.index(tensor)
    for kind in ("", "fused_")[:1 + ("fused_walk" in found)]:
        walk = found[kind + "walk"][i]
        assert np.isfinite(walk).all() and walk.any()
        np.testing.assert_array_equal(walk, found[kind + "all_partial"][i])


@pytest.mark.parametrize("causal,S_k,match", [
    (False, 256, "causal mask over Sq = Sk"),
    (True, 384, "causal mask over Sq = Sk")])
def test_a_window_that_does_not_fit_is_refused(causal, S_k, match):
    q = jnp.zeros((1, 256, 2, 128), jnp.float32)
    k = jnp.zeros((1, S_k, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match=match):
        multi_head_attention(q, k, k, causal=causal, mask=CausalWindow(64))


def test_a_window_never_falls_back_silently_on_a_tpu(monkeypatch):
    monkeypatch.setattr(att, "_tpu_backend", lambda: True)
    seen = {}

    def stub(q, k, v, mask, causal, plan, interpret, structure):
        seen.update(mask=mask, causal=causal, structure=structure)
        return q

    monkeypatch.setattr(att, "flash_attention_planned", stub)
    monkeypatch.setattr(att, "attention_reference",
                        lambda *a, **k: pytest.fail("reference taken"))
    q = jnp.zeros((1, 256, 7, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 256, 1, 128), jnp.bfloat16)
    multi_head_attention(q, kv, kv, causal=True, mask=CausalWindow(100))
    assert seen == {"mask": None, "causal": True,
                    "structure": CausalWindow(100)}


def test_the_three_kernels_compile_for_the_v5e_under_a_window():
    """The cell's windowed layer, through the real Mosaic compiler: GQA 28/4
    (a group of 7) at S 16,384 under a window of 4,096."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu, or one without AOT
        pytest.skip("libtpu cannot describe a v5e:2x2 topology: {!r}".format(e))
    dev = SingleDeviceSharding(topo.devices[0])
    S, H, Hkv, D = 16384, 28, 4, 128
    mask = CausalWindow(4096)
    plan = tile_plan(S, S, D, H, Hkv, 2, True, False, mask)
    q = jax.ShapeDtypeStruct((1, S, H, D), jnp.bfloat16, sharding=dev)
    kv = jax.ShapeDtypeStruct((1, S, Hkv, D), jnp.bfloat16, sharding=dev)

    def loss(q, k, v):
        out = att.flash_attention_planned(q, k, v, None, True, plan, False,
                                          mask)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile() \
        .as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("flash_fwd", "flash_bwd"):
        assert "%{}".format(name) in text
