"""The flash kernels read q, k, v and dO where the projections wrote them,
[B, S, H*D], and write o, dQ, dK and dV there. Values in interpret mode
against `attention_reference` (float32, highest matmul precision) at the
head layouts of the benchmark's kernel cells, at small S, and the traced
program, which holds no transpose outside the kernels.
tests/test_flash_compile.py holds the same boundary in the v5e's compiled
steps."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.ops import attention as att
from maggy_tpu.ops.attention import (BlockDiffusionMask, CausalWindow,
                                     attention_reference, lane_pack,
                                     multi_head_attention, tile_plan)
from test_flash_tiles import flash_lse, reference_lse

TENSORS = ("out", "lse", "dq", "dk", "dv")
LAYOUTS = {
    # name: B, S, H, Hkv, D, causal, mask kind
    # BERT: MHA at D 64, two heads to a slab's 128 lanes, key padding.
    "mha_d64_packed_key_padding": (2, 256, 4, 4, 64, False, "keys"),
    # Ouro: MHA at D 128, causal.
    "mha_d128_causal": (1, 256, 4, 4, 128, True, None),
    # SDAR: GQA 32/4 under the block-diffusion description.
    "gqa_32_4_block_diffusion": (1, 256, 32, 4, 128, False, "block"),
    # SmallThinker: GQA 28/4, a group of 7, under a window.
    "gqa_28_4_window": (1, 256, 28, 4, 128, True, "window"),
    # Nemotron-H: GQA 32/2, causal.
    "gqa_32_2_causal": (1, 256, 32, 2, 128, True, None),
    # GQA at D 64: one K/V head is half a lane tile, so a step holds whole
    # groups, each with its K/V head.
    "gqa_4_2_d64_whole_groups": (1, 256, 4, 2, 64, True, None),
}


def _inputs(name):
    B, S, H, Hkv, D, causal, kind = LAYOUTS[name]
    rng = np.random.default_rng(S + H + Hkv + D)
    q, w = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
            for _ in range(2))
    if kind == "keys":
        keep = np.arange(S)[None, :] < rng.integers(S // 2, S + 1, (B, 1))
        mask = dense = jnp.asarray(keep)[:, None, None, :]
    elif kind == "block":
        mask = BlockDiffusionMask(S // 2, 4)
        dense = mask.dense()
    elif kind == "window":
        mask = CausalWindow(100)
        dense = mask.dense(S)
    else:
        mask = dense = None
    return (q, k, v, w), causal, mask, dense


def _flash(q, k, v, causal, mask):
    return multi_head_attention(q, k, v, causal=causal, mask=mask,
                                force="flash")


@functools.lru_cache(maxsize=None)
def _results(name):
    """{"flash" | "reference": (out, lse, dq, dk, dv)} of one layout."""
    (q, k, v, w), causal, mask, dense = _inputs(name)
    B, S, H, Hkv, D, _, kind = LAYOUTS[name]
    structure = mask if kind in ("block", "window") else None
    keep = dense[:, 0, 0] if kind == "keys" else None

    def reference(q, k, v):
        return attention_reference(q, k, v, causal=causal, mask=dense)

    found = {}
    with jax.default_matmul_precision("highest"):
        lse = {"flash": flash_lse(q, k, v, keep, causal, tile_plan(
                   S, S, D, H, Hkv, 4, causal, keep is not None,
                   structure).fwd, structure),
               "reference": reference_lse(q, k, causal, dense)}
        for label, fn in (("flash", lambda q, k, v: _flash(q, k, v, causal,
                                                           mask)),
                          ("reference", reference)):
            out, vjp = jax.vjp(fn, q, k, v)
            found[label] = [np.asarray(x)
                            for x in (out, lse[label]) + vjp(w)]
    return found


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_in_place_kernels_match_the_reference(name, tensor):
    found, i = _results(name), TENSORS.index(tensor)
    got, want = found["flash"][i], found["reference"][i]
    assert got.shape == want.shape and np.isfinite(got).all()
    # As tests/test_models.py; the log-sum-exp to float32 rounding.
    limit = {"out": 1e-4, "lse": 1e-5}.get(tensor, 1e-3)
    assert np.abs(got - want).max() <= limit * np.abs(want).max()


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_the_plan_takes_more_than_one_head_a_step(name):
    """Every layout runs some kernel with several heads a step: several
    slabs of columns side by side in one block, or lane-packed pairs."""
    B, S, H, Hkv, D, causal, kind = LAYOUTS[name]
    _, causal, mask, _ = _inputs(name)
    structure = mask if kind in ("block", "window") else None
    plan = tile_plan(S, S, D, H, Hkv, 4, causal, kind == "keys", structure)
    assert max(t.heads for _, t in plan.kernels()) > lane_pack(H, Hkv, D)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs it calls, but not the
    bodies of the Pallas kernels."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_no_transpose_outside_the_kernels(name):
    """Forward and backward, between the caller's arrays and the two
    kernels there is no transpose: every operand and result is a view of
    [B, S, H*D] (the row statistics are made and read in their own layout,
    and delta is made by the fused backward)."""
    (q, k, v, w), causal, mask, _ = _inputs(name)

    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v, causal, mask) * w)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr
    names = [e.primitive.name for e in _eqns(jaxpr)]
    assert names.count("pallas_call") == 2
    assert "transpose" not in names


@pytest.mark.parametrize("H,Hkv,D,heads", [
    (12, 12, 64, (2, 4, 6, 12)),     # BERT: pairs of heads, whole slabs
    (16, 16, 128, (1, 2, 4, 8, 16)),
    (32, 4, 128, (1, 2, 4, 8)),      # heads of one K/V group
    (28, 4, 128, (1, 7)),
    (4, 2, 64, (4,)),                # a K/V head is 64 lanes: both groups
    (8, 4, 64, (4, 8)),
    (3, 3, 64, (3,)),                # no pair, 192 columns: the whole row
    (4, 4, 72, (4,)),
])
def test_a_step_holds_blocks_mosaic_moves(H, Hkv, D, heads):
    """The head counts a step may hold: whole 128-lane tiles of columns, or
    the whole row, on both the query and the K/V side."""
    assert att._step_heads(H, Hkv, D) == heads
