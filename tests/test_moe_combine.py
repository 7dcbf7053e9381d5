"""The experts' combine as Pallas kernels (`models.moe._combine`:
`moe_combine_fwd`, `moe_combine_bwd`), in interpret mode, against XLA's
scatter-add of the same rows: on seeded routings that balance, crowd one
expert, leave an expert or a whole block of tokens without rows, hold one
to sixteen experts, and tile the width by 384 as the Nemotron cell's 2688
does. The padding rows of the buffer hold NaN here, and no token sees it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.models import moe

N, TILE = 512, 16


def routing(case, rng):
    """(ids [N, k], first, held, experts) of a named routing."""
    E, k, first, G = 8, 2, 2, 4
    if case == "held_1":
        G = 1
    if case == "held_16":
        E, k, first, G = 32, 4, 8, 16
    ids = np.stack([rng.choice(E, k, replace=False) for _ in range(N)])
    if case == "one_expert":  # every pair that is held is expert `first`
        ids[:, 0], ids[:, 1] = first, (first + G + rng.integers(
            0, E - G, N)) % E
    if case == "expert_without_rows":
        ids[ids == first + 1] = (first + G) % E
        ids[:, 1] = np.where(ids[:, 0] == ids[:, 1], (ids[:, 0] + 7) % E,
                             ids[:, 1])
    if case == "block_no_expert_reaches":
        absent = [e for e in range(E) if not first <= e < first + G]
        ids[N // 2:] = np.stack([rng.choice(absent, k, replace=False)
                                 for _ in range(N // 2)])
    if case == "one_pair_a_token":
        ids[:, 0] = rng.integers(first, first + G, N)
        ids[:, 1] = (first + G + rng.integers(0, E - G, N)) % E
    assert all(len(set(r)) == len(r) for r in ids)
    return jnp.asarray(ids, jnp.int32), first, G, E


def layout(ids, first, G, width):
    """The buffer's rows (padding rows and rows past the used tiles NaN),
    the pairs' gates, and what both combines need."""
    rng = np.random.default_rng(7)
    row_pair, _, _ = moe.grouped_layout(ids, first, G, TILE)
    tokens, cols = moe.combine_plan(N, width, TILE)
    runs = moe.combine_runs(ids, first, G, TILE, tokens)
    P = ids.size
    valid = row_pair < P
    gate = jnp.asarray(rng.uniform(0.1, 1.0, P), jnp.float32)

    def rows(dtype):
        r = jnp.asarray(rng.normal(size=(row_pair.shape[0], width)), dtype)
        return jnp.where(valid[:, None], r, jnp.nan).astype(dtype)

    return row_pair, valid, gate, runs, tokens, cols, rows


def scatter_add(row_pair, valid, k, terms):
    """XLA's scatter-add of float32 ``terms`` [rows, D] to their tokens."""
    token = jnp.where(valid, row_pair // k, 0)
    return jnp.zeros((N, terms.shape[1]), jnp.float32).at[token].add(
        jnp.where(valid[:, None], terms, 0.0))


def kernel(sources, scale, runs, tokens, name):
    row_of, _, start, length = runs
    return moe._combine(sources, scale, row_of, start, length, tokens=tokens,
                        out_dtype=jnp.float32, name=name, interpret=True)


def within_rounding(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=4e-7 * float(np.abs(want).max()))


CASES = ["balanced", "one_expert", "expert_without_rows",
         "block_no_expert_reaches", "held_1", "held_16", "one_pair_a_token"]


@pytest.mark.parametrize("width", [256, 768], ids=["d256", "d768_by_384"])
@pytest.mark.parametrize("case", CASES)
def test_the_forward_kernel_is_the_gate_weighted_scatter_add(case, width):
    ids, first, G, _ = routing(case, np.random.default_rng(len(case)))
    row_pair, valid, gate, runs, tokens, cols, rows = layout(
        ids, first, G, width)
    assert cols == (384 if width == 768 else 256)
    y = rows(jnp.bfloat16)
    row_of = runs[0]
    scale = jnp.where(row_of >= 0, gate[jnp.minimum(
        row_pair[jnp.maximum(row_of, 0)], ids.size - 1)], 0.0)
    got = kernel((y,), scale, runs, tokens, "moe_combine_fwd")
    g_row = jnp.where(valid, gate[jnp.minimum(row_pair, ids.size - 1)], 0.0)
    want = scatter_add(row_pair, valid, ids.shape[1],
                       y.astype(jnp.float32) * g_row[:, None])
    if case in ("one_pair_a_token", "one_expert", "held_1"):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        within_rounding(got, want)
    if case == "block_no_expert_reaches":
        assert not np.asarray(got)[N // 2:].any()
    if case == "expert_without_rows":
        assert int(runs[3].reshape(-1, G)[:, 1].sum()) == 0


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("products", [1, 2], ids=["relu2", "two_into"])
@pytest.mark.parametrize("case", ["balanced", "one_expert", "held_16",
                                  "one_pair_a_token"])
def test_the_backward_kernel_sums_the_products_of_each_row(case, products,
                                                           dtype):
    """The gradient's rows come one a matrix into the width (two for SwiGLU
    and ReGLU), the gate already in them: summed in float32, each row once.
    bfloat16 rows enter the MXU as they are, float32 ones split in three."""
    ids, first, G, _ = routing(case, np.random.default_rng(3 + len(case)))
    row_pair, valid, _, runs, tokens, _, rows = layout(ids, first, G, 256)
    sources = tuple(rows(dtype) for _ in range(products))
    got = kernel(sources, None, runs, tokens, "moe_combine_bwd")
    want = scatter_add(row_pair, valid, ids.shape[1], functools.reduce(
        jnp.add, (s.astype(jnp.float32) for s in sources)))
    if case in ("one_pair_a_token", "one_expert"):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        within_rounding(got, want)


def test_the_runs_are_where_grouped_layout_put_the_rows():
    """`combine_runs` reads no buffer: its rows are the ones the sort gave,
    and each run of a block and an expert is contiguous and in token
    order."""
    ids, first, G, _ = routing("balanced", np.random.default_rng(11))
    row_pair, _, _ = moe.grouped_layout(ids, first, G, TILE)
    row_of, slot_of, start, length = (np.asarray(a) for a in
                                      moe.combine_runs(ids, first, G, TILE,
                                                       128))
    pairs = np.asarray(row_pair)
    k = ids.shape[1]
    for g in range(G):
        for n in range(N):
            r = row_of[g, n]
            chose = np.asarray(ids[n]).tolist()
            assert (r >= 0) == (first + g in chose)
            if r >= 0:
                assert pairs[r] == n * k + slot_of[g, n]
                assert chose[slot_of[g, n]] == first + g
            else:
                assert slot_of[g, n] == k
    for b in range(N // 128):
        for g in range(G):
            s, n = start[b * G + g], length[b * G + g]
            toks = pairs[s:s + n] // k
            assert list(toks) == sorted(toks)
            assert all(b * 128 <= t < (b + 1) * 128 for t in toks)
    assert length.sum() == (row_of >= 0).sum()


@pytest.mark.parametrize("kind,dtype", [("swiglu", jnp.float32),
                                        ("relu2", jnp.bfloat16)])
def test_expert_ffn_with_the_kernels_is_the_scatter_add_layer(kind, dtype):
    """The whole layer, forward and every gradient, with the combine as the
    kernels (interpret mode) and as XLA's scatter-add."""
    ids, first, G, E = routing("balanced", np.random.default_rng(5))
    rng = np.random.default_rng(9)
    D, F = 256, 128
    x = jnp.asarray(rng.normal(size=(N, D)), dtype)
    into = moe.EXPERT_KINDS[kind].into
    weights = tuple(jnp.asarray(rng.normal(size=s) / np.sqrt(s[1]),
                                jnp.float32)
                    for s in [(G, D, F)] * len(into) + [(G, F, D)])
    gate = jnp.asarray(rng.uniform(0.1, 1.0, ids.size), jnp.float32)
    route = moe.grouped_layout(ids, first, G, TILE)
    tokens, _ = moe.combine_plan(N, D, TILE)
    runs = moe.combine_runs(ids, first, G, TILE, tokens)
    chunk = moe.buffer_rows(N, 2, G, TILE) // 4
    w = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)

    def loss(x, weights, gate, runs):
        out = moe.expert_ffn(x, weights, gate, *route, runs, 2, chunk, TILE,
                             True, kind)
        return jnp.sum(out.astype(jnp.float32) * w)

    grad = jax.value_and_grad(loss, (0, 1, 2))
    (got_loss, got), (want_loss, want) = (grad(x, weights, gate, r)
                                          for r in (runs, ()))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=tol)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=tol * float(np.abs(b).max()))


def test_no_shape_that_does_not_tile_takes_the_kernels():
    assert moe.combine_plan(16384, 2048, 512) == (256, 512)
    assert moe.combine_plan(16384, 2688, 512) == (256, 384)
    assert moe.combine_plan(16384, 2560, 512) == (256, 512)
    assert moe.combine_plan(80, 256, 16) is None      # tokens
    assert moe.combine_plan(512, 32, 16) is None      # width
    assert moe.combine_plan(512, 256, 8) is None      # a window crosses tiles
