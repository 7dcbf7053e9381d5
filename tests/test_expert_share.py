"""`models.moe.ExpertShareMLP`: dropless routing over a share of the experts,
against a dense float32 evaluation of the same equations (every expert on
every token, weighted by its gate). The grouped products run in Pallas
interpret mode here; the last test builds them for the v5e."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.models import moe
from maggy_tpu.models.moe import ExpertShareMLP

E, K, D, F = 8, 2, 32, 48


def dense_layer(p, x, first, held, top_k=K, renormalize=True):
    """Every held expert applied to every token; gates zero where the token
    did not choose the expert. ``p``: the layer's parameter tree."""
    shape = x.shape
    x = x.reshape(-1, shape[-1]).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
        top, ids = jax.lax.top_k(probs, top_k)
        if renormalize:
            top = top / top.sum(-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(x.shape[0])[:, None], ids].set(top)
        if held < p["router"].shape[1]:  # a share does not train the router
            gates = jax.lax.stop_gradient(gates)
        out = 0.0
        for g in range(held):
            h = jax.nn.silu(x @ p["gate_proj"][g]) * (x @ p["up_proj"][g])
            out = out + gates[:, first + g, None] * (h @ p["down_proj"][g])
    return out.reshape(shape)


def make(first=0, held=None, top_k=K, renormalize=True, experts=E, **kw):
    layer = ExpertShareMLP(D, F, experts, top_k, experts_held=held,
                           first_expert=first, renormalize=renormalize,
                           tile_rows=8, dtype=jnp.float32, **kw)
    x = jnp.asarray(np.random.default_rng(first + experts).normal(
        size=(2, 40, D)), jnp.float32)
    params = nn.meta.unbox(layer.init(jax.random.key(1), x))["params"]
    return layer, params, x


def close(got, want, tol=2e-5):
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= tol * max(scale, 1e-30)


@pytest.mark.parametrize("first,held,renormalize", [
    (0, None, True), (2, 2, True), (6, 2, False), (0, 4, True)])
def test_forward_and_every_gradient_match_the_dense_layer(first, held,
                                                          renormalize):
    layer, params, x = make(first, held, renormalize=renormalize)
    held = E if held is None else held
    w = jnp.asarray(np.random.default_rng(0).normal(size=x.shape), jnp.float32)
    got, got_vjp = jax.vjp(lambda p, x: layer.apply({"params": p}, x),
                           params, x)
    want, want_vjp = jax.vjp(
        lambda p, x: dense_layer(p, x, first, held, renormalize=renormalize),
        params, x)
    close(got, want)
    (gp, gx), (wp, wx) = got_vjp(w), want_vjp(w)
    close(gx, wx)
    for name in ("router", "gate_proj", "up_proj", "down_proj"):
        close(gp[name], wp[name])
    # Only a layer that holds all its experts trains its router.
    assert bool(jnp.any(gp["router"] != 0)) == (held == E)


def test_the_shares_add_up_to_the_whole_layer():
    """Four holders of 2 of 8 experts each, given the same weights, sum to
    the uncut layer, which is the dense evaluation over all 8."""
    whole, params, x = make(0, None)
    total = 0.0
    for first in (0, 2, 4, 6):
        share = ExpertShareMLP(D, F, E, K, experts_held=2, first_expert=first,
                               tile_rows=8, dtype=jnp.float32)
        mine = dict(params, **{k: params[k][first:first + 2] for k in (
            "gate_proj", "up_proj", "down_proj")})
        total = total + share.apply({"params": mine}, x)
    close(total, whole.apply({"params": params}, x))
    close(total, dense_layer(params, x, 0, E))


@pytest.mark.parametrize("target,held_first", [(5, 4), (1, 4)])
def test_no_token_is_lost_when_every_token_takes_one_expert(target,
                                                            held_first):
    """A router that sends every token to expert ``target`` (top-1): where
    it is held, every token comes back through it, none dropped, however
    many they are; where it is not, the share is exactly zero."""
    layer, params, x = make(held_first, 2, top_k=1)
    # Positive inputs and a positive column make ``target`` every row's max.
    x = jnp.abs(x) + 0.1
    params = dict(params, router=jnp.zeros((D, E)).at[:, target].set(1.0))
    ids, gates = moe.route_top_k(x.reshape(-1, D), params["router"], 1, True)
    assert (np.asarray(ids) == target).all()
    out = layer.apply({"params": params}, x)
    want = dense_layer(params, x, held_first, 2, top_k=1)
    close(out, want)
    if held_first <= target < held_first + 2:
        assert float(jnp.abs(out).min(axis=-1).max()) > 0  # every token
        row_pair, _groups, tiles_used = moe.grouped_layout(
            ids, held_first, 2, 8)
        assert int((row_pair < ids.size).sum()) == ids.size  # all 80 rows
        assert int(tiles_used) == ids.size // 8
    else:
        assert float(jnp.abs(out).max()) == 0.0


def test_the_buffer_holds_the_worst_case_and_chunks_follow_the_rows():
    # 80 tokens, top-2, 2 held: at most 160 pairs here, plus a tile each.
    assert moe.buffer_rows(80, 2, 2, 8) == 160 + 16
    # top-2 over 4 held of 8: still at most two pairs a token.
    assert moe.buffer_rows(80, 2, 4, 8) == 160 + 32
    # One held expert can take one pair a token at most.
    assert moe.buffer_rows(80, 8, 1, 8) == 80 + 8
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 8, size=(80, 2)))
    row_pair, tile_group, tiles_used = moe.grouped_layout(ids, 2, 2, 8)
    local = np.asarray(ids).reshape(-1) - 2
    held = (local >= 0) & (local < 2)
    pairs = np.asarray(row_pair)
    assert sorted(pairs[pairs < ids.size]) == sorted(np.nonzero(held)[0])
    # Each tile's rows belong to the tile's expert (or are padding).
    for t in range(int(tiles_used)):
        rows = pairs[8 * t:8 * t + 8]
        assert all(local[r] == int(tile_group[t]) for r in rows
                   if r < ids.size)
    assert (pairs[8 * int(tiles_used):] == ids.size).all()


def test_the_layer_says_what_it_holds_and_which_scopes_are_its_own():
    from maggy_tpu.telemetry.plans import traced

    layer, params, x = make(2, 2)
    with traced() as said:
        layer.apply({"params": params}, x)
    # 22 tiles of 8 rows; a round takes the most tiles, up to CHUNK_TILES,
    # that divide them.
    assert said.plans == {
        "moe": ["experts 2+2/8 top2 rows 176 chunk 16 tile 8 pallas_gmm "
                "combine xla"]}
    assert said.scopes == {"moe": moe.SCOPES}


@functools.lru_cache(maxsize=None)
def _under_checkpoint(held):
    """Every gradient leaf, and what the gradient's jaxpr holds, of a layer
    between two products: with no `jax.checkpoint`, under one that keeps
    nothing and under one that keeps the routing's name."""
    from jaxpr_counts import primitives

    layer, params, x = make(0 if held is None else 2, held)
    w = jnp.asarray(np.random.default_rng(5).normal(size=x.shape),
                    jnp.float32)

    def loss(p, x):
        return jnp.sum(jnp.tanh(layer.apply({"params": p}, jnp.tanh(x))) * w)

    policy = jax.checkpoint_policies.save_only_these_names(*moe.REMAT_KEEP)
    found = {}
    for name, fn in (("plain", loss), ("remat", jax.checkpoint(loss)),
                     ("policy", jax.checkpoint(loss, policy=policy))):
        grad = jax.grad(fn, (0, 1))
        found[name] = (jax.tree_util.tree_leaves_with_path(grad(params, x)),
                       primitives(grad, params, x))
    return found


@pytest.mark.parametrize("held", [2, None])
def test_a_checkpoint_that_keeps_the_route_sorts_once(held):
    """The layout's sort (and for a share the scores and the selection with
    it) runs again in the backward pass of a `jax.checkpoint` with no
    policy; keeping `REMAT_KEEP` it does not. A layer that holds all its
    experts trains its router, so it makes the softmax again either way."""
    found = _under_checkpoint(held)
    sorts, top_ks = ({name: counts[prim] for name, (_g, counts)
                      in found.items()} for prim in ("sort", "top_k"))
    assert sorts == {"plain": 1, "remat": 2, "policy": 1}
    assert top_ks == {"plain": 1, "remat": 2,
                      "policy": 1 if held is not None else 2}
    for name in ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"):
        kernel = "pallas_call:" + name
        assert found["policy"][1][kernel] == found["remat"][1][kernel] > 0


@pytest.mark.parametrize("held", [2, None])
def test_the_kept_route_is_bitwise_what_a_second_routing_gives(held):
    found = _under_checkpoint(held)
    plain = found["plain"][0]
    assert len(plain) == 5  # four weights and x
    for name in ("remat", "policy"):
        for (path, want), (_p, got) in zip(plain, found[name][0]):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=name + jax.tree_util.keystr(path))
    router = dict((jax.tree_util.keystr(p), g) for p, g in plain)[
        "[0]['router']"]
    assert bool(jnp.any(router != 0)) == (held is None)


def test_experts_outside_the_routed_ones_are_refused():
    with pytest.raises(ValueError, match="not among"):
        make(7, 2)


def test_the_capacity_layer_is_still_the_sharded_one():
    """`MoEMLP` keeps its capacity path for the ``dp_ep`` strategy; the
    dropless layer has no capacity factor to set."""
    assert "capacity_factor" in moe.MoEMLP.__dataclass_fields__
    assert not any("capacity" in f
                   for f in ExpertShareMLP.__dataclass_fields__)


def test_the_grouped_products_compile_for_the_v5e_under_their_names():
    """One layer at the cell's widths (16 of 128 experts of 2048 x 768,
    top-8) on 2,048 positions, forward and backward, through Mosaic."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu, or one without AOT
        pytest.skip("libtpu cannot describe a v5e:2x2 topology: {!r}".format(e))
    dev = SingleDeviceSharding(topo.devices[0])
    layer = ExpertShareMLP(2048, 768, 128, 8, experts_held=16)
    x = jax.ShapeDtypeStruct((1, 2048, 2048), jnp.bfloat16, sharding=dev)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
        nn.meta.unbox(jax.eval_shape(layer.init, jax.random.key(0), x)))

    def loss(p, x):
        return jnp.sum(layer.apply(p, x).astype(jnp.float32) ** 2)

    import unittest.mock

    with unittest.mock.patch.object(moe, "_on_tpu", lambda: True):
        text = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile() \
            .as_text()
    for name, count in (("moe_gmm_fwd", 5), ("moe_gmm_dlhs", 3),
                        ("moe_gmm_drhs", 3)):
        assert text.count("%{}".format(name) + ".") \
            + text.count("%{} ".format(name)) >= count, name
    from maggy_tpu.telemetry.hlo_scopes import ops_by_scope

    scopes = ops_by_scope(text, moe.SCOPES)
    assert set(scopes) == set(moe.SCOPES)
    assert any(n.startswith("moe_gmm_fwd") for n in scopes["moe_experts"])
