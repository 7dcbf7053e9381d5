"""`models.smallthinker.SmallThinker` and the next-token step against the
benchmark's plain float32 reference (``benchmark/reference/smallthinker.py``,
which imports nothing of ``maggy_tpu`` and builds every mask from indices):
per-position likelihoods, loss and EVERY gradient leaf, at toy sizes in
float32, where the two must agree to rounding. And what the model brought to
the expert layer: ReGLU experts, and a router that reads another tensor than
the one the experts transform (the layer's input before attention)."""

import functools
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402
from maggy_tpu.models import SmallThinker, SmallThinkerConfig, moe  # noqa: E402
from maggy_tpu.models.moe import ExpertShareMLP  # noqa: E402

#: Two periods, a window of 8 at S 32, GQA 7:1, 8 experts of which 4 held.
MODEL = {
    "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 8,
    "num_attention_heads": 7, "num_key_value_heads": 1, "head_dim": 16,
    "moe_ffn_hidden_size": 24, "num_experts_routed": 8,
    "moe_num_primary_experts": 4, "first_expert": 2,
    "moe_num_active_primary_experts": 2, "norm_topk_prob": True,
    "moe_primary_router_apply_softmax": True, "rope_theta": 1.5e6,
    "rms_norm_eps": 1e-6, "sliding_window_size": 8,
    "sliding_window_layout": [0, 1, 1, 1] * 2, "rope_layout": [0, 1, 1, 1] * 2,
    "tie_word_embeddings": False, "rope_scaling": None, "residual_layers": 52,
    "head_chunk": 24, "activation_dtype": "float32", "param_dtype": "float32",
    "remat": True,
}
VARIANTS = {
    "held_share_remat": {},
    # Every expert held: the router trains, through the input before
    # attention; a window that is not a multiple of anything, no remat.
    "all_experts_window5": {"moe_num_primary_experts": 8, "first_expert": 0,
                            "sliding_window_size": 5, "remat": False},
}


@functools.lru_cache(maxsize=None)
def _both(variant):
    model = dict(MODEL, **VARIANTS[variant])
    family = spec.load_module("families", "smallthinker")
    ref = spec.load_module("reference", "smallthinker")
    module, _ = family.build(model)
    batch = jax.tree_util.tree_map(
        jnp.asarray, family.batches(model, 2, 32, seed=11, n=1)[0])
    params = nn.meta.unbox(module.init(jax.random.key(3), *batch["inputs"]))[
        "params"]

    def model_fn(p):
        nll = module.apply({"params": p}, *batch["inputs"])
        return family.loss(nll, batch), nll

    def ref_fn(p):
        nll = ref.forward(p, batch["inputs"], model)
        return ref.loss_from_logits(nll, batch["labels"]), nll

    return tuple(jax.value_and_grad(f, has_aux=True)(params)
                 for f in (model_fn, ref_fn))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_likelihoods_and_loss_match_the_reference(variant):
    ((loss, nll), _), ((ref_loss, ref_nll), _) = _both(variant)
    assert nll.shape == (2, 32) and nll.dtype == jnp.float32
    assert float(jnp.abs(nll - ref_nll).max()) \
        <= 2e-5 * float(jnp.abs(ref_nll).max())
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_every_gradient_leaf_matches_the_reference(variant):
    (_, grads), (_, ref_grads) = _both(variant)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = jax.tree_util.tree_leaves(ref_grads)
    assert len(flat) == len(ref_flat) == 8 * 10 + 3
    largest = max(float(jnp.abs(r).max()) for r in ref_flat)
    share = VARIANTS[variant].get("moe_num_primary_experts", 4) < 8
    for (key, got), want in zip(flat, ref_flat):
        name = jax.tree_util.keystr(key)
        scale = float(jnp.abs(want).max())
        if "router" in name:
            # A share does not train its router; all experts held, it does.
            assert bool(jnp.any(want)) is not share, name
        assert float(jnp.abs(got - want).max()) <= 1e-4 * max(
            scale, 1e-3 * largest), name


@pytest.mark.parametrize("fault", ["no_window", "route_after_attn",
                                   "rope_global", "no_rope"])
def test_a_fault_in_the_reference_is_read(fault):
    """The comparison above tells the equations apart: the reference with
    one term wrong is not the model, by its likelihoods or by the first
    layer's gradient."""
    variant = "all_experts_window5"
    model = dict(MODEL, **VARIANTS[variant])
    ref = spec.load_module("reference", "smallthinker")
    family = spec.load_module("families", "smallthinker")
    batch = jax.tree_util.tree_map(
        jnp.asarray, family.batches(model, 2, 32, seed=11, n=1)[0])
    ((_, nll), grads), _ = _both(variant)
    knobs = ref.Knobs(lambda x: x, ref.FAULTS[fault])

    def wrong_fn(p):
        out = ref.forward(p, batch["inputs"], model, knobs)
        return ref.loss_from_logits(out, batch["labels"]), out

    (_, wrong), wrong_grads = jax.value_and_grad(wrong_fn, has_aux=True)(
        _params(variant))
    gaps = [float(jnp.abs(wrong - nll).max() / jnp.abs(nll).max())] + [
        float(jnp.abs(a - b).max() / jnp.abs(b).max())
        for a, b in zip(jax.tree_util.tree_leaves(wrong_grads["layer_0"]),
                        jax.tree_util.tree_leaves(grads["layer_0"]))]
    assert max(gaps) > 1e-2, fault


@functools.lru_cache(maxsize=None)
def _params(variant):
    model = dict(MODEL, **VARIANTS[variant])
    family = spec.load_module("families", "smallthinker")
    module, _ = family.build(model)
    batch = family.batches(model, 2, 32, seed=11, n=1)[0]
    return nn.meta.unbox(module.init(jax.random.key(3), *batch["inputs"]))[
        "params"]


@pytest.mark.parametrize("windowed, seen", [
    (1, lambda i, j: j <= i < j + 8),   # i - 8 < j <= i: the window's edge
    (0, lambda i, j: j <= i)])
def test_a_position_sees_exactly_its_window(windowed, seen):
    """One layer of each kind: changing token j moves the likelihood at
    position i iff i sees j; the window is its own position and the 7
    before it, not 8."""
    cfg = SmallThinkerConfig.tiny(
        dtype=jnp.float32, sliding_window_layout=(windowed,),
        rope_layout=(windowed,))
    module = SmallThinker(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 64, size=(1, 32)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    params = module.init(jax.random.key(0), tokens, targets)
    base = module.apply(params, tokens, targets)
    for j in (3, 12, 20):
        moved = module.apply(params, tokens.at[0, j].set(
            (tokens[0, j] + 1) % 64), targets)
        changed = np.asarray(base != moved)[0]
        assert changed.tolist() == [seen(i, j) for i in range(32)], j


def test_the_step_says_its_plans():
    from maggy_tpu.telemetry.plans import traced

    module = SmallThinker(SmallThinkerConfig.tiny(remat=True))
    tokens = jnp.zeros((1, 16), jnp.int32)
    with traced() as said:
        jax.eval_shape(module.init, jax.random.key(0), tokens, tokens)
    assert said.plans["moe"] == [
        "experts 2+4/8 top2 rows 2560 chunk 512 tile 512 pallas_gmm reglu "
        "route pre-attn combine xla"]
    assert said.scopes["moe"] == moe.SCOPES
    assert said.plans["remat"] == ["layer keeps flash_out flash_lse moe_route"]


def test_layouts_are_one_entry_a_layer():
    with pytest.raises(ValueError, match="one entry a layer"):
        SmallThinkerConfig(sliding_window_layout=(0, 1), rope_layout=(0,))


# ------------------------------------------------------- the expert layer

E, K, D, F = 8, 2, 32, 24


def layer(first=0, held=None):
    return ExpertShareMLP(D, F, E, K, experts_held=held, first_expert=first,
                          expert_kind="reglu", tile_rows=8, dtype=jnp.float32)


@functools.lru_cache(maxsize=None)
def whole_layer():
    rng = np.random.default_rng(4)
    x, u = (jnp.asarray(rng.normal(size=(2, 40, D)), jnp.float32)
            for _ in range(2))
    params = nn.meta.unbox(layer().init(jax.random.key(1), x, u))["params"]
    return params, x, u


def plain(params, x, u):
    """The layer written out: softmax over all experts of ``u W_r``, the top
    K renormalised, every expert on every token of ``x``."""
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(u.reshape(-1, D) @ params["router"], axis=-1)
        top, ids = jax.lax.top_k(probs, K)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(probs.shape[0])[:, None], ids].set(
                top / jnp.sum(top, axis=-1, keepdims=True))
        flat = x.reshape(-1, D)
        out = sum(gates[:, e:e + 1] * ((
            jax.nn.relu(flat @ params["gate_proj"][e])
            * (flat @ params["up_proj"][e])) @ params["down_proj"][e])
            for e in range(E))
        return out.reshape(x.shape)


def test_reglu_routed_from_another_input_is_the_plain_layer():
    params, x, u = whole_layer()
    got = layer().apply({"params": params}, x, route_from=u)
    want = plain(params, x, u)
    assert float(jnp.abs(got - want).max()) \
        <= 2e-5 * float(jnp.abs(want).max())
    # Routed from x itself it is another layer.
    assert float(jnp.abs(layer().apply({"params": params}, x) - want).max()) \
        > 1e-2 * float(jnp.abs(want).max())


def test_the_shares_add_up_to_the_uncut_reference_layer():
    """Holders {0-1, 2-5, 6-7} of the 8 ReGLU experts, each routing from
    ``u``: their parts add up to the benchmark reference's whole layer."""
    params, x, u = whole_layer()
    ref = spec.load_module("reference", "smallthinker")
    model = {"moe_num_active_primary_experts": K, "norm_topk_prob": True,
             "first_expert": 0, "moe_num_primary_experts": E,
             "num_experts_routed": E}
    with jax.default_matmul_precision("highest"):
        want = ref.experts(x.reshape(-1, D), u.reshape(-1, D), params, model,
                           ref.PLAIN).reshape(x.shape)
    total = 0.0
    for first, held in ((0, 2), (2, 4), (6, 2)):
        mine = dict(params, **{k: params[k][first:first + held]
                               for k in ("gate_proj", "up_proj", "down_proj")})
        total = total + layer(first, held).apply({"params": mine}, x,
                                                 route_from=u)
    assert float(jnp.abs(total - want).max()) \
        <= 2e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("wrt", ["params", "x", "u"])
def test_reglu_gradients_are_the_plain_layers(wrt):
    """Every gradient, the router's through ``u`` among them: the layer
    holds all its experts, so its gates pass theirs."""
    params, x, u = whole_layer()
    w = jnp.asarray(np.random.default_rng(5).normal(size=x.shape),
                    jnp.float32)
    args = {"params": params, "x": x, "u": u}

    def loss_of(fn):
        def loss(value):
            a = dict(args, **{wrt: value})
            return jnp.sum(fn(a["params"], a["x"], a["u"]) * w)
        return jax.grad(loss)(args[wrt])

    got = loss_of(lambda p, x, u: layer().apply({"params": p}, x,
                                                 route_from=u))
    want = loss_of(plain)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert bool(jnp.any(r != 0))
        assert float(jnp.abs(g - r).max()) <= 1e-4 * float(jnp.abs(r).max())
