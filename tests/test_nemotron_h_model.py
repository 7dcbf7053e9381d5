"""`models.nemotron_h.NemotronH` and the next-token step against the
benchmark's plain float32 reference (``benchmark/reference/nemotron_h.py``,
which imports nothing of ``maggy_tpu`` and runs the state-space recurrence
position by position): logits, loss and EVERY gradient leaf, at toy sizes in
float32, where the two must agree to rounding. And the expert layer's new
arguments: a sigmoid router whose bias moves the choice and not the gates,
the balance rule that moves the bias through the optimizer, relu^2 experts,
and a shared expert that the shares count once."""

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
from maggy_tpu.models import NemotronH, NemotronHConfig, moe  # noqa: E402
from maggy_tpu.models.moe import ExpertShareMLP  # noqa: E402

MODEL = {
    "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 4,
    "hybrid_override_pattern": "EM*M", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 8, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 40,
    "num_experts_routed": 8, "n_routed_experts": 4, "first_expert": 2,
    "residual_blocks": None,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
    "mlp_hidden_act": "relu2", "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "activation_dtype": "float32",
    "param_dtype": "float32", "remat": True,
}
VARIANTS = {
    "held_share_remat": {},
    "all_experts_chunk16": {"n_routed_experts": 8, "first_expert": 0,
                            "chunk_size": 16, "remat": False},
    "one_period_of_the_cell": {"hybrid_override_pattern": "EMEMEMEM*",
                               "num_hidden_layers": 9,
                               "residual_blocks": 52},
}


@functools.lru_cache(maxsize=None)
def _both(variant):
    model = dict(MODEL, **VARIANTS[variant])
    family = spec.load_module("families", "nemotron_h")
    ref = spec.load_module("reference", "nemotron_h")
    module, _ = family.build(model)
    batch = jax.tree_util.tree_map(
        jnp.asarray, family.batches(model, 2, 32, seed=11, n=1)[0])
    params = nn.meta.unbox(module.init(jax.random.key(3), *batch["inputs"]))[
        "params"]

    def model_fn(p):
        logits = module.apply({"params": p}, *batch["inputs"])
        return family.loss(logits, batch), logits

    def ref_fn(p):
        logits = ref.forward(p, batch["inputs"], model)
        return ref.loss_from_logits(logits, batch["labels"]), logits

    return tuple(jax.value_and_grad(f, has_aux=True)(params)
                 for f in (model_fn, ref_fn))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_logits_and_loss_match_the_reference(variant):
    ((loss, logits), _), ((ref_loss, ref_logits), _) = _both(variant)
    assert logits.shape == (2, 32, 64) and logits.dtype == jnp.float32
    assert float(jnp.abs(logits - ref_logits).max()) \
        <= 2e-5 * float(jnp.abs(ref_logits).max())
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_every_gradient_leaf_matches_the_reference(variant):
    (_, grads), (_, ref_grads) = _both(variant)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = jax.tree_util.tree_leaves(ref_grads)
    assert len(flat) == len(ref_flat) > 30
    largest = max(float(jnp.abs(r).max()) for r in ref_flat)
    share = VARIANTS[variant].get("n_routed_experts", 4) < 8
    for (key, got), want in zip(flat, ref_flat):
        name = jax.tree_util.keystr(key)
        scale = float(jnp.abs(want).max())
        if "router" in name and ("bias" in name or share):
            # The bias gets no gradient; a share does not train its router.
            assert not jnp.any(got) and not jnp.any(want), name
            continue
        assert float(jnp.abs(got - want).max()) <= 1e-4 * max(
            scale, 1e-3 * largest), name


def test_the_model_is_causal_and_has_no_positions():
    """Logits at position i move with tokens up to i and with none after
    it, through all three kinds of block."""
    cfg = NemotronHConfig.tiny(dtype=jnp.float32)
    module = NemotronH(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 64, size=(1, 24)), jnp.int32)
    params = module.init(jax.random.key(0), tokens)
    base = module.apply(params, tokens)
    moved = module.apply(params, tokens.at[0, 10].set((tokens[0, 10] + 1)
                                                      % 64))
    changed = np.asarray(jnp.any(base != moved, axis=-1))[0]
    assert not changed[:10].any() and changed[10:].all()
    assert not any("pos" in jax.tree_util.keystr(k) for k, _ in
                   jax.tree_util.tree_leaves_with_path(params))


def test_the_step_says_its_plans_and_names_its_scopes():
    from maggy_tpu.ops import ssd
    from maggy_tpu.telemetry.plans import traced

    cfg = NemotronHConfig.tiny()
    assert not cfg.remat
    module = NemotronH(NemotronHConfig.tiny(remat=True))
    tokens = jnp.zeros((2, 16), jnp.int32)
    with traced() as said:
        jax.eval_shape(module.init, jax.random.key(0), tokens)
    assert said.plans["ssm"] == [
        "heads 4x8 groups 2 state 16 conv 4 chunk 8 S 16 xla_products "
        "conv xla"]
    assert said.scopes["ssm"] == ssd.SCOPES
    assert said.plans["moe"] == [
        "experts 2+4/8 top2 rows 2560 chunk 512 tile 512 pallas_gmm "
        "sigmoid+bias x2.5 relu2 shared 40"]
    assert said.scopes["moe"] == moe.SCOPES + (moe.SHARED_SCOPE,)
    assert said.plans["remat"] == [
        "block keeps flash_out flash_lse moe_route ssd_out"]


def test_a_pattern_is_letters_of_the_three_kinds():
    with pytest.raises(ValueError, match="letters of M, E"):
        NemotronHConfig(pattern="MEX")


# ------------------------------------------------------- the expert layer

E, K, D, F, FS = 8, 2, 32, 24, 40


def layer(first=0, held=None, **kw):
    return ExpertShareMLP(
        D, F, E, K, experts_held=held, first_expert=first, scoring="sigmoid",
        route_scale=2.5, expert_kind="relu2", shared_dim=FS, tile_rows=8,
        dtype=jnp.float32, **kw)


@functools.lru_cache(maxsize=None)
def whole_layer():
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 40, D)),
                    jnp.float32)
    params = nn.meta.unbox(layer().init(jax.random.key(1), x))["params"]
    return params, x


def uncut_reference(params, x):
    """The whole layer through the benchmark's reference, holding all."""
    ref = spec.load_module("reference", "nemotron_h")
    model = {"num_experts_per_tok": K, "norm_topk_prob": True,
             "routed_scaling_factor": 2.5, "first_expert": 0,
             "n_routed_experts": E, "num_experts_routed": E}
    with jax.default_matmul_precision("highest"):
        return ref.experts(x.reshape(-1, D), params, model,
                           ref.PLAIN).reshape(x.shape)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """At 8 routed experts the routed parts of the holders {0-1, 2-5, 6-7}
    plus the shared expert ONCE equal the uncut reference's whole layer."""
    params, x = whole_layer()
    ref = spec.load_module("reference", "nemotron_h")
    with jax.default_matmul_precision("highest"):
        flat = x.reshape(-1, D)
        shared = (ref.relu2(flat @ params["shared_up_proj"])
                  @ params["shared_down_proj"]).reshape(x.shape)
    total, holders = 0.0, ((0, 2), (2, 4), (6, 2))
    for first, held in holders:
        mine = dict(params, **{k: params[k][first:first + held]
                               for k in ("up_proj", "down_proj")})
        total = total + layer(first, held).apply({"params": mine}, x)
    total = total - (len(holders) - 1) * shared
    want = uncut_reference(params, x)
    assert float(jnp.abs(total - want).max()) \
        <= 2e-5 * float(jnp.abs(want).max())
    got = layer().apply({"params": params}, x)
    assert float(jnp.abs(got - want).max()) \
        <= 2e-5 * float(jnp.abs(want).max())


def test_a_bias_moves_the_choice_and_not_the_gates():
    params, x = whole_layer()
    flat = x.reshape(-1, D)
    ids0, gates0 = moe.route_top_k(flat, params["router"], K, False,
                                   "sigmoid", jnp.zeros(E), 1.0)
    bias = jnp.zeros(E).at[5].set(10.0)     # every token now takes expert 5
    ids1, gates1 = moe.route_top_k(flat, params["router"], K, False,
                                   "sigmoid", bias, 1.0)
    assert bool(jnp.any(ids0 != ids1)) and bool(jnp.all(ids1[:, 0] == 5))
    scores = jax.nn.sigmoid(jnp.dot(
        flat, params["router"], precision=jax.lax.Precision.HIGHEST))
    # A gate is the chosen expert's own score: under 1, the bias not in it.
    np.testing.assert_allclose(
        np.asarray(gates1), np.asarray(jnp.take_along_axis(scores, ids1, 1)),
        rtol=1e-6)
    assert float(gates1.max()) < 1.0
    # And the bias gets no gradient, whatever it is.
    grad = jax.grad(lambda b: jnp.sum(layer().apply(
        {"params": dict(params, router_bias=b)}, x)))(bias)
    assert not jnp.any(grad)


def _loads(ids):
    return np.bincount(np.asarray(ids).reshape(-1), minlength=E)


def test_the_balance_term_is_zero_and_its_gradient_is_the_load_error():
    params, x = whole_layer()
    ids, _ = moe.route_top_k(x.reshape(-1, D), params["router"], K, True,
                             "sigmoid", None, 2.5)
    bias = jnp.linspace(-0.3, 0.3, E)
    term, grad = jax.value_and_grad(
        lambda b: moe.balance_pull(ids, b, E))(bias)
    assert float(term) == 0.0
    want = _loads(ids) / (ids.size / E) - 1.0
    np.testing.assert_allclose(np.asarray(grad), want, atol=1e-6)
    assert abs(float(grad.sum())) < 1e-5  # the errors of all experts cancel


@pytest.mark.parametrize("held", [None, 4])
def test_the_balance_rule_rides_in_losses_and_leaves_the_output_alone(held):
    params, x = whole_layer()
    if held:
        params = dict(params, **{k: params[k][:held]
                                 for k in ("up_proj", "down_proj")})
    plain, ruled = layer(held=held), layer(held=held, balance_scale=512.0)

    def objective(b, mutable):
        out = ruled.apply({"params": dict(params, router_bias=b)}, x,
                          mutable=mutable)
        out, sowed = out if mutable else (out, {})
        return jnp.sum(out) + sum(
            jnp.sum(v) for v in jax.tree_util.tree_leaves(sowed)), out

    zero = jnp.zeros(E)
    (_, out), grad = jax.value_and_grad(objective, has_aux=True)(
        zero, ["losses"])
    # At a bias of zero the layer is the one without the rule, bit for bit.
    assert bool(jnp.all(out == plain.apply({"params": params}, x)))
    ids, _ = moe.route_top_k(x.reshape(-1, D), params["router"], K, True,
                             "sigmoid", zero, 2.5)
    np.testing.assert_allclose(
        np.asarray(grad), _loads(ids) / (ids.size / E) - 1.0, atol=1e-6)
    # A plain apply (the reference check's) sows nothing: no gradient.
    assert not jnp.any(jax.grad(objective, has_aux=True)(zero, False)[0])
    with pytest.raises(ValueError, match="sigmoid"):
        ExpertShareMLP(D, F, E, K, balance_scale=512.0,
                       dtype=jnp.float32).init(jax.random.key(0), x)


@pytest.mark.parametrize("scale,lr", [(512.0, 3e-5), (64.0, 3e-4)])
def test_an_optimizer_balances_a_skewed_router(scale, lr):
    """Adam on the sowed term alone: tokens that share a direction load
    the experts unevenly, and the rule brings them to even loads, the choice
    seeing ``scale x`` the parameter."""
    import optax

    params, _ = whole_layer()
    rng = np.random.default_rng(9)
    # Every token shares a direction, so the router's logits share offsets.
    x = jnp.asarray(rng.normal(size=(4, 256, D)) + rng.normal(size=(D,)),
                    jnp.float32)
    ruled = layer(balance_scale=scale)

    def sowed(b):
        _, got = ruled.apply({"params": dict(params, router_bias=b)}, x,
                             mutable=["losses"])
        return sum(jnp.sum(v) for v in jax.tree_util.tree_leaves(got))

    def loads(b):
        return _loads(moe.route_top_k(
            x.reshape(-1, D), params["router"], K, True, "sigmoid",
            b * scale, 2.5)[0])

    tx = optax.adamw(lr)
    bias = jnp.zeros(E)
    state = tx.init(bias)
    before = loads(bias)

    @jax.jit
    def step(bias, state):
        updates, state = tx.update(jax.grad(sowed)(bias), state, bias)
        return optax.apply_updates(bias, updates), state

    for _ in range(60):
        bias, state = step(bias, state)
    after, even = loads(bias), x.shape[0] * x.shape[1] * K / E
    assert before.max() > 1.5 * even
    assert after.max() < 1.25 * even and after.min() > 0.75 * even


@pytest.mark.parametrize("renormalize,scale", [(True, 2.5), (False, 1.0)])
def test_sigmoid_gates_are_scores_over_their_sum_times_the_scale(renormalize,
                                                                 scale):
    params, x = whole_layer()
    flat = x.reshape(-1, D)
    ids, gates = moe.route_top_k(flat, params["router"], K, renormalize,
                                 "sigmoid", None, scale)
    scores = jax.nn.sigmoid(jnp.dot(
        flat, params["router"], precision=jax.lax.Precision.HIGHEST))
    top, top_ids = jax.lax.top_k(scores, K)
    want = top / (top.sum(-1, keepdims=True) + 1e-20) * scale \
        if renormalize else top
    assert bool(jnp.all(ids == top_ids))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want), rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route_top_k(flat, params["router"], K, True, "tanh")


@pytest.mark.parametrize("first,held", [(0, None), (2, 4)])
def test_relu2_experts_match_the_reference_forward_and_backward(first, held):
    params, x = whole_layer()
    ref = spec.load_module("reference", "nemotron_h")
    n = E if held is None else held
    mine = dict(params, **{k: params[k][first:first + n]
                           for k in ("up_proj", "down_proj")})
    model = {"num_experts_per_tok": K, "norm_topk_prob": True,
             "routed_scaling_factor": 2.5, "first_expert": first,
             "n_routed_experts": n, "num_experts_routed": E}
    w = jnp.asarray(np.random.default_rng(0).normal(size=x.shape), jnp.float32)

    def ref_fn(p, x):
        with jax.default_matmul_precision("highest"):
            return ref.experts(x.reshape(-1, D), p, model,
                               ref.PLAIN).reshape(x.shape)

    got, got_vjp = jax.vjp(
        lambda p, x: layer(first, held).apply({"params": p}, x), mine, x)
    want, want_vjp = jax.vjp(ref_fn, mine, x)
    (gp, gx), (wp, wx) = got_vjp(w), want_vjp(w)
    for a, b in [(got, want), (gx, wx)] + [(gp[k], wp[k]) for k in sorted(gp)]:
        assert float(jnp.abs(a - b).max()) <= 2e-5 * max(
            float(jnp.abs(b).max()), 1e-30)
    assert bool(jnp.any(gp["router"] != 0)) == (held is None)


def test_an_unknown_expert_kind_is_refused():
    _, x = whole_layer()
    with pytest.raises(ValueError, match="expert_kind"):
        ExpertShareMLP(D, F, E, K, expert_kind="gelu").init(
            jax.random.key(0), x)
