"""`models.sdar.SdarMoe` and the block-diffusion step against the
benchmark's plain float32 reference (``benchmark/reference/sdar_moe.py``,
which imports nothing of ``maggy_tpu``): logits, loss and EVERY gradient
leaf, at toy sizes in float32, where the two must agree to rounding."""

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
from maggy_tpu.ops.losses import weighted_token_xent  # noqa: E402

MODEL = {
    "vocab_size": 64, "mask_token_id": 63, "hidden_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 24,
    "num_experts_routed": 8, "num_experts": 4, "first_expert": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "block_length": 4, "noise_schedule": "linear",
    "activation_dtype": "float32", "param_dtype": "float32", "remat": True,
}
VARIANTS = {
    "held_share_remat": {},
    "all_experts_block8": {"num_experts": 8, "first_expert": 0,
                           "block_length": 8, "remat": False},
    "no_renormalisation": {"norm_topk_prob": False},
}


@functools.lru_cache(maxsize=None)
def _both(variant):
    model = dict(MODEL, **VARIANTS[variant])
    family = spec.load_module("families", "sdar_moe")
    ref = spec.load_module("reference", "sdar_moe")
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


def _leaves(variant):
    (_, grads), (_, ref_grads) = _both(variant)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref_flat = jax.tree_util.tree_leaves(ref_grads)
    return [(jax.tree_util.keystr(k), g, r)
            for (k, g), r in zip(flat, ref_flat)]


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_every_gradient_leaf_matches_the_reference(variant):
    leaves = _leaves(variant)
    assert len(leaves) == 3 + 2 * 12  # embedding, head, final norm; 2 layers
    largest = max(float(jnp.abs(r).max()) for _k, _g, r in leaves)
    for name, got, want in leaves:
        # A leaf relative to itself, or to the tree where it vanishes.
        scale = max(float(jnp.abs(want).max()), 1e-6 * largest)
        assert float(jnp.abs(got - want).max()) <= 5e-5 * scale, name
    # A share held alone does not train its routers; the whole layer does.
    share = dict(MODEL, **VARIANTS[variant])["num_experts"] \
        < MODEL["num_experts_routed"]
    routers = [got for name, got, _want in leaves if "router" in name]
    assert len(routers) == 2
    assert all(bool(jnp.any(g != 0)) != share for g in routers)


def test_the_head_runs_on_the_noised_half_only_and_positions_repeat():
    """The clean half's logits do not exist, and the output does not change
    when the positions are given as the two ramps the model assumes."""
    from maggy_tpu.models import SdarMoe, SdarMoeConfig

    cfg = SdarMoeConfig.tiny(dtype=jnp.float32)
    module = SdarMoe(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 63, size=(2, 32)), jnp.int32)
    params = module.init(jax.random.key(0), tokens)
    out = module.apply(params, tokens)
    assert out.shape == (2, 16, cfg.vocab_size)
    ramps = jnp.broadcast_to(jnp.tile(jnp.arange(16), 2), tokens.shape)
    assert jnp.array_equal(out, module.apply(params, tokens, ramps))
    with pytest.raises(ValueError, match="whole blocks"):
        module.apply(params, tokens[:, :30])


def test_weighted_token_xent_is_the_weighted_sum_of_cross_entropies():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 5, 7)), jnp.bfloat16)
    targets = jnp.asarray(rng.integers(0, 7, size=(2, 5)))
    weights = jnp.asarray(rng.random(size=(2, 5)) * (rng.random((2, 5)) < .5),
                          jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    want = -jnp.sum(weights * jnp.take_along_axis(
        logp, targets[..., None], -1)[..., 0])
    assert abs(float(weighted_token_xent(logits, targets, weights))
               - float(want)) < 1e-6
    # A position of weight 0 counts nothing, whatever its logits are.
    wild = logits.at[0, 0].set(jnp.where(weights[0, 0] == 0, 1e4, 0.0))
    if float(weights[0, 0]) == 0.0:
        assert float(weighted_token_xent(wild, targets, weights)) \
            == float(weighted_token_xent(logits, targets, weights))


def test_the_batches_are_the_block_diffusion_step():
    family = spec.load_module("families", "sdar_moe")
    (batch,) = family.batches(MODEL, 4, 32, seed=5, n=1)
    (tokens,), labels = batch["inputs"], batch["labels"]
    xt, x0 = tokens[:, :32], tokens[:, 32:]
    assert tokens.shape == (4, 64) and x0.max() < 63
    masked = xt == 63
    assert (xt[~masked] == x0[~masked]).all()
    assert (labels["targets"] == x0).all()
    assert ((labels["weights"] > 0) == masked).all()
    # One t per sequence: the weights of a row are all 1 / (t B L).
    for row, m in zip(labels["weights"], masked):
        if m.any():
            assert np.ptp(row[m]) == 0 and row[m][0] >= 1.0 / (4 * 32)
    again = family.batches(MODEL, 4, 32, seed=5, n=1)[0]
    assert (again["inputs"][0] == tokens).all()  # the seed decides
