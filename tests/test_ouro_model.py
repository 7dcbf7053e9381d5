"""`models.ouro.Ouro` and the loss over its exits against the benchmark's
plain float32 reference (``benchmark/reference/ouro.py``, which imports
nothing of ``maggy_tpu`` and loops in Python over passes and layers): dense
logits, gates, the ``[2, T, B, S]`` array, the loss and EVERY gradient leaf,
at toy sizes in float32, where the two must agree to rounding. And what
makes the model a loop: the passes share one set of parameters, T passes
equal an unshared stack of T x L copies with the final norm between, a
shared weight's gradient is the sum of its copies', and no pass lets a
position see its successor."""

import functools
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec  # noqa: E402
from maggy_tpu.models import Ouro, OuroConfig  # noqa: E402
from maggy_tpu.models import ouro as ouro_model  # noqa: E402

T, L, B, S, V = 3, 2, 2, 32, 96
MODEL = {
    "vocab_size": V, "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": L, "published_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "rope_theta": 1e6,
    "rms_norm_eps": 1e-6, "total_ut_steps": T, "hidden_act": "silu",
    "tie_word_embeddings": False, "use_sliding_window": False,
    "rope_scaling": None, "exit_entropy_beta": 0.05,
    "head_chunk": 40, "activation_dtype": "float32",
    "param_dtype": "float32", "remat": False,
}
VARIANTS = {
    "plain": {},
    "remat": {"remat": True},
    "one_layer_remat": {"num_hidden_layers": 1, "remat": True},
    "grouped_kv_one_chunk": {"num_key_value_heads": 2, "head_chunk": 4096},
}


def _family():
    return spec.load_module("families", "ouro")


def _reference():
    return spec.load_module("reference", "ouro")


def _seeded(model, seed=3):
    """(module, batch, parameters): the gate's weight and bias, which start
    at zero, drawn at random so that the gate's path carries something."""
    family = _family()
    module, _ = family.build(model)
    batch = jax.tree_util.tree_map(
        jnp.asarray, family.batches(model, B, S, seed=11, n=1)[0])
    params = nn.meta.unbox(module.init(
        jax.random.key(seed), *batch["inputs"]))["params"]
    k1, k2 = jax.random.split(jax.random.key(seed + 1))
    params = dict(params, exit_gate={
        "kernel": 0.3 * jax.random.normal(k1, (model["hidden_size"],)),
        "bias": 0.5 * jax.random.normal(k2, ())})
    return module, batch, params


@functools.lru_cache(maxsize=None)
def _both(variant):
    model = dict(MODEL, **VARIANTS[variant])
    family, ref = _family(), _reference()
    module, batch, params = _seeded(model)

    def model_fn(p):
        out = module.apply({"params": p}, *batch["inputs"])
        return family.loss(out, batch), out

    def ref_fn(p):
        out = ref.forward(p, batch["inputs"], model)
        return ref.loss_from_logits(out, batch["labels"]), out

    return tuple(jax.jit(jax.value_and_grad(f, has_aux=True))(params)
                 for f in (model_fn, ref_fn))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_likelihoods_gates_and_loss_match_the_reference(variant):
    ((loss, out), _), ((ref_loss, ref_out), _) = _both(variant)
    assert out.shape == (2, T, B, S) and out.dtype == jnp.float32
    np.testing.assert_allclose(out[0], ref_out[0], atol=2e-4)  # l_t
    np.testing.assert_allclose(out[1], ref_out[1], atol=2e-4)  # g_t
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * abs(float(ref_loss))
    assert float(jnp.abs(ref_out[1]).max()) > 0.1  # the gates say something


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_every_gradient_matches_the_reference(variant):
    (_, grad), (_, ref_grad) = _both(variant)
    scale = max(float(jnp.abs(g).max())
                for g in jax.tree_util.tree_leaves(ref_grad))
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grad),
            jax.tree_util.tree_leaves(ref_grad)):
        assert float(jnp.abs(want).max()) > 0, path
        # Each leaf to its own magnitude, floored at a thousandth of the
        # tree's (a norm's scale against an embedding row's).
        tol = 2e-4 * max(float(jnp.abs(want).max()), 1e-3 * scale)
        np.testing.assert_allclose(got, want, atol=tol,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_dense_path_gives_every_exits_logits():
    """Without targets: ``(logits [T, B, S, V], gates [T, B, S])``, and the
    fused likelihoods are those logits' own."""
    ref = _reference()
    module, batch, params = _seeded(MODEL)
    tokens, targets = batch["inputs"]
    logits, gates = module.apply({"params": params}, tokens)
    assert logits.shape == (T, B, S, V) and gates.shape == (T, B, S)
    out = module.apply({"params": params}, tokens, targets)
    picked = jnp.take_along_axis(
        jax.nn.log_softmax(logits),
        jnp.broadcast_to(targets, (T, B, S))[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(out[0], -picked, atol=1e-5)
    np.testing.assert_allclose(out[1], gates, atol=1e-6)
    states = ref.states(params, tokens, MODEL)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([s @ params["lm_head"] for s in states])
    np.testing.assert_allclose(logits, want, atol=2e-4)


def test_bfloat16_activations_stay_near_the_reference():
    model = dict(MODEL, activation_dtype="bfloat16", remat=True)
    ref = _reference()
    module, batch, params = _seeded(model)
    out = module.apply({"params": params}, *batch["inputs"])
    want = ref.forward(params, batch["inputs"], model)
    assert out.dtype == jnp.float32
    assert float(jnp.abs(out - want).max()) < 0.05 * float(
        jnp.abs(want).max())


# ------------------------------------------------- the loop ties to the model
def _unshared(copies, tokens, targets, model, ref):
    """One pass over an UNSHARED stack: ``copies[t][i]`` is pass t's own
    copy of layer i, ``copies[t]["final_norm"]`` its own final norm; the
    embedding, head and gate are the model's. The reference's layer
    function, and no loop over shared weights anywhere."""
    p = copies["rest"]
    with jax.default_matmul_precision("highest"):
        x = p["embedding"][tokens]
        nll, gates = [], []
        for t in range(model["total_ut_steps"]):
            for i in range(model["num_hidden_layers"]):
                x = ref.layer(x, copies["passes"][t]["layer_{}".format(i)],
                              model, ref.PLAIN)
            x = ref.rms_norm(x, copies["passes"][t]["final_norm"]["scale"],
                             model["rms_norm_eps"])
            nll.append(ref.exit_nll(x.reshape(B * S, -1), p["lm_head"],
                                    targets.reshape(-1)).reshape(B, S))
            gates.append(x @ p["exit_gate"]["kernel"]
                         + p["exit_gate"]["bias"])
    return jnp.stack([jnp.stack(nll), jnp.stack(gates)])


@pytest.mark.parametrize("remat", [False, True])
def test_passes_over_shared_layers_are_an_unshared_stack_of_copies(remat):
    """T passes over L shared layers = one pass over T x L copies with the
    final norm between, and each shared weight's gradient is the sum of its
    T copies', each application rematerialised on its own or not."""
    model = dict(MODEL, remat=remat)
    family, ref = _family(), _reference()
    module, batch, params = _seeded(model)
    copies = {"passes": [params["stack"] for _ in range(T)],
              "rest": {k: v for k, v in params.items() if k != "stack"}}

    def shared(p):
        out = module.apply({"params": p}, *batch["inputs"])
        return family.loss(out, batch), out

    def unshared(c):
        out = _unshared(c, *batch["inputs"], model, ref)
        return family.loss(out, batch), out

    (loss, out), grad = jax.jit(jax.value_and_grad(shared, has_aux=True))(
        params)
    (u_loss, u_out), u_grad = jax.jit(jax.value_and_grad(
        unshared, has_aux=True))(copies)
    np.testing.assert_allclose(out, u_out, atol=2e-4)
    assert abs(float(loss) - float(u_loss)) < 1e-5 * abs(float(u_loss))
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *u_grad["passes"])
    first = u_grad["passes"][0]["layer_0"]["q_proj"]["kernel"]
    # The sum is not one copy's: every pass adds its own.
    assert float(jnp.abs(summed["layer_0"]["q_proj"]["kernel"]
                         - first).max()) > 0.1 * float(jnp.abs(first).max())
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grad["stack"]),
            jax.tree_util.tree_leaves(summed)):
        np.testing.assert_allclose(
            got, want, atol=2e-4 * float(jnp.abs(want).max()) + 1e-8,
            err_msg=jax.tree_util.keystr(path))


def _loss_and_grad(model):
    family = _family()
    module, batch, params = _seeded(model)

    def loss(p):
        return family.loss(module.apply({"params": p}, *batch["inputs"]),
                           batch)

    return jax.value_and_grad(loss), params


@pytest.mark.parametrize("remat", [False, True])
def test_a_rematerialised_application_makes_two_products_again(remat):
    """The products in the gradient's program. Without remat 169: 7 forward
    and 14 backward an application (and XLA attention's 2 and 4 on a CPU,
    where no flash kernel runs), the heads and the gates. A rematerialised
    application adds 4: ``gate_proj`` and ``up_proj``, the two that
    `REMAT_KEEP` does not name, and XLA attention's two, which no
    ``flash_out`` names here. (Keeping nothing it added 9.)"""
    fn, params = _loss_and_grad(dict(MODEL, remat=remat))
    assert str(jax.make_jaxpr(fn)(params)).count("dot_general") \
        == 169 + (4 * T * L if remat else 0)


@pytest.mark.parametrize("activations, tolerance", [
    ("float32", 2e-4), ("bfloat16", 0.05)])
def test_what_an_application_keeps_changes_no_value(activations, tolerance):
    """Loss and every gradient leaf of the rematerialised model against the
    model that rematerialises nothing: a kept value is the one the backward
    pass would have made again. Not bit for bit on a CPU, where XLA fuses
    the two programs differently and so rounds to bfloat16 in different
    places: the worst leaf reads 4e-7 of its largest entry in float32, and
    0.018 in bfloat16 (0.027 when the application kept the flash kernel's
    two alone), whose limit is the one the bfloat16 forward pass is held
    to against the reference."""
    model = dict(MODEL, activation_dtype=activations)

    def run(remat):
        fn, params = _loss_and_grad(dict(model, remat=remat))
        return jax.jit(fn)(params)

    (want_loss, want), (loss, grad) = run(False), run(True)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    leaves = jax.tree_util.tree_leaves_with_path(grad)
    assert len(leaves) == 4 + 1 + L * 11
    for (path, got), ref in zip(leaves, jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            got, ref, atol=tolerance * float(jnp.abs(ref).max()) + 1e-8,
            err_msg=jax.tree_util.keystr(path))


def test_the_tree_holds_the_layers_once_rematerialised_or_not():
    trees = {}
    for remat in (False, True):
        module, _, params = _seeded(dict(MODEL, remat=remat))
        trees[remat] = params
        assert sorted(params) == ["embedding", "exit_gate", "lm_head",
                                  "stack"]
        assert sorted(params["stack"]) == ["final_norm", "layer_0",
                                           "layer_1"]  # L, never T x L
    for a, b in zip(*(jax.tree_util.tree_leaves(t) for t in trees.values())):
        np.testing.assert_array_equal(a, b)


def test_one_pass_is_the_plain_stack():
    """T = 1 is a decoder with one exit, and it is the looped model's first
    exit: a later pass changes nothing before it."""
    module, batch, params = _seeded(MODEL)
    one, _, _ = _seeded(dict(MODEL, total_ut_steps=1))
    out = module.apply({"params": params}, *batch["inputs"])
    first = one.apply({"params": params}, *batch["inputs"])
    assert first.shape == (2, 1, B, S)
    np.testing.assert_allclose(first[:, 0], out[:, 0], atol=1e-6)
    assert float(jnp.abs(out[0, 1] - out[0, 0]).max()) > 1e-3


@pytest.mark.parametrize("at", [1, 20])
def test_no_pass_lets_a_position_see_its_successor(at):
    module, batch, params = _seeded(MODEL)
    tokens, _ = batch["inputs"]
    other = tokens.at[:, at].set((tokens[:, at] + 1) % V)
    logits, gates = module.apply({"params": params}, tokens)
    moved, moved_gates = module.apply({"params": params}, other)
    np.testing.assert_array_equal(logits[:, :, :at], moved[:, :, :at])
    np.testing.assert_array_equal(gates[:, :, :at], moved_gates[:, :, :at])
    # ... and every exit at and after it does.
    assert all(float(jnp.abs(logits[t, :, at:] - moved[t, :, at:]).max())
               > 1e-3 for t in range(T))


# ------------------------------------------------------ the exit distribution
def test_the_exit_probabilities_sum_to_one():
    gates = 3.0 * jax.random.normal(jax.random.key(0), (4, B, S))
    p, log_p = _family().exit_probabilities(gates)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(jnp.exp(log_p), p, atol=1e-7)
    np.testing.assert_allclose(
        p, _reference().exit_distribution(gates), atol=1e-6)
    lam = jax.nn.sigmoid(gates)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), atol=1e-6)


def test_the_last_exit_takes_what_is_left_whatever_its_gate():
    gates = jax.random.normal(jax.random.key(1), (4, B, S))
    p, _ = _family().exit_probabilities(gates)
    q, _ = _family().exit_probabilities(gates.at[-1].add(5.0))
    np.testing.assert_array_equal(p, q)
    lam = jax.nn.sigmoid(gates)
    np.testing.assert_allclose(
        p[-1], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), atol=1e-6)


def test_the_loss_is_the_expected_likelihood_less_the_entropy():
    family = _family()
    out = jnp.stack([jnp.abs(jax.random.normal(jax.random.key(2), (4, B, S))),
                     jax.random.normal(jax.random.key(3), (4, B, S))])
    batch = jax.tree_util.tree_map(
        jnp.asarray, family.batches(MODEL, B, S, seed=5, n=1)[0])
    p, _ = family.exit_probabilities(out[1])
    w = batch["labels"]["weights"]
    assert float(w[:, -1].max()) == 0.0 and abs(float(w.sum()) - 1.0) < 1e-6
    want = jnp.sum(w * (jnp.sum(p * out[0], 0)
                        + 0.05 * jnp.sum(p * jnp.log(p), 0)))
    assert abs(float(family.loss(out, batch)) - float(want)) < 1e-6
    tokens, targets = batch["inputs"]
    np.testing.assert_array_equal(targets[:, :-1], tokens[:, 1:])


# ----------------------------------------------------------------- the program
def test_the_model_says_its_loop_and_what_an_application_keeps():
    from maggy_tpu.telemetry import plans

    module, batch, params = _seeded(dict(MODEL, remat=True))
    with plans.traced() as said:
        jax.eval_shape(lambda p: module.apply({"params": p},
                                              *batch["inputs"]), params)
    assert said.plans["loop"] == [
        "3 passes x 2 layers heads 4x16 S 32 head chunks vocab 40 "
        "x 3 over 192 rows"]
    assert said.scopes["loop"] == ouro_model.LOOP_SCOPES
    assert said.plans["remat"] == [
        "layer application keeps flash_out flash_lse loop_q loop_k loop_v "
        "loop_o_proj loop_down_proj"]


def test_the_scopes_name_the_compiled_steps_instructions():
    from maggy_tpu.telemetry.hlo_scopes import ops_by_scope

    module, batch, params = _seeded(MODEL)
    family = _family()
    text = jax.jit(jax.grad(lambda p: family.loss(
        module.apply({"params": p}, *batch["inputs"]), batch))).lower(
            params).compile().as_text()
    found = ops_by_scope(text, ouro_model.LOOP_SCOPES)
    assert set(found) == set(ouro_model.LOOP_SCOPES)


def test_it_trains_through_the_trainer():
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.train import Trainer, swept_transform

    family = _family()
    model = dict(MODEL, activation_dtype="bfloat16", remat=True)
    module, _ = family.build(model)
    batches = family.batches(model, B, S, seed=7)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    trainer = Trainer(module, swept_transform(optax.adamw,
                                              learning_rate=3e-3),
                      family.loss, mesh, strategy="dp")
    example, kwargs = family.init_args(batches[0])
    trainer.init(jax.random.key(0), example, init_kwargs=kwargs)
    gate = trainer.variables["params"]["exit_gate"]
    assert float(jnp.abs(gate["kernel"]).max()) == 0.0  # lambda starts 1/2
    losses = [float(trainer.step(trainer.place_batch(batches[i % 4])))
              for i in range(12)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    gate = trainer.variables["params"]["exit_gate"]
    assert float(jnp.abs(gate["kernel"]).max()) > 0.0  # the gate learns


def test_the_configuration_rejects_what_is_not_written_down():
    with pytest.raises(ValueError):
        OuroConfig.tiny(total_ut_steps=0)
    with pytest.raises(ValueError):
        _family().build(dict(MODEL, hidden_act="gelu"))
    assert OuroConfig().layers == 48 and OuroConfig.tiny().layers == 2
    assert isinstance(Ouro(OuroConfig.tiny()), nn.Module)
