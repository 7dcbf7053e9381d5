"""What `models.sdar.SdarMoe`'s rematerialised layers keep (PR 27): bits and
the ``compiled`` record, at toy size on the CPU. Off the TPU attention is
XLA's and the expert layer's grouped products run in interpret mode, so the
routing's name is the one that is kept here; the flash names are counted in
``test_block_diffusion_flash.py`` and, through XLA:TPU, in
``test_flash_compile.py``. The ``compiled`` record's ``remat_plan`` is read
here for `models.ouro.Ouro` too. A file of its own so that the test workers
share the model tests' time."""

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

#: All that the family's batches ask of the model's keys.
MODEL = {"mask_token_id": 63}

REMAT_CASES = {"held_share": {}, "all_experts": {"experts_held": None,
                                                 "first_expert": 0}}


# One program in float32, and bfloat16 activations primitive by primitive:
# XLA:CPU fuses a bfloat16 program's rematerialised operations otherwise
# than the first ones and keeps float32 inside a fusion, so there a jitted
# bfloat16 pair differs in the last bits whatever the layers keep.
REMAT_MODES = {"float32_jit": (jnp.float32, jax.jit),
               "bfloat16_eager": (jnp.bfloat16, lambda f: f)}


def _loss_and_grads(case, mode, remat):
    from maggy_tpu.models import SdarMoe, SdarMoeConfig

    dtype, wrap = REMAT_MODES[mode]
    family = spec.load_module("families", "sdar_moe")
    batch = jax.tree_util.tree_map(
        jnp.asarray, family.batches(MODEL, 2, 32, seed=7, n=1)[0])
    module = SdarMoe(SdarMoeConfig.tiny(remat=remat, dtype=dtype,
                                        **REMAT_CASES[case]))
    params = nn.meta.unbox(module.init(jax.random.key(3), *batch["inputs"]))[
        "params"]
    return wrap(jax.value_and_grad(lambda p: family.loss(
        module.apply({"params": p}, *batch["inputs"]), batch)))(params)


@pytest.mark.parametrize("case,mode", [
    ("held_share", "float32_jit"), ("all_experts", "float32_jit"),
    ("held_share", "bfloat16_eager")])  # the cell's case in both
def test_what_the_rematerialised_layers_keep_changes_no_bit(case, mode):
    """``remat=True`` keeps `models.sdar.REMAT_KEEP` of each layer and makes
    the rest again: the loss and every gradient leaf are bitwise those of
    the model that rematerialises nothing."""
    (loss, grads), (plain_loss, plain_grads) = (
        _loss_and_grads(case, mode, remat) for remat in (True, False))
    assert float(loss) == float(plain_loss) and np.isfinite(float(loss))
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == 3 + 2 * 12
    for (path, got), want in zip(leaves,
                                 jax.tree_util.tree_leaves(plain_grads)):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=jax.tree_util.keystr(path))
    routers = [g for p, g in leaves if "router" in jax.tree_util.keystr(p)]
    assert all(bool(jnp.any(g != 0)) == (case == "all_experts")
               for g in routers)


#: family: (its module and config in `maggy_tpu.models`, what its batches
#: ask of the model's keys, the plan beside ``remat_plan`` and how it
#: starts, what a rematerialised trial's record says).
RECORD_CASES = {
    "sdar_moe": ("SdarMoe", "SdarMoeConfig", MODEL,
                 "moe_plan", "experts 2+4/8 top2",
                 "layer keeps flash_out flash_lse moe_route"),
    "ouro": ("Ouro", "OuroConfig",
             {"vocab_size": 96, "exit_entropy_beta": 0.05},
             "loop_plan", "3 passes x 2 layers",
             "layer application keeps flash_out flash_lse loop_q loop_k "
             "loop_v loop_o_proj loop_down_proj"),
}


@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("family_name", sorted(RECORD_CASES))
def test_the_compiled_record_says_what_the_layers_keep(family_name, remat):
    """A trial that traces a rematerialised model notes ``remat_plan``
    beside its family's own plan; one whose model rematerialises nothing
    has none."""
    import optax

    from maggy_tpu import models
    from maggy_tpu.parallel import make_mesh
    from maggy_tpu.telemetry.runnerstats import RunnerStats, span
    from maggy_tpu.train import Trainer, clear_warm, swept_transform, warm

    module, config, model, plan, starts, keeps = RECORD_CASES[family_name]
    module = getattr(models, module)(
        getattr(models, config).tiny(remat=remat))
    family = spec.load_module("families", family_name)
    batch = family.batches(model, 2, 32, seed=7, n=1)[0]
    stats = RunnerStats()
    stats.trial_start("t1")
    clear_warm()
    with warm.trial_scope(trial_id="t1", stats=stats), \
            span("trial", stats=stats, trial_id="t1"):
        trainer = Trainer(
            module, swept_transform(optax.adamw, learning_rate=1e-3),
            family.loss, make_mesh({"data": 1}, devices=jax.devices()[:1]))
        trainer.init(jax.random.key(0), batch["inputs"])
        assert np.isfinite(float(trainer.step(trainer.place_batch(batch))))
    stats.trial_end("t1")
    clear_warm()
    (compiled,) = stats.snapshot_delta()["compile_events"]
    assert compiled[plan].startswith(starts)
    if remat:
        assert compiled["remat_plan"] == keeps
    else:
        assert "remat_plan" not in compiled
