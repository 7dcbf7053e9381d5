"""The reference check at the tiny presets: it passes on the program's
model, it fails on a wrong one, and its programs hold nothing of the seed."""

import json
import os

import pytest

from benchmark.harness import checks, spec
from benchmark.run import merged

CONFIGS = [("bert-base", 32), ("vit-base-16", None)]


def tiny(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", name + ".json")) as f:
        config = json.load(f)
    return merged(config, config.pop("rehearse", {}))


@pytest.mark.parametrize("name,seq", CONFIGS)
def test_the_model_agrees_with_its_reference(name, seq):
    got = checks.model_vs_reference(tiny(name), seq, seed=3)
    assert got["ok"], got
    assert 0 < got["errors"]["grad"] <= got["tolerances"]["grad"]


def test_a_wrong_model_is_an_error_of_order_one(monkeypatch):
    config = tiny("bert-base")
    ref = spec.load_module("reference", config["family"])
    forward = ref.forward
    monkeypatch.setattr(  # a reference that forgets the padding mask
        ref, "forward", lambda p, inputs, model: forward(
            p, (inputs[0], inputs[1] | True), model))
    got = checks.model_vs_reference(config, 32, seed=3)
    assert not got["ok"] and got["errors"]["grad"] > 0.1, got


@pytest.mark.parametrize("name,seq", CONFIGS)
def test_the_checks_programs_hold_nothing_of_the_seed(name, seq):
    """Every run has another seed; the persistent cache serves the check's
    programs only if the seeded values are arguments, never constants."""
    import jax
    import jax.numpy as jnp

    config = tiny(name)
    family = spec.load_module("families", config["family"])
    fns = checks.programs(config)
    texts = []
    for seed in (1, 2):
        batch = jax.tree_util.tree_map(jnp.asarray, family.batches(
            config["model"], 2, seq, seed, n=1)[0])
        params = jax.jit(fns["init"])(jax.random.key(seed), batch)
        texts.append([
            jax.jit(fns["init"]).lower(jax.random.key(seed), batch).as_text(),
            jax.jit(jax.value_and_grad(fns["model"], has_aux=True)).lower(
                params, batch).as_text(),
            jax.jit(jax.value_and_grad(fns["reference"], has_aux=True)).lower(
                params, batch).as_text()])
    assert texts[0] == texts[1]
