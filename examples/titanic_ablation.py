"""Titanic-style tabular LOCO ablation study — the reference's ablation
example notebook, TPU-native with declarative specs.

The dataset rides in a ``.tfrecord`` file consumed through the study's
``train_set`` path — the same feature-store format + built-in
feature-dropping pipeline the reference's LOCO used
(reference ``loco.py:41-80``), with no TensorFlow import.

Run: python examples/titanic_ablation.py
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))


import jax
import jax.numpy as jnp
import numpy as np
import optax

from maggy_tpu import AblationConfig, experiment
from maggy_tpu.ablation import AblationStudy
from maggy_tpu.models.surgery import ablatable_model_generator

FEATURES = ["pclass", "sex", "age", "fare", "embarked"]


def make_titanic_like(n=2048, seed=0):
    rng = np.random.default_rng(seed)
    X = {f: rng.normal(size=n).astype(np.float32) for f in FEATURES}
    logits = 1.5 * X["sex"] - 0.8 * X["pclass"] + 0.3 * X["fare"]
    y = (logits + 0.5 * rng.normal(size=n) > 0).astype(np.int32)
    return X, y


def write_dataset_tfrecord(path):
    """Persist the dataset as tf.train.Example records (one per row)."""
    from maggy_tpu.train.tfrecord import write_tfrecord

    X, y = make_titanic_like()
    write_tfrecord(path, (
        {**{f: float(X[f][i]) for f in FEATURES}, "survived": int(y[i])}
        for i in range(len(y))))


def model_layers():
    import flax.linen as nn

    return (
        ("input_dense", lambda: nn.Dense(32)),
        ("hidden_1", lambda: nn.Sequential([nn.Dense(32), nn.relu])),
        ("hidden_2", lambda: nn.Sequential([nn.Dense(32), nn.relu])),
        ("head", lambda: nn.Dense(2)),
    )


def model_generator(ablated_layers=frozenset()):
    return ablatable_model_generator(model_layers(), ablated_layers)


def train_fn(dataset_function, model_function, ablated_feature, ablated_layer,
             reporter=None):
    # dataset_function() is the built-in feature dropper over the study's
    # train_set tfrecord: a dict of per-feature arrays (minus the ablated
    # one) plus the label column.
    data = dataset_function()
    model = model_function()
    y = np.asarray(data.pop("survived"), dtype=np.int32)
    cols = sorted(data)
    X = jnp.asarray(np.stack([data[c] for c in cols], axis=1))
    y = jnp.asarray(y)
    params = model.init(jax.random.key(0), X[:1])
    tx = optax.adam(1e-2)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt):
        def loss_fn(p):
            logits = model.apply(p, X)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(logp[jnp.arange(len(y)), y])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt)
        return optax.apply_updates(params, updates), opt, loss

    for i in range(60):
        params, opt, loss = step(params, opt)
        if reporter is not None and i % 20 == 0:
            reporter.broadcast(-float(loss), step=i)
    acc = float(jnp.mean(jnp.argmax(model.apply(params, X), -1) == y))
    return {"metric": acc, "loss": float(loss),
            "ablated_feature": str(ablated_feature),
            "ablated_layer": str(ablated_layer)}


def main():
    import tempfile

    data_path = _os.path.join(tempfile.mkdtemp(prefix="titanic_"),
                              "titanic.tfrecord")
    write_dataset_tfrecord(data_path)
    # Publish the dataset under a name@version in the dataset registry —
    # the featurestore workflow: the study then addresses it by name only
    # (the reference resolved training_dataset_name/version through
    # Hopsworks, `loco.py:41-80`).
    from maggy_tpu.train import DatasetRegistry

    version = DatasetRegistry().register(
        "titanic", data_path, description="synthetic titanic-like tabular")
    study = AblationStudy("titanic", version, "survived")
    study.features.include(*FEATURES)
    study.model.set_base_model_generator(model_generator)
    study.model.layers.include("hidden_1", "hidden_2")
    study.model.layers.include_groups(prefix="hidden")

    config = AblationConfig(name="titanic_loco", ablation_study=study,
                            ablator="loco", direction="max", num_workers=3)
    result = experiment.lagom(train_fn, config)
    print("Trials:", result["num_trials"])
    print("Best (least-harmful ablation):", result["best_hp"],
          "->", result["best_val"])
    print("Worst (most important component):", result["worst_hp"],
          "->", result["worst_val"])


if __name__ == "__main__":
    main()
