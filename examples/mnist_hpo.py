"""MNIST CNN random-search HPO — the reference's README example
(`README.rst:56-84`), TPU-native.

Run: python examples/mnist_hpo.py [--trials 8] [--workers 4]
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))


import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from maggy_tpu import OptimizationConfig, Searchspace, experiment
from maggy_tpu.models import MnistCNN
from maggy_tpu.parallel import make_mesh
from maggy_tpu.train import (ShardedBatchIterator, Trainer,
                             cross_entropy_loss, swept_transform)


def make_mnist_like(n=4096, seed=0):
    """Synthetic MNIST stand-in (the image ships no datasets; swap in real
    MNIST arrays if you have them on disk)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    y = ((X[:, :14].mean(axis=(1, 2, 3)) > X[:, 14:].mean(axis=(1, 2, 3)))
         .astype(np.int32))
    return X, y


X_TRAIN, Y_TRAIN = make_mnist_like()


def loss_fn(logits, batch):
    """Module-level (not a per-trial lambda) so the warm cache's automatic
    program key matches across trials — see docs/user.md "Compile-once
    sweeps"."""
    return cross_entropy_loss(logits, batch["labels"])


def train_fn(kernel, pool, dropout, lr, reporter=None):
    """One trial: train the CNN, heartbeat val accuracy, return final acc.

    Compile-once: lr rides in opt_state (swept_transform), so trials that
    share (kernel, pool, dropout) — the hparams that change the PROGRAM —
    reuse the runner's warm-compiled step; only distinct model configs
    recompile (bounded by the warm cache's LRU)."""
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    model = MnistCNN(kernel_size=kernel, pool_size=pool, dropout=dropout,
                     num_classes=2)
    trainer = Trainer(
        model, swept_transform(optax.adam, learning_rate=lr),
        loss_fn, mesh,
    )
    trainer.init(jax.random.key(0), (jnp.zeros((1, 28, 28, 1)),))
    it = ShardedBatchIterator({"x": X_TRAIN, "y": Y_TRAIN}, batch_size=256,
                              epochs=2, seed=1)
    acc = 0.0
    for step, b in enumerate(it):
        loss = trainer.step(trainer.place_batch(
            {"inputs": (jnp.asarray(b["x"]),), "labels": jnp.asarray(b["y"])}))
        if reporter is not None and step % 5 == 0:
            reporter.broadcast(-float(loss), step=step)
    return {"metric": -float(loss), "final_loss": float(loss)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=8)
    ap.add_argument("--workers", type=int, default=4)
    args = ap.parse_args()

    sp = Searchspace(
        kernel=("DISCRETE", [3, 5]),
        pool=("DISCRETE", [2, 3]),
        dropout=("DOUBLE", [0.0, 0.5]),
        lr=("DOUBLE", [1e-4, 1e-2]),
    )
    config = OptimizationConfig(
        name="mnist_hpo", num_trials=args.trials, optimizer="randomsearch",
        searchspace=sp, direction="max", num_workers=args.workers,
        es_policy="median", es_min=3, seed=0,
    )
    result = experiment.lagom(train_fn, config)
    print("Best:", result["best_val"], "with", result["best_hp"])


if __name__ == "__main__":
    main()
