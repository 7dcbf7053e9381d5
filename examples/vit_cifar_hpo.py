"""ViT/CIFAR-10 TPE sweep: the Vision Transformer over the same tabular
HPO machinery as the ResNet example, with a Bayesian (TPE) optimizer.

lr / width / patch size are swept (all three actually change the trained
model — Trainer runs eval-mode apply, so a dropout hparam would be inert);
tiny dims by default so the example runs on CPU CI. On a chip, use
ViTConfig.base() and real CIFAR arrays.

Run: python examples/vit_cifar_hpo.py [--trials 8]
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
from maggy_tpu.models import ViT, ViTConfig
from maggy_tpu.parallel import make_mesh
from maggy_tpu.train import Trainer, cross_entropy_loss

STEPS = 12


def make_cifar_like(n=512, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    y = (X.mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    return X, y


X_TRAIN, Y_TRAIN = make_cifar_like()


def train_fn(lr, width, patch, reporter=None):
    # Every swept hparam here (width/patch — and lr via a fresh adamw)
    # changes the compiled program, so this sweep recompiles per config by
    # design; see docs/user.md "Compile-once sweeps" for the swept_transform
    # idiom when only optimizer hparams vary.
    cfg = ViTConfig(image_size=32, patch_size=int(patch), channels=3,
                    hidden_dim=int(width), intermediate_dim=2 * int(width),
                    num_layers=2, num_heads=2, num_classes=2)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        ViT(cfg), optax.adamw(float(lr)),
        lambda logits, batch: cross_entropy_loss(logits, batch["labels"]),
        mesh, strategy="dp")
    x, y = jnp.asarray(X_TRAIN), jnp.asarray(Y_TRAIN)
    trainer.init(jax.random.key(0), (x[:1],))
    batch = trainer.place_batch({"inputs": (x,), "labels": y})
    loss = None
    for i in range(STEPS):
        loss = trainer.step(batch)
        if reporter is not None and i % 4 == 0:
            reporter.broadcast(-loss, step=i)
    return {"metric": -float(loss)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--trials", type=int, default=8)
    args = p.parse_args()
    sp = Searchspace(lr=("DOUBLE_LOG", [1e-4, 1e-2]),
                     width=("DISCRETE", [32, 48]),
                     patch=("DISCRETE", [4, 8]))
    config = OptimizationConfig(
        name="vit_cifar_tpe", num_trials=args.trials, optimizer="tpe",
        searchspace=sp, direction="max", num_workers=2, seed=0,
        es_policy="none")
    result = experiment.lagom(train_fn, config)
    print("Best:", result["best_hp"], "->", result["best_val"])


if __name__ == "__main__":
    main()
