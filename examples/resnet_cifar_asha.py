"""ResNet/CIFAR-10 ASHA sweep (BASELINE.md config 3: the reference's
torch-distributed example, TPU-native as a data-parallel JAX sweep).

Budget-scaled training epochs are ASHA's fidelity axis; lr / width /
weight-decay are swept. Depth 18 with small widths by default so the
example runs on CPU CI; on a chip, pass --depth 50 (widths are swept
hyperparameters — widen the DISCRETE choices in `main`) and feed real
CIFAR arrays.

Run: python examples/resnet_cifar_asha.py [--trials 9] [--resource-max 9]
                                          [--depth 50]
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
from maggy_tpu.models import ResNet
from maggy_tpu.optimizers import Asha
from maggy_tpu.parallel import make_mesh
from maggy_tpu.train import (ShardedBatchIterator, Trainer,
                             cross_entropy_loss, swept_transform)

DEPTH = 18  # overridden by --depth
STEPS_PER_BUDGET = 8


def make_cifar_like(n=1024, seed=0):
    """Synthetic CIFAR stand-in (the image ships no datasets; swap in real
    CIFAR-10 arrays if you have them on disk)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    y = (X.mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    return X, y


X_TRAIN, Y_TRAIN = make_cifar_like()


def loss_fn(logits, batch):
    return cross_entropy_loss(logits, batch["labels"])


def train_fn(lr, width, weight_decay, budget=1, reporter=None):
    """One ASHA trial: budget-scaled ResNet training, data-parallel over
    every visible chip (GSPMD all-reduces gradients over ICI)."""
    mesh = make_mesh({"data": len(jax.devices())})
    model = ResNet(depth=DEPTH, num_classes=2, width=int(width))
    # lr/weight_decay ride in opt_state (swept_transform) and the loss is
    # module-level, so trials sharing a width reuse one warm-compiled
    # step; only distinct widths (a PROGRAM hparam) recompile.
    trainer = Trainer(
        model, swept_transform(optax.adamw, learning_rate=lr,
                               weight_decay=weight_decay),
        loss_fn, mesh, strategy="dp", has_aux_collections=True,
        train_kwargs={"train": True},
    )
    trainer.init(jax.random.key(0), (jnp.zeros((1, 32, 32, 3)),),
                 init_kwargs={"train": True})
    it = iter(ShardedBatchIterator({"x": X_TRAIN, "y": Y_TRAIN},
                                   batch_size=128, epochs=None, seed=1))
    loss = None
    for step in range(int(STEPS_PER_BUDGET * budget)):
        b = next(it)
        loss = trainer.step(trainer.place_batch(
            {"inputs": (jnp.asarray(b["x"]),), "labels": jnp.asarray(b["y"])}))
        if reporter is not None and step % 2 == 0:
            reporter.broadcast(-loss, step=step)  # lazy device scalar
    return {"metric": -float(loss), "final_loss": float(loss)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=9)
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--resource-max", type=float, default=9,
                    help="ASHA top-rung budget (1 = single rung for smoke)")
    ap.add_argument("--depth", type=int, default=18, choices=[18, 50],
                    help="ResNet depth (50 for the full baseline config)")
    args = ap.parse_args()
    global DEPTH
    DEPTH = args.depth

    sp = Searchspace(
        lr=("DOUBLE", [1e-4, 1e-2]),
        width=("DISCRETE", [8, 16, 32]),
        weight_decay=("DOUBLE", [1e-5, 1e-3]),
    )
    config = OptimizationConfig(
        name="resnet_cifar_asha", num_trials=args.trials,
        optimizer=Asha(reduction_factor=3, resource_min=1,
                       resource_max=args.resource_max, seed=0),
        searchspace=sp, direction="max", num_workers=args.workers,
        es_policy="median", es_min=3, seed=0,
    )
    result = experiment.lagom(train_fn, config)
    print("Best:", result["best_val"], "with", result["best_hp"])


if __name__ == "__main__":
    main()
