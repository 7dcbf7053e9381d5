"""Distributed data-parallel training — the reference's torch-dist example
(`examples/` notebook 2), TPU-native: the train function gets a ShardingEnv
instead of a DDP-wrapped model; GSPMD inserts the gradient all-reduce.

Run: python examples/distributed_training.py           (single process, all chips)
     JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         python examples/distributed_training.py       (8 virtual devices)
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))


import jax
import jax.numpy as jnp
import numpy as np
import optax

from maggy_tpu import DistributedConfig, experiment
from maggy_tpu.models import ResNet
from maggy_tpu.train import ShardedBatchIterator, cross_entropy_loss
from maggy_tpu.train.trainer import init_train_state, make_train_step


def train_fn(sharding_env, reporter=None):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 32, 32, 3)).astype(np.float32)
    y = (X.mean(axis=(1, 2, 3)) > 0).astype(np.int32)

    model = ResNet(depth=18, num_classes=2, width=16)
    tx = optax.sgd(0.05, momentum=0.9)
    variables, opt_state, _ = init_train_state(
        model, tx, jax.random.key(0), (jnp.zeros((1, 32, 32, 3)),),
        sharding_env.mesh, strategy="dp",
        init_kwargs={"train": True},
    )
    step = make_train_step(
        model, tx,
        lambda out, batch: cross_entropy_loss(out, batch["labels"]),
        sharding_env.mesh, has_aux_collections=True,
        train_kwargs={"train": True},
    )
    # Input sharded by this process's rank (patching.py:70-79 semantics),
    # then across local devices via the mesh.
    it = ShardedBatchIterator(
        {"x": X, "y": y}, batch_size=128,
        shard_count=sharding_env.shard_count,
        current_shard=sharding_env.current_shard,
        epochs=4, seed=1, mesh=sharding_env.mesh,
    )
    loss = None
    for i, b in enumerate(it):
        variables, opt_state, loss = step(
            variables, opt_state,
            {"inputs": (b["x"],), "labels": b["y"]})
        if reporter is not None and i % 4 == 0:
            reporter.broadcast(float(loss), step=i)
    return {"metric": float(loss)}


def main():
    config = DistributedConfig(
        name="resnet_dp", num_workers=1,
        mesh_shape={"data": len(jax.devices())},
    )
    result = experiment.lagom(train_fn, config)
    print("Average final loss across workers:", result["average_metric"])


if __name__ == "__main__":
    main()
