"""Llama LoRA hyperparameter sweep (BASELINE.md config 5) — ASHA over
(lora_rank, lora_alpha, lr) with per-trial FSDP sharding.

Uses the tiny config by default so it runs anywhere; switch to
`LlamaConfig.llama3_8b(...)` on a v4-32 with a real corpus.

Run: python examples/llama_lora_sweep.py [--trials 9]
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
from maggy_tpu.models import Llama, LlamaConfig
from maggy_tpu.ops.losses import chunked_next_token_loss
from maggy_tpu.optimizers import Asha
from maggy_tpu.parallel import make_mesh
from maggy_tpu.train import Trainer
from maggy_tpu.train.lora import only_lora

VOCAB = 256


def make_corpus(n=256, seq=64, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, VOCAB, size=(n, seq)).astype(np.int32)


CORPUS = make_corpus()


def train_fn(lora_rank, lora_alpha, lr, budget=1, reporter=None):
    n_dev = len(jax.devices())
    axes = {"fsdp": n_dev} if n_dev > 1 else {"data": 1}
    mesh = make_mesh(axes)
    cfg = LlamaConfig.tiny(vocab_size=VOCAB, lora_rank=int(lora_rank))
    cfg = LlamaConfig(**{**cfg.__dict__, "lora_alpha": float(lora_alpha)})
    model = Llama(cfg)
    # The flagship recipe: the 8B base stays FROZEN (only_lora masks the
    # optimizer to the adapters — no moments for 8B of weights) and the
    # loss is computed vocab-chunked from pre-head activations, never
    # materializing the [B, S, 128k] logits (ops/losses.py).
    trainer = Trainer(
        model, only_lora(optax.adamw(lr)),
        lambda out, batch: chunked_next_token_loss(
            out[0], out[1], batch["tokens"], vocab_chunk=128),
        mesh, strategy="fsdp" if n_dev > 1 else "dp",
        train_kwargs={"return_hidden": True},
    )
    trainer.init(jax.random.key(0), (jnp.ones((1, 16), jnp.int32),))
    steps = int(20 * budget)
    loss = None
    for i in range(steps):
        batch_tokens = jnp.asarray(CORPUS[(i * 16) % 240:(i * 16) % 240 + 16])
        loss = trainer.step(trainer.place_batch(
            {"inputs": (batch_tokens,), "tokens": batch_tokens}))
        if reporter is not None and i % 5 == 0:
            reporter.broadcast(-float(loss), step=i)
    return {"metric": -float(loss), "final_loss": float(loss)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=9)
    ap.add_argument("--resource-max", type=float, default=9,
                    help="ASHA top-rung budget (1 = single rung, e.g. for "
                         "smoke runs with few trials)")
    args = ap.parse_args()

    sp = Searchspace(
        lora_rank=("DISCRETE", [4, 8, 16]),
        lora_alpha=("DOUBLE", [4.0, 32.0]),
        lr=("DOUBLE", [1e-4, 3e-3]),
    )
    config = OptimizationConfig(
        name="llama_lora_sweep", num_trials=args.trials,
        optimizer=Asha(reduction_factor=3, resource_min=1,
                       resource_max=args.resource_max, seed=0),
        searchspace=sp, direction="max", num_workers=3, es_policy="none",
        seed=0,
    )
    result = experiment.lagom(train_fn, config)
    print("Best:", result["best_val"], "with", result["best_hp"])


if __name__ == "__main__":
    main()
