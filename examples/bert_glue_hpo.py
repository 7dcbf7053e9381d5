"""BERT GLUE-style fine-tune HPO (BASELINE.md config 4): TPE search over
(lr, warmup, batch) with the tiny config; swap `BertConfig.base()` + a real
GLUE task on a 4-chip slice.

Run: python examples/bert_glue_hpo.py [--trials 8]
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
from maggy_tpu.models import BertConfig, BertEncoder
from maggy_tpu.parallel import make_mesh
from maggy_tpu.train import Trainer, cross_entropy_loss

VOCAB = 128


def make_sst_like(n=512, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(2, VOCAB, size=(n, seq)).astype(np.int32)
    # sentiment = whether "positive tokens" (upper half) dominate
    y = (tokens > VOCAB // 2).mean(axis=1) > 0.5
    return tokens, y.astype(np.int32)


TOKENS, LABELS = make_sst_like()


def train_fn(lr, warmup_frac, batch, reporter=None):
    n_dev = len(jax.devices())
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    cfg = BertConfig.tiny(num_classes=2)
    model = BertEncoder(cfg)
    total_steps = 40
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, int(total_steps * warmup_frac), total_steps)
    trainer = Trainer(
        model, optax.adamw(sched),
        lambda logits, b: cross_entropy_loss(logits, b["labels"]),
        mesh,
    )
    trainer.init(jax.random.key(0), (jnp.ones((1, 32), jnp.int32),))
    loss = None
    for i in range(total_steps):
        lo = (i * batch) % (len(TOKENS) - batch)
        tb = jnp.asarray(TOKENS[lo:lo + batch])
        yb = jnp.asarray(LABELS[lo:lo + batch])
        loss = trainer.step(trainer.place_batch(
            {"inputs": (tb,), "labels": yb}))
        if reporter is not None and i % 10 == 0:
            reporter.broadcast(-float(loss), step=i)
    preds = jnp.argmax(model.apply(trainer.variables,
                                   jnp.asarray(TOKENS[:256])), -1)
    acc = float(jnp.mean(preds == jnp.asarray(LABELS[:256])))
    return {"metric": acc, "final_loss": float(loss)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=8)
    args = ap.parse_args()

    sp = Searchspace(
        lr=("DOUBLE", [1e-5, 1e-3]),
        warmup_frac=("DOUBLE", [0.0, 0.3]),
        batch=("DISCRETE", [32, 64]),
    )
    config = OptimizationConfig(
        name="bert_glue_hpo", num_trials=args.trials, optimizer="tpe",
        searchspace=sp, direction="max", num_workers=2,
        es_policy="median", es_min=3, seed=0,
    )
    result = experiment.lagom(train_fn, config)
    print("Best accuracy:", result["best_val"], "with", result["best_hp"])


if __name__ == "__main__":
    main()
