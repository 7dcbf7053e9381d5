"""Family ``bert``: `maggy_tpu.models.BertEncoder` from a configuration file
that carries the keys of the published ``config.json``.

A family gives the harness five things and nothing else: the flax module,
the example inputs that `Trainer.init` traces, seeded host batches, the
loss, and the FLOPs a token needs.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import flops


def build(model: dict):
    """(module, model config) from the configuration's ``model`` keys."""
    import jax.numpy as jnp

    from maggy_tpu.models import BertConfig, BertEncoder

    if model["hidden_dropout_prob"] or model["attention_probs_dropout_prob"]:
        raise ValueError(
            "Trainer feeds no dropout rng, so a step runs with dropout off; "
            "a configuration that asks for dropout cannot be run as written")
    if model["type_vocab_size"]:
        raise ValueError("models/bert.py has no token-type embedding")
    cfg = BertConfig(
        vocab_size=model["vocab_size"], hidden_dim=model["hidden_size"],
        intermediate_dim=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        max_seq_len=model["max_position_embeddings"],
        num_classes=model["num_labels"], dropout=0.0,
        dtype=jnp.dtype(model["activation_dtype"]),
        param_dtype=jnp.dtype(model["param_dtype"]))
    return BertEncoder(cfg), cfg


def positions(model: dict, seq) -> int:
    """Positions (tokens) in one example at the mix's sequence length."""
    return int(seq)


def batches(model: dict, batch: int, seq, seed: int, n: int = 4):
    """``n`` seeded host batches: tokens, a ragged key-padding mask (each
    row keeps between half and all of its positions) and binary labels.
    Copied from ``chip_smoke.synthetic_batches``, with the seed an argument."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        tokens = rng.integers(0, model["vocab_size"], size=(batch, seq))
        lengths = rng.integers(seq // 2, seq + 1, size=(batch, 1))
        out.append({
            "inputs": (tokens.astype(np.int32),
                       np.arange(seq)[None, :] < lengths),
            "labels": (tokens[:, 0] % 2).astype(np.int32),
        })
    return out


def init_args(batch: dict):
    """(example_inputs, init_kwargs) for `Trainer.init`."""
    tokens, mask = batch["inputs"]
    return (tokens,), {"attention_mask": mask}


def loss(logits, batch):
    from maggy_tpu.train import cross_entropy_loss

    return cross_entropy_loss(logits, batch["labels"])


def checked_grads(grads):
    """The part of the gradient tree the reference check compares: the first
    encoder layer's weights, which the gradient reaches last."""
    return grads["layer_0"]


def flops_per_token(model: dict, seq) -> dict:
    """Forward + backward FLOPs one position needs: the encoder's matmuls
    and attention, and the pooler and classifier on the first position of
    each sequence. The two embedding tables are look-ups and count nothing."""
    fwd = flops.encoder_forward_flops(
        tokens=seq, seq=seq, hidden=model["hidden_size"],
        intermediate=model["intermediate_size"],
        layers=model["num_hidden_layers"])
    head = 2 * model["hidden_size"] * (model["hidden_size"]
                                      + model["num_labels"])
    return {"matmul": flops.train_flops(fwd["matmul"] + head) / seq,
            "attention": flops.train_flops(fwd["attention"]) / seq}
