"""Family ``vit``: `maggy_tpu.models.ViT` from a configuration file that
carries the keys of the published ``config.json``. The sequence is fixed by
the image and patch sizes, so the mix's ``seq`` is not read."""

from __future__ import annotations

import numpy as np

from benchmark.harness import flops


def build(model: dict):
    import jax.numpy as jnp

    from maggy_tpu.models import ViT, ViTConfig

    if model["hidden_dropout_prob"] or model["attention_probs_dropout_prob"]:
        raise ValueError(
            "Trainer feeds no dropout rng, so a step runs with dropout off; "
            "a configuration that asks for dropout cannot be run as written")
    cfg = ViTConfig(
        image_size=model["image_size"], patch_size=model["patch_size"],
        channels=model["num_channels"], hidden_dim=model["hidden_size"],
        intermediate_dim=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_classes=model["num_labels"], dropout=0.0,
        dtype=jnp.dtype(model["activation_dtype"]),
        param_dtype=jnp.dtype(model["param_dtype"]))
    return ViT(cfg), cfg


def positions(model: dict, seq=None) -> int:
    """Patches plus the class token: 197 for 224 / 16."""
    return (model["image_size"] // model["patch_size"]) ** 2 + 1


def batches(model: dict, batch: int, seq, seed: int, n: int = 4):
    """``n`` seeded host batches of float32 images (NHWC, standard normal,
    as after mean/std normalisation) and labels."""
    rng = np.random.default_rng(seed)
    size, ch = model["image_size"], model["num_channels"]
    out = []
    for _ in range(n):
        images = rng.standard_normal((batch, size, size, ch), dtype=np.float32)
        labels = rng.integers(0, model["num_labels"], size=(batch,))
        out.append({"inputs": (images,), "labels": labels.astype(np.int32)})
    return out


def init_args(batch: dict):
    return batch["inputs"], {}


def loss(logits, batch):
    from maggy_tpu.train import cross_entropy_loss

    return cross_entropy_loss(logits, batch["labels"])


def checked_grads(grads):
    """The part of the gradient tree the reference check compares: the first
    encoder layer's weights, which the gradient reaches last."""
    return grads["layer_0"]


def flops_per_token(model: dict, seq=None) -> dict:
    """Forward + backward FLOPs per position: the encoder over all 197
    positions, the patch projection over the 196 patches, and the head on
    the class token."""
    s = positions(model)
    fwd = flops.encoder_forward_flops(
        tokens=s, seq=s, hidden=model["hidden_size"],
        intermediate=model["intermediate_size"],
        layers=model["num_hidden_layers"])
    patch_in = model["patch_size"] ** 2 * model["num_channels"]
    extra = 2 * (s - 1) * patch_in * model["hidden_size"] \
        + 2 * model["hidden_size"] * model["num_labels"]
    return {"matmul": flops.train_flops(fwd["matmul"] + extra) / s,
            "attention": flops.train_flops(fwd["attention"]) / s}
