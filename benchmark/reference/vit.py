"""Plain float32 reference of the ``vit`` family: forward, loss, gradient.

`jax.numpy` only; nothing imported from ``maggy_tpu``. The encoder is the
same pre-LN layer as in the ``bert`` reference, which ViT (Dosovitskiy et
al. 2020) publishes as pre-LN, so only two departures remain, both the
program's and mirrored here: GELU in its tanh approximation (published: erf)
and layer-norm epsilon 1e-6 (published ``config.json``: 1e-12). The patch
embedding is written as the matmul it is: each 16x16x3 patch, flattened in
(row, column, channel) order, times the [768-row] projection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.bert import (dense, encoder,  # noqa: F401
                                      loss_from_logits)


def forward(params, inputs, model: dict):
    """Logits [B, num_labels] in float32. ``inputs`` = (images NHWC,)."""
    images, = inputs
    with jax.default_matmul_precision("highest"):
        B, size, _, ch = images.shape
        p = model["patch_size"]
        n = size // p
        patches = images.astype(jnp.float32).reshape(B, n, p, n, p, ch)
        patches = patches.transpose(0, 1, 3, 2, 4, 5).reshape(
            B, n * n, p * p * ch)
        embed = params["patch_embed"]
        x = patches @ embed["kernel"].reshape(p * p * ch, -1) + embed["bias"]
        cls = jnp.broadcast_to(params["cls_token"], (B, 1, x.shape[-1]))
        x = jnp.concatenate([cls, x], axis=1) + params["pos_embedding"][None]
        keep = jnp.ones(x.shape[:2], bool)
        x = encoder(x, keep, params, model["num_hidden_layers"],
                    model["num_attention_heads"])
        return dense(x[:, 0], params["head"])


