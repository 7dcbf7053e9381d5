"""Vision Transformer (ViT) classifier.

Extends the model zoo beyond the reference's CNN/torch examples with the
TPU-sweet architecture (patch embedding is one big conv that lowers to an
MXU matmul; everything else is the shared transformer encoder). Reuses
`maggy_tpu.models.bert.EncoderLayer` — pre-LN, logical partitioning — so
ViT shards under the same dp/fsdp/tp rule table as the language models.

Attention dispatch caveat: the Pallas flash kernel needs the sequence to
tile by 128, and a standard ViT's patch sequence doesn't (base/16 at 224px
is 196 patches + CLS = 197), so attention runs on the XLA reference path.
That is the right trade at these lengths — a 197x197 score matrix is tiny —
and XLA fuses it fine; pick image/patch sizes with num_patches+1 divisible
by 128 if you want the kernel.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from maggy_tpu.models.bert import BertConfig, EncoderLayer, _dense
from maggy_tpu.models.llama import EMBED


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    hidden_dim: int = 768
    intermediate_dim: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_classes: int = 1000
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    def encoder_cfg(self) -> BertConfig:
        """The shared EncoderLayer consumes a BertConfig; only the fields
        the layer reads matter (vocab/seq fields are unused there)."""
        return BertConfig(
            hidden_dim=self.hidden_dim,
            intermediate_dim=self.intermediate_dim,
            num_heads=self.num_heads, dropout=self.dropout,
            dtype=self.dtype, param_dtype=self.param_dtype)

    @staticmethod
    def tiny(num_classes: int = 10) -> "ViTConfig":
        return ViTConfig(image_size=32, patch_size=8, channels=3,
                         hidden_dim=32, intermediate_dim=64, num_layers=2,
                         num_heads=2, num_classes=num_classes)

    @staticmethod
    def base(num_classes: int = 1000) -> "ViTConfig":
        return ViTConfig(num_classes=num_classes)


class ViT(nn.Module):
    cfg: ViTConfig

    @nn.compact
    def __call__(self, images, train: bool = False):
        """images: [B, H, W, C] -> logits [B, num_classes]."""
        cfg = self.cfg
        B = images.shape[0]
        p = cfg.patch_size
        if images.shape[1] != cfg.image_size or images.shape[2] != cfg.image_size:
            raise ValueError(
                "Expected {0}x{0} images, got {1}x{2}".format(
                    cfg.image_size, images.shape[1], images.shape[2]))
        # Patch embedding: a stride-p conv == one [p*p*C, D] matmul per
        # patch; XLA lowers it straight onto the MXU.
        with jax.named_scope("embed"):
            x = nn.Conv(
                cfg.hidden_dim, kernel_size=(p, p), strides=(p, p),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="patch_embed",
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), (None, None, None, EMBED)),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (EMBED,)),
            )(images.astype(cfg.dtype))
            x = x.reshape(B, cfg.num_patches, cfg.hidden_dim)
            cls = self.param(
                "cls_token", nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (None, None, EMBED)),
                (1, 1, cfg.hidden_dim), cfg.param_dtype)
            x = jnp.concatenate(
                [jnp.broadcast_to(cls.astype(cfg.dtype),
                                  (B, 1, cfg.hidden_dim)), x], axis=1)
            pos = self.param(
                "pos_embedding", nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), (None, EMBED)),
                (cfg.num_patches + 1, cfg.hidden_dim), cfg.param_dtype)
            x = x + pos[None].astype(cfg.dtype)
        enc = self.cfg.encoder_cfg()
        mask = jnp.ones((B, cfg.num_patches + 1), bool)
        for i in range(cfg.num_layers):
            x = EncoderLayer(enc, name="layer_{}".format(i))(
                x, mask, train=train)
        with jax.named_scope("head"):
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
            return _dense(cfg.num_classes, (EMBED, None), enc, "head")(
                x[:, 0].astype(cfg.dtype)).astype(jnp.float32)
