"""BERT-style encoder (BASELINE.md config 4: "BERT-base GLUE fine-tune HPO").

Green-field Flax implementation: pre-LN transformer encoder with learned
positional embeddings and a pooled classification head, bfloat16 activations,
logically-partitioned weights (same rule table as the Llama model) so it
shards on a 4-chip "model" axis per the baseline config.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from maggy_tpu.models.llama import EMBED, HEADS, MLP, VOCAB
from maggy_tpu.ops.attention import multi_head_attention


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_dim: int = 768
    intermediate_dim: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 512
    num_classes: int = 2
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def tiny(num_classes: int = 2) -> "BertConfig":
        return BertConfig(vocab_size=128, hidden_dim=32, intermediate_dim=64,
                          num_layers=2, num_heads=2, max_seq_len=64,
                          num_classes=num_classes, dropout=0.0)

    @staticmethod
    def base(num_classes: int = 2) -> "BertConfig":
        return BertConfig(num_classes=num_classes)


def _dense(features, axes, cfg, name):
    return nn.Dense(
        features, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(0.02), axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (axes[1],)),
    )


class EncoderLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, pad_mask, train: bool = False):
        cfg = self.cfg
        B, S, _ = x.shape
        head_dim = cfg.hidden_dim // cfg.num_heads
        with jax.named_scope("attn"):
            h = nn.LayerNorm(dtype=jnp.float32, name="ln_attn")(x).astype(
                cfg.dtype)
            q = _dense(cfg.hidden_dim, (EMBED, HEADS), cfg, "q_proj")(h)
            k = _dense(cfg.hidden_dim, (EMBED, HEADS), cfg, "k_proj")(h)
            v = _dense(cfg.hidden_dim, (EMBED, HEADS), cfg, "v_proj")(h)
            shape4 = (B, S, cfg.num_heads, head_dim)
            att = multi_head_attention(
                q.reshape(shape4), k.reshape(shape4), v.reshape(shape4),
                causal=False, mask=pad_mask[:, None, None, :])
            att = att.reshape(B, S, cfg.hidden_dim)
            att = _dense(cfg.hidden_dim, (HEADS, EMBED), cfg, "o_proj")(att)
            if cfg.dropout > 0:
                att = nn.Dropout(cfg.dropout, deterministic=not train)(att)
            x = x + att
        with jax.named_scope("mlp"):
            h = nn.LayerNorm(dtype=jnp.float32, name="ln_mlp")(x).astype(
                cfg.dtype)
            h = _dense(cfg.intermediate_dim, (EMBED, MLP), cfg, "fc_in")(h)
            h = nn.gelu(h)
            h = _dense(cfg.hidden_dim, (MLP, EMBED), cfg, "fc_out")(h)
            if cfg.dropout > 0:
                h = nn.Dropout(cfg.dropout, deterministic=not train)(h)
            return x + h


class BertEncoder(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, tokens, attention_mask=None, train: bool = False):
        cfg = self.cfg
        B, S = tokens.shape
        if attention_mask is None:
            attention_mask = jnp.ones((B, S), bool)
        tok_emb = self.param("tok_embedding", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), (VOCAB, EMBED)),
            (cfg.vocab_size, cfg.hidden_dim), cfg.param_dtype)
        pos_emb = self.param("pos_embedding", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), (None, EMBED)),
            (cfg.max_seq_len, cfg.hidden_dim), cfg.param_dtype)
        with jax.named_scope("embed"):
            x = tok_emb.astype(cfg.dtype)[tokens] \
                + pos_emb[None, :S].astype(cfg.dtype)
        for i in range(cfg.num_layers):
            x = EncoderLayer(cfg, name="layer_{}".format(i))(
                x, attention_mask.astype(bool), train=train)
        with jax.named_scope("head"):
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
            # [CLS] pooling + classification head (GLUE fine-tune shape).
            # (EMBED, None), not (EMBED, EMBED): one PartitionSpec must not
            # name the same mesh axis twice under fsdp strategies.
            pooled = nn.tanh(_dense(
                cfg.hidden_dim, (EMBED, None), cfg, "pooler")(
                x[:, 0].astype(cfg.dtype)))
            return _dense(cfg.num_classes, (EMBED, None), cfg, "classifier")(
                pooled).astype(jnp.float32)
