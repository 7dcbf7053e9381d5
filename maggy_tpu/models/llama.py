"""Llama-style decoder-only transformer with optional LoRA adapters.

The flagship model (BASELINE.md config 5: "Llama-3-8B LoRA hyperparameter
sweep"). The reference contains no model code at all (SURVEY.md §5.7) — this
is green-field TPU-first design:

- bfloat16 activations; fp32 params + softmax accumulations (MXU-friendly)
- RMSNorm + RoPE + SwiGLU + grouped-query attention (Llama-3 architecture)
- every weight created with `nn.with_logical_partitioning`, so one
  `logical_axis_rules` table maps the model onto any dp/fsdp/tp mesh
- attention dispatches to the Pallas flash kernel on TPU (ops/attention.py),
  falling back to an XLA softmax path elsewhere
- LoRA: frozen base + low-rank adapters on q/k/v/o, the idiomatic target for
  hyperparameter sweeps over (rank, alpha, lr)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

from maggy_tpu.ops.attention import multi_head_attention

# Logical axis names -> mesh axes (see parallel/sharding.LOGICAL_RULES).
EMBED = "embed"
MLP = "mlp"
HEADS = "heads"
KV = "kv"
VOCAB = "vocab"


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_dim: int = 4096
    intermediate_dim: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # LoRA: rank 0 disables adapters.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    remat: bool = True
    # Sequence/context parallelism: attention_impl="ring" runs blockwise
    # ring attention over ``seq_mesh``'s ``seq_axis`` (Q/K/V sharded on the
    # sequence dim, K/V shards circulated via ppermute over ICI). "auto"
    # dispatches to the Pallas flash kernel / XLA reference path.
    attention_impl: str = "auto"
    seq_axis: str = "seq"
    seq_mesh: Any = None
    # Mixture-of-experts: num_experts > 0 replaces the dense MLP with a
    # top-k routed MoE MLP (experts sharded over the "expert" mesh axis).
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0

    @staticmethod
    def tiny(vocab_size: int = 256, lora_rank: int = 0) -> "LlamaConfig":
        """Test-size config: same code path, toy shapes."""
        return LlamaConfig(
            vocab_size=vocab_size, hidden_dim=64, intermediate_dim=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_seq_len=128, lora_rank=lora_rank, remat=False,
        )

    @staticmethod
    def llama3_8b(lora_rank: int = 16) -> "LlamaConfig":
        # 8.03B params: the Llama-3 128k vocabulary, not Llama-2's 32k.
        return LlamaConfig(vocab_size=128256, lora_rank=lora_rank)


def _rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


class RMSNorm(nn.Module):
    eps: float = 1e-5
    param_dtype: Any = jnp.float32
    #: Logical axis of the scale: the model width, or None for a norm over
    #: some other last axis (a head's 128).
    axis: Optional[str] = EMBED
    #: What the learned scale starts at.
    scale_init: float = 1.0

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.with_logical_partitioning(
            nn.initializers.constant(self.scale_init), (self.axis,)),
            (x.shape[-1],), self.param_dtype)
        return _rms_norm(x, w.astype(x.dtype), self.eps)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary position embedding over the last (head_dim) axis.

    x: [B, S, H, D]; positions: [B, S].
    """
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class LoRADense(nn.Module):
    """Dense with an optional frozen-base + low-rank adapter.

    Adapter params are the `lora_a`/`lora_b` leaves of the params tree;
    `train.lora.only_lora(tx)` masks an optimizer so only they train (and
    only they carry optimizer state — the 8B-scale memory win).
    """

    features: int
    kernel_axes: Tuple[str, str]
    lora_rank: int = 0
    lora_alpha: float = 16.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_bias: bool = False

    @nn.compact
    def __call__(self, x):
        in_dim = x.shape[-1]
        kernel = self.param("kernel", nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), self.kernel_axes),
            (in_dim, self.features), self.param_dtype)
        y = jnp.dot(x, kernel.astype(self.dtype))
        if self.lora_rank > 0:
            a = self.param("lora_a", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (self.kernel_axes[0], None)),
                (in_dim, self.lora_rank), self.param_dtype)
            b = self.param("lora_b", nn.with_logical_partitioning(
                nn.initializers.zeros_init(), (None, self.kernel_axes[1])),
                (self.lora_rank, self.features), self.param_dtype)
            scale = self.lora_alpha / self.lora_rank
            y = y + jnp.dot(jnp.dot(x, a.astype(self.dtype)),
                            b.astype(self.dtype)) * scale
        if self.use_bias:
            bias = self.param("bias", nn.with_logical_partitioning(
                nn.initializers.zeros_init(), (self.kernel_axes[1],)),
                (self.features,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        return y


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, mask=None):
        cfg = self.cfg
        dense = lambda feat, axes, name: LoRADense(  # noqa: E731
            feat, axes, lora_rank=cfg.lora_rank, lora_alpha=cfg.lora_alpha,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)
        B, S, _ = x.shape
        q = dense(cfg.num_heads * cfg.head_dim, (EMBED, HEADS), "q_proj")(x)
        k = dense(cfg.num_kv_heads * cfg.head_dim, (EMBED, KV), "k_proj")(x)
        v = dense(cfg.num_kv_heads * cfg.head_dim, (EMBED, KV), "v_proj")(x)
        q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
        k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        if cfg.attention_impl == "ring":
            if cfg.seq_mesh is None:
                raise ValueError(
                    "attention_impl='ring' requires cfg.seq_mesh (a Mesh "
                    "with a '{}' axis)".format(cfg.seq_axis))
            if mask is not None:
                raise ValueError(
                    "attention_impl='ring' supports only causal masking; "
                    "got an explicit mask")
            from maggy_tpu.parallel.ring_attention import ring_attention

            # GQA rides the ring natively: k/v rotate with Hkv heads and
            # the flash path indexes the shared kv head per group.
            out = ring_attention(q, k, v, cfg.seq_mesh,
                                 axis_name=cfg.seq_axis, causal=True)
        else:
            out = multi_head_attention(q, k, v, causal=True, mask=mask)
        out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
        return dense(cfg.hidden_dim, (HEADS, EMBED), "o_proj")(out)


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feat, axes, name: LoRADense(  # noqa: E731
            feat, axes, lora_rank=0, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        gate = dense(cfg.intermediate_dim, (EMBED, MLP), "gate_proj")(x)
        up = dense(cfg.intermediate_dim, (EMBED, MLP), "up_proj")(x)
        return dense(cfg.hidden_dim, (MLP, EMBED), "down_proj")(
            nn.silu(gate) * up)


class DecoderLayer(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, mask=None):
        cfg = self.cfg
        h = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.norm_eps, cfg.param_dtype, name="attn_norm")(x),
            positions, mask)
        if cfg.num_experts > 0:
            from maggy_tpu.models.moe import MoEMLP

            mlp = MoEMLP(
                hidden_dim=cfg.hidden_dim,
                intermediate_dim=cfg.intermediate_dim,
                num_experts=cfg.num_experts, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="moe_mlp")
        else:
            mlp = MLP(cfg, name="mlp")
        return h + mlp(
            RMSNorm(cfg.norm_eps, cfg.param_dtype, name="mlp_norm")(h))


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden=False):
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
        emb = self.param("embedding", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), (VOCAB, EMBED)),
            (cfg.vocab_size, cfg.hidden_dim), cfg.param_dtype)
        x = emb.astype(cfg.dtype)[tokens]
        layer_cls = DecoderLayer
        if cfg.remat:
            # Rematerialize each layer: trade FLOPs for HBM (activation
            # memory is the binding constraint at 8B scale).
            layer_cls = nn.remat(DecoderLayer, static_argnums=())
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, name="layer_{}".format(i))(x, positions)
        x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
        # Tied-untied choice: untied lm head (Llama-3 style).
        head = self.param("lm_head", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), (EMBED, VOCAB)),
            (cfg.hidden_dim, cfg.vocab_size), cfg.param_dtype)
        if return_hidden:
            # Pre-head output for the vocab-chunked loss
            # (ops.losses.chunked_next_token_loss): at 128k vocab the full
            # [B, S, V] fp32 logits are the largest activation in the
            # model — the chunked loss never materializes them.
            return x, head
        return jnp.dot(x, head.astype(cfg.dtype)).astype(jnp.float32)
