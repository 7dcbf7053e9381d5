"""SDAR-MoE: a Qwen3-MoE-shaped decoder trained with the block-diffusion
objective (SDAR, arXiv:2510.06303; the training step is BD3-LM's vectorised
form, arXiv:2503.09573), as a trial body that holds a share of the experts.

A layer is ``h = x + Attn(RMSNorm(x)); y = h + MoE(RMSNorm(h))``:

- ``Attn``: q, k, v projections without bias (GQA), an RMSNorm over each
  head's ``head_dim`` on q and on k (one learned scale each), rope at the
  given positions, softmax attention under the block-diffusion mask, output
  projection.
- ``MoE``: `models.moe.ExpertShareMLP`: softmax over all experts, the
  ``top_k`` largest, gates renormalised over the chosen, SwiGLU experts, no
  shared expert, no capacity, no auxiliary loss. The layer holds
  ``experts_held`` experts from ``first_expert`` on and computes their part
  of the sum.

**The training step's input** is, per data sequence of L tokens, 2 L
positions: the noised copy ``xt`` (tokens replaced by the mask id) and then
the clean copy ``x0``, both at positions 0..L-1, under
`ops.attention.BlockDiffusionMask` (L, ``block_length``). The head runs on
the noised half only, so the output is [B, L, vocab] logits, which
`ops.losses.weighted_token_xent` takes with the targets ``x0`` and weights
that carry the masked positions and 1/t.

**Initial values.** A seeded model stands for the trained checkpoint that
continued training starts from, and such a checkpoint routes evenly and
token by token. A small (0.02) embedding under unit-scale branches does
not: attention, which at random weights averages over hundreds of keys,
makes all positions alike within two layers and one expert takes nearly
every token. So the stream starts as the token's own embedding (unit normal,
as PaLM has it); the q and k norms' scales start at `QK_SCALE_INIT`, which
makes attention as peaked as a trained one's, so that what it adds to a
position is a few keys' values and not everybody's mean; ``down_proj`` is
scaled by ``(2 x layers) ** -0.5`` (GPT-2's depth scaling); and the mask
id's row starts at `MASK_EMBED_SCALE` of a token's, so that the masked
positions, a quarter of all, are told apart by their context and do not all
take the same eight experts. Every other matrix is LeCun-normal. The two
constants were chosen on routing balance alone (PERF.md section 6, PR 26).
What keeps the routing there while a share trains is the expert layer's:
a share held alone passes no gradient through its gates.

Not flags on `LlamaConfig` (ROADMAP C7): the q/k norms, the repeated
positions and the expert share are this family's own.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from maggy_tpu.models import moe
from maggy_tpu.models.llama import (EMBED, HEADS, KV, VOCAB, LoRADense,
                                    RMSNorm, rope)
from maggy_tpu.ops import attention
from maggy_tpu.telemetry.plans import remember_plan

#: What the q and k norms' learned scales start at: the scores' spread is
#: then 3 where unit scales give 1.
QK_SCALE_INIT = 3 ** 0.5
#: The scale the mask id's embedding row starts at, beside the unit-normal
#: rows of the data tokens (a reserved token that no pretraining trained).
MASK_EMBED_SCALE = 0.3
#: What a rematerialised layer keeps beside its input: what is dear to make
#: again and cheap to hold, by the names the two layers below give it (the
#: flash kernel's output and log-sum-exp, twice the layer's input in bytes;
#: the routing's indices and gates, under 3 MB). All else is made again in
#: the backward pass.
REMAT_KEEP = attention.REMAT_KEEP + moe.REMAT_KEEP


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    """Defaults are SDAR-30B-A3B-Chat's published ``config.json``."""
    vocab_size: int = 151936
    hidden_dim: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_dim: int = 768
    num_experts: int = 128
    top_k: int = 8
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    #: Block length of the diffusion mask (the release's generation default).
    block_length: int = 4
    #: The id that stands for a noised token.
    mask_token_id: Optional[int] = None
    #: The share of each layer's experts this holder has: ``experts_held``
    #: (None: all) from ``first_expert`` on.
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def residual_scale(self) -> float:
        """Initial scale of the experts' projection into the stream."""
        return (2 * self.num_layers) ** -0.5

    @staticmethod
    def tiny(**overrides) -> "SdarMoeConfig":
        """Test-size config: same code path, toy shapes."""
        base = dict(vocab_size=64, hidden_dim=32, num_layers=2, num_heads=4,
                    num_kv_heads=2, head_dim=16, moe_intermediate_dim=24,
                    num_experts=8, top_k=2, experts_held=4, first_expert=2,
                    mask_token_id=63, remat=False)
        return SdarMoeConfig(**{**base, **overrides})


class SdarAttention(nn.Module):
    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, x, positions, mask):
        cfg = self.cfg
        B, S, _ = x.shape

        def dense(features, axes, name):
            return LoRADense(features, axes, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name=name)

        def heads(y, n, norm):
            y = y.reshape(B, S, n, cfg.head_dim)
            y = RMSNorm(cfg.norm_eps, cfg.param_dtype, axis=None,
                        scale_init=QK_SCALE_INIT, name=norm)(y)
            return rope(y, positions, cfg.rope_theta)

        q = heads(dense(cfg.num_heads * cfg.head_dim, (EMBED, HEADS),
                        "q_proj")(x), cfg.num_heads, "q_norm")
        k = heads(dense(cfg.num_kv_heads * cfg.head_dim, (EMBED, KV),
                        "k_proj")(x), cfg.num_kv_heads, "k_norm")
        v = dense(cfg.num_kv_heads * cfg.head_dim, (EMBED, KV), "v_proj")(
            x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
        out = attention.multi_head_attention(q, k, v, causal=False,
                                             mask=mask)
        return dense(cfg.hidden_dim, (HEADS, EMBED), "o_proj")(
            out.reshape(B, S, cfg.num_heads * cfg.head_dim))


class SdarLayer(nn.Module):
    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        mask = attention.BlockDiffusionMask(x.shape[1] // 2,
                                            cfg.block_length)
        with jax.named_scope("attn"):
            h = x + SdarAttention(cfg, name="attn")(
                RMSNorm(cfg.norm_eps, cfg.param_dtype, name="attn_norm")(x),
                positions, mask)
        experts = moe.ExpertShareMLP(
            hidden_dim=cfg.hidden_dim,
            intermediate_dim=cfg.moe_intermediate_dim,
            num_experts=cfg.num_experts, top_k=cfg.top_k,
            experts_held=cfg.experts_held, first_expert=cfg.first_expert,
            renormalize=cfg.norm_topk_prob,
            down_init_scale=cfg.residual_scale, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="moe")
        # The expert layer names its own parts inside; its norm, its loop
        # of rounds and the residual are the block's.
        with jax.named_scope("block"):
            return h + experts(
                RMSNorm(cfg.norm_eps, cfg.param_dtype, name="mlp_norm")(h))


class SdarMoe(nn.Module):
    """tokens [B, 2 L] (``xt`` then ``x0``) -> float32 logits [B, L, vocab]
    at the noised half's positions."""

    cfg: SdarMoeConfig

    @nn.compact
    def __call__(self, tokens, positions=None):
        cfg = self.cfg
        B, S = tokens.shape
        if S % (2 * cfg.block_length):
            raise ValueError(
                "the input is a noised and a clean copy of a sequence of "
                "whole blocks of {}; got {} positions".format(
                    cfg.block_length, S))
        L = S // 2
        if positions is None:  # both copies at 0..L-1
            positions = jnp.broadcast_to(
                jnp.tile(jnp.arange(L), 2), tokens.shape)

        def embedding_init(key, shape, dtype):
            rows = nn.initializers.normal(1.0)(key, shape, dtype)
            if cfg.mask_token_id is None:
                return rows
            return rows.at[cfg.mask_token_id].multiply(MASK_EMBED_SCALE)

        emb = self.param("embedding", nn.with_logical_partitioning(
            embedding_init, (VOCAB, EMBED)),
            (cfg.vocab_size, cfg.hidden_dim), cfg.param_dtype)
        with jax.named_scope("embed"):
            x = emb.astype(cfg.dtype)[tokens]
        layer_cls = SdarLayer
        if cfg.remat:
            remember_plan("remat", "layer keeps " + " ".join(REMAT_KEEP))
            layer_cls = nn.remat(
                SdarLayer,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *REMAT_KEEP))
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, name="layer_{}".format(i))(x, positions)
        head = self.param("lm_head", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), (EMBED, VOCAB)),
            (cfg.hidden_dim, cfg.vocab_size), cfg.param_dtype)
        with jax.named_scope("head"):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(
                x[:, :L])
            return jnp.dot(x, head.astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
