"""SmallThinker: a decoder whose layers alternate windowed and global
attention in a period, over routed experts whose router reads the layer's
input before attention ("SmallThinker: A Family of Efficient Large Language
Models Natively Trained for Local Deployment", PowerInfer, 2025), as a trial
body that holds a share of the experts.

Layer l, of the kind its ``sliding_window_layout`` entry says (0 global,
1 windowed), with ``rope_layout`` saying whether it turns q and k:

- ``u = RMSNorm_in(x)``;
- the router scores ``u`` (BEFORE attention): softmax over all experts, the
  ``top_k`` largest, renormalised over the chosen;
- ``q, k, v = u W_q, u W_k, u W_v`` without bias (GQA); rope on q and k where
  ``rope_layout[l]`` is 1, none where it is 0 (NoPE); softmax attention
  under the causal mask, and for a windowed layer under `CausalWindow`:
  query i sees key j iff ``i - window < j <= i``;
  ``h = x + a W_o``;
- ``y = h + sum over the chosen held experts of g_e W_down,e (relu(w W_gate,e)
  * (w W_up,e))``, ``w = RMSNorm_post(h)``: ReGLU experts through
  `models.moe.ExpertShareMLP`, routed from ``u``.

Then the final RMSNorm and the untied head. The model takes the targets and
returns no logits but each position's negative log-likelihood of its target
(`ops.losses.chunked_token_nll`, the per-row form of
`chunked_next_token_loss`: the head's columns a chunk at a time, no ``[N,
vocab]`` tensor in either pass), float32 [B, S].

A layer's attention runs under the scope of its kind, ``attn_global`` or
``attn_window`` (`ATTN_SCOPES`); the experts name their own parts inside
``block``, the norm and residual around them.

**Initial values.** As `models.sdar` argues: a seeded model stands for the
trained checkpoint continued training starts from, which routes evenly; the
embedding rows are unit normal, so the stream starts as the token's own;
``down_proj`` is scaled by ``(2 x layers) ** -0.5`` over the PUBLISHED depth
(``residual_layers``: a stage cut from a deeper model is scaled as the
checkpoint it is cut from was, `models.nemotron_h`); the q and k projections
start at `QK_INIT_SCALE` of LeCun-normal, which makes attention as peaked as
a trained one's, so that what a window leaves out is read in the output.
Every other matrix is LeCun-normal, every norm's scale one.

Not flags on `LlamaConfig` (ROADMAP C7): the two layouts, the router's place
and the expert share are this family's own.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from maggy_tpu.models import moe
from maggy_tpu.models.llama import EMBED, HEADS, KV, VOCAB, RMSNorm, rope
from maggy_tpu.models.nemotron_h import QK_INIT_SCALE, Projection
from maggy_tpu.ops import attention
from maggy_tpu.ops.losses import chunked_token_nll
from maggy_tpu.telemetry.plans import remember_plan

#: The scopes a layer's attention runs under, by ``sliding_window_layout``'s
#: entry: 0 global, 1 windowed.
ATTN_SCOPES = ("attn_global", "attn_window")
#: What a rematerialised layer keeps beside its input: the flash kernel's
#: output and log-sum-exp, and the routing (computed from the layer's input,
#: before attention). All else is made again in the backward pass.
REMAT_KEEP = attention.REMAT_KEEP + moe.REMAT_KEEP


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Defaults are SmallThinker-21BA3B-Instruct's published
    ``config.json``."""
    vocab_size: int = 151936
    hidden_dim: int = 2560
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_dim: int = 768
    num_experts: int = 64
    top_k: int = 6
    norm_topk_prob: bool = True
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    sliding_window: int = 4096
    #: Per layer: 1 windowed, 0 global (the published 52 entries repeat
    #: ``[0, 1, 1, 1]``); its length is the number of layers.
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    #: Per layer: 1 rope on q and k, 0 none (NoPE).
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    #: The share of each layer's experts this holder has: ``experts_held``
    #: (None: all) from ``first_expert`` on.
    experts_held: Optional[int] = None
    first_expert: int = 0
    #: The depth ``down_proj``'s initial values are scaled by (None: the
    #: layers held).
    residual_layers: Optional[int] = None
    #: Columns of the vocabulary a step of the fused head-and-loss forms.
    head_chunk: int = 4864
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    def __post_init__(self):
        if len(self.rope_layout) != len(self.sliding_window_layout) \
                or not set(self.sliding_window_layout + self.rope_layout) \
                <= {0, 1}:
            raise ValueError("two layouts of 0s and 1s, one entry a layer; "
                             "got {} and {}".format(self.sliding_window_layout,
                                                    self.rope_layout))

    @property
    def num_layers(self) -> int:
        return len(self.sliding_window_layout)

    @property
    def residual_scale(self) -> float:
        """Initial scale of the experts' projection into the stream."""
        return (2 * (self.residual_layers or self.num_layers)) ** -0.5

    @staticmethod
    def tiny(**overrides) -> "SmallThinkerConfig":
        """Test-size config: same code path, toy shapes (two periods, GQA
        7:1, 8 experts of which 4 held)."""
        base = dict(vocab_size=64, hidden_dim=32, num_heads=7, num_kv_heads=1,
                    head_dim=16, moe_intermediate_dim=24, num_experts=8,
                    top_k=2, experts_held=4, first_expert=2, sliding_window=8,
                    sliding_window_layout=(0, 1, 1, 1) * 2,
                    rope_layout=(0, 1, 1, 1) * 2, head_chunk=24, remat=False)
        return SmallThinkerConfig(**{**base, **overrides})


class SmallThinkerLayer(nn.Module):
    cfg: SmallThinkerConfig
    windowed: bool
    turned: bool

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        B, S, _ = x.shape

        def heads(n, axes, name, scale=1.0):
            return Projection(n * cfg.head_dim, axes, scale, cfg.dtype,
                              cfg.param_dtype, name=name)(u).reshape(
                                  B, S, n, cfg.head_dim)

        def turn(y):
            return rope(y, positions, cfg.rope_theta) if self.turned else y

        scope = jax.named_scope("attn_window") if self.windowed \
            else jax.named_scope("attn_global")
        with scope:
            u = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="input_norm")(x)
            out = attention.multi_head_attention(
                turn(heads(cfg.num_heads, (EMBED, HEADS), "q_proj",
                           QK_INIT_SCALE)),
                turn(heads(cfg.num_kv_heads, (EMBED, KV), "k_proj",
                           QK_INIT_SCALE)),
                heads(cfg.num_kv_heads, (EMBED, KV), "v_proj"), causal=True,
                mask=attention.CausalWindow(cfg.sliding_window)
                if self.windowed else None)
            h = x + Projection(cfg.hidden_dim, (HEADS, EMBED), 1.0, cfg.dtype,
                               cfg.param_dtype, name="o_proj")(
                out.reshape(B, S, cfg.num_heads * cfg.head_dim))
        experts = moe.ExpertShareMLP(
            hidden_dim=cfg.hidden_dim,
            intermediate_dim=cfg.moe_intermediate_dim,
            num_experts=cfg.num_experts, top_k=cfg.top_k,
            experts_held=cfg.experts_held, first_expert=cfg.first_expert,
            renormalize=cfg.norm_topk_prob, expert_kind="reglu",
            down_init_scale=cfg.residual_scale, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="moe")
        # The expert layer names its own parts inside (the routing of ``u``
        # among them); its norm and the residual are the block's.
        with jax.named_scope("block"):
            return h + experts(
                RMSNorm(cfg.norm_eps, cfg.param_dtype, name="post_norm")(h),
                route_from=u)


class SmallThinker(nn.Module):
    """tokens [B, S] and targets [B, S] -> float32 [B, S], each position's
    negative log-likelihood of its target."""

    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, tokens, targets):
        cfg = self.cfg
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), tokens.shape)
        emb = self.param("embedding", nn.with_logical_partitioning(
            nn.initializers.normal(1.0), (VOCAB, EMBED)),
            (cfg.vocab_size, cfg.hidden_dim), cfg.param_dtype)
        with jax.named_scope("embed"):
            x = emb.astype(cfg.dtype)[tokens]
        layer_cls = SmallThinkerLayer
        if cfg.remat:
            remember_plan("remat", "layer keeps " + " ".join(REMAT_KEEP))
            layer_cls = nn.remat(
                SmallThinkerLayer,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *REMAT_KEEP))
        for i, (windowed, turned) in enumerate(zip(cfg.sliding_window_layout,
                                                   cfg.rope_layout)):
            x = layer_cls(cfg, bool(windowed), bool(turned),
                          name="layer_{}".format(i))(x, positions)
        head = self.param("lm_head", nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), (EMBED, VOCAB)),
            (cfg.hidden_dim, cfg.vocab_size), cfg.param_dtype)
        with jax.named_scope("head"):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
            return chunked_token_nll(
                x.reshape(B * S, cfg.hidden_dim), head, targets.reshape(-1),
                min(cfg.head_chunk, cfg.vocab_size)).reshape(B, S)
