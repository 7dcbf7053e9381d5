"""Ouro: a looped language model (``model_type`` ``ouro``; "Scaling Latent
Reasoning via Looped Language Models", ByteDance Seed et al., 2025), as a
trial body: ONE stack of decoder layers applied ``total_ut_steps`` times
over the same weights, an exit after every pass, and a training loss over
all the exits.

- norm: ``n(x) = w * x / sqrt(mean(x^2) + eps)``, float32 statistics.
- layer, four norms ("sandwich"): ``h = x + n2(Attn(n1(x)))``,
  ``y = h + n4(MLP(n3(h)))``. ``Attn``: q, k, v without bias, rope on q and
  k over the whole head (halves layout, `models.llama.rope`), causal softmax
  attention through `ops.attention.multi_head_attention` (the flash kernels
  on a TPU), output projection. ``MLP``: ``W_down (silu(u W_gate) * (u
  W_up))``.
- model: ``s_0 = E[tokens]``; for t = 1..T: ``s_t = n_f(Layer_L(..
  Layer_1(s_{t-1})))`` with the SAME layers and the same final norm every
  pass. The normed state is the exit's input and the next pass's. Exit t:
  logits ``s_t W_head`` (untied), gate ``g_t = s_t w_g + b_g``.

With ``targets`` the model returns what a loss over the exits needs and no
logits: per exit and position the negative log-likelihood of the target
(`ops.losses.chunked_token_nll` over the T exits' states as ONE block of
rows, so the head's kernel is read once a vocabulary chunk for all of them
and no ``[N, vocab]`` tensor is formed in either pass) and the gate's value,
as one float32 array ``[2, T, B, S]``. The exit distribution and the
expected loss under it are the caller's (``benchmark/families/ouro.py`` is
the whole recipe): the gradient reaches the gate through the probabilities
and every shared weight through all T of its uses.

**The passes share parameters by construction**: the stack is one module
(``stack``: ``layer_0``.. and ``final_norm``) called T times, so the tree
holds L layers and never T x L, and the program holds T x L layer bodies
(XLA adds a weight's T gradient products). Every layer APPLICATION is
rematerialised on its own (``remat``), so a step keeps T x L inputs for L
layers of weights, and beside each input what `REMAT_KEEP` names: the flash
kernel's out and lse, and the application's five hidden-wide products: q
and k after rope and v (the arrays the attention's backward rule reads),
``o_proj``'s output and ``down_proj``'s (the inputs of the two after-norms).
At the published widths that is 7 x 2048 bfloat16 values and 16 float32 lse
a token and application, 28,736 bytes (the input, out and lse alone are
8,256). The backward pass makes again the two intermediate-wide products,
``gate_proj`` and ``up_proj`` (2 x 5632 values a token, which nothing has
the memory to keep), the four norms, the SwiGLU product and no other matrix
product: neither the five kept ones, nor rope, nor the forward kernel.

**Initial values.** Embedding rows unit normal, every matrix LeCun-normal,
every norm's scale one (the after-norms make a branch's size its scale's,
so no depth scaling of ``o_proj`` or ``down_proj`` means anything here), the
gate's weight and bias zero: every exit starts at ``sigmoid(0) = 1/2``.

Not flags on `LlamaConfig` (ROADMAP C7): the loop, the after-norms and the
exits are this family's own.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from maggy_tpu.models.llama import (EMBED, HEADS, KV, MLP, VOCAB, LoRADense,
                                    RMSNorm, rope)
from maggy_tpu.ops import attention
from maggy_tpu.ops.losses import chunked_token_nll
from maggy_tpu.telemetry.plans import remember_plan

#: The `jax.named_scope`s of the model. A layer application runs under
#: ``loop_attn`` (two norms, projections, rope, attention) and then
#: ``loop_mlp`` (two norms, SwiGLU); a pass closes under ``exit_norm``; the
#: exits read under ``exit_gate`` and ``exit_head``. The model names them
#: with its plan, so the step's instructions under each are ``loop_ops`` of
#: the ``compiled`` record.
LOOP_SCOPES = ("loop_attn", "loop_mlp", "exit_norm", "exit_gate", "exit_head")
#: What a rematerialised layer application keeps beside its input: the flash
#: kernel's two, and the five products whose output is hidden-wide, named in
#: `OuroLayer` where the backward pass reads them (q and k after rope, v,
#: and the outputs of ``o_proj`` and ``down_proj``). The two
#: intermediate-wide products (``gate_proj``, ``up_proj``) are made again.
REMAT_KEEP = attention.REMAT_KEEP + (
    "loop_q", "loop_k", "loop_v", "loop_o_proj", "loop_down_proj")


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """Defaults are Ouro-2.6B's published ``config.json``."""
    vocab_size: int = 49152
    hidden_dim: int = 2048
    intermediate_dim: int = 5632
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    #: Passes over the stack, an exit after each (``total_ut_steps``).
    total_ut_steps: int = 4
    #: Layers this holder has of the ``num_layers`` (None: all): one stage
    #: of a pipeline that a microbatch goes round ``total_ut_steps`` times.
    layers_held: Optional[int] = None
    #: Columns of the vocabulary a step of the fused head-and-loss forms.
    head_chunk: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    def __post_init__(self):
        if self.total_ut_steps < 1:
            raise ValueError("at least one pass")

    @property
    def layers(self) -> int:
        return self.num_layers if self.layers_held is None \
            else self.layers_held

    @staticmethod
    def tiny(**overrides) -> "OuroConfig":
        """Test-size config: same code path, toy shapes."""
        base = dict(vocab_size=96, hidden_dim=32, intermediate_dim=48,
                    num_layers=4, layers_held=2, num_heads=4, num_kv_heads=4,
                    head_dim=16, total_ut_steps=3, head_chunk=40,
                    remat=False)
        return OuroConfig(**{**base, **overrides})


class OuroLayer(nn.Module):
    """``h = x + n2(Attn(n1(x)))``; ``y = h + n4(MLP(n3(h)))``."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        B, S, _ = x.shape

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)

        def dense(features, axes, name):
            return LoRADense(features, axes, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name=name)

        with jax.named_scope("loop_attn"):
            u = norm("attn_norm")(x)

            def heads(n, axes, name):
                return dense(n * cfg.head_dim, axes, name)(u).reshape(
                    B, S, n, cfg.head_dim)

            q = checkpoint_name(
                rope(heads(cfg.num_heads, (EMBED, HEADS), "q_proj"),
                     positions, cfg.rope_theta), "loop_q")
            k = checkpoint_name(
                rope(heads(cfg.num_kv_heads, (EMBED, KV), "k_proj"),
                     positions, cfg.rope_theta), "loop_k")
            v = checkpoint_name(
                heads(cfg.num_kv_heads, (EMBED, KV), "v_proj"), "loop_v")
            out = attention.multi_head_attention(q, k, v, causal=True)
            out = checkpoint_name(
                dense(cfg.hidden_dim, (HEADS, EMBED), "o_proj")(
                    out.reshape(B, S, cfg.num_heads * cfg.head_dim)),
                "loop_o_proj")
            h = x + norm("attn_after_norm")(out)
        with jax.named_scope("loop_mlp"):
            u = norm("mlp_norm")(h)
            gated = jax.nn.silu(dense(cfg.intermediate_dim, (EMBED, MLP),
                                      "gate_proj")(u)) \
                * dense(cfg.intermediate_dim, (EMBED, MLP), "up_proj")(u)
            return h + norm("mlp_after_norm")(checkpoint_name(
                dense(cfg.hidden_dim, (MLP, EMBED), "down_proj")(gated),
                "loop_down_proj"))


class OuroStack(nn.Module):
    """One pass: the held layers, then the final norm. The normed state is
    this pass's exit and the next pass's input."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        layer_cls = OuroLayer
        if cfg.remat:
            layer_cls = nn.remat(
                OuroLayer,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *REMAT_KEEP))
        for i in range(cfg.layers):
            x = layer_cls(cfg, name="layer_{}".format(i))(x, positions)
        with jax.named_scope("exit_norm"):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
        return x


class ExitGate(nn.Module):
    """``g = s w_g + b_g`` in float32: states [..., hidden] -> [...]."""

    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, states):
        w_g = self.param("kernel", nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (EMBED,)),
            (states.shape[-1],), self.param_dtype)
        b_g = self.param("bias", nn.initializers.zeros_init(), (),
                         self.param_dtype)
        return jnp.einsum("...h,h->...", states.astype(jnp.float32),
                          w_g.astype(jnp.float32)) + b_g.astype(jnp.float32)


class Ouro(nn.Module):
    """tokens [B, S] (and targets [B, S]) -> float32 ``[2, T, B, S]``: per
    exit and position, ``[0]`` the negative log-likelihood of the target
    and ``[1]`` the gate's value. Without targets: ``(logits [T, B, S,
    vocab], gates [T, B, S])``, dense."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, tokens, targets=None):
        cfg = self.cfg
        B, S = tokens.shape
        T, f32 = cfg.total_ut_steps, jnp.float32
        chunk = min(cfg.head_chunk, cfg.vocab_size)
        remember_plan(
            "loop", "{} passes x {} layers heads {}x{} S {} head chunks "
            "vocab {} x {} over {} rows".format(
                T, cfg.layers, cfg.num_heads, cfg.head_dim, S,
                chunk, -(-cfg.vocab_size // chunk), T * B * S), LOOP_SCOPES)
        if cfg.remat:
            remember_plan("remat", "layer application keeps "
                          + " ".join(REMAT_KEEP))
        positions = jnp.broadcast_to(jnp.arange(S), tokens.shape)
        emb = self.param("embedding", nn.with_logical_partitioning(
            nn.initializers.normal(1.0), (VOCAB, EMBED)),
            (cfg.vocab_size, cfg.hidden_dim), cfg.param_dtype)
        with jax.named_scope("embed"):
            x = emb.astype(cfg.dtype)[tokens]
        stack, states = OuroStack(cfg, name="stack"), []
        for _ in range(T):
            x = stack(x, positions)
            states.append(x)
        states = jnp.stack(states)
        with jax.named_scope("exit_gate"):
            gates = ExitGate(cfg.param_dtype, name="exit_gate")(states)
        head = self.param("lm_head", nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), (EMBED, VOCAB)),
            (cfg.hidden_dim, cfg.vocab_size), cfg.param_dtype)
        with jax.named_scope("exit_head"):
            if targets is None:
                return jnp.dot(states, head.astype(cfg.dtype),
                               preferred_element_type=f32), gates
            nll = chunked_token_nll(
                states.reshape(T * B * S, cfg.hidden_dim), head,
                jnp.broadcast_to(targets, (T, B, S)).reshape(-1), chunk)
        return jnp.stack([nll.reshape(T, B, S), gates])
