"""Nemotron-H: a decoder built from a PATTERN of blocks of unequal cost
(Nemotron-H, arXiv:2504.03624; ``model_type`` ``nemotron_h``), as a trial
body that holds a share of the experts.

A block is ONE mixer behind one norm, ``x = x + mixer(RMSNorm(x))``, and the
pattern's letter says which: ``M`` a Mamba-2 state-space mixer, ``*`` causal
grouped-query attention, ``E`` routed experts beside a shared one. There is
no positional embedding anywhere: the state-space blocks carry the order.

- ``M`` (`Mamba2Mixer`; SSD, arXiv:2405.21060), H heads of P channels, G
  groups of N states: ``[z | xBC | dt] = u W_in``; ``xBC`` through a causal
  depthwise convolution of ``conv_kernel`` taps and a silu
  (`ops.ssd.conv_silu`, which reads xBC where the projection wrote it);
  split into ``x [H, P]``, ``B [G, N]``, ``C [G, N]``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the scan ``h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t`` (`ops.ssd.ssd_scan`, in
  chunks);
  ``y = GroupRMSNorm(y * silu(z))``, gate first and then the norm over each
  of the G groups of ``H P / G`` channels; ``y W_out``.
- ``*`` (`NemotronHAttention`): q, k, v without bias and WITHOUT rope,
  causal softmax attention through `ops.attention.multi_head_attention`
  (the flash kernels on a TPU), output projection.
- ``E``: `models.moe.ExpertShareMLP` with a sigmoid router whose bias only
  the choice sees (and, with ``balance_scale``, a balance rule moves:
  `moe.balance_pull`), gates over their sum times ``routed_scaling_factor``,
  relu^2 experts of two matrices, and one shared expert. The block holds
  ``experts_held`` routed experts from ``first_expert`` on and computes
  their part of the sum; the shared expert is whole on every holder.

**Initial values.** A seeded model stands for the trained checkpoint that
continued training starts from. Embedding rows unit normal, so the stream
starts as the token's own; every matrix LeCun-normal, the projections back
into the stream scaled by ``(number of blocks) ** -0.5`` (the published
``rescale_prenorm_residual``; a stage cut from a deeper model scales by
THAT model's depth, ``residual_blocks``: under the 9 blocks' own 0.33 the
branches' common direction skews the deeper routers, one held expert taking
1,734 rows of a balanced 768, where the published 52's 0.139 keeps every
router near its first block's balance; PERF.md section 6, PR 30), the q and
k projections by `QK_INIT_SCALE`; the state-space parameters as the Mamba-2
release draws them from the configuration's ``time_step_*`` keys (dt
log-uniform in [min, max], floored, ``dt_bias`` its inverse softplus;
``A_log = log U(1, 16)``; ``D = 1``); convolution taps uniform in
``+- conv_kernel ** -0.5``, its bias zero; every norm's scale one.

Not flags on `LlamaConfig` (ROADMAP C7): a block's kind is the pattern's
letter, and a model of three kinds of unequal cost is this family's own.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from maggy_tpu.models import moe
from maggy_tpu.models.llama import EMBED, HEADS, KV, MLP, VOCAB, RMSNorm
from maggy_tpu.ops import attention, ssd
from maggy_tpu.telemetry.plans import remember_plan

#: What a rematerialised block keeps beside its input, by the names its
#: mixer gives them: the flash kernel's output and log-sum-exp, the
#: routing's indices and gates, the scan kernel's output (its gradient
#: keeps the scan's inputs, which the block makes again, so `ssd_fwd` runs
#: once; the XLA products name nothing and make the rest again, `ops.ssd`).
REMAT_KEEP = attention.REMAT_KEEP + moe.REMAT_KEEP + ssd.REMAT_KEEP

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
#: The scale the q and k projections' initial values are drawn at, beside
#: LeCun-normal's one: the scores' spread is then 3 where unit scales give
#: 1, which makes attention as peaked as a trained one's. At 1 a query
#: averages some 1,500 of its 4,096 keys' values, the block adds next to
#: nothing to the stream, and whether it is causal cannot be read from the
#: logits (PERF.md section 6, PR 30).
QK_INIT_SCALE = 3 ** 0.5


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Defaults are NVIDIA-Nemotron-3-Nano-30B-A3B's published
    ``config.json``."""
    vocab_size: int = 131072
    hidden_dim: int = 2688
    #: One letter a block (``hybrid_override_pattern``).
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_intermediate_dim: int = 1856
    shared_intermediate_dim: int = 3712
    num_experts: int = 128
    top_k: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5
    #: The share of each ``E`` block's routed experts this holder has:
    #: ``experts_held`` (None: all) from ``first_expert`` on.
    experts_held: Optional[int] = None
    first_expert: int = 0
    #: The balance rule of the routers' bias (`moe.balance_pull`): None, no
    #: rule and the bias stays where it is; a number, the optimizer steps
    #: each expert's bias against its load error and the choice sees this
    #: many times the parameter (`moe.ExpertShareMLP.balance_scale`).
    balance_scale: Optional[float] = None
    #: The depth the projections back into the stream are scaled by (None:
    #: the pattern's own). A stage cut from a deeper model passes that
    #: model's depth, as the checkpoint it stands for was scaled.
    residual_blocks: Optional[int] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    def __post_init__(self):
        unknown = set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}
        if unknown or not self.pattern:
            raise ValueError("a pattern is letters of M, E and *; got "
                             "{!r}".format(self.pattern))

    @property
    def residual_scale(self) -> float:
        """Initial scale of every projection back into the stream."""
        return (self.residual_blocks or len(self.pattern)) ** -0.5

    @staticmethod
    def tiny(**overrides) -> "NemotronHConfig":
        """Test-size config: same code path, toy shapes."""
        base = dict(vocab_size=64, hidden_dim=32, pattern="EM*M",
                    num_heads=4, num_kv_heads=2, head_dim=16, mamba_heads=4,
                    mamba_head_dim=8, ssm_groups=2, ssm_state=16,
                    chunk_size=8, moe_intermediate_dim=24,
                    shared_intermediate_dim=40, num_experts=8, top_k=2,
                    experts_held=4, first_expert=2, remat=False)
        return NemotronHConfig(**{**base, **overrides})


class Projection(nn.Module):
    """``x W`` without bias, W LeCun-normal times ``init_scale``."""

    features: int
    kernel_axes: tuple
    init_scale: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.with_logical_partitioning(
            nn.initializers.variance_scaling(
                self.init_scale ** 2, "fan_in", "truncated_normal"),
            self.kernel_axes), (x.shape[-1], self.features), self.param_dtype)
        return jnp.dot(x, kernel.astype(self.dtype))


def _projection(cfg, features, axes, name, scale=1.0):
    return Projection(features, axes, scale, cfg.dtype, cfg.param_dtype,
                      name=name)


class Mamba2Mixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        B, S, _ = u.shape
        H, P = cfg.mamba_heads, cfg.mamba_head_dim
        G, N, K = cfg.ssm_groups, cfg.ssm_state, cfg.conv_kernel
        inner, f32 = H * P, jnp.float32
        conv_dim = inner + 2 * G * N
        remember_plan("ssm", "heads {}x{} groups {} state {} conv {} chunk {} "
                      "S {} {} {}".format(
                          H, P, G, N, K, cfg.chunk_size, S,
                          ssd.scan_plan(S, H, P, G, N, cfg.chunk_size),
                          ssd.conv_plan(S, conv_dim, inner)),
                      ssd.SCOPES)

        def vector(name, init, shape):
            return self.param(name, nn.with_logical_partitioning(
                init, (None,) * len(shape)), shape, cfg.param_dtype)

        def dt_bias_init(key, shape, dtype):
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, dtype, lo, hi)), cfg.time_step_floor)
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse

        def a_log_init(key, shape, dtype):
            return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))

        def taps_init(key, shape, dtype):
            bound = K ** -0.5
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        with jax.named_scope("ssm_proj"):
            zxbcdt = _projection(cfg, inner + conv_dim + H, (EMBED, MLP),
                                 "in_proj")(u)
        z, dt = zxbcdt[..., :inner], zxbcdt[..., inner + conv_dim:]
        with jax.named_scope("ssm_conv"):
            # xBC read where the projection wrote it (`ssd.conv_silu`).
            xBC = ssd.conv_silu(
                zxbcdt, vector("conv_kernel", taps_init, (K, conv_dim)),
                vector("conv_bias", nn.initializers.zeros_init(),
                       (conv_dim,)), inner).astype(cfg.dtype)
        x, Bm, Cm = jnp.split(xBC, (inner, inner + G * N), axis=-1)
        with jax.named_scope("ssm_scan"):
            dt = jax.nn.softplus(dt.astype(f32) + vector(
                "dt_bias", dt_bias_init, (H,)).astype(f32))
            A = -jnp.exp(vector("A_log", a_log_init, (H,)).astype(f32))
            y = ssd.ssd_scan(
                x.reshape(B, S, H, P), dt, A, Bm.reshape(B, S, G, N),
                Cm.reshape(B, S, G, N),
                vector("D", nn.initializers.ones_init(), (H,)),
                cfg.chunk_size)
        with jax.named_scope("ssm_gate_norm"):
            # Gate first, then an RMS norm over each group's channels.
            y = y.reshape(B, S, inner).astype(f32) * jax.nn.silu(
                z.astype(f32))
            y = y.reshape(B, S, G, inner // G)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + cfg.norm_eps)
            y = (y.reshape(B, S, inner) * vector(
                "norm_scale", nn.initializers.ones_init(),
                (inner,)).astype(f32)).astype(cfg.dtype)
        with jax.named_scope("ssm_proj"):
            return _projection(cfg, cfg.hidden_dim, (MLP, EMBED), "out_proj",
                               cfg.residual_scale)(y)


class NemotronHAttention(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, S, _ = x.shape

        def heads(n, axes, name, scale=1.0):
            return _projection(cfg, n * cfg.head_dim, axes, name, scale)(
                x).reshape(B, S, n, cfg.head_dim)

        with jax.named_scope("attn"):
            out = attention.multi_head_attention(
                heads(cfg.num_heads, (EMBED, HEADS), "q_proj", QK_INIT_SCALE),
                heads(cfg.num_kv_heads, (EMBED, KV), "k_proj", QK_INIT_SCALE),
                heads(cfg.num_kv_heads, (EMBED, KV), "v_proj"), causal=True)
            return _projection(cfg, cfg.hidden_dim, (HEADS, EMBED), "o_proj",
                               cfg.residual_scale)(
                out.reshape(B, S, cfg.num_heads * cfg.head_dim))


def _experts(cfg: NemotronHConfig, name: str) -> moe.ExpertShareMLP:
    return moe.ExpertShareMLP(
        hidden_dim=cfg.hidden_dim, intermediate_dim=cfg.moe_intermediate_dim,
        num_experts=cfg.num_experts, top_k=cfg.top_k,
        experts_held=cfg.experts_held, first_expert=cfg.first_expert,
        renormalize=cfg.norm_topk_prob, scoring="sigmoid",
        route_scale=cfg.routed_scaling_factor,
        balance_scale=cfg.balance_scale, expert_kind="relu2",
        shared_dim=cfg.shared_intermediate_dim,
        down_init_scale=cfg.residual_scale, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=name)


class NemotronHBlock(nn.Module):
    """``x + mixer(RMSNorm(x))``, the mixer by the pattern's letter."""

    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        mixer = {MAMBA: lambda: Mamba2Mixer(cfg, name="mixer"),
                 ATTENTION: lambda: NemotronHAttention(cfg, name="mixer"),
                 EXPERTS: lambda: _experts(cfg, "mixer")}[self.kind]()
        # A mixer names its own parts inside; the norm, the residual and
        # what a mixer does between its parts are the block's.
        with jax.named_scope("block"):
            return x + mixer(
                RMSNorm(cfg.norm_eps, cfg.param_dtype, name="norm")(x))


class NemotronH(nn.Module):
    """tokens [B, S] -> float32 logits [B, S, vocab], causal."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        emb = self.param("embedding", nn.with_logical_partitioning(
            nn.initializers.normal(1.0), (VOCAB, EMBED)),
            (cfg.vocab_size, cfg.hidden_dim), cfg.param_dtype)
        with jax.named_scope("embed"):
            x = emb.astype(cfg.dtype)[tokens]
        block_cls = NemotronHBlock
        if cfg.remat:
            remember_plan("remat", "block keeps " + " ".join(REMAT_KEEP))
            block_cls = nn.remat(
                NemotronHBlock,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *REMAT_KEEP))
        for i, kind in enumerate(cfg.pattern):
            x = block_cls(cfg, kind, name="block_{}".format(i))(x)
        head = self.param("lm_head", nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), (EMBED, VOCAB)),
            (cfg.hidden_dim, cfg.vocab_size), cfg.param_dtype)
        with jax.named_scope("head"):
            x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
            return jnp.dot(x, head.astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
