"""The state-space scan of a Mamba-2 layer (SSD, arXiv:2405.21060) in its
chunked form, and the short causal depthwise convolution that feeds it.

**The recurrence.** Per batch row and head h (P channels, N states; head h
reads the B and C of group ``h // (H / G)``), with ``dt_t > 0`` and
``A < 0`` scalars of the head::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        h_t [P, N], h_{-1} = 0
    y_t = h_t C_t + D x_t

Unrolled, ``y_l = sum_{s <= l} exp(sum_{s < i <= l} dt_i A) dt_s (C_l . B_s)
x_s + D x_l``: a causal attention whose scores ``C_l . B_s`` are weighted by
a decay that factors as ``exp(cum_l - cum_s)``, ``cum`` the running sum of
``dt A``.

**Three forms, one answer.**

- `ssd_reference`: the recurrence itself, position by position (a
  `lax.scan` over S in float32). Its state is [B, H, P, N]; differentiated,
  it holds that state at EVERY position (B S H P N floats: 34 GB at B 2,
  S 8192, H 64, P 64, N 128), which is why nothing trains through it.
- the quadratic form (the unrolled sum as one masked [S, S] product per
  head): no state at all, but B H S^2 scores. In
  ``tests/test_ssd.py``, as the third witness.
- `ssd_scan`, what the model runs: the sequence in chunks of ``chunk``
  positions. Inside a chunk the quadratic form, [chunk, chunk] a head
  (``scores = C B^T`` once a group, times the decay and ``dt_s``, times
  ``x``); a chunk's CLOSING STATE ``sum_s exp(cum_last - cum_s) dt_s x_s
  B_s^T``; the states CARRIED chunk to chunk (``h_c = exp(total_c) h_{c-1} +
  closing_c``, S / chunk steps written as one small product over chunks);
  and the carried state's part of the output, ``exp(cum_l) (h_{c-1} C_l)``.
  Nothing of [S, S] is formed: the largest intermediates are the decayed
  scores [B, S / chunk, heads, chunk, chunk] and the chunk states
  [B, S / chunk, heads, P, N].

**Precision.** Every large product (scores, scores x values, closing
states, state x C) takes bfloat16 operands (the input dtype) on the MXU and
accumulates in float32; ``dt A``, its cumulative sums, every exponent and the
chunk-to-chunk carry are float32.

**Memory, forward and backward.** The heads of one group share B and C and
nothing else, so `ssd_scan` runs a group at a time (`lax.map` over the G
groups), each under `jax.checkpoint`: the backward pass keeps the op's
INPUTS only and makes a group's intermediates again beside their gradients
(XLA's transposes of the same products). What is live is one group's: at the
shapes above 67 MB of decayed scores and 34 MB of chunk states, where all 64
heads at once would hold 537 MB and 268 MB in each direction. No
per-position state exists in either pass.

These are XLA products under the caller's `jax.named_scope`; a Pallas
kernel that fuses the decay into the score tile would carry a ``name=``
beginning ``ssd_`` (PERF.md section 7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: The `jax.named_scope`s a state-space mixer opens around its parts, in the
#: order a token meets them (``models/nemotron_h.py`` `Mamba2Mixer`; the
#: step's instructions under each are ``ssm_ops`` of the ``compiled``
#: record).
SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm")


def causal_conv1d(x, w, b):
    """``y_t = b + sum_j w_j * x_{t - (K - 1) + j}``, depthwise and causal:
    x [B, S, C], w [K, C], b [C] -> float32 [B, S, C]. Positions before the
    sequence's start are zero, per batch row; position t reads t - K + 1 ..
    t and nothing later. K shifted multiply-adds that XLA fuses into one
    pass over x."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32)
    w = w.astype(jnp.float32)
    y = jnp.broadcast_to(b.astype(jnp.float32), x.shape)
    for j in range(K):
        y = y + w[j] * padded[:, j:j + S]
    return y


def ssd_reference(x, dt, A, B, C, D):
    """The recurrence, position by position, in float32: x [B, S, H, P],
    dt [B, S, H] (after its softplus), A [H] (negative), B and C
    [B, S, G, N], D [H] -> [B, S, H, P]."""
    f32 = jnp.float32
    H, G = x.shape[2], B.shape[2]
    x, dt, A, D = x.astype(f32), dt.astype(f32), A.astype(f32), D.astype(f32)
    Bh = jnp.repeat(B.astype(f32), H // G, axis=2)
    Ch = jnp.repeat(C.astype(f32), H // G, axis=2)

    def step(h, at):
        x_t, dt_t, b_t, c_t = at  # [B, H, P], [B, H], [B, H, N], [B, H, N]
        h = jnp.exp(dt_t * A)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], f32)
    _, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x


def _group_scan(x, dt, A, B, C, D, chunk: int):
    """The chunked form for the heads of ONE group: x [Bt, S, Hg, P], dt
    [Bt, S, Hg] float32, A and D [Hg] float32, B and C [Bt, S, N]."""
    f32 = jnp.float32
    Bt, S, Hg, P = x.shape
    N, L, c = B.shape[-1], chunk, S // chunk
    xs = x.reshape(Bt, c, L, Hg, P)
    Bs, Cs = B.reshape(Bt, c, L, N), C.reshape(Bt, c, L, N)
    dth = jnp.moveaxis(dt.reshape(Bt, c, L, Hg), 3, 2)       # [Bt, c, Hg, L]
    cum = jnp.cumsum(dth * A[:, None], axis=-1)              # float32, <= 0
    total = cum[..., -1]                                     # [Bt, c, Hg]

    # Inside a chunk: (C B^T) under exp(cum_l - cum_s) dt_s for s <= l.
    scores = jnp.einsum("bcln,bcsn->bcls", Cs, Bs, preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    weights = (scores[:, :, None] * decay * dth[..., None, :]).astype(x.dtype)
    y = jnp.einsum("bchls,bcshp->bclhp", weights, xs,
                   preferred_element_type=f32)

    # A chunk's closing state, as if it started from zero.
    closing_w = jnp.exp(total[..., None] - cum) * dth         # [Bt, c, Hg, L]
    xw = (xs.astype(f32) * jnp.moveaxis(closing_w, 2, 3)[..., None]).astype(
        x.dtype)
    closing = jnp.einsum("bcsn,bcshp->bchpn", Bs, xw,
                         preferred_element_type=f32)

    # Carried chunk to chunk: entering chunk z, sum over c < z of
    # exp(total_{c+1} + .. + total_{z-1}) closing_c.
    run = jnp.cumsum(total, axis=1)                           # [Bt, c, Hg]
    before = jnp.tril(jnp.ones((c, c), bool), k=-1)           # [z, c]
    carry = jnp.exp(jnp.where(
        before[None, :, :, None],
        (run - total)[:, :, None] - run[:, None, :], -jnp.inf))
    entering = jnp.einsum("bzch,bchpn->bzhpn", carry, closing,
                          precision=jax.lax.Precision.HIGHEST)
    y = y + jnp.einsum("bcln,bchpn->bclhp", Cs, entering.astype(x.dtype),
                       preferred_element_type=f32) \
        * jnp.moveaxis(jnp.exp(cum), 2, 3)[..., None]
    y = y + D[:, None] * xs.astype(f32)
    return y.reshape(Bt, S, Hg, P).astype(x.dtype)


def ssd_scan(x, dt, A, B, C, D, chunk: int = 128):
    """The chunked scan (the module's docstring): x [B, S, H, P], dt
    [B, S, H] (after its softplus), A [H] (negative), B and C [B, S, G, N],
    D [H] -> [B, S, H, P] in x's dtype. ``S`` is a multiple of ``chunk``
    and ``H`` of ``G``."""
    Bt, S, H, P = x.shape
    G = B.shape[2]
    if S % chunk or H % G:
        raise ValueError("the scan takes whole chunks of {} and whole groups "
                         "of heads; got S={}, H={}, G={}".format(
                             chunk, S, H, G))
    f32 = jnp.float32
    by_group = (
        jnp.moveaxis(x.reshape(Bt, S, G, H // G, P), 2, 0),
        jnp.moveaxis(dt.astype(f32).reshape(Bt, S, G, H // G), 2, 0),
        A.astype(f32).reshape(G, H // G),
        jnp.moveaxis(B, 2, 0), jnp.moveaxis(C, 2, 0),
        D.astype(f32).reshape(G, H // G))
    one = jax.checkpoint(lambda args: _group_scan(*args, chunk=chunk))
    y = jax.lax.map(one, by_group)                     # [G, B, S, H / G, P]
    return jnp.moveaxis(y, 0, 2).reshape(Bt, S, H, P)
