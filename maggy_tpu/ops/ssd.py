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
  ``x``); the state CARRIED chunk to chunk (``h_c = exp(total_c) h_{c-1} +
  sum_s exp(cum_last - cum_s) dt_s x_s B_s^T``); and the carried state's
  part of the output, ``exp(cum_l) (h_{c-1} C_l)``. Nothing of [S, S] is
  formed.

**Two ways to multiply it, chosen by what the code can see** (as
``ops/attention.py`` `multi_head_attention` chooses; no switch, `scan_plan`
says which):

- on a TPU, where a chunk is one 128-lane tile, the states whole ones and
  a group's heads fill whole 128-lane slabs (`chunks_a_step`): Pallas kernels
  under a `jax.custom_vjp` (`kernel_scan`). A grid row is one batch row and
  group, its sequential axis the chunks. `ssd_fwd` reads a chunk's x, B, C
  and dt and writes y, the least bytes there are; the scores, each head's
  decayed [chunk, chunk] tile and the carried state [N, Hg P] (float32, a
  VMEM scratch zeroed at chunk 0) never leave VMEM. The gradient first
  walks the states once more (`ssd_states`: the state ENTERING each chunk,
  [B, S / chunk, H, P, N] in the input dtype, which is what the forward
  product takes it in), then the chunks in REVERSE (`ssd_bwd`), carrying
  the state's gradient in VMEM, and writes the gradients of x, B and C
  (summed over the group's heads in the kernel), of dt and of the running
  sums of ``dt A``. XLA keeps what is a pass over [B, S, H]: dt laid out a
  chunk a row, the running sums (a product with a triangle of ones), their
  transpose, and the gradients of A and D summed.
- elsewhere (a CPU; toy shapes): XLA products, a group at a time
  (`lax.map` over the G groups, each under `jax.checkpoint`), with the
  chunks' closing states and the carry over chunks as one small product.
  The largest intermediates are one group's decayed scores [B, S / chunk,
  heads, chunk, chunk] and chunk states [B, S / chunk, heads, P, N].

**Precision**, of both. Every large product (scores, scores x values, the
state's step, state x C, and their transposes) takes bfloat16 operands (the
input dtype) on the MXU and accumulates in float32; ``dt A``, its cumulative
sums, every exponent (a decay is ``exp(cum_l - cum_s)`` under the causal
mask, by difference), the carried state and its gradient are float32. The
entering state is rounded to the input dtype where a product takes it, and
nowhere else.

**Memory, forward and backward.** Either way the backward pass keeps the
op's INPUTS only, and no per-position state exists in either pass. The
kernels' forward holds nothing in HBM but its operands; their backward the
entering states (134 MB at the shapes above, live inside one block's
backward). XLA's products make a group's intermediates again beside their
gradients (its transposes of the same products): 67 MB of decayed scores
and 34 MB of chunk states a group, where all 64 heads at once would hold
537 MB and 268 MB in each direction. The kernels' forward rule names its
output `REMAT_KEEP`, so a rematerialised caller that keeps it runs
`ssd_fwd` once.

**The convolution that feeds it** (`conv_silu`: ``silu(causal_conv1d(xBC,
w, b))``, 4 taps over [B, S, 6144] in the Nemotron cell), chosen the same
way (`conv_plan` says ``conv pallas <rows>x<cols>`` or ``conv xla``):

- on a TPU, where the channels and their first column tile by 128 lanes and
  S by a strip of 16 rows (`conv_tile`): two Pallas kernels under a
  `jax.custom_vjp` (`kernel_conv`), each a pass over the data bound by its
  bytes. `ssd_conv_fwd` reads a [rows, cols] block of xBC IN PLACE in the
  in-projection's output (a column block's index is offset by the start),
  and a strip of 16 rows before it (zero at the sequence's start, per
  batch row), applies the taps and the bias in float32 in
  `causal_conv1d`'s order and the silu, and writes the input dtype.
  `ssd_conv_bwd` reads the block, the strips before and after it, dy and
  dy's strip after; makes the pre-activation again, ``g = dy silu'(pre)``
  (zero past the sequence's end), writes ``dx_t = sum_j w_j g_{t + K - 1 -
  j}`` and sums dw and db in a float32 VMEM scratch over a column block's
  batch rows and blocks. Its residuals are the inputs; dx is padded to the
  wider array's width, which XLA joins to dz and ddt inside the
  in-projection's backward products.
- elsewhere: `causal_conv1d` of the slice and XLA's silu.

The kernels carry ``name=``s beginning ``ssd_``, and the caller's
`jax.named_scope` in their ``op_name``, forward and backward.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from maggy_tpu.ops.attention import _LANES, _NN, _NT, _dot

#: The `jax.named_scope`s a state-space mixer opens around its parts, in the
#: order a token meets them (``models/nemotron_h.py`` `Mamba2Mixer`; the
#: step's instructions under each are ``ssm_ops`` of the ``compiled``
#: record).
SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm")
#: The name the kernels' forward rule gives the scan's output
#: (`jax.ad_checkpoint.checkpoint_name`). A rematerialised caller that keeps
#: it (``save_only_these_names(*REMAT_KEEP)``) runs `ssd_fwd` once: the
#: residuals are inputs, which it makes again anyway.
REMAT_KEEP = ("ssd_out",)


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


def _xla_scan(x, dt, A, B, C, D, chunk: int):
    """`ssd_scan` as XLA products, a group at a time."""
    Bt, S, H, P = x.shape
    G = B.shape[2]
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


# ---------------------------------------------------------------- kernels
#
# One (batch row, group) is a grid row and the chunks its sequential axis.
# A chunk's per-head scalars (dt and the running sums) come as ROWS
# [Hg, L], a position a lane: what varies along a tile's columns. What
# varies along its rows is their transpose, COLUMNS [L, 128] with a head a
# lane, which the kernels make on the XLU (an [L, Hg] operand would be 16
# times its size in HBM's tiles). Heads narrower than 128 lanes share a
# 128-lane SLAB of x and y; a product is kept to one head of a slab by
# zeros in the other heads' lanes.


def _tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def chunks_a_step(S: int, H: int, P: int, G: int, N: int,
                  chunk: int) -> Optional[int]:
    """How many chunks one grid step of the kernels holds at these shapes,
    or None where they cannot tile: a chunk is one 128-lane tile and a
    state whole ones, a group's heads fill whole slabs, and a head is a
    whole slab's share or whole slabs."""
    if S % chunk or H % G:
        return None
    Hg = H // G
    if chunk != _LANES or N % _LANES or (Hg * P) % _LANES or Hg > _LANES \
            or (P % _LANES and _LANES % P):
        return None
    return max(k for k in (8, 4, 2, 1) if (S // chunk) % k == 0)


def _kernel_chunks(S, H, P, G, N, chunk) -> Optional[int]:
    """`chunks_a_step` where the kernels run at all: on a TPU."""
    return chunks_a_step(S, H, P, G, N, chunk) if _tpu_backend() else None


def _slabs(Hg: int, P: int):
    """(lanes of a slab, heads in it, slabs of a group)."""
    slab = max(P, _LANES)
    return slab, slab // P, Hg * P // slab


def _columns(rows):
    """Per-head rows [Hg, L] as columns [L, 128], head h in lane h."""
    Hg, L = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((_LANES - Hg, L), rows.dtype)], axis=0).T


def _head_lanes(per_head, q: int, pack: int, P: int):
    """[rows, 128] -> [rows, pack P]: head ``q pack + j``'s column across
    the P lanes head j has in slab q."""
    rows = per_head.shape[0]
    out = jnp.broadcast_to(per_head[:, q * pack:q * pack + 1],
                           (rows, pack * P))
    lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    for j in range(1, pack):
        out = jnp.where(lane >= j * P, jnp.broadcast_to(
            per_head[:, q * pack + j:q * pack + j + 1], out.shape), out)
    return out


def _lanes_of(j: int, pack: int, P: int, v):
    """``v`` in head j's lanes and zeros in the slab's other heads'."""
    if pack == 1:
        return v
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    return jnp.where((lane >= j * P) & (lane < (j + 1) * P), v,
                     jnp.zeros_like(v))


def _transposed(v):
    """A [L, N] tile of MXU operands as [N, L] (through float32, which the
    XLU transposes; the round trip is exact)."""
    return v.astype(jnp.float32).T.astype(v.dtype)


def _each_chunk(k: int, L: int, body, reverse: bool = False):
    """``body(i, rows)`` for the k chunks of a grid step and their rows of
    the step's positions, in order (or in reverse). A loop on the core: its
    body is traced and lowered once, where unrolled a step of four chunks
    and eight heads cost every run's set-up seconds."""
    from jax.experimental import pallas as pl

    if k == 1:
        return body(0, slice(0, L))

    def chunk(n, _):
        i = k - 1 - n if reverse else n
        body(i, pl.ds(pl.multiple_of(i * L, L), L))

    jax.lax.fori_loop(0, k, chunk, None)


def _state_step(state, x_ref, b, cumc, dtc, rows, Hg, P):
    """``h = exp(total) h + (x w)^T B`` on the carried state [N, Hg P]
    (its transpose, so that no product takes a transposed operand but
    B's)."""
    f32 = jnp.float32
    L = b.shape[0]
    slab, pack, slabs = _slabs(Hg, P)
    total = cumc[L - 1:L]                                      # [1, 128]
    closing_w = jnp.exp(total - cumc) * dtc                    # [L, 128]
    carried = jnp.exp(total)
    bt = _transposed(b)
    for q in range(slabs):
        lanes = slice(q * slab, (q + 1) * slab)
        xw = (x_ref[rows, lanes].astype(f32)
              * _head_lanes(closing_w, q, pack, P)).astype(x_ref.dtype)
        state[:, lanes] = _head_lanes(carried, q, pack, P) * state[:, lanes] \
            + _dot(bt, xw, _NN)


def _fwd_kernel(x_ref, b_ref, c_ref, dtr_ref, cumr_ref, d_ref, y_ref, state,
                *, k: int, L: int, P: int):
    """grid (B, G, S / (k L)): the chunks of one batch row and group in
    order, the state carried in ``state``. y = (C B^T under the decay) x +
    exp(cum) (C h) + D x, and then the state's own step."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    Hg = dtr_ref.shape[1]
    slab, pack, slabs = _slabs(Hg, P)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    causal = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)

    def chunk(i, rows):
        b, c = b_ref[rows], c_ref[rows]
        cumr, dtr = cumr_ref[i], dtr_ref[i]                    # [Hg, L]
        cumc, dtc = _columns(cumr), _columns(dtr)              # [L, 128]
        scores = _dot(c, b, _NT)                               # [l, s]
        from_state = _dot(c, state[...].astype(c.dtype), _NN)  # [L, Hg P]
        out_w = jnp.exp(cumc)
        for q in range(slabs):
            lanes = slice(q * slab, (q + 1) * slab)
            xq = x_ref[rows, lanes]
            y = from_state[:, lanes] * _head_lanes(out_w, q, pack, P) \
                + d_ref[:, lanes] * xq.astype(f32)
            for j in range(pack):
                h = q * pack + j
                decay = jnp.exp(jnp.where(
                    causal, cumc[:, h:h + 1] - cumr[h:h + 1], -jnp.inf))
                weights = (scores * decay * dtr[h:h + 1]).astype(xq.dtype)
                y = y + _dot(weights, _lanes_of(j, pack, P, xq), _NN)
            y_ref[rows, lanes] = y.astype(y_ref.dtype)
        _state_step(state, x_ref, b, cumc, dtc, rows, Hg, P)

    _each_chunk(k, L, chunk)


def _states_kernel(x_ref, b_ref, dtr_ref, cumr_ref, s_ref, state, *, k: int,
                   L: int, P: int):
    """The forward kernel's state steps alone: writes the state ENTERING
    each chunk, in the dtype the products take it in."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def chunk(i, rows):
        s_ref[i] = state[...].astype(s_ref.dtype)
        _state_step(state, x_ref, b_ref[rows], _columns(cumr_ref[i]),
                    _columns(dtr_ref[i]), rows, dtr_ref.shape[1], P)

    _each_chunk(k, L, chunk)


def _bwd_kernel(x_ref, b_ref, c_ref, g_ref, dtr_ref, cumr_ref, d_ref, s_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dd_ref, grad, *,
                k: int, L: int, P: int):
    """grid (B, G, S / (k L)), the chunks in REVERSE: ``grad`` carries the
    gradient of the state [N, Hg P] LEAVING the chunk at hand. The chunk's
    tile is taken transposed, [s, l], so that dx = W^T dy and dW^T = x dy^T
    are plain products; the per-head sums come out as columns, are set a
    head a lane and turned to rows once a chunk."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    Hg = dtr_ref.shape[1]
    slab, pack, slabs = _slabs(Hg, P)

    @pl.when(pl.program_id(2) == 0)
    def _():
        grad[...] = jnp.zeros_like(grad)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    causal_t = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) \
        <= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (L, _LANES), 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (Hg, L), 0)
    last = jax.lax.broadcasted_iota(jnp.int32, (Hg, L), 1) == L - 1

    def as_rows(per_head):  # [L, 128], a head a lane -> [Hg, L]
        return per_head.T[:Hg]

    def chunk(i, rows):
        b, c = b_ref[rows], c_ref[rows]
        cumr, dtr = cumr_ref[i], dtr_ref[i]                    # [Hg, L]
        cumc, dtc = _columns(cumr), _columns(dtr)              # [L, 128]
        entering = s_ref[i]                                    # [N, Hg P]
        leaving_g = grad[...]
        total = cumc[L - 1:L]
        out_w = jnp.exp(cumc)
        closing_w = jnp.exp(total - cumc) * dtc
        carried = jnp.exp(total)
        scores_t = _dot(b, c, _NT)                             # [s, l]
        from_state = _dot(c, entering, _NN)                    # [L, Hg P]
        dxw = _dot(b, leaving_g.astype(b.dtype), _NN)          # [L, Hg P]
        ct = _transposed(c)
        dscores_t = jnp.zeros((L, L), f32)
        db = dc = jnp.zeros(b.shape, f32)
        # Per head, a head a lane: the sums over l of dW^T W^T / dt, of dy
        # (C h) and of d(x w) x; a head a row: the sums over s of dW^T W^T.
        d_w = d_out = d_close = jnp.zeros((L, _LANES), f32)
        d_cum_l = jnp.zeros((Hg, L), f32)
        d_carried = jnp.zeros((Hg, 1), f32)
        for q in range(slabs):
            lanes = slice(q * slab, (q + 1) * slab)
            xq, gq = x_ref[rows, lanes], g_ref[rows, lanes]
            xf, gf = xq.astype(f32), gq.astype(f32)
            cw = _head_lanes(closing_w, q, pack, P)
            g_out = (gf * _head_lanes(out_w, q, pack, P)).astype(xq.dtype)
            xw = (xf * cw).astype(xq.dtype)
            dx = d_ref[:, lanes] * gf + dxw[:, lanes] * cw
            of_out = gf * from_state[:, lanes]
            of_close = dxw[:, lanes] * xf
            state_g = leaving_g[:, lanes]
            of_carried = jnp.sum(state_g * entering[:, lanes].astype(f32),
                                 axis=0, keepdims=True)        # [1, slab]
            for j in range(pack):
                h = q * pack + j
                decay = jnp.exp(jnp.where(
                    causal_t, cumr[h:h + 1] - cumc[:, h:h + 1], -jnp.inf))
                dt_s = dtc[:, h:h + 1]
                plain = scores_t * decay
                weights_t = (plain * dt_s).astype(xq.dtype)
                dx = dx + _dot(weights_t, _lanes_of(j, pack, P, gq), _NN)
                dweights_t = _dot(_lanes_of(j, pack, P, xq), gq, _NT)
                dscores_t = dscores_t + dweights_t * (decay * dt_s)
                moved = dweights_t * plain
                here = head_lane == h
                d_w = jnp.where(here, jnp.sum(
                    moved, axis=1, keepdims=True), d_w)
                d_cum_l = jnp.where(head_row == h, jnp.sum(
                    moved * dt_s, axis=0, keepdims=True), d_cum_l)
                d_out = jnp.where(here, jnp.sum(
                    _lanes_of(j, pack, P, of_out), axis=1, keepdims=True),
                    d_out)
                d_close = jnp.where(here, jnp.sum(
                    _lanes_of(j, pack, P, of_close), axis=1, keepdims=True),
                    d_close)
                d_carried = jnp.where(head_row[:, :1] == h, jnp.sum(
                    _lanes_of(j, pack, P, of_carried), axis=1, keepdims=True),
                    d_carried)
            dx_ref[rows, lanes] = dx.astype(dx_ref.dtype)
            dc = dc + _dot(g_out, entering[:, lanes], _NT)
            db = db + _dot(xw, leaving_g[:, lanes].astype(xq.dtype), _NT)
            grad[:, lanes] = _head_lanes(carried, q, pack, P) * state_g \
                + _dot(ct, g_out, _NN)
            dd_ref[:, lanes] += jnp.sum(gf * xf, axis=0, keepdims=True)
        db_ref[rows] = (db + _dot(dscores_t.astype(c.dtype), c, _NN)).astype(
            db_ref.dtype)
        dc_ref[rows] = (dc + _dot(dscores_t.T.astype(b.dtype), b, _NN)).astype(
            dc_ref.dtype)
        # In rows, all heads at once: dt's own gradient and cum's.
        d_w, d_out, d_close = as_rows(d_w), as_rows(d_out), as_rows(d_close)
        total_r = cumr[:, L - 1:L]                             # [Hg, 1]
        closing_r = jnp.exp(total_r - cumr)
        through_close = d_close * closing_r * dtr
        ddt_ref[i] = d_w + d_close * closing_r
        d_total = jnp.sum(through_close, axis=1, keepdims=True) \
            + d_carried * jnp.exp(total_r)
        dcum_ref[i] = d_cum_l + d_out * jnp.exp(cumr) - dtr * d_w \
            - through_close + jnp.where(last, d_total, 0.0)

    _each_chunk(k, L, chunk, reverse=True)


def _rows(dt, A, G: int, chunk: int):
    """dt [B, S, H] and the running sums of ``dt A`` inside each chunk, as
    the per-head rows the kernels read: float32 [B, G, S / chunk, Hg,
    chunk]. The sums are a product with a triangle of ones, all of float32
    kept (XLA's own cumulative sum along a minor axis takes the chip ten
    times as long)."""
    Bt, S, H = dt.shape
    dtr = dt.reshape(Bt, S // chunk, chunk, G, H // G).transpose(0, 3, 1, 4, 2)
    until = jnp.triu(jnp.ones((chunk, chunk), dt.dtype))       # [s, l >= s]
    return dtr, jnp.einsum("bgchs,sl->bgchl", dtr * A.reshape(G, 1, H // G, 1),
                           until, precision=jax.lax.Precision.HIGHEST)


def _kernel_operands(x, B, C, D):
    """x, B, C with their channels in one axis, D a channel."""
    Bt, S, H, P = x.shape
    G, N = B.shape[2:]
    return (x.reshape(Bt, S, H * P), B.reshape(Bt, S, G * N),
            C.reshape(Bt, S, G * N),
            jnp.repeat(D, P).reshape(G, 1, H // G * P))


def _grid(Bt, S, Hg, P, G, N, chunk, k, reverse=False):
    """The grid and the block of each kind of operand: positions by
    channels, positions by states, per-head rows, D, entering states."""
    from jax.experimental import pallas as pl

    steps, span = S // (k * chunk), k * chunk

    def at(i):
        return steps - 1 - i if reverse else i

    return (Bt, G, steps), {
        "x": pl.BlockSpec((None, span, Hg * P), lambda b, g, i: (b, at(i), g)),
        "bc": pl.BlockSpec((None, span, N), lambda b, g, i: (b, at(i), g)),
        "rows": pl.BlockSpec((None, None, k, Hg, chunk),
                             lambda b, g, i: (b, g, at(i), 0, 0)),
        "d": pl.BlockSpec((None, 1, Hg * P), lambda b, g, i: (g, 0, 0)),
        "states": pl.BlockSpec((None, k, None, N, Hg * P),
                               lambda b, g, i: (b, at(i), g, 0, 0)),
    }


# The calls are jitted so that a model of many state-space blocks, each
# traced again by its remat, traces and lowers every kernel once.
_STATIC = ("chunk", "k", "interpret")


def _pallas(kernel, name: str, grid, chunk: int, k: int, interpret: bool,
            P: int, N: int, Hg: int, **specs):
    """A `pallas_call` of one of the three kernels: the carried [N, Hg P]
    float32 in scratch, the chunk axis sequential."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(kernel, k=k, L=chunk, P=P), grid=grid,
        scratch_shapes=[pltpu.VMEM((N, Hg * P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name, **specs)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(x, dt, A, B, C, D, chunk: int, k: int, interpret: bool):
    """y with its channels in one axis, [B, S, H P]."""
    Bt, S, H, P = x.shape
    G, N = B.shape[2:]
    grid, block = _grid(Bt, S, H // G, P, G, N, chunk, k)
    x2, b2, c2, d2 = _kernel_operands(x, B, C, D)
    return _pallas(
        _fwd_kernel, "ssd_fwd", grid, chunk, k, interpret, P, N, H // G,
        in_specs=[block["x"], block["bc"], block["bc"], block["rows"],
                  block["rows"], block["d"]],
        out_specs=block["x"],
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
    )(x2, b2, c2, *_rows(dt, A, G, chunk), d2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def kernel_scan(x, dt, A, B, C, D, chunk: int, k: int,
                interpret: bool = False):
    """`ssd_scan` as Pallas kernels, ``k`` chunks a grid step (float32 dt,
    A and D; shapes that `chunks_a_step` takes): `ssd_fwd`, and for the
    gradient `ssd_states` and `ssd_bwd`, whose residuals are the inputs."""
    return _forward(x, dt, A, B, C, D, chunk=chunk, k=k,
                    interpret=interpret).reshape(x.shape)


def _kernel_scan_fwd(x, dt, A, B, C, D, chunk, k, interpret):
    # Named with its channels in one axis, as the kernel wrote it: a caller
    # that keeps it keeps that array, and its own reshape undoes ours.
    y = checkpoint_name(_forward(x, dt, A, B, C, D, chunk=chunk, k=k,
                                 interpret=interpret), REMAT_KEEP[0])
    return y.reshape(x.shape), (x, dt, A, B, C, D)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _entering(x2, b2, dtr, cumr, chunk: int, k: int, interpret: bool):
    """The state entering each chunk, [B, S / chunk, G, N, Hg P] in x's
    dtype (`ssd_states`)."""
    Bt, S, _ = x2.shape
    _, G, _, Hg, _ = dtr.shape
    N, P = b2.shape[2] // G, x2.shape[2] // (G * Hg)
    grid, block = _grid(Bt, S, Hg, P, G, N, chunk, k)
    return _pallas(
        _states_kernel, "ssd_states", grid, chunk, k, interpret, P, N, Hg,
        in_specs=[block["x"], block["bc"], block["rows"], block["rows"]],
        out_specs=block["states"],
        out_shape=jax.ShapeDtypeStruct(
            (Bt, S // chunk, G, N, Hg * P), x2.dtype),
    )(x2, b2, dtr, cumr)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(x, dt, A, B, C, D, g, chunk: int, k: int, interpret: bool):
    from jax.experimental import pallas as pl

    Bt, S, H, P = x.shape
    G, N = B.shape[2:]
    Hg, f32 = H // G, jnp.float32
    (dtr, cumr), rows_vjp = jax.vjp(
        functools.partial(_rows, G=G, chunk=chunk), dt, A)
    x2, b2, c2, d2 = _kernel_operands(x, B, C, D)
    entering = _entering(x2, b2, dtr, cumr, chunk=chunk, k=k,
                         interpret=interpret)
    grid, block = _grid(Bt, S, Hg, P, G, N, chunk, k, reverse=True)
    dx, db, dc, ddt, dcum, dd = _pallas(
        _bwd_kernel, "ssd_bwd", grid, chunk, k, interpret, P, N, Hg,
        in_specs=[block["x"], block["bc"], block["bc"], block["x"],
                  block["rows"], block["rows"], block["d"], block["states"]],
        out_specs=[block["x"], block["bc"], block["bc"], block["rows"],
                   block["rows"],
                   pl.BlockSpec((None, None, 1, Hg * P),
                                lambda b, g, i: (b, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct(b2.shape, B.dtype),
                   jax.ShapeDtypeStruct(c2.shape, C.dtype),
                   jax.ShapeDtypeStruct(dtr.shape, f32),
                   jax.ShapeDtypeStruct(dtr.shape, f32),
                   jax.ShapeDtypeStruct((Bt, G, 1, Hg * P), f32)],
    )(x2, b2, c2, g.reshape(x2.shape), dtr, cumr, d2, entering)
    # The rows' gradients back through the running sums, to dt's and A's.
    ddt, dA = rows_vjp((ddt, dcum))
    return (dx.reshape(x.shape), ddt, dA, db.reshape(B.shape),
            dc.reshape(C.shape), jnp.sum(dd.reshape(Bt, H, P), axis=(0, 2)))


def _kernel_scan_bwd(chunk, k, interpret, inputs, g):
    return _backward(*inputs, g, chunk=chunk, k=k, interpret=interpret)


kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def scan_plan(S: int, H: int, P: int, G: int, N: int, chunk: int) -> str:
    """What multiplies in `ssd_scan` at these shapes here: ``pallas`` and
    the positions a grid step holds, or ``xla_products``."""
    k = _kernel_chunks(S, H, P, G, N, chunk)
    return "xla_products" if k is None else "pallas {}".format(k * chunk)


def ssd_scan(x, dt, A, B, C, D, chunk: int = 128):
    """The chunked scan (the module's docstring): x [B, S, H, P], dt
    [B, S, H] (after its softplus), A [H] (negative), B and C [B, S, G, N],
    D [H] -> [B, S, H, P] in x's dtype. ``S`` is a multiple of ``chunk``
    and ``H`` of ``G``. On a TPU, at shapes the kernels tile
    (`chunks_a_step`), the Pallas kernels; else the XLA products."""
    Bt, S, H, P = x.shape
    G, N = B.shape[2:]
    if S % chunk or H % G:
        raise ValueError("the scan takes whole chunks of {} and whole groups "
                         "of heads; got S={}, H={}, G={}".format(
                             chunk, S, H, G))
    k = _kernel_chunks(S, H, P, G, N, chunk)
    if k is None:
        return _xla_scan(x, dt, A, B, C, D, chunk)
    f32 = jnp.float32
    return kernel_scan(x, dt.astype(f32), A.astype(f32), B, C, D.astype(f32),
                       chunk, k, False)


# ------------------------------------------------------ convolution kernels
#
# ``silu(causal_conv1d(x, w, b))`` in one pass over x, where x is a column
# range of a wider array (the mixer's in-projection output), read in place.
# A grid step holds a [rows, cols] block of one batch row; its arithmetic
# walks the block in strips of `_STRIP` rows (one bfloat16 tile, two float32
# vreg rows), a tap's shift by k rows being a roll of the strip and of the
# strip before it, one row of the roll chosen from each. The rows before the
# block (and, for the gradient, after it) come as a halo, a second block of
# one strip over the same array, zero past either end of the sequence.

#: Rows of a strip and of a halo: one bfloat16 (16, 128) tile.
_STRIP = 16
#: The largest block a convolution kernel's grid step holds: rows of the
#: sequence by channels (`conv_tile`).
_CONV_ROWS, _CONV_COLS = 512, 1024
#: Lanes of a strip the kernels' arithmetic takes at a time.
_PASS_LANES = 512


def conv_tile(S: int, C: int, start: int) -> Optional[tuple]:
    """The convolution kernels' block at these shapes, (rows, cols), or
    None where they cannot tile: C channels from column ``start`` of a
    wider array, S positions. Columns are whole 128-lane tiles that divide
    both C and ``start`` (so a block reads the wider array in place); rows
    a power of two of at least a strip that divides S."""
    if C % _LANES or start % _LANES:
        return None
    rows = next((r for r in (_CONV_ROWS, 256, 128, 64, 32, _STRIP)
                 if S % r == 0), None)
    cols = next(c for c in (_CONV_COLS, 512, 256, _LANES)
                if C % c == 0 and start % c == 0)
    return None if rows is None else (rows, cols)


def _kernel_tile(S, C, start) -> Optional[tuple]:
    """`conv_tile` where the kernels run at all: on a TPU."""
    return conv_tile(S, C, start) if _tpu_backend() else None


def conv_plan(S: int, C: int, start: int) -> str:
    """What runs `conv_silu` at these shapes here: ``conv pallas`` and the
    block ``<rows>x<cols>``, or ``conv xla``."""
    tile = _kernel_tile(S, C, start)
    return "conv xla" if tile is None else "conv pallas {}x{}".format(*tile)


def _shifted(v, before, k: int):
    """Rows t - k of a strip: ``v`` where t >= k, the rows ``before`` it
    (the previous strip) where t < k. Rows t + k of a strip ``g`` are rows
    t - (strip - k) of the strip after it, ``_shifted(after, g, strip -
    k)``."""
    from jax.experimental.pallas import tpu as pltpu

    if k == 0:
        return v
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    return jnp.where(row < k, pltpu.roll(before, k, 0), pltpu.roll(v, k, 0))


def _passes(cols: int):
    """A strip's columns in passes of `_PASS_LANES`: static lane slices, so
    that a pass's values stay in the vector registers."""
    lanes = min(cols, _PASS_LANES)
    return [slice(c, c + lanes) for c in range(0, cols, lanes)]


def _taps(v, before, K: int):
    """``x_{t - (K - 1) + j}`` of a strip for j = 0 .. K - 1."""
    return [_shifted(v, before, K - 1 - j) for j in range(K)]


def _pre(xs, w_ref, b_ref, cs):
    """``b + sum_j w_j x_{t - (K - 1) + j}`` over a strip's columns ``cs``
    from its `_taps`, float32, in `causal_conv1d`'s order."""
    pre = jnp.broadcast_to(b_ref[:, cs], xs[0].shape)
    for j, x in enumerate(xs):
        pre = pre + w_ref[j:j + 1, cs] * x
    return pre


def _rows_before(x_ref, before_ref, before_rows, cs):
    """The strip before a strip's columns ``cs``, float32: the halo for
    strip 0 (zero in the sequence's first block), else the block's own."""
    from jax.experimental import pallas as pl

    if before_rows is None:
        return jnp.where(pl.program_id(2) == 0, 0.0,
                         before_ref[:, cs].astype(jnp.float32))
    return x_ref[before_rows, cs].astype(jnp.float32)


def _each_strip(n: int, body):
    """``body(rows, before_rows)`` for strips 0 .. n - 1 of a block, in
    order, ``before_rows`` None for strip 0 (whose rows before are the
    halo): strip 0, then the others as a loop on the core."""
    from jax.experimental import pallas as pl

    def strip(s, _):
        at = pl.multiple_of(s * _STRIP, _STRIP)
        body(pl.ds(at, _STRIP), pl.ds(at - _STRIP, _STRIP))

    body(pl.ds(0, _STRIP), None)
    if n > 1:
        jax.lax.fori_loop(1, n, strip, None)


def _conv_fwd_kernel(x_ref, before_ref, w_ref, b_ref, y_ref):
    """grid (C / cols, B, S / rows): y = silu(pre), pre from the block's
    rows and the halo before it (zero at the sequence's start)."""

    def strip(rows, before_rows):
        for cs in _passes(x_ref.shape[1]):
            before = _rows_before(x_ref, before_ref, before_rows, cs)
            pre = _pre(_taps(x_ref[rows, cs].astype(jnp.float32), before,
                             w_ref.shape[0]), w_ref, b_ref, cs)
            y_ref[rows, cs] = jax.nn.silu(pre).astype(y_ref.dtype)

    _each_strip(x_ref.shape[0] // _STRIP, strip)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     w_ref, b_ref, dx_ref, dwb_ref, g, acc):
    """grid (C / cols, B, S / rows), a column block's batch rows and
    blocks in order. A strip's taps make ``g`` = dy silu'(pre) and add
    dw_j += sum_t g_t x_{t - (K - 1) + j}, db += sum_t g_t into ``acc``
    [K + 1, 8, cols] (written as [K + 1, cols] once the column block's last
    step is done); ``g`` is kept over the block's rows and one strip after
    (zero past the sequence's end), from which dx_t = sum_j w_j g_{t + K -
    1 - j}."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    K = w_ref.shape[0]
    n = x_ref.shape[0] // _STRIP
    i, b = pl.program_id(2), pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(2) - 1

    @pl.when(first & (b == 0))
    def _():
        acc[...] = jnp.zeros_like(acc)

    def grad(xs, dy, cs):
        pre = _pre(xs, w_ref, b_ref, cs)
        sig = jax.nn.sigmoid(pre)
        return dy.astype(f32) * sig * (1.0 + pre * (1.0 - sig))

    def fold(v):  # [strip, lanes] -> [8, lanes]: whole vregs added
        return v[:8] + v[8:]

    def grad_strip(rows, before_rows):
        for cs in _passes(x_ref.shape[1]):
            xs = _taps(x_ref[rows, cs].astype(f32), _rows_before(
                x_ref, before_ref, before_rows, cs), K)
            gc = grad(xs, dy_ref[rows, cs], cs)
            g[rows, cs] = gc
            for j in range(K):
                acc[j, :, cs] += fold(gc * xs[j])
            acc[K, :, cs] += fold(gc)

    _each_strip(n, grad_strip)
    tail = slice((n - 1) * _STRIP, n * _STRIP)
    for cs in _passes(x_ref.shape[1]):
        g[n * _STRIP:, cs] = jnp.where(last, 0.0, grad(
            _taps(after_ref[:, cs].astype(f32), x_ref[tail, cs].astype(f32),
                  K), dy_after_ref[:, cs], cs))

    def dx_strip(rows, _):
        ahead_rows = pl.ds(rows.start + _STRIP, _STRIP)
        for cs in _passes(x_ref.shape[1]):
            gc, ga = g[rows, cs], g[ahead_rows, cs]
            dx = w_ref[K - 1:K, cs] * gc
            for k in range(1, K):
                dx = dx + w_ref[K - 1 - k:K - k, cs] * _shifted(
                    ga, gc, _STRIP - k)
            dx_ref[rows, cs] = dx.astype(dx_ref.dtype)

    _each_strip(n, dx_strip)

    @pl.when(last & (b == pl.num_programs(1) - 1))
    def _():
        dwb_ref[...] = jnp.sum(acc[...], axis=1)


def _conv_specs(S, start, rows, cols):
    """The blocks of the convolution kernels' operands, grid (C / cols, B,
    S / rows): a block of x in place in the wider array, the strip before
    it and after it (clamped inside the sequence; the kernels mask what is
    past its ends), a block of a [B, S, C] array, and a column block of
    the [K, C] taps and of a [1, C] row."""
    from jax.experimental import pallas as pl

    if start % cols or rows % _STRIP or S % rows:
        raise ValueError("a block of {} x {} from column {} does not tile S "
                         "{}".format(rows, cols, start, S))
    at, per, strips = start // cols, rows // _STRIP, S // _STRIP
    return {
        "x": pl.BlockSpec((None, rows, cols), lambda j, b, i: (b, i, at + j)),
        "before": pl.BlockSpec(
            (None, _STRIP, cols),
            lambda j, b, i: (b, jnp.maximum(i * per - 1, 0), at + j)),
        "after": pl.BlockSpec(
            (None, _STRIP, cols),
            lambda j, b, i: (b, jnp.minimum((i + 1) * per, strips - 1),
                             at + j)),
        "y": pl.BlockSpec((None, rows, cols), lambda j, b, i: (b, i, j)),
        "y_after": pl.BlockSpec(
            (None, _STRIP, cols),
            lambda j, b, i: (b, jnp.minimum((i + 1) * per, strips - 1), j)),
        "col": lambda k: pl.BlockSpec((k, cols), lambda j, b, i: (0, j)),
    }


_CONV_STATIC = ("start", "rows", "cols", "interpret")


@functools.partial(jax.jit, static_argnames=_CONV_STATIC)
def _conv_forward(x, w, b, start: int, rows: int, cols: int,
                  interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Bt, S, _ = x.shape
    K, C = w.shape
    spec = _conv_specs(S, start, rows, cols)
    return pl.pallas_call(
        _conv_fwd_kernel, grid=(C // cols, Bt, S // rows),
        in_specs=[spec["x"], spec["before"], spec["col"](K), spec["col"](1)],
        out_specs=spec["y"],
        out_shape=jax.ShapeDtypeStruct((Bt, S, C), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=interpret, name="ssd_conv_fwd",
    )(x, x, w.astype(jnp.float32), b.astype(jnp.float32).reshape(1, C))


@functools.partial(jax.jit, static_argnames=_CONV_STATIC)
def _conv_backward(x, w, b, dy, start: int, rows: int, cols: int,
                   interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Bt, S, width = x.shape
    K, C = w.shape
    f32 = jnp.float32
    spec = _conv_specs(S, start, rows, cols)
    dx, dwb = pl.pallas_call(
        _conv_bwd_kernel, grid=(C // cols, Bt, S // rows),
        in_specs=[spec["x"], spec["before"], spec["after"], spec["y"],
                  spec["y_after"], spec["col"](K), spec["col"](1)],
        out_specs=[spec["y"], spec["col"](K + 1)],
        out_shape=[jax.ShapeDtypeStruct((Bt, S, C), x.dtype),
                   jax.ShapeDtypeStruct((K + 1, C), f32)],
        scratch_shapes=[pltpu.VMEM((rows + _STRIP, cols), f32),
                        pltpu.VMEM((K + 1, 8, cols), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="ssd_conv_bwd",
    )(x, x, x, dy, dy, w.astype(f32), b.astype(f32).reshape(1, C))
    dx = jax.lax.pad(dx, jnp.zeros((), dx.dtype),
                     ((0, 0, 0), (0, 0, 0), (start, width - start - C, 0)))
    return dx, dwb[:K].astype(w.dtype), dwb[K].astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def kernel_conv(x, w, b, start: int, rows: int, cols: int,
                interpret: bool = False):
    """`conv_silu` as Pallas kernels, a grid step a [rows, cols] block
    (shapes that `conv_tile` takes): `ssd_conv_fwd`, and for the gradient
    `ssd_conv_bwd`, whose residuals are the inputs."""
    return _conv_forward(x, w, b, start=start, rows=rows, cols=cols,
                         interpret=interpret)


def _kernel_conv_fwd(x, w, b, start, rows, cols, interpret):
    return _conv_forward(x, w, b, start=start, rows=rows, cols=cols,
                         interpret=interpret), (x, w, b)


def _kernel_conv_bwd(start, rows, cols, interpret, inputs, dy):
    return _conv_backward(*inputs, dy, start=start, rows=rows, cols=cols,
                          interpret=interpret)


kernel_conv.defvjp(_kernel_conv_fwd, _kernel_conv_bwd)


def conv_silu(x, w, b, start: int = 0):
    """``silu(causal_conv1d(...))`` of the C = ``w.shape[1]`` channels of x
    [B, S, width] from column ``start``, in x's dtype: [B, S, C]. On a TPU,
    at shapes the kernels tile (`conv_tile`), the Pallas kernels, reading x
    in place; else `causal_conv1d` of the slice and XLA's silu."""
    S, C = x.shape[1], w.shape[1]
    tile = _kernel_tile(S, C, start)
    if tile is None:
        xs = jax.lax.slice_in_dim(x, start, start + C, axis=2)
        return jax.nn.silu(causal_conv1d(xs, w, b)).astype(x.dtype)
    return kernel_conv(x, w, b, start, *tile, False)
