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
