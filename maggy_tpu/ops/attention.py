"""Attention: Pallas flash kernel (TPU) with an XLA reference path.

The reference framework has no attention code (SURVEY.md §5.7); this is the
TPU-first hot-op design the BERT/Llama baseline configs need:

- `flash_attention`: Pallas TPU kernels — tiled online-softmax forward and
  a two-kernel backward (dK/dV streaming Q tiles, dQ streaming K/V tiles),
  fp32 accumulators in VMEM scratch, causal block skipping, O(tile) VMEM
  and no S x S materialization in either direction. Natively supports:
    * GQA — K/V carry Hkv < H heads and are NEVER repeat-expanded: the
      query heads are viewed as [B, Hkv, rep, S, D] and the kv BlockSpec
      index maps simply ignore the rep axis, so each kv tile is fetched
      once per group and dK/dV accumulate across the group's rep
      (sequential) grid dimension.
    * key-padding masks ([B, Sk] keep-mask) — the BERT fine-tune config's
      mask shape, streamed as one [1, blk_k] tile per k-block.
    * Sq != Sk, with bottom-right-aligned causal masking (offset = Sk-Sq),
      e.g. decode windows / ring-attention shards.
    * head_dim >= 64 (64 for BERT-base; Mosaic lane-pads D < 128 tiles).
  Per-row statistics (log-sum-exp, and delta in the backward) are stored
  COMPACTLY as [B, G, rep, 1, Sq] fp32 with q-rows on the lane dimension
  (one [1, blk_q] tile per q-block) — not broadcast to 128 lanes in HBM.
- `attention_reference`: straightforward XLA softmax attention (CPU tests,
  odd shapes).
- `multi_head_attention`: public entry — dispatches to the kernel when
  shapes tile cleanly on a TPU backend, XLA reference otherwise.

Kernel layout follows the pallas guide (/opt/skills/guides/pallas_guide.md):
the k-block grid dimension is sequential ("arbitrary") and carries the
online-softmax state in persistent VMEM scratch, so VMEM holds one K/V tile
at a time (long-context capable); (8,128)-aligned tiles,
`preferred_element_type=float32` on every MXU dot.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ----------------------------------------------------------------- reference


def attention_reference(q, k, v, causal: bool = True, mask=None):
    """[B,Sq,H,D]x[B,Sk,Hkv,D] softmax attention in plain XLA (fp32 softmax).

    ``mask`` broadcasts against [B,H,Sq,Sk] logits (True = attend). When
    ``causal`` and Sq != Sk the mask is bottom-right aligned (the last query
    row sees every key), matching the flash kernel.
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(D).astype(jnp.float32)
    if causal:
        Sk = k.shape[1]
        cm = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------------------- head views


def _grouped_q(x, Hkv):
    """[B,S,H,D] -> [B, Hkv, rep, S, D]: query heads grouped by the kv head
    they share, so kv index maps can drop the rep axis (GQA without repeat)."""
    B, S, H, D = x.shape
    rep = H // Hkv
    return x.transpose(0, 2, 1, 3).reshape(B, Hkv, rep, S, D)


def _grouped_kv(x):
    """[B,S,Hkv,D] -> [B, Hkv, S, D]."""
    return x.transpose(0, 2, 1, 3)


def _ungroup_q(x):
    """[B, Hkv, rep, S, D] -> [B,S,H,D]."""
    B, G, R, S, D = x.shape
    return x.reshape(B, G * R, S, D).transpose(0, 2, 1, 3)


def _ungroup_kv(x):
    """[B, Hkv, S, D] -> [B,S,Hkv,D]."""
    return x.transpose(0, 2, 1, 3)


def _causal_tile_mask(s, qi, kb, blk_q, blk_k, offset):
    """Bottom-right-aligned causal mask for one [blk_q, blk_k] tile:
    query row p attends key col c iff c <= p + offset (offset = Sk - Sq)."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kb * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos + offset >= k_pos, s, NEG_INF)


def _apply_pad_mask(s, mask_ref):
    """mask_ref: [1, blk_k] int32 keep-mask tile, broadcast over q rows."""
    return jnp.where(mask_ref[0][None, :] != 0, s, NEG_INF)


# -------------------------------------------------------------- pallas kernel


def _flash_fwd_kernel(*refs, causal, sm_scale, has_mask, offset):
    """One (b, g, r, q-block, k-block) program: K/V stream through the
    grid's innermost (sequential) dimension, so VMEM holds only one
    [blk_k, D] tile of K and V at a time — sequence length is bounded by
    HBM, not VMEM. Online-softmax state (acc, running max, running sum)
    lives in VMEM scratch that persists across the k-block iterations of
    each program group.

    Refs: q [BLK_Q, D]; k/v [BLK_K, D]; (mask [1, BLK_K] int32);
    o [BLK_Q, D]; lse [1, BLK_Q] (q-rows on lanes — compact, no 128x pad);
    scratch acc [BLK_Q, D], m/l [BLK_Q, 128] fp32.
    """
    from jax.experimental import pallas as pl

    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        mask_ref = None

    blk_q = q_ref.shape[0]
    blk_k = k_ref.shape[0]
    qi = pl.program_id(3)
    kb = pl.program_id(4)
    num_kb = pl.num_programs(4)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def contribute():
        q = q_ref[:].astype(jnp.float32) * sm_scale
        k_blk = k_ref[:].astype(jnp.float32)
        v_blk = v_ref[:].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_tile_mask(s, qi, kb, blk_q, blk_k, offset)
        if mask_ref is not None:
            s = _apply_pad_mask(s, mask_ref)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    if causal:
        # Blocks entirely above the diagonal contribute nothing — skip the
        # compute (the tile fetch still happens; cheap next to the MXU work).
        @pl.when(kb * blk_k < (qi + 1) * blk_q + offset)
        def _():
            contribute()
    else:
        contribute()

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[:] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse = m_ref[:, 0] + jnp.log(l_safe)
        lse_ref[:] = lse[None, :]


def _flash_fwd(qg, kg, vg, mask, causal, blk_q, blk_k, interpret):
    """qg: [B,G,R,Sq,D]; kg/vg: [B,G,Sk,D]; mask: [B,1,Sk] int32 or None.
    Returns (out [B,G,R,Sq,D], lse [B,G,R,1,Sq] fp32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, G, R, Sq, D = qg.shape
    Sk = kg.shape[2]
    offset = Sk - Sq
    sm_scale = 1.0 / (D ** 0.5)
    grid = (B, G, R, Sq // blk_q, Sk // blk_k)

    q_spec = pl.BlockSpec((None, None, None, blk_q, D),
                          lambda b, g, r, qi, kb: (b, g, r, qi, 0))
    kv_spec = pl.BlockSpec((None, None, blk_k, D),
                           lambda b, g, r, qi, kb: (b, g, kb, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qg, kg, vg]
    if mask is not None:
        in_specs.append(pl.BlockSpec((None, 1, blk_k),
                                     lambda b, g, r, qi, kb: (b, 0, kb)))
        operands.append(mask)

    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               sm_scale=sm_scale, has_mask=mask is not None,
                               offset=offset)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            q_spec,
            pl.BlockSpec((None, None, None, 1, blk_q),
                         lambda b, g, r, qi, kb: (b, g, r, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, G, R, Sq, D), qg.dtype),
            jax.ShapeDtypeStruct((B, G, R, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # b/g/r/qi programs are independent (megacore-splittable); the
            # k-block dimension carries the online-softmax accumulation and
            # must run sequentially.
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention(q, k, v, mask=None, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool = False):
    """Flash attention on q [B,Sq,H,D], k/v [B,Sk,Hkv,D] (Hkv divides H —
    GQA handled without materializing repeated K/V). ``mask``: optional
    [B, Sk] (or [B,1,Sk]) keep-mask over keys. A query row whose keys are
    ALL masked outputs the uniform average of V (p = exp(NEG_INF-NEG_INF)
    per key — the same value the reference's softmax-of-all-masked
    produces); such rows are padding and must be excluded from the loss."""
    out, _ = _flash_fwd_4d(q, k, v, mask, causal, blk_q, blk_k, interpret)
    return out


def _canon_mask(mask, B, Sk):
    if mask is None:
        return None
    m = jnp.asarray(mask)
    if m.ndim == 1:
        m = m[None, :]
    if m.ndim == 2:
        m = m[:, None, :]
    if m.shape != (B, 1, Sk):
        m = jnp.broadcast_to(m, (B, 1, Sk))
    return m.astype(jnp.int32)


def _flash_fwd_4d(q, k, v, mask, causal, blk_q, blk_k, interpret):
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    mask3 = _canon_mask(mask, B, k.shape[1])
    out_g, lse = _flash_fwd(_grouped_q(q, Hkv), _grouped_kv(k), _grouped_kv(v),
                            mask3, causal, blk_q, blk_k, interpret)
    return _ungroup_q(out_g), lse


def _flash_fwd_rule(q, k, v, mask, causal, blk_q, blk_k, interpret):
    out, lse = _flash_fwd_4d(q, k, v, mask, causal, blk_q, blk_k, interpret)
    return out, (q, k, v, mask, out, lse)


def _recompute_p_ds(q, k_blk, v_blk, do, lse, delta, qi, kb, blk_q, blk_k,
                    causal, sm_scale, offset, mask_ref):
    """Shared bwd block math: probabilities from the saved LSE, then the
    softmax-transpose ds = p * (dO·Vᵀ - delta) * scale. All [blk_q, blk_k].
    ``lse``/``delta`` arrive as [blk_q, 1] (lane->sublane relayout done by
    the caller from the compact [1, blk_q] tiles)."""
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if causal:
        s = _causal_tile_mask(s, qi, kb, blk_q, blk_k, offset)
    if mask_ref is not None:
        s = _apply_pad_mask(s, mask_ref)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * sm_scale
    return p, ds


def _flash_bwd_dkdv_kernel(*refs, causal, sm_scale, has_mask, offset):
    """grid (B, G, kb, r, qi): one K/V tile per program group; the two
    sequential inner dims stream every (rep, q-block) pair of the group
    through it, accumulating dK/dV in VMEM scratch — GQA gradients sum over
    the group's query heads without any repeated K/V in HBM."""
    from jax.experimental import pallas as pl

    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        mask_ref = None

    blk_q = q_ref.shape[0]
    blk_k = k_ref.shape[0]
    kb = pl.program_id(2)
    r = pl.program_id(3)
    qi = pl.program_id(4)
    num_r = pl.num_programs(3)
    num_qb = pl.num_programs(4)

    @pl.when((r == 0) & (qi == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def contribute():
        q = q_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)
        p, ds = _recompute_p_ds(
            q, k_ref[:].astype(jnp.float32), v_ref[:].astype(jnp.float32),
            do, lse_ref[0][:, None], delta_ref[0][:, None],
            qi, kb, blk_q, blk_k, causal, sm_scale, offset, mask_ref)
        dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal:
        # Q blocks strictly above this K tile's diagonal see none of it.
        @pl.when(kb * blk_k < (qi + 1) * blk_q + offset)
        def _():
            contribute()
    else:
        contribute()

    @pl.when((r == num_r - 1) & (qi == num_qb - 1))
    def _finalize():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(*refs, causal, sm_scale, has_mask, offset):
    """grid (B, G, r, qi, kb): one Q tile per program group; stream K/V
    tiles through the sequential kb dimension, accumulating dQ in VMEM."""
    from jax.experimental import pallas as pl

    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
        mask_ref = None

    blk_q = q_ref.shape[0]
    blk_k = k_ref.shape[0]
    qi = pl.program_id(3)
    kb = pl.program_id(4)
    num_kb = pl.num_programs(4)

    @pl.when(kb == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def contribute():
        _, ds = _recompute_p_ds(
            q_ref[:].astype(jnp.float32), k_ref[:].astype(jnp.float32),
            v_ref[:].astype(jnp.float32), do_ref[:].astype(jnp.float32),
            lse_ref[0][:, None], delta_ref[0][:, None],
            qi, kb, blk_q, blk_k, causal, sm_scale, offset, mask_ref)
        dq_acc[:] += jax.lax.dot_general(ds, k_ref[:].astype(jnp.float32),
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal:
        @pl.when(kb * blk_k < (qi + 1) * blk_q + offset)
        def _():
            contribute()
    else:
        contribute()

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(qg, kg, vg, dog, lse, delta, mask, causal, blk_q, blk_k,
               interpret):
    """Pallas flash backward. qg/dog: [B,G,R,Sq,D]; kg/vg: [B,G,Sk,D];
    lse/delta: [B,G,R,1,Sq] fp32 (compact); mask: [B,1,Sk] int32 or None.
    Returns (dq [B,G,R,Sq,D], dk/dv [B,G,Sk,D])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, G, R, Sq, D = qg.shape
    Sk = kg.shape[2]
    offset = Sk - Sq
    sm_scale = 1.0 / (D ** 0.5)
    has_mask = mask is not None

    # --- dK/dV: grid (B, G, kb, r, qi); r+qi sequential, accumulating.
    q_by_inner = pl.BlockSpec((None, None, None, blk_q, D),
                              lambda b, g, kb, r, qi: (b, g, r, qi, 0))
    kv_by_outer = pl.BlockSpec((None, None, blk_k, D),
                               lambda b, g, kb, r, qi: (b, g, kb, 0))
    stat_by_inner = pl.BlockSpec((None, None, None, 1, blk_q),
                                 lambda b, g, kb, r, qi: (b, g, r, 0, qi))
    in_specs = [q_by_inner, kv_by_outer, kv_by_outer, q_by_inner,
                stat_by_inner, stat_by_inner]
    operands = [qg, kg, vg, dog, lse, delta]
    if has_mask:
        in_specs.append(pl.BlockSpec((None, 1, blk_k),
                                     lambda b, g, kb, r, qi: (b, 0, kb)))
        operands.append(mask)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, causal=causal,
                          sm_scale=sm_scale, has_mask=has_mask, offset=offset),
        grid=(B, G, Sk // blk_k, R, Sq // blk_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, blk_k, D),
                         lambda b, g, kb, r, qi: (b, g, kb, 0)),
            pl.BlockSpec((None, None, blk_k, D),
                         lambda b, g, kb, r, qi: (b, g, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, G, Sk, D), kg.dtype),
            jax.ShapeDtypeStruct((B, G, Sk, D), vg.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, D), jnp.float32),
            pltpu.VMEM((blk_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(*operands)

    # --- dQ: grid (B, G, r, qi, kb); kb sequential, accumulating.
    q_spec = pl.BlockSpec((None, None, None, blk_q, D),
                          lambda b, g, r, qi, kb: (b, g, r, qi, 0))
    kv_spec = pl.BlockSpec((None, None, blk_k, D),
                           lambda b, g, r, qi, kb: (b, g, kb, 0))
    stat_spec = pl.BlockSpec((None, None, None, 1, blk_q),
                             lambda b, g, r, qi, kb: (b, g, r, 0, qi))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec]
    operands = [qg, kg, vg, dog, lse, delta]
    if has_mask:
        in_specs.append(pl.BlockSpec((None, 1, blk_k),
                                     lambda b, g, r, qi, kb: (b, 0, kb)))
        operands.append(mask)

    (dq,) = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal,
                          sm_scale=sm_scale, has_mask=has_mask, offset=offset),
        grid=(B, G, R, Sq // blk_q, Sk // blk_k),
        in_specs=in_specs,
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((B, G, R, Sq, D), qg.dtype)],
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*operands)
    return dq, dk, dv


def _flash_bwd_rule(causal, blk_q, blk_k, interpret, res, g):
    """Flash backward as two Pallas kernels (dK/dV then dQ), recomputing
    probabilities from the saved log-sum-exp — the S x S matrix never
    materializes and VMEM holds one tile pair at a time."""
    q, k, v, mask, out, lse = res
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [B,Sq,H]
    delta_g = delta.transpose(0, 2, 1).reshape(
        B, Hkv, H // Hkv, 1, Sq)
    mask3 = _canon_mask(mask, B, k.shape[1])
    dqg, dkg, dvg = _flash_bwd(
        _grouped_q(q, Hkv), _grouped_kv(k), _grouped_kv(v),
        _grouped_q(g, Hkv), lse, delta_g, mask3,
        causal, blk_q, blk_k, interpret)
    return (_ungroup_q(dqg).astype(q.dtype),
            _ungroup_kv(dkg).astype(k.dtype),
            _ungroup_kv(dvg).astype(v.dtype),
            None)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ------------------------------------------------- ring-attention building blocks


def flash_block_fwd(q, k, v, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool = False):
    """One (Q shard, K/V shard) flash forward returning BOTH the normalized
    block output and its log-sum-exp — the partial-softmax state ring
    attention merges across shards. q: [B,Sq,H,D], k/v: [B,Sk,Hkv,D];
    returns (out [B,Sq,H,D], lse [B,H,Sq] fp32). Not differentiable on its
    own: the ring owns the VJP (see parallel/ring_attention.py)."""
    out, lse = _flash_fwd_4d(q, k, v, None, causal, blk_q, blk_k, interpret)
    B, Sq, H, _ = q.shape
    return out, lse.reshape(B, H, Sq)


def flash_block_bwd(q, k, v, do, lse, delta, causal: bool = True,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False):
    """One block of the ring-attention backward: given the GLOBAL per-row
    log-sum-exp and delta = sum(dO*O), each (Q shard, K/V shard) pair's
    gradient contribution is independent and additive — p recomputed from
    the global lse is the true global probability for this block.
    lse/delta: [B,H,Sq] fp32. Returns (dq, dk, dv) fp32."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    R = H // Hkv
    stats = lambda x: x.reshape(B, Hkv, R, 1, Sq).astype(jnp.float32)  # noqa: E731
    dqg, dkg, dvg = _flash_bwd(
        _grouped_q(q, Hkv), _grouped_kv(k), _grouped_kv(v),
        _grouped_q(do, Hkv), stats(lse), stats(delta), None,
        causal, blk_q, blk_k, interpret)
    return (_ungroup_q(dqg).astype(jnp.float32),
            _ungroup_kv(dkg).astype(jnp.float32),
            _ungroup_kv(dvg).astype(jnp.float32))


# ----------------------------------------------------------------- dispatch


def _tpu_backend() -> bool:
    return jax.default_backend() == "tpu"


def _flash_disabled() -> bool:
    """Operational kill switch: MAGGY_TPU_NO_FLASH=1 forces the XLA
    reference path everywhere (e.g. to isolate a Mosaic regression on a new
    libtpu without touching code)."""
    import os

    return os.environ.get("MAGGY_TPU_NO_FLASH") == "1"


def resolve_seq_parallel_impl(seq_len: int, head_dim: int, impl: str,
                              interpret: bool, what: str) -> str:
    """Shared flash/xla dispatch for the sequence-parallel wrappers (ring
    attention's inner blocks, Ulysses' full-sequence kernel): one policy so
    the two entry points cannot drift. ``seq_len`` is whatever length the
    kernel actually sees (the ring's shard, Ulysses' gathered S)."""
    flash_ok = seq_len % 128 == 0 and head_dim >= 64 and head_dim % 8 == 0
    if impl == "auto":
        impl = "flash" if flash_ok and not _flash_disabled() \
            and (interpret or _tpu_backend()) else "xla"
    if impl == "flash" and not flash_ok:
        raise ValueError(
            "impl='flash' needs {} divisible by 128 and D>=64 with D%8==0; "
            "got {}, D={}".format(what, seq_len, head_dim))
    return impl


def _key_padding_mask(mask, B, Sk):
    """Reduce an attention mask to a [B, Sk] keep-mask, or (None, False)
    when it cannot be PROVEN key-padding-only. Only the unambiguous forms
    are accepted: [B,1,1,Sk] (broadcast against [B,H,Sq,Sk] logits) and
    [Sk]. A 2-d mask is NOT accepted — [B, Sk] and a per-query [Sq, Sk]
    mask are indistinguishable by shape when B == Sq, and misreading the
    latter as key padding silently corrupts attention; ambiguous or unknown
    shapes fall back to the XLA reference, which broadcasts them exactly.
    Returns (mask2d, ok)."""
    if mask is None:
        return None, True
    try:
        m = jnp.asarray(mask)
        if m.ndim == 4 and m.shape[1] == 1 and m.shape[2] == 1 \
                and m.shape[3] == Sk and m.shape[0] in (1, B):
            return jnp.broadcast_to(m[:, 0, 0, :], (B, Sk)), True
        if m.ndim == 1 and m.shape[0] == Sk:
            return jnp.broadcast_to(m[None, :], (B, Sk)), True
    except Exception:  # noqa: BLE001 - unbroadcastable -> fall back
        pass
    return None, False


@jax.named_scope("attention")  # names it in a trace whichever path runs it
def multi_head_attention(q, k, v, causal: bool = True, mask=None,
                         force: Optional[str] = None):
    """Public attention entry: kernel dispatch with XLA fallback.

    q: [B,Sq,H,D], k/v: [B,Sk,Hkv,D]. ``force`` in {"flash", "reference"}
    overrides dispatch (tests). Flash handles GQA natively (no kv repeat),
    key-padding masks, Sq != Sk, and head_dim >= 64; masks with per-query
    structure or non-tiling shapes fall back to the XLA reference.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    if H % Hkv != 0:
        raise ValueError("H={} not divisible by Hkv={}".format(H, Hkv))
    pad_mask, mask_ok = _key_padding_mask(mask, B, Sk)
    tiles_ok = (
        mask_ok and D >= 64 and D % 8 == 0
        and Sq % 128 == 0 and Sk % 128 == 0
    )
    if force == "flash":
        if not tiles_ok:
            raise ValueError(
                "force='flash' requires a key-padding (or no) mask, "
                "D>=64 with D%8==0, and 128-tiling Sq/Sk; got D={}, Sq={}, "
                "Sk={}, mask shape={}".format(
                    D, Sq, Sk, None if mask is None else jnp.shape(mask)))
        use_flash = True
    else:
        use_flash = force is None and _tpu_backend() and tiles_ok \
            and not _flash_disabled()
    if not use_flash:
        return attention_reference(q, k, v, causal=causal, mask=mask)
    interpret = not _tpu_backend()
    return flash_attention(q, k, v, pad_mask, causal, 128, 128, interpret)
