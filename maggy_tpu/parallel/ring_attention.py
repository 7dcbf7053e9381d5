"""Ring attention: sequence/context parallelism over a "seq" mesh axis.

Long-context training support the reference entirely lacks (SURVEY.md §5.7).
Design (blockwise ring attention, Liu et al. 2023): Q/K/V are sharded along
the sequence axis across devices; each device holds its Q shard and, over
`seq`-axis ring steps, receives successive K/V shards via `jax.lax.ppermute`
(ICI neighbor exchange), accumulating attention with a numerically-stable
online softmax. Peak memory per device is O(S/n) and the K/V transfer
overlaps compute under XLA's async collectives.

Causal masking is block-aware: a device skips K/V shards strictly in its
future; the diagonal shard applies the intra-block triangular mask.
Implemented with `shard_map` so it runs identically on a CPU test mesh and a
TPU pod.

Two inner-block implementations:

- **flash** (default on TPU when shards tile): the Pallas kernels from
  `ops/attention.py` per (Q shard, K/V shard) pair — no [S/n, S/n] score
  materialization even per step, GQA without kv repetition. The diagonal
  step is peeled out of the ring loop so every kernel call has a STATIC
  causal flag (offset-free); off-diagonal visible shards run non-causal.
  Gradients are a ring of their own: with the final log-sum-exp and
  delta = sum(dO*O), each block's backward is independent and additive, so
  dK/dV partials simply ride the ring with their shard (custom VJP below).
- **xla**: plain einsum blocks (odd shapes, CPU tests); differentiable by
  autodiff through the fori_loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from maggy_tpu.ops.attention import (NEG_INF, flash_block_bwd,
                                     flash_block_fwd)


# ------------------------------------------------------------------ xla path


def _block_attend(q, k, v, q_offset, k_offset, causal, sm_scale):
    """Online-softmax partial attention of one (Q shard, K/V shard) pair.

    q: [B,Sq,H,D], k/v: [B,Sk,Hkv,D]; returns (acc [B,Sq,H,D] fp32,
    m [B,Sq,H] fp32, l [B,Sq,H] fp32) partial-softmax statistics.
    """
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B,H,Sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return acc, m.transpose(0, 2, 1), l.transpose(0, 2, 1)


def _merge(acc1, m1, l1, acc2, m2, l2):
    """Merge two partial online-softmax states."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    # acc layout [B,Sq,H,D]; m/l are [B,Sq,H]
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    l = l1 * a1 + l2 * a2
    return acc, m, l


def _ring_xla_shard(q_blk, k_blk, v_blk, axis_name, n, causal):
    B, shard, H, D = q_blk.shape
    sm_scale = 1.0 / (D ** 0.5)
    idx = jax.lax.axis_index(axis_name)
    q_off = idx * shard

    def ring_step(step, carry):
        acc, m, l, k_cur, v_cur = carry
        # Which global shard does k_cur hold? It started at `idx` and has
        # been passed backward `step` times: origin = (idx + step) % n.
        origin = (idx + step) % n
        k_off = origin * shard

        def attend(args):
            acc, m, l = args
            a2, m2, l2 = _block_attend(q_blk, k_cur, v_cur, q_off, k_off,
                                       causal, sm_scale)
            acc, m, l = _merge(acc, m, l, a2, m2, l2)
            return acc, m, l

        # Causal: skip shards strictly in the future (k_off > q end).
        if causal:
            acc, m, l = jax.lax.cond(
                k_off > q_off + shard - 1, lambda a: a, attend, (acc, m, l))
        else:
            acc, m, l = attend((acc, m, l))
        # Pass K/V to the previous neighbor (receive from next) so the
        # ring sweeps forward through global shards.
        perm = [(i, (i - 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, m, l, k_nxt, v_nxt

    acc0 = jnp.zeros((B, shard, H, D), jnp.float32)
    m0 = jnp.full((B, shard, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, shard, H), jnp.float32)
    acc, m, l, _, _ = jax.lax.fori_loop(
        0, n, ring_step, (acc0, m0, l0, k_blk, v_blk))
    l = jnp.maximum(l, 1e-30)
    return (acc / l[..., None]).astype(q_blk.dtype)


# ---------------------------------------------------------------- flash path


def _merge_lse(o1, lse1, o2, lse2):
    """Merge two NORMALIZED partial outputs via their log-sum-exps.
    o: [B,S,H,D] fp32; lse: [B,H,S] fp32. The global output is
    sum_i exp(lse_i - lse_global) * o_i."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = w1 + w2
    lse = m + jnp.log(denom)
    wt = lambda w: (w / denom).transpose(0, 2, 1)[..., None]  # noqa: E731
    return o1 * wt(w1) + o2 * wt(w2), lse


def _rotate(xs, axis_name, n):
    perm = [(i, (i - 1) % n) for i in range(n)]
    return [jax.lax.ppermute(x, axis_name, perm) for x in xs]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash_shard(q_blk, k_blk, v_blk, axis_name, n, causal, interpret):
    out, _ = _ring_flash_fwd_impl(q_blk, k_blk, v_blk, axis_name, n, causal,
                                  interpret)
    return out


def _ring_flash_fwd_impl(q_blk, k_blk, v_blk, axis_name, n, causal, interpret):
    idx = jax.lax.axis_index(axis_name)
    # Step 0 is peeled: the resident K/V shard is the DIAGONAL block, the
    # only one needing the triangular mask — so every kernel call in the
    # ring has a static causal flag.
    o, lse = flash_block_fwd(q_blk, k_blk, v_blk, causal=causal,
                             interpret=interpret)
    o = o.astype(jnp.float32)
    k_cur, v_cur = _rotate([k_blk, v_blk], axis_name, n)

    def ring_step(step, carry):
        o_acc, lse_acc, k_cur, v_cur = carry
        origin = (idx + step) % n

        def attend(args):
            o_acc, lse_acc = args
            o2, lse2 = flash_block_fwd(q_blk, k_cur, v_cur, causal=False,
                                       interpret=interpret)
            return _merge_lse(o_acc, lse_acc, o2.astype(jnp.float32), lse2)

        if causal:
            # Visible iff the shard is strictly in the past (the diagonal
            # was step 0; future shards contribute nothing).
            o_acc, lse_acc = jax.lax.cond(
                origin < idx, attend, lambda a: a, (o_acc, lse_acc))
        else:
            o_acc, lse_acc = attend((o_acc, lse_acc))
        k_cur, v_cur = _rotate([k_cur, v_cur], axis_name, n)
        return o_acc, lse_acc, k_cur, v_cur

    o, lse, _, _ = jax.lax.fori_loop(1, n, ring_step, (o, lse, k_cur, v_cur))
    return o.astype(q_blk.dtype), lse


def _ring_flash_fwd_rule(q_blk, k_blk, v_blk, axis_name, n, causal, interpret):
    out, lse = _ring_flash_fwd_impl(q_blk, k_blk, v_blk, axis_name, n, causal,
                                    interpret)
    return out, (q_blk, k_blk, v_blk, out, lse)


def _ring_flash_bwd_rule(axis_name, n, causal, interpret, res, do):
    """Ring backward: dK/dV partials travel WITH their K/V shard. Each step
    adds the local device's gradient contribution to the resident shard;
    after n rotations every shard (and its fully-accumulated gradient) is
    home. dQ accumulates locally."""
    q_blk, k_blk, v_blk, out, lse = res
    idx = jax.lax.axis_index(axis_name)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)  # [B,H,Sq]

    dq, dk, dv = flash_block_bwd(q_blk, k_blk, v_blk, do, lse, delta,
                                 causal=causal, interpret=interpret)
    k_cur, v_cur, dk_cur, dv_cur = _rotate(
        [k_blk, v_blk, dk, dv], axis_name, n)

    def ring_step(step, carry):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        origin = (idx + step) % n

        def attend(args):
            dq, dk_cur, dv_cur = args
            dq2, dk2, dv2 = flash_block_bwd(
                q_blk, k_cur, v_cur, do, lse, delta, causal=False,
                interpret=interpret)
            return dq + dq2, dk_cur + dk2, dv_cur + dv2

        if causal:
            dq, dk_cur, dv_cur = jax.lax.cond(
                origin < idx, attend, lambda a: a, (dq, dk_cur, dv_cur))
        else:
            dq, dk_cur, dv_cur = attend((dq, dk_cur, dv_cur))
        k_cur, v_cur, dk_cur, dv_cur = _rotate(
            [k_cur, v_cur, dk_cur, dv_cur], axis_name, n)
        return dq, k_cur, v_cur, dk_cur, dv_cur

    dq, _, _, dk_cur, dv_cur = jax.lax.fori_loop(
        1, n, ring_step, (dq, k_cur, v_cur, dk_cur, dv_cur))
    return (dq.astype(q_blk.dtype), dk_cur.astype(k_blk.dtype),
            dv_cur.astype(v_blk.dtype))


_ring_flash_shard.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


# ------------------------------------------------------------------- public


def ring_attention(q, k, v, mesh, axis_name: str = "seq",
                   causal: bool = True, impl: str = "auto",
                   interpret: bool = False):
    """Sequence-parallel attention. q: [B, S, H, D] GLOBALLY, k/v:
    [B, S, Hkv, D] (GQA: Hkv divides H), all sharded on dim 1 over
    ``axis_name``. Returns out with q's sharding.

    ``impl``: "flash" (Pallas blocks + ring VJP), "xla" (einsum blocks,
    autodiff), or "auto" (flash on a TPU backend when each [S/n] shard
    tiles by 128 and D >= 64; xla otherwise). ``interpret`` runs the
    Pallas path in interpret mode (CPU tests).
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis_name]
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if S % n:
        raise ValueError("Sequence length {} must divide over {} '{}' shards"
                         .format(S, n, axis_name))
    if H % Hkv:
        raise ValueError("H={} not divisible by Hkv={}".format(H, Hkv))
    from maggy_tpu.ops.attention import resolve_seq_parallel_impl

    shard = S // n
    impl = resolve_seq_parallel_impl(shard, D, impl, interpret, "S/n")

    qspec = P(None, axis_name, None, None)
    if impl == "flash":
        # Positional pass-through: custom_vjp's nondiff_argnums are
        # positional-only.
        def fn(qb, kb, vb):
            return _ring_flash_shard(qb, kb, vb, axis_name, n, causal,
                                     interpret)
    else:
        def fn(qb, kb, vb):
            return _ring_xla_shard(qb, kb, vb, axis_name, n, causal)
    out = jax.shard_map(
        fn, mesh=mesh, in_specs=(qspec, qspec, qspec), out_specs=qspec,
        check_vma=False,
    )(q, k, v)
    return out
