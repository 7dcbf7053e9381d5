"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The second of the two standard long-context schemes (SURVEY.md §5.7; the
reference has neither). Ring attention (`ring_attention.py`) keeps Q local
and streams K/V around a `ppermute` ring — O(S/n) memory, n ring steps.
Ulysses (DeepSpeed-Ulysses, arXiv:2309.14509) instead swaps WHICH dim is
sharded: inputs arrive sharded on sequence, one `all_to_all` over the ICI
re-shards them on heads, every device runs ordinary FULL-sequence attention
for its head subset, and a second `all_to_all` swaps back.

Trade-offs (why both exist):
- Ulysses does 2 collectives total (vs n-1 ring hops) and reuses the plain
  single-device flash kernel unmodified — including its causal handling —
  so it composes with any attention implementation.
- Its parallel degree is capped by the HEAD count (n must divide H; GQA
  caps it at the KV-head count), while the ring scales with sequence
  length alone. Memory is O(S) per device for the attention inputs, vs
  the ring's O(S/n).

Use the ring for extreme context on few heads; Ulysses when heads are
plentiful and collective count (latency) dominates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ulysses_attention(q, k, v, mesh, axis_name: str = "seq",
                      causal: bool = True, impl: str = "auto",
                      interpret: bool = False):
    """Sequence-parallel attention via head/sequence all-to-all.

    q: [B, S, H, D] GLOBALLY, k/v: [B, S, Hkv, D] (GQA: Hkv divides H),
    all sharded on dim 1 over ``axis_name``. Returns out with q's
    sharding. The mesh degree n must divide Hkv (each device needs whole
    KV heads after the swap).

    ``impl``: "flash" (Pallas single-device kernel per head subset),
    "xla" (reference einsum attention), "auto" (flash on TPU when shapes
    tile). ``interpret`` runs Pallas in interpret mode (CPU tests).
    """
    from jax.sharding import PartitionSpec as P

    from maggy_tpu.ops.attention import (attention_reference, flash_attention,
                                         resolve_seq_parallel_impl)

    n = mesh.shape[axis_name]
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if S % n:
        raise ValueError("Sequence length {} must divide over {} '{}' shards"
                         .format(S, n, axis_name))
    if H % Hkv:
        raise ValueError("H={} not divisible by Hkv={}".format(H, Hkv))
    if Hkv % n:
        raise ValueError(
            "Ulysses needs the KV-head count ({}) divisible by the '{}' "
            "degree ({}); use ring_attention for more shards than heads."
            .format(Hkv, axis_name, n))

    # Shared dispatch policy with ring_attention — here the kernel sees the
    # FULL gathered sequence, so global S (not the shard) must tile.
    use_flash = resolve_seq_parallel_impl(S, D, impl, interpret, "S") == "flash"

    def local_fn(q_l, k_l, v_l):
        # [B, S/n, H, D] -> all_to_all splits heads n ways and gathers the
        # full sequence: [B, S, H/n, D]. One ICI collective each way.
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                      concat_axis=1, tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                      concat_axis=2, tiled=True)

        q_h = seq_to_heads(q_l)
        k_h = seq_to_heads(k_l)
        v_h = seq_to_heads(v_l)
        if use_flash:
            out = flash_attention(q_h, k_h, v_h, None, causal,
                                  interpret=interpret)
        else:
            out = attention_reference(q_h, k_h, v_h, causal=causal)
        return heads_to_seq(out.astype(q_l.dtype))

    spec = P(None, axis_name, None, None)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
