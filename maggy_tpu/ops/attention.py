"""Attention: Pallas flash kernel (TPU) with an XLA reference path.

The reference framework has no attention code (SURVEY.md §5.7); this is the
TPU-first hot-op design the BERT/Llama baseline configs need:

- `flash_attention`: Pallas TPU kernels — tiled online-softmax forward and
  a two-kernel backward (dK/dV streaming Q tiles, dQ streaming K/V tiles),
  fp32 accumulators in VMEM scratch, O(tile) VMEM and no S x S
  materialization in either direction. Under a mask that empties tiles by
  their indices (causal, a description) a kernel's grid walks only the tiles
  that hold a visible pair (`tile_walk`: a schedule in SMEM), and a tile in
  which every pair is visible computes no mask. Natively supports:
    * GQA — K/V carry Hkv < H heads and are NEVER repeat-expanded: the
      query heads are viewed as [B, Hkv, rep, S, D] and the kv BlockSpec
      index maps simply ignore the rep axis, so each kv tile is fetched
      once per group and dK/dV accumulate across the group's rep
      (sequential) grid dimension.
    * key-padding masks ([B, Sk] keep-mask) — the BERT fine-tune config's
      mask shape, streamed as one [1, blk_k] tile per k-block.
    * mask *descriptions* with per-query structure (`BlockDiffusionMask`:
      the block-diffusion training mask over a noised and a clean copy of
      the sequence) — computed per tile from indices as the causal mask is;
      the tiles a description empties are no steps of the walk
      (`_tile_runs`) and the plan counts only those that run.
    * Sq != Sk, with bottom-right-aligned causal masking (offset = Sk-Sq),
      e.g. decode windows / ring-attention shards.
    * head_dim >= 64. At D 64 (BERT-base) two heads of an MHA layer share
      a tile's 128 lanes (`lane_pack`), so every tile moves in whole rows.
  Per-row statistics (log-sum-exp, and delta in the backward) are stored
  COMPACTLY as [B, G, rep, pack, Sq] fp32 with q-rows on the lane dimension
  (one [pack, blk_q] tile per q-block) — not broadcast to 128 lanes in HBM.
- `tile_plan`: what one grid step of each kernel covers, chosen from the
  shape: the [blk_q, blk_k] score tile (multiples of 128 that divide the
  lengths; the largest that a stated VMEM budget holds, smaller under a
  causal mask as block skipping asks) and how many heads share the step.
  At BERT's S 512 the tile is the whole sequence: the k axis has one step
  and the softmax is taken once, with no running state.
  `multi_head_attention` runs the plan; `flash_attention` and the ring's
  `flash_block_fwd` / `flash_block_bwd` take explicit tiles.
- `attention_reference`: straightforward XLA softmax attention (CPU tests,
  odd shapes).
- `multi_head_attention`: public entry — dispatches to the kernel when
  shapes tile cleanly on a TPU backend (no mask, key padding, causal, or a
  mask description), XLA reference otherwise (mask tensors with per-query
  structure, lengths that do not tile by 128, head_dim under 64).

Kernel layout follows the pallas guide (/opt/skills/guides/pallas_guide.md):
the k-block grid dimension is sequential ("arbitrary") and carries the
online-softmax state in persistent VMEM scratch, so VMEM holds one K/V tile
a head at a time (long-context capable). A step's tile is what the pipeline
moves; the arithmetic walks it in row chunks (`_PASS_ELEMS`). Every MXU dot
takes its operands in the input dtype (bf16 in, bf16 on the MXU; p and ds
are rounded to it as the left operand of their products) and accumulates in
float32 (`preferred_element_type`); scores, exp, the row statistics, the
masks and every accumulator are float32.
"""

from __future__ import annotations

import functools
import operator
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

# `plans_traced` is re-exported: PR 24's tests and callers take it from here.
from maggy_tpu.telemetry.plans import plans_traced, remember_plan  # noqa: F401

NEG_INF = -1e30

#: The names `flash_attention_planned`'s forward rule gives its output and
#: its log-sum-exp (`jax.ad_checkpoint.checkpoint_name`). A caller that
#: rematerialises a layer and keeps these (`save_only_these_names`) does not
#: run the forward kernel again in its backward pass; outside a
#: `jax.checkpoint` a name is an identity and lowers to nothing.
REMAT_KEEP = ("flash_out", "flash_lse")


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


_LANES = 128


def lane_pack(H: int, Hkv: int, D: int) -> int:
    """Heads that share the 128 lanes of a tile: 2 for MHA at D <= 64 with
    an even head count (BERT-base: D 64), else 1. [B,S,H,D] is [B,S,H/2,2D]
    for free, and a [S, 2D] tile of it moves in whole 128-lane rows where a
    [S, 64] one moves half-rows (a copy through the same blocks takes 2.6
    times as long on a v5e: PERF.md section 6, PR 24). The kernels tell a
    pair's two heads apart by zeroing the other's lanes in one operand of
    each product, which costs the MXU nothing: a 64-deep contraction and a
    64-wide result each left half of it idle."""
    return 2 if H == Hkv and H % 2 == 0 and 2 * D <= _LANES else 1


def _grouped_q(x, Hkv, pack=1):
    """[B,S,H,D] -> [B, Hkv, rep, S, D]: query heads grouped by the kv head
    they share, so kv index maps can drop the rep axis (GQA without repeat).
    With ``pack`` heads to a tile's lanes: [B, H/pack, 1, S, pack*D]."""
    B, S, H, D = x.shape
    x = x.reshape(B, S, H // pack, pack * D)
    rep = H // Hkv
    return x.transpose(0, 2, 1, 3).reshape(B, Hkv // pack, rep, S, pack * D)


def _grouped_kv(x, pack=1):
    """[B,S,Hkv,D] -> [B, Hkv, S, D] ([B, Hkv/pack, S, pack*D])."""
    B, S, Hkv, D = x.shape
    return x.reshape(B, S, Hkv // pack, pack * D).transpose(0, 2, 1, 3)


def _ungroup_q(x, pack=1):
    """[B, Hkv, rep, S, D] -> [B,S,H,D]."""
    B, G, R, S, D = x.shape
    return x.reshape(B, G * R, S, D).transpose(0, 2, 1, 3).reshape(
        B, S, G * R * pack, D // pack)


def _grouped_stats(x, Hkv, pack=1):
    """[B,H,Sq] row statistics -> [B, Hkv/pack, rep, pack, Sq] fp32, one
    [pack, Sq] tile of rows to a [Sq, pack*D] slab of heads."""
    B, H, Sq = x.shape
    return x.reshape(B, Hkv // pack, H // Hkv, pack, Sq).astype(jnp.float32)


def _ungroup_kv(x, pack=1):
    """[B, Hkv, S, D] -> [B,S,Hkv,D]."""
    B, G, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, G * pack, D // pack)


def _causal_tile_mask(s, q0, k0, offset, q_axis=0):
    """Bottom-right-aligned causal mask for a score tile whose first query
    is ``q0`` and first key ``k0``, [queries, keys] or, with ``q_axis=1``,
    its transpose: query row p attends key col c iff c <= p + offset
    (offset = Sk - Sq)."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos + offset >= k_pos, s, NEG_INF)


class BlockDiffusionMask(NamedTuple):
    """A *description* of the block-diffusion training mask (BD3-LM's
    vectorised step, arXiv:2503.09573), which the kernels compute per tile
    from indices: no [Sq, Sk] tensor exists anywhere.

    The sequence is two copies of ``length`` data positions, the noised copy
    first and the clean copy after it (Sq = Sk = 2 x ``length``), both cut
    into blocks of ``block`` positions. With b(i) the block of data position
    i, query i sees key j iff

    - i noised, j noised: b(j) == b(i) (its own block);
    - i noised, j clean:  b(j) <  b(i) (the clean past);
    - i clean,  j clean:  b(j) <= b(i) (block-causal);
    - i clean,  j noised: never.

    Every query row sees at least its own position's block, so no row is
    empty. Hashable: it is a static argument of the kernels' jits."""
    length: int
    block: int

    def dense(self) -> jnp.ndarray:
        """The [2 length, 2 length] keep-mask, for `attention_reference`
        (off the TPU, and shapes the kernels cannot tile)."""
        pos = jnp.arange(2 * self.length)
        return _block_diffusion_visible(pos[:, None], pos[None, :], self)


def _block_diffusion_visible(q_pos, k_pos, mask: BlockDiffusionMask):
    """``q_pos`` [rows, 1] (or [1, cols]) against ``k_pos`` the other way
    round, both int32 positions in the doubled sequence: the boolean tile.
    The per-position quantities stay column and row vectors; only the two
    comparisons and their union work on the whole tile."""
    q_clean, k_clean = q_pos >= mask.length, k_pos >= mask.length
    q_blk = (q_pos - jnp.where(q_clean, mask.length, 0)) // mask.block
    k_blk = (k_pos - jnp.where(k_clean, mask.length, 0)) // mask.block
    # Clean keys: blocks up to the query's own (clean) or before it (noised).
    # Noised keys: the query's own block, and only for a noised query. Each
    # side's vector carries a value the other rule can never meet, so the
    # tile needs no select between booleans (Mosaic has none).
    upto = jnp.where(q_clean, q_blk, q_blk - 1)
    own = jnp.where(q_clean, -1, q_blk)
    k_as_clean = jnp.where(k_clean, k_blk, jnp.iinfo(jnp.int32).max)
    k_as_noised = jnp.where(k_clean, -2, k_blk)
    return (k_as_clean <= upto) | (k_as_noised == own)


def _structure_tile_mask(s, q0, k0, structure, q_axis=0):
    """The block-diffusion mask for a score tile whose first query is ``q0``
    and first key ``k0``, [queries, keys] or, with ``q_axis=1``, transposed."""
    rows, cols = s.shape
    q_shape = (rows, 1) if q_axis == 0 else (1, cols)
    k_shape = (1, cols) if q_axis == 0 else (rows, 1)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, q_shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, k_shape, 1 - q_axis)
    return jnp.where(_block_diffusion_visible(q_pos, k_pos, structure), s,
                     NEG_INF)


def _tile_parts(q0, blk_q, k0, blk_k, structure):
    """A tile under a `BlockDiffusionMask`, from indices alone: the data
    blocks (first, last) of the noised and of the clean part of its queries
    and of its keys, and whether it has each part (where it has not, first
    > last and the pair means nothing)."""
    L, b = structure.length, structure.block

    def parts(x0, blk):
        noised = (x0 // b, (np.minimum(x0 + blk, L) - 1) // b)
        clean = ((np.maximum(x0, L) - L) // b, (x0 + blk - 1 - L) // b)
        return noised, clean, x0 < L, x0 + blk > L

    return parts(q0, blk_q) + parts(k0, blk_k)


def _tile_runs(q0, blk_q, k0, blk_k, offset, causal, structure):
    """Whether the [blk_q, blk_k] tile at (q0, k0) holds a visible pair, from
    indices alone: None where every tile does (no causal mask and no mask
    description), else a boolean: the tiles `tile_walk` schedules and
    `_blocks_run` counts. On Python ints and numpy arrays alike."""
    runs = None
    if causal:
        # Tiles strictly above the diagonal see nothing.
        runs = k0 < q0 + blk_q + offset
    if structure is not None:
        (qn0, qn1), (_, qc1), q_n, q_c, (kn0, kn1), (kc0, _), k_n, k_c = \
            _tile_parts(q0, blk_q, k0, blk_k, structure)
        seen = (q_n & k_n & (qn0 <= kn1) & (kn0 <= qn1)) \
            | (q_n & k_c & (kc0 < qn1)) | (q_c & k_c & (kc0 <= qc1))
        runs = seen if runs is None else runs & seen
    return runs


def _tile_whole(q0, blk_q, k0, blk_k, offset, causal, structure):
    """Whether EVERY pair of the tile is visible, from indices alone (beside
    `_tile_runs`, the same rules with "all" for "any"): such a tile needs no
    index mask, the select would be all-true. Under a description each part
    of the queries must see the whole of each part of the keys: noised on
    noised one block both, noised on clean the keys' last block before the
    queries' first, clean on clean up to it, clean on noised never."""
    whole = True
    if causal:
        # The tile's last key is visible to its first query.
        whole = k0 + blk_k - 1 <= q0 + offset
    if structure is not None:
        (qn0, qn1), (qc0, _), q_n, q_c, (kn0, kn1), (_, kc1), k_n, k_c = \
            _tile_parts(q0, blk_q, k0, blk_k, structure)

        def if_both(a, b, then):  # a pair of parts the tile does not have
            return np.logical_not(a & b) | then  # asks nothing

        whole = whole & np.logical_not(q_c & k_n) \
            & if_both(q_n, k_n, (qn0 == kn1) & (kn0 == qn1)) \
            & if_both(q_n, k_c, kc1 < qn0) & if_both(q_c, k_c, kc1 <= qc0)
    return whole


# ------------------------------------------------------------------ tile plan


class KernelTiles(NamedTuple):
    """What one grid step of one kernel covers: a [blk_q, blk_k] score tile
    for each of ``heads`` query heads (heads of one batch row for MHA, the
    query heads of one K/V group for GQA, which share the step's K/V tile)."""
    blk_q: int
    blk_k: int
    heads: int = 1


class FlashPlan(NamedTuple):
    """The tiles of the three kernels, by the kernels' names."""
    fwd: KernelTiles
    dkdv: KernelTiles
    dq: KernelTiles

    @classmethod
    def explicit(cls, blk_q: int, blk_k: int) -> "FlashPlan":
        """The caller's own tiles in all three kernels, one head a step."""
        return cls(*(KernelTiles(blk_q, blk_k),) * 3)

    def describe(self) -> str:
        """``fwd q512 k512 h4; dkdv q512 k512 h2; dq q512 k512 h4``."""
        return "; ".join("{} q{} k{} h{}".format(name, *tiles)
                         for name, tiles in zip(self._fields, self))


#: What a step's buffers may take of VMEM as `step_vmem_bytes` reckons them:
#: half of the 16 MiB a v5e kernel gets by default, because the reckoning
#: leaves out what Mosaic adds (relayouts, spills, the temporaries of more
#: than one pass where it overlaps unrolled passes).
VMEM_BUDGET = 8 * 2 ** 20
#: The cost of stepping the grid once (about 0.35 us: pipeline bookkeeping
#: and the tiles' DMA descriptors), in the plan's unit: one score element
#: of one head through one kernel (a 512 x 512 tile is 0.9-1.4 us). Read
#: from the chip: PERF.md section 6, PR 24.
STEP_COST = 64 * 1024
#: Heads are added to a step until stepping costs less than this share of it.
STEP_SHARE = 1 / 16

#: Score elements one pass of a kernel's inner loop covers (128 vector
#: registers of float32). A step's [blk_q, blk_k] tile is what the pipeline
#: moves; the arithmetic walks it in row chunks of this size, so that a
#: chunk's scores, probabilities and their rounded copy stay near the
#: registers where a whole 512 x 512 tile (1 MiB each) streams through VMEM
#: once per elementwise operation.
_PASS_ELEMS = 128 * 1024
#: Loops of at most this many rounds are unrolled where the kernel is
#: traced (static slices, and the scheduler may overlap a chunk's MXU work
#: with the next one's); longer ones are `fori_loop`s.
_UNROLL = 8


def _lane_pad(n: int) -> int:
    return -(-n // _LANES) * _LANES


def _pass_rows(rows: int, cols: int) -> int:
    """Rows of a [rows, cols] tile one pass of a kernel's inner loop takes:
    the most multiples of 128 that divide ``rows`` and keep the pass within
    `_PASS_ELEMS`; 128 at least (a row of statistics is stored 128 lanes at
    a time)."""
    fit = [r for r in range(_LANES, rows + 1, _LANES)
           if rows % r == 0 and r * cols <= _PASS_ELEMS]
    return max(fit, default=_LANES)


def step_vmem_bytes(kernel: str, tiles: KernelTiles, D: int, itemsize: int,
                    kv_shared: bool, pack: int = 1) -> int:
    """VMEM one grid step of ``kernel`` holds, from the shapes alone: every
    operand and result tile twice (the pipeline's double buffer), the
    float32 accumulators once, and the float32 scores, probabilities and
    their rounded copies of one pass over one head (passes and heads run
    one after the other). The last dimension pads to 128 lanes (``pack``
    heads share them); a row of statistics or mask pads to 8 sublanes."""
    blk_q, blk_k, heads = tiles
    slabs = -(-heads // pack)  # [rows, lanes] tiles on the query side
    q_tile = slabs * blk_q * _lane_pad(pack * D)
    kv_tile = (1 if kv_shared else slabs) * blk_k * _lane_pad(pack * D)
    rows = 2 * 8 * 4 * (slabs * blk_q + blk_k)  # lse (delta) and the mask
    if kernel == "fwd":      # q, o | k, v | acc, m, l | s, p and p rounded
        tiles_io = 2 * itemsize * (2 * q_tile + 2 * kv_tile) + rows
        scratch = 4 * (q_tile + 2 * heads * blk_q * _LANES)
        temps = _pass_rows(blk_q, blk_k) * blk_k * (2 * 4 + itemsize)
    elif kernel == "dkdv":   # q, dO | k, v, dk, dv | dk, dv | s, dp, ds
        tiles_io = 2 * itemsize * (2 * q_tile + 4 * kv_tile) + 2 * rows
        scratch = 4 * 2 * kv_tile
        temps = _pass_rows(blk_k, blk_q) * blk_q * (3 * 4 + 2 * itemsize)
    elif kernel == "dq":     # q, dO, dq | k, v | dq | s, dp, ds
        tiles_io = 2 * itemsize * (3 * q_tile + 2 * kv_tile) + 2 * rows
        scratch = 4 * q_tile
        temps = _pass_rows(blk_q, blk_k) * blk_k * (3 * 4 + itemsize)
    else:
        raise ValueError("no kernel {!r}".format(kernel))
    return tiles_io + scratch + temps


def _tiles_at(Sq: int, Sk: int, blk_q: int, blk_k: int, predicate, causal,
              structure):
    """``predicate`` (`_tile_runs`, `_tile_whole`) over every tile of one
    head, [q-blocks, k-blocks], or None where it gives None."""
    nq, nk = Sq // blk_q, Sk // blk_k
    found = predicate(np.arange(nq)[:, None] * blk_q, blk_q,
                      np.arange(nk)[None, :] * blk_k, blk_k, Sk - Sq, causal,
                      structure)
    return None if found is None else np.broadcast_to(found, (nq, nk))


def _blocks_run(Sq: int, Sk: int, blk_q: int, blk_k: int, causal: bool,
                structure: Optional[BlockDiffusionMask] = None) -> int:
    """Tiles of one head that do work: all of them, or under a causal mask
    those the diagonal reaches, or under a mask description those that hold
    a visible pair (the tiles `tile_walk` schedules: the same `_tile_runs`)."""
    runs = _tiles_at(Sq, Sk, blk_q, blk_k, _tile_runs, causal, structure)
    return (Sq // blk_q) * (Sk // blk_k) if runs is None else int(runs.sum())


# A step of a walk, in its ``flags``: what its tile is, and whether the step
# opens and closes its accumulator.
_EMPTY, _PARTIAL, _WHOLE, _KIND = 0, 1, 2, 3
_FIRST, _LAST = 4, 8


class TileWalk(NamedTuple):
    """The grid steps of one kernel under a mask that empties tiles: per
    step the q-block and k-block of its tile, the block of query heads it
    belongs to (``rep``; the dK/dV kernel streams a K/V group's rep blocks
    through one accumulator, the other two kernels have the rep axis on the
    grid) and its ``flags`` (`_KIND`, `_FIRST`, `_LAST`). `table` is what
    the kernel and its index maps read from SMEM."""
    q_blk: tuple
    k_blk: tuple
    flags: tuple
    rep: tuple

    @property
    def steps(self) -> int:
        return len(self.flags)

    def count(self, kind: int) -> int:
        return sum(f & _KIND == kind for f in self.flags)

    def table(self):
        """The rows, one after the other, as one int32 vector: row i of
        step t is at ``i * steps + t`` (`_walk_step`, `_walk_tile`)."""
        return np.asarray(self, np.int32).reshape(-1)

    def describe(self) -> str:
        """``80+0 (24 partial)``: steps that run a tile + steps that only
        initialise and finalise an accumulator no tile reaches, and of the
        first how many run under the index mask."""
        return "{}+{} ({} partial)".format(
            self.steps - self.count(_EMPTY), self.count(_EMPTY),
            self.count(_PARTIAL))


@functools.lru_cache(maxsize=None)
def tile_walk(kernel: str, Sq: int, Sk: int, blk_q: int, blk_k: int,
              causal: bool, structure: Optional[BlockDiffusionMask],
              reps: int = 1) -> Optional[TileWalk]:
    """The steps ``kernel`` walks in place of a dense (q-block x k-block)
    grid, or None where no tile is empty by its indices (no causal mask and
    no description): the dense grid is the walk.

    The tiles that hold a visible pair (`_tile_runs`), **in the order the
    dense grid visits them**, so every accumulator sees the same terms in
    the same order: ``fwd`` and ``dq`` by q-block, then ascending k-block;
    ``dkdv`` by k-block, then rep block (``reps`` of them), then ascending
    q-block. A tile in which every pair is visible (`_tile_whole`) is
    marked whole, the others partial. An accumulator that no tile reaches
    (under a causal mask with Sq > Sk the first query rows see no key) still
    gets one step, marked empty, on the block the walk already holds, which
    only initialises and finalises it: its ``out`` / ``dq`` block is written
    (zeros, and the log-sum-exp of no key) as the dense grid wrote it. Every
    k-block is seen by some query under both mask kinds."""
    runs = _tiles_at(Sq, Sk, blk_q, blk_k, _tile_runs, causal, structure)
    if runs is None:
        return None
    whole = _tiles_at(Sq, Sk, blk_q, blk_k, _tile_whole, causal, structure)
    by_k = kernel == "dkdv"
    if by_k:
        runs, whole = runs.T, whole.T
    rows = []  # (accumulator's block, streamed block, rep block, flags)
    for acc in range(len(runs)):
        steps = [(int(blk), rep, _WHOLE if whole[acc, blk] else _PARTIAL)
                 for rep in range(reps) for blk in runs[acc].nonzero()[0]] \
            or [(rows[-1][1] if rows else 0, 0, _EMPTY)]
        for n, (blk, rep, kind) in enumerate(steps):
            rows.append((acc, blk, rep, kind | _FIRST * (n == 0)
                         | _LAST * (n == len(steps) - 1)))
    acc_blk, blk, rep, flags = zip(*rows)
    return TileWalk(blk if by_k else acc_blk, acc_blk if by_k else blk, flags,
                    rep)


def _divisors(n: int):
    return [d for d in range(1, n + 1) if n % d == 0]


def _tiles_of(length: int):
    return [t for t in range(_LANES, length + 1, _LANES) if length % t == 0]


@functools.lru_cache(maxsize=None)
def tile_plan(Sq: int, Sk: int, D: int, H: int, Hkv: int, itemsize: int,
              causal: bool, has_mask: bool,
              structure: Optional[BlockDiffusionMask] = None) -> FlashPlan:
    """The tiles each kernel takes at this shape. Pure: the shape decides.

    For each kernel, among the tiles that are multiples of 128, divide the
    lengths and fit ``VMEM_BUDGET``, the pair that costs one head least:
    ``steps x (STEP_COST + blk_q x blk_k)``, over the steps that run. Without
    a causal mask that is the largest pair (at S 512, D 64 the whole
    sequence: the k axis has one step and the online softmax rescales once).
    Under one, block skipping is coarser at large tiles, and the same sum
    settles for a smaller pair as the sequence grows. Ties go to the larger
    tile along the axis the kernel accumulates over. Then a step takes on
    query heads (of one batch row, or of one K/V group, which share the K/V
    tile) until stepping is under ``STEP_SHARE`` of it or VMEM is full.
    ``has_mask`` moves nothing yet: a key-padding row is 4 KB a step. A mask
    description (``structure``) weighs in as the causal mask does, through
    the tiles it leaves to run."""
    del has_mask
    kv_shared = H != Hkv
    pack = lane_pack(H, Hkv, D)
    head_axis = H // Hkv if kv_shared else H
    plan = []
    for kernel in FlashPlan._fields:
        def cost(pair):
            blk_q, blk_k = pair
            along = blk_q if kernel == "dkdv" else blk_k
            return (_blocks_run(Sq, Sk, blk_q, blk_k, causal, structure)
                    * (STEP_COST + blk_q * blk_k), -along)

        def fits(blk_q, blk_k, heads):
            return step_vmem_bytes(kernel, KernelTiles(blk_q, blk_k, heads),
                                   D, itemsize, kv_shared, pack) <= VMEM_BUDGET

        pairs = [(bq, bk) for bq in _tiles_of(Sq) for bk in _tiles_of(Sk)
                 if fits(bq, bk, pack)] or [(_LANES, _LANES)]
        blk_q, blk_k = min(pairs, key=cost)
        heads = pack
        for h in _divisors(head_axis):
            if h % pack:
                continue
            if not fits(blk_q, blk_k, h):
                break
            heads = h
            if STEP_COST / h <= STEP_SHARE * blk_q * blk_k:
                break
        plan.append(KernelTiles(blk_q, blk_k, heads))
    return FlashPlan(*plan)


def _remember(plan: FlashPlan, Sq: int, Sk: int, causal: bool,
              structure: Optional[BlockDiffusionMask]) -> None:
    said = plan.describe()
    if structure is not None:
        # The mask kind, its block length, per kernel the tiles of a head
        # that run of those there are, and what its grid walks for them.
        walks = [tile_walk(name, Sq, Sk, t.blk_q, t.blk_k, causal, structure)
                 for name, t in zip(plan._fields, plan)]
        said += "; block_diffusion b{} L{} tiles {}; walk {}".format(
            structure.block, structure.length,
            " ".join("{} {}/{}".format(
                name, walk.steps - walk.count(_EMPTY),
                (Sq // t.blk_q) * (Sk // t.blk_k))
                for name, t, walk in zip(plan._fields, plan, walks)),
            " ".join("{} {}".format(name, walk.describe())
                     for name, walk in zip(plan._fields, walks)))
    remember_plan("flash", said)


# -------------------------------------------------------------- pallas kernel
#
# Every kernel sees its query-side tiles (q, o, dO, dQ) as [slabs, rows, D]
# and their row statistics (lse, delta) as [slabs, pack, rows]: a slab is one
# [rows, D] tile of ``pack`` heads side by side in its lanes (`lane_pack`; one
# head where pack is 1), and a step's heads are its slabs' heads. K/V-side
# tiles (k, v, dK, dV) carry the slab axis when the heads are heads of one
# batch row (MHA) and none when they are the query heads of one K/V group
# (GQA), which share one tile. Slabs, row chunks and a slab's heads run one
# after the other, so the [rows, blk_k] temporaries are one head's.

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _dot(a, b, dims):
    """MXU dot on the operands as they are, accumulated in float32."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _each(n, body):
    """``body(i)`` for i in range(n), in order."""
    if n <= _UNROLL:
        for i in range(n):
            body(i)
    else:
        jax.lax.fori_loop(0, n, lambda i, _: body(i), None)


def _chunk(c, rows):
    """The c-th run of ``rows`` rows (or lanes) of a tile."""
    if isinstance(c, int):
        return slice(c * rows, (c + 1) * rows)
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(c * rows, rows), rows)


def _lanes_of(j, pack, x, other=0):
    """``x`` in the lanes of the j-th packed head and ``other`` in the rest
    (``x`` itself where a tile holds one head). With zeros: the operand
    that keeps a product to head j."""
    if pack == 1:
        return x
    width = x.shape[-1] // pack
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where((lane >= j * width) & (lane < (j + 1) * width), x,
                     jnp.full_like(x, other))


def _as_row(col):
    """A [rows, 1] column of statistics as the [1, rows] row it is stored
    as: spread over 128 lanes and transposed on the XLU, which a v5e does
    in a fifth of the time the squeeze-and-expand relayout takes it."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _each_chunk(slabs, chunks, body):
    """``body(h, c)`` for every row chunk c of every slab h of a step."""
    _each(slabs, lambda h: _each(chunks, lambda c: body(h, c)))


def _scores(q, k_blk, q0, k0, causal, sm_scale, offset, mask_ref,
            structure=None):
    """[rows, blk_k] float32 logits of the queries from ``q0`` on against the
    keys from ``k0`` on: Q K^T on the operands as they are, scaled after the
    dot (forward and backward alike, so that the backward's p is the
    forward's), then the masks."""
    s = _dot(q, k_blk, _NT) * sm_scale
    if causal:
        s = _causal_tile_mask(s, q0, k0, offset)
    if structure is not None:
        s = _structure_tile_mask(s, q0, k0, structure)
    if mask_ref is not None:
        s = jnp.where(mask_ref[...] != 0, s, NEG_INF)
    return s


class _Step(NamedTuple):
    """Where one grid step of a kernel stands: the q-block and the k-block
    of its tile, whether the step opens (``first()``) and closes
    (``last()``) its accumulator, and the tile's kind (`_KIND` of a walk's
    flags; None on the dense grid, where every tile runs under whatever
    mask the kernel has). ``first`` and ``last`` are asked where the kernel
    needs them, and in that order."""
    qi: jax.Array
    kb: jax.Array
    first: Callable[[], jax.Array]
    last: Callable[[], jax.Array]
    kind: Optional[jax.Array] = None


def _dense_step(q_axis: int, k_axis: int, streamed: tuple) -> _Step:
    """A step of the dense grid: the blocks are program ids, and an
    accumulator opens at the first and closes at the last program of the
    ``streamed`` (sequential) axes. Ids, extents and comparisons are traced
    where the kernels always traced them, so a kernel with nothing to walk
    lowers to the Mosaic module it always did."""
    from jax.experimental import pallas as pl

    ids = {a: pl.program_id(a) for a in sorted({q_axis, k_axis, *streamed})}
    extents = []

    def first():
        extents.extend(pl.num_programs(a) for a in streamed)
        return functools.reduce(operator.and_,
                                (ids[a] == 0 for a in streamed))

    def last():
        return functools.reduce(operator.and_, (
            ids[a] == n - 1 for a, n in zip(streamed, extents)))

    return _Step(ids[q_axis], ids[k_axis], first, last)


def _walk_step(walk_ref, axis: int) -> _Step:
    """A step of a `TileWalk`, read from its table in SMEM: grid axis
    ``axis`` counts the walk's steps."""
    from jax.experimental import pallas as pl

    t, steps = pl.program_id(axis), pl.num_programs(axis)
    flags = walk_ref[2 * steps + t]
    return _Step(walk_ref[t], walk_ref[steps + t],
                 lambda: flags & _FIRST != 0, lambda: flags & _LAST != 0,
                 flags & _KIND)


def _step_and_refs(refs, causal, structure, q_axis, k_axis, streamed):
    """(this grid step, the kernel's operand, result and scratch refs). A
    kernel walks where its mask kind empties tiles by their indices: the
    walk's table is then its first ref (scalar prefetch) and the walk's
    steps the grid axis that follows the parallel ones the dense grid
    begins with. Else the grid is dense, with the q-blocks, the k-blocks
    and the ``streamed`` (sequential) axes where the arguments say."""
    if causal or structure is not None:
        return _walk_step(refs[0], min(q_axis, k_axis)), refs[1:]
    return _dense_step(q_axis, k_axis, streamed), refs


def _on_tile(step: _Step, body) -> None:
    """``body(masked)`` on the step's tile. On the dense grid always, under
    whatever mask the kernel has. On a walk by the tile's kind: a partial
    tile under the index masks (causal, description), a whole one without
    them (every pair of it is visible: the select was all-true, the values
    are the same), and an empty step, which stands for an accumulator that
    no tile reaches, not at all. A tile that holds no visible pair is not a
    step of the walk: nothing is fetched for it and nothing stepped (on the
    dense grid such a step cost its blocks' DMA and the step itself: in the
    SDAR cell's training step 0.15 us forward, 0.56 us dQ and 1.5 us dK/dV,
    a quarter of a dK/dV step that runs. What the missing mask buys is less:
    nothing forward and 0.44 ms a layer backward there. PERF.md section 6,
    PR 29)."""
    from jax.experimental import pallas as pl

    if step.kind is None:
        body(True)
    else:
        pl.when(step.kind == _PARTIAL)(lambda: body(True))
        pl.when(step.kind == _WHOLE)(lambda: body(False))


def _kv(ref, h, rows=slice(None)):
    """Index of ``rows`` of head ``h`` in a K/V-side tile: its leading axis
    carries the step's heads, or it has none and the heads share it."""
    return (h, rows) if len(ref.shape) == 3 else (rows,)


def _flash_fwd_kernel(*refs, causal, sm_scale, has_mask, offset, one_pass,
                      structure=None):
    """One (b, head block, q-block, k-block) program: K/V stream through the
    grid's innermost (sequential) dimension, so VMEM holds one [blk_k, D]
    tile of K and V a head — sequence length is bounded by HBM, not VMEM.
    Under a causal mask or a description that dimension is a `TileWalk`'s
    steps, and the walk's table comes first among the refs.
    Online-softmax state (acc, running max, running sum) lives in VMEM
    scratch that persists across the k-block steps of each program group;
    where one k-block is the whole row (``one_pass``) there is no state to
    keep and no scratch: the softmax is taken once.

    Refs: q [slabs, BLK_Q, D]; k/v [slabs, BLK_K, D] or, for a GQA group,
    [BLK_K, D]; (mask [1, BLK_K] int32); o [slabs, BLK_Q, D]; lse
    [slabs, pack, BLK_Q] (q-rows on lanes — compact, no 128x pad); scratch
    acc [slabs, BLK_Q, D], m/l [slabs, pack, BLK_Q, 128] fp32.
    """
    from jax.experimental import pallas as pl

    step, refs = _step_and_refs(refs, causal, structure, 3, 4, (4,))
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    o_ref, lse_ref = refs[3 + has_mask:5 + has_mask]

    slabs, blk_q, _ = q_ref.shape
    blk_k = k_ref.shape[-2]
    pack = lse_ref.shape[1]
    rows = _pass_rows(blk_q, blk_k)

    def scores(masked, h, c, j):
        return _scores(_lanes_of(j, pack, q_ref[h, _chunk(c, rows)]),
                       k_ref[_kv(k_ref, h)], step.qi * blk_q + c * rows,
                       step.kb * blk_k, causal and masked, sm_scale, offset,
                       mask_ref, structure if masked else None)

    def values(h, j, p):
        v_blk = _lanes_of(j, pack, v_ref[_kv(v_ref, h)])
        return _dot(p.astype(v_blk.dtype), v_blk, _NN)

    each_chunk = functools.partial(_each_chunk, slabs, blk_q // rows)

    if one_pass:
        def whole_rows(masked, h, c):
            r = _chunk(c, rows)
            out = None
            for j in range(pack):
                s = scores(masked, h, c, j)
                m = jnp.max(s, axis=1, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=1, keepdims=True)  # >= 1: the row's max
                o = values(h, j, p) / l  # zero in the other head's lanes
                out = o if out is None else out + o
                lse_ref[h, j:j + 1, r] = _as_row(m + jnp.log(l))
            o_ref[h, r] = out.astype(o_ref.dtype)

        _on_tile(step, lambda masked: each_chunk(
            functools.partial(whole_rows, masked)))
        return

    acc_ref, m_ref, l_ref = refs[5 + has_mask:]

    @pl.when(step.first())
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def contribute(masked, h, c):
        r = _chunk(c, rows)
        acc = acc_ref[h, r]
        for j in range(pack):
            s = scores(masked, h, c, j)
            m_prev = m_ref[h, j, r][:, :1]
            l_prev = l_ref[h, j, r][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            # Head j's lanes of the accumulator are rescaled and added to;
            # the other head's keep what they hold.
            acc = acc * _lanes_of(j, pack, jnp.broadcast_to(alpha, acc.shape),
                                  1.0) + values(h, j, p)
            m_ref[h, j, r] = jnp.broadcast_to(m_new, (rows, _LANES))
            l_ref[h, j, r] = jnp.broadcast_to(l_new, (rows, _LANES))
        acc_ref[h, r] = acc

    _on_tile(step, lambda masked: each_chunk(
        functools.partial(contribute, masked)))

    @pl.when(step.last())
    def _finalize():
        def finish(h, c):
            r = _chunk(c, rows)
            acc, out = acc_ref[h, r], None
            for j in range(pack):
                l_safe = jnp.maximum(l_ref[h, j, r][:, :1], 1e-30)
                o = _lanes_of(j, pack, acc / l_safe)
                out = o if out is None else out + o
                lse_ref[h, j:j + 1, r] = _as_row(m_ref[h, j, r][:, :1]
                                                 + jnp.log(l_safe))
            o_ref[h, r] = out.astype(o_ref.dtype)

        each_chunk(finish)


def _head_blocks(Sq, Sk, G, R, tiles, pack):
    """How a step's heads lie in [B, G, R, ...]: (slabs over G, slabs over
    R, the K/V block's leading dims), a slab being a [rows, lanes] tile of
    ``pack`` heads. MHA (R == 1) blocks the G axis and K/V with it; GQA
    blocks the rep axis over one shared K/V tile. One head a step of
    lane-packed heads is one slab, its two heads. Tiles or heads that do
    not divide the shape are refused."""
    if Sq % tiles.blk_q or Sk % tiles.blk_k:
        raise ValueError("tiles q{} k{} do not divide Sq={}, Sk={}".format(
            tiles.blk_q, tiles.blk_k, Sq, Sk))
    slabs = -(-tiles.heads // pack)
    if R == 1:
        if G % slabs:
            raise ValueError("{} heads a step do not divide H={}".format(
                tiles.heads, G * pack))
        return slabs, 1, (None, slabs)
    if R % slabs:
        raise ValueError("{} heads a step do not divide the {} query heads "
                         "of a K/V group".format(tiles.heads, R))
    return 1, slabs, (None, None)


def _q_side(hg, hr, inner):
    """Block shape of a query-side operand [B, G, R, *inner]: the step's
    heads on whichever axis carries them, the other squeezed."""
    return (None, hg, None) + inner if hr == 1 else (None, None, hr) + inner


# Where a tile's blocks lie in the kernels' operands, from its (b, g, r, qi,
# kb): query-side tiles, K/V-side tiles, row statistics, the key-padding row.
def _q_at(b, g, r, qi, kb):
    return b, g, r, qi, 0


def _kv_at(b, g, r, qi, kb):
    return b, g, kb, 0


def _stat_at(b, g, r, qi, kb):
    return b, g, r, 0, qi


def _mask_at(b, g, r, qi, kb):
    return b, 0, kb


def _walk_tile(steps: int):
    """The (b, g, r, qi, kb) of a walk's step, from the step's program ids
    and the walk's table: the rep axis is on the grid (forward, dQ) or in
    the walk (dK/dV)."""
    def to_tile(b, g, *ids):
        *r, t, table = ids
        rep, = r or (table[3 * steps + t],)
        return b, g, rep, table[t], table[steps + t]

    return to_tile


def _kernel_grid(walk: Optional[TileWalk], lead: tuple, parallel: tuple,
                 sequential: tuple, to_tile=lambda *ids: ids):
    """How one kernel's grid is laid and read: (grid, dimension semantics,
    the operands that go first as scalar prefetch, ``on``). With no walk the
    grid is ``lead + parallel + sequential``, the last carrying the
    accumulation, and ``to_tile`` turns a step's program ids into the
    (b, g, r, qi, kb) of its tile (where they are not that already). With
    one, a single sequential axis after ``lead`` counts the walk's steps
    and the tile is read from the walk's table. ``on(at)`` is the index map
    that takes ``at(b, g, r, qi, kb)``."""
    prefetch = []
    if walk is not None:
        parallel, sequential = (), (walk.steps,)
        prefetch, to_tile = [jnp.asarray(walk.table())], _walk_tile(walk.steps)
    semantics = ("parallel",) * len(lead + parallel) \
        + ("arbitrary",) * len(sequential)
    return lead + parallel + sequential, semantics, prefetch, \
        lambda at: lambda *ids: at(*to_tile(*ids))


@functools.partial(jax.jit, static_argnames=("causal", "tiles", "pack",
                                             "interpret", "structure"))
def _flash_fwd(qg, kg, vg, mask, causal, tiles, pack, interpret,
               structure=None):
    """qg: [B,G,R,Sq,D]; kg/vg: [B,G,Sk,D], ``pack`` heads to the D lanes;
    mask: [B,1,Sk] int32 or None.
    Returns (out [B,G,R,Sq,D], lse [B,G,R,pack,Sq] fp32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, G, R, Sq, D = qg.shape
    Sk = kg.shape[2]
    blk_q, blk_k, _ = tiles
    hg, hr, kv_lead = _head_blocks(Sq, Sk, G, R, tiles, pack)
    slabs = hg * hr
    offset = Sk - Sq
    sm_scale = 1.0 / ((D // pack) ** 0.5)
    grid, semantics, prefetch, on = _kernel_grid(
        tile_walk("fwd", Sq, Sk, blk_q, blk_k, causal, structure),
        (B, G // hg, R // hr), (Sq // blk_q,), (Sk // blk_k,))

    q_spec = pl.BlockSpec(_q_side(hg, hr, (blk_q, D)),
                          on(_q_at))
    kv_spec = pl.BlockSpec(kv_lead + (blk_k, D),
                           on(_kv_at))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qg, kg, vg]
    if mask is not None:
        in_specs.append(pl.BlockSpec((None, 1, blk_k),
                                     on(_mask_at)))
        operands.append(mask)

    # One k-block a row, and every query row sees a key: the softmax is
    # whole in one step (under a causal mask with Sq > Sk the first rows see
    # none, and the stepping path's skip gives them the zeros it always did).
    one_pass = Sk == blk_k and not (causal and offset < 0)
    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               sm_scale=sm_scale, has_mask=mask is not None,
                               offset=offset, one_pass=one_pass,
                               structure=structure)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                q_spec,
                pl.BlockSpec(_q_side(hg, hr, (pack, blk_q)),
                             on(_stat_at)),
            ],
            scratch_shapes=[] if one_pass else [
                pltpu.VMEM((slabs, blk_q, D), jnp.float32),
                pltpu.VMEM((slabs, pack, blk_q, _LANES), jnp.float32),
                pltpu.VMEM((slabs, pack, blk_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, G, R, Sq, D), qg.dtype),
            jax.ShapeDtypeStruct((B, G, R, pack, Sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # b/g/r/qi programs are independent (megacore-splittable); the
            # k-block dimension, or the walk, carries the online-softmax
            # accumulation and must run sequentially.
            dimension_semantics=semantics,
        ),
        interpret=interpret,
        name="flash_fwd",
    )(*prefetch, *operands)
    return out, lse


def flash_attention(q, k, v, mask=None, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool = False):
    """Flash attention on q [B,Sq,H,D], k/v [B,Sk,Hkv,D] (Hkv divides H —
    GQA handled without materializing repeated K/V) at the caller's own
    [blk_q, blk_k] tiles in all three kernels, one head a step (ring
    attention, Ulysses and the tests; `multi_head_attention` plans its
    tiles from the shape). ``mask``: optional [B, Sk] (or [B,1,Sk])
    keep-mask over keys. A query row whose keys are ALL masked outputs the
    uniform average of V (p = exp(NEG_INF-NEG_INF) per key — the same value
    the reference's softmax-of-all-masked produces); such rows are padding
    and must be excluded from the loss."""
    return flash_attention_planned(q, k, v, mask, causal,
                                   FlashPlan.explicit(blk_q, blk_k), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_planned(q, k, v, mask, causal: bool, plan: FlashPlan,
                            interpret: bool = False,
                            structure: Optional[BlockDiffusionMask] = None):
    """`flash_attention` with the tiles of each kernel given as a
    `FlashPlan` (`tile_plan` makes one from the shape), and optionally a
    mask description (``structure``) the kernels compute from indices."""
    out, _ = _flash_fwd_4d(q, k, v, mask, causal, plan.fwd, interpret,
                           structure)
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


def _flash_fwd_4d(q, k, v, mask, causal, tiles, interpret, structure=None):
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    pack = lane_pack(H, Hkv, D)
    mask3 = _canon_mask(mask, B, k.shape[1])
    out_g, lse = _flash_fwd(_grouped_q(q, Hkv, pack), _grouped_kv(k, pack),
                            _grouped_kv(v, pack), mask3, causal, tiles, pack,
                            interpret, structure)
    return _ungroup_q(out_g, pack), lse


def _flash_fwd_rule(q, k, v, mask, causal, plan, interpret, structure=None):
    out, lse = _flash_fwd_4d(q, k, v, mask, causal, plan.fwd, interpret,
                             structure)
    # Named here, so that the primal and the residuals are the named values:
    # naming the output in the caller would keep `out` and still run the
    # kernel again for `lse`.
    out, lse = map(checkpoint_name, (out, lse), REMAT_KEEP)
    return out, (q, k, v, mask, out, lse)


def _flash_bwd_dkdv_kernel(*refs, causal, sm_scale, has_mask, offset,
                           structure=None):
    """grid (B, head block, kb, r, qi): one K/V tile a head per program
    group; the two sequential inner dims stream every (rep, q-block) pair of
    the group through it, accumulating dK/dV in VMEM scratch — GQA gradients
    sum over the group's query heads without any repeated K/V in HBM.

    The scores are taken transposed, [keys, queries]: the row statistics
    are used as the [1, blk_q] rows they are stored as, P^T dO and dS^T Q
    are plain products with no transposed operand, and a chunk of key rows
    owns its rows of dK and dV."""
    from jax.experimental import pallas as pl

    step, refs = _step_and_refs(refs, causal, structure, 4, 2, (3, 4))
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        mask_ref = None

    slabs, blk_q, _ = q_ref.shape
    blk_k = k_ref.shape[-2]
    pack = lse_ref.shape[1]
    qi, kb = step.qi, step.kb
    rows = _pass_rows(blk_k, blk_q)

    @pl.when(step.first())
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def contribute(masked, h, c):
        keys = _chunk(c, rows)
        k_blk, v_blk = k_ref[_kv(k_ref, h, keys)], v_ref[_kv(v_ref, h, keys)]
        dk = dv = 0.0
        for j in range(pack):
            q = _lanes_of(j, pack, q_ref[h])
            do = _lanes_of(j, pack, do_ref[h])
            s = _dot(k_blk, q, _NT) * sm_scale
            if causal and masked:
                s = _causal_tile_mask(s, qi * blk_q, kb * blk_k + c * rows,
                                      offset, q_axis=1)
            if structure is not None and masked:
                s = _structure_tile_mask(s, qi * blk_q, kb * blk_k + c * rows,
                                         structure, q_axis=1)
            if mask_ref is not None:  # the key mask as a [rows, 1] column
                s = jnp.where(mask_ref[:, keys][0][:, None] != 0, s, NEG_INF)
            p = jnp.exp(s - lse_ref[h, j:j + 1])
            ds = p * (_dot(v_blk, do, _NT) - delta_ref[h, j:j + 1])
            dv += _dot(p.astype(do.dtype), do, _NN)  # head j's lanes alone
            dk += _dot(ds.astype(q.dtype), q, _NN)
        dv_acc[_kv(dv_acc, h, keys)] += dv
        dk_acc[_kv(dk_acc, h, keys)] += dk

    _on_tile(step, lambda masked: _each_chunk(
        slabs, blk_k // rows, functools.partial(contribute, masked)))

    @pl.when(step.last())
    def _finalize():
        # ds lacked the scale; dK takes it once, on [blk_k, D].
        dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(*refs, causal, sm_scale, has_mask, offset,
                         structure=None):
    """grid (B, head block, r, qi, kb): one Q tile a head per program group;
    stream K/V tiles through the sequential kb dimension, accumulating dQ
    in VMEM."""
    from jax.experimental import pallas as pl

    step, refs = _step_and_refs(refs, causal, structure, 3, 4, (4,))
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
        mask_ref = None

    slabs, blk_q, _ = q_ref.shape
    blk_k = k_ref.shape[-2]
    pack = lse_ref.shape[1]
    qi, kb = step.qi, step.kb
    rows = _pass_rows(blk_q, blk_k)

    @pl.when(step.first())
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def contribute(masked, h, c):
        r = _chunk(c, rows)
        k_blk, v_blk = k_ref[_kv(k_ref, h)], v_ref[_kv(v_ref, h)]
        dq = 0.0
        for j in range(pack):
            q = _lanes_of(j, pack, q_ref[h, r])
            do = _lanes_of(j, pack, do_ref[h, r])
            s = _scores(q, k_blk, qi * blk_q + c * rows, kb * blk_k,
                        causal and masked, sm_scale, offset, mask_ref,
                        structure if masked else None)
            # lane->sublane relayout of the compact [1, rows] statistics
            p = jnp.exp(s - lse_ref[h, j:j + 1, r][0][:, None])
            ds = p * (_dot(do, v_blk, _NT)
                      - delta_ref[h, j:j + 1, r][0][:, None])
            dq += _dot(ds.astype(q.dtype), _lanes_of(j, pack, k_blk), _NN)
        dq_acc[h, r] += dq

    _on_tile(step, lambda masked: _each_chunk(
        slabs, blk_q // rows, functools.partial(contribute, masked)))

    @pl.when(step.last())
    def _finalize():
        dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "plan", "pack",
                                             "interpret", "structure"))
def _flash_bwd(qg, kg, vg, dog, lse, delta, mask, causal, plan, pack,
               interpret, structure=None):
    """Pallas flash backward. qg/dog: [B,G,R,Sq,D]; kg/vg: [B,G,Sk,D],
    ``pack`` heads to the D lanes; lse/delta: [B,G,R,pack,Sq] fp32
    (compact); mask: [B,1,Sk] int32 or None.
    Returns (dq [B,G,R,Sq,D], dk/dv [B,G,Sk,D])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, G, R, Sq, D = qg.shape
    Sk = kg.shape[2]
    offset = Sk - Sq
    sm_scale = 1.0 / ((D // pack) ** 0.5)
    has_mask = mask is not None
    kernel_args = dict(causal=causal, sm_scale=sm_scale, has_mask=has_mask,
                       offset=offset, structure=structure)

    # --- dK/dV: grid (B, G, kb, r, qi); r+qi sequential, accumulating.
    blk_q, blk_k, _ = plan.dkdv
    hg, hr, kv_lead = _head_blocks(Sq, Sk, G, R, plan.dkdv, pack)
    grid, semantics, prefetch, on = _kernel_grid(
        tile_walk("dkdv", Sq, Sk, blk_q, blk_k, causal, structure, R // hr),
        (B, G // hg), (Sk // blk_k,), (R // hr, Sq // blk_q),
        lambda b, g, kb, r, qi: (b, g, r, qi, kb))
    q_by_inner = pl.BlockSpec(_q_side(hg, hr, (blk_q, D)),
                              on(_q_at))
    kv_by_outer = pl.BlockSpec(kv_lead + (blk_k, D),
                               on(_kv_at))
    stat_by_inner = pl.BlockSpec(_q_side(hg, hr, (pack, blk_q)),
                                 on(_stat_at))
    in_specs = [q_by_inner, kv_by_outer, kv_by_outer, q_by_inner,
                stat_by_inner, stat_by_inner]
    operands = [qg, kg, vg, dog, lse, delta]
    if has_mask:
        in_specs.append(pl.BlockSpec((None, 1, blk_k),
                                     on(_mask_at)))
        operands.append(mask)
    kv_acc = tuple(n for n in kv_lead if n is not None) + (blk_k, D)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, **kernel_args),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=[kv_by_outer, kv_by_outer],
            scratch_shapes=[
                pltpu.VMEM(kv_acc, jnp.float32),
                pltpu.VMEM(kv_acc, jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, G, Sk, D), kg.dtype),
            jax.ShapeDtypeStruct((B, G, Sk, D), vg.dtype),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(*prefetch, *operands)

    # --- dQ: grid (B, G, r, qi, kb); kb sequential, accumulating.
    blk_q, blk_k, _ = plan.dq
    hg, hr, kv_lead = _head_blocks(Sq, Sk, G, R, plan.dq, pack)
    grid, semantics, prefetch, on = _kernel_grid(
        tile_walk("dq", Sq, Sk, blk_q, blk_k, causal, structure),
        (B, G // hg, R // hr), (Sq // blk_q,), (Sk // blk_k,))
    q_spec = pl.BlockSpec(_q_side(hg, hr, (blk_q, D)),
                          on(_q_at))
    kv_spec = pl.BlockSpec(kv_lead + (blk_k, D),
                           on(_kv_at))
    stat_spec = pl.BlockSpec(_q_side(hg, hr, (pack, blk_q)),
                             on(_stat_at))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec]
    operands = [qg, kg, vg, dog, lse, delta]
    if has_mask:
        in_specs.append(pl.BlockSpec((None, 1, blk_k),
                                     on(_mask_at)))
        operands.append(mask)

    (dq,) = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kernel_args),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=[q_spec],
            scratch_shapes=[pltpu.VMEM((hg * hr, blk_q, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, G, R, Sq, D), qg.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*prefetch, *operands)
    return dq, dk, dv


def _flash_bwd_rule(causal, plan, interpret, structure, res, g):
    """Flash backward as two Pallas kernels (dK/dV then dQ), recomputing
    probabilities from the saved log-sum-exp — the S x S matrix never
    materializes and VMEM holds one tile pair a head at a time."""
    q, k, v, mask, out, lse = res
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    pack = lane_pack(H, Hkv, D)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [B,Sq,H]
    mask3 = _canon_mask(mask, B, k.shape[1])
    dqg, dkg, dvg = _flash_bwd(
        _grouped_q(q, Hkv, pack), _grouped_kv(k, pack), _grouped_kv(v, pack),
        _grouped_q(g, Hkv, pack), lse,
        _grouped_stats(delta.transpose(0, 2, 1), Hkv, pack), mask3,
        causal, plan, pack, interpret, structure)
    return (_ungroup_q(dqg, pack).astype(q.dtype),
            _ungroup_kv(dkg, pack).astype(k.dtype),
            _ungroup_kv(dvg, pack).astype(v.dtype),
            None)


flash_attention_planned.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ------------------------------------------------- ring-attention building blocks


def flash_block_fwd(q, k, v, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool = False):
    """One (Q shard, K/V shard) flash forward returning BOTH the normalized
    block output and its log-sum-exp — the partial-softmax state ring
    attention merges across shards. q: [B,Sq,H,D], k/v: [B,Sk,Hkv,D];
    returns (out [B,Sq,H,D], lse [B,H,Sq] fp32). Not differentiable on its
    own: the ring owns the VJP (see parallel/ring_attention.py)."""
    out, lse = _flash_fwd_4d(q, k, v, None, causal,
                             KernelTiles(blk_q, blk_k), interpret)
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
    Hkv = k.shape[2]
    pack = lane_pack(q.shape[2], Hkv, q.shape[3])
    dqg, dkg, dvg = _flash_bwd(
        _grouped_q(q, Hkv, pack), _grouped_kv(k, pack), _grouped_kv(v, pack),
        _grouped_q(do, Hkv, pack), _grouped_stats(lse, Hkv, pack),
        _grouped_stats(delta, Hkv, pack), None,
        causal, FlashPlan.explicit(blk_q, blk_k), pack, interpret)
    return (_ungroup_q(dqg, pack).astype(jnp.float32),
            _ungroup_kv(dkg, pack).astype(jnp.float32),
            _ungroup_kv(dvg, pack).astype(jnp.float32))


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

    q: [B,Sq,H,D], k/v: [B,Sk,Hkv,D]. ``mask`` is a keep-mask tensor that
    broadcasts against [B,H,Sq,Sk], or a mask *description*
    (`BlockDiffusionMask`) that the kernels compute from indices. ``force``
    in {"flash", "reference"} overrides dispatch (tests). Flash handles GQA
    natively (no kv repeat), no mask, key-padding masks ([B,1,1,Sk] or
    [Sk]), causal masks, mask descriptions, Sq != Sk, and head_dim >= 64 at
    lengths that tile by 128. A mask *tensor* with per-query structure, and
    any shape that does not tile, go to the XLA reference (a description is
    made dense for it); on a TPU a shape and mask the kernels can take never
    does, short of the ``MAGGY_TPU_NO_FLASH`` switch.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    if H % Hkv != 0:
        raise ValueError("H={} not divisible by Hkv={}".format(H, Hkv))
    structure = mask if isinstance(mask, BlockDiffusionMask) else None
    if structure is not None:
        if Sq != Sk or Sq != 2 * structure.length \
                or structure.length % structure.block:
            raise ValueError(
                "{} describes Sq = Sk = {} in whole blocks; got Sq={}, "
                "Sk={}".format(structure, 2 * structure.length, Sq, Sk))
        pad_mask, mask_ok = None, True
    else:
        pad_mask, mask_ok = _key_padding_mask(mask, B, Sk)
    tiles_ok = (
        mask_ok and D >= 64 and D % 8 == 0
        and Sq % 128 == 0 and Sk % 128 == 0
    )
    if force == "flash":
        if not tiles_ok:
            raise ValueError(
                "force='flash' requires no mask, a key-padding mask "
                "([B,1,1,Sk] or [Sk]) or a mask description, D>=64 with "
                "D%8==0, and 128-tiling Sq/Sk; got D={}, Sq={}, Sk={}, "
                "mask={}".format(
                    D, Sq, Sk, mask if structure is not None or mask is None
                    else jnp.shape(mask)))
        use_flash = True
    else:
        use_flash = force is None and _tpu_backend() and tiles_ok \
            and not _flash_disabled()
    if not use_flash:
        if structure is not None:
            mask = structure.dense()
        return attention_reference(q, k, v, causal=causal, mask=mask)
    plan = tile_plan(Sq, Sk, D, H, Hkv, q.dtype.itemsize, causal,
                     pad_mask is not None, structure)
    _remember(plan, Sq, Sk, causal, structure)
    # A description rides as one more static argument; without one the call
    # is the one it always was.
    extra = () if structure is None else (structure,)
    return flash_attention_planned(q, k, v, pad_mask, causal, plan,
                                   not _tpu_backend(), *extra)
