"""Attention: Pallas flash kernel (TPU) with an XLA reference path.

The reference framework has no attention code (SURVEY.md §5.7); this is the
TPU-first hot-op design the BERT/Llama baseline configs need:

- `flash_attention`: Pallas TPU kernels — tiled online-softmax forward and
  a fused backward (one walk of the visible tiles makes each tile's scores
  once and from them dV, dK and dQ, accumulated in float32 VMEM over the
  whole lengths; where those accumulators do not fit, a two-kernel
  backward: dK/dV streaming Q tiles, dQ streaming K/V tiles), and no S x S
  materialization in either direction. Under a mask that empties tiles by
  their indices (causal, a description) a kernel's grid walks only the tiles
  that hold a visible pair (`tile_walk`: a schedule in SMEM), and a tile in
  which every pair is visible computes no mask. Every kernel reads q, k,
  v and dO where the projections wrote them, [B, S, H*D] (a free view of
  [B, S, H, D]), and writes o, dQ, dK and dV there: a step's heads are a
  run of columns (`_columns`, `_step_heads`), so XLA moves nothing in or
  out of the kernels. Natively supports:
    * GQA — K/V carry Hkv < H heads and are NEVER repeat-expanded: the
      query heads of a K/V group are one run of columns, the kv BlockSpec
      index maps ignore which of the group's heads a step holds, so each
      kv tile is fetched once per group and dK/dV accumulate across the
      group's (sequential) grid dimension.
    * key-padding masks ([B, Sk] keep-mask) — the BERT fine-tune config's
      mask shape, streamed as one [1, blk_k] tile per k-block.
    * mask *descriptions* with per-query structure (`BlockDiffusionMask`:
      the block-diffusion training mask over a noised and a clean copy of
      the sequence; `CausalWindow`: a sliding window under the causal
      mask) — computed per tile from indices as the causal mask is; the
      tiles a description empties are no steps of the walk (`_tile_runs`)
      and the plan counts only those that run.
    * Sq != Sk, with bottom-right-aligned causal masking (offset = Sk-Sq),
      e.g. decode windows / ring-attention shards.
    * head_dim >= 64. At D 64 (BERT-base) two heads of an MHA layer share
      a tile's 128 lanes (`lane_pack`), so every tile moves in whole rows.
  Per-row statistics (log-sum-exp, and delta in the backward, which the
  backward makes) are stored COMPACTLY as [B, H/pack, pack, Sq] fp32 with
  q-rows on the lane dimension (one [pack, blk_q] tile per q-block) — not
  broadcast to 128 lanes in HBM. The forward and the backward take their
  scores keys-major, [keys, queries], so the statistics are used and made
  as the rows they are stored as: the forward's running max and sum are
  reductions over sublanes, its output accumulates transposed (o^T += V^T
  p^T) and turns back once a q-block, as it is written, and the fused
  backward's dQ accumulates transposed the same way.
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


def _columns(x):
    """[B, S, H, D] -> [B, S, H*D]: the heads side by side in the columns,
    as the projections wrote them (a free view). The kernels read and write
    this layout: head ``h`` is columns ``[h*D, (h+1)*D)``, so the query
    heads of K/V group ``g`` are one run of columns, ``[g*rep*D,
    (g+1)*rep*D)``, and ``pack`` heads that share a tile's lanes are one
    slab of ``pack*D`` of them."""
    B, S, H, D = x.shape
    return x.reshape(B, S, H * D)


def _slab_stats(x, pack=1):
    """[B, H, Sq] row statistics -> [B, H/pack, pack, Sq] fp32, one
    [pack, Sq] tile of rows to a slab of ``pack`` heads."""
    B, H, Sq = x.shape
    return x.reshape(B, H // pack, pack, Sq).astype(jnp.float32)


def _step_heads(H: int, Hkv: int, D: int) -> tuple:
    """The query heads one grid step may hold, fewest first. A step's query
    heads are one run of columns and its K/V heads another, each a block
    that Mosaic moves only if its width is whole 128-lane tiles or the
    whole row: heads of a batch row (MHA, in whole slabs of `lane_pack`
    heads), else query heads of one K/V group, which share one K/V head's
    columns; where that head is narrower than a tile and not the whole row
    (GQA at D 64), whole groups, each with its K/V head."""
    pack, rep = lane_pack(H, Hkv, D), H // Hkv
    within = [h for h in _divisors(rep if rep > 1 else H)
              if h % pack == 0 and _moves(H, Hkv, D, h)]
    return tuple(within or [rep * g for g in _divisors(Hkv)
                            if _moves(H, Hkv, D, rep * g)])


def _moves(H: int, Hkv: int, D: int, heads: int) -> bool:
    """Whether Mosaic moves the blocks of a step of ``heads`` query heads:
    the query side's columns and the K/V side's (one K/V head to a group's
    heads) are each whole 128-lane tiles or the whole row."""
    kv = max(1, heads // (H // Hkv))
    return all(w % _LANES == 0 or w == row
               for w, row in ((heads * D, H * D), (kv * D, Hkv * D)))


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


class CausalWindow(NamedTuple):
    """A *description* of the sliding-window causal mask, which the kernels
    compute per tile from indices: query i sees key j iff ``i - window < j
    <= i`` (Sq = Sk), its own position and the ``window - 1`` before it.

    The upper edge is the kernels' causal mask (the description goes with
    ``causal=True``); the description adds the lower one. Tiles wholly
    below the band are no steps of the walk (`_tile_runs`), so a layer
    under a window of W at S positions walks about ``W / S`` of the causal
    walk's tiles. Hashable: it is a static argument of the kernels' jits."""
    window: int

    def dense(self, length: int) -> jnp.ndarray:
        """The [length, length] keep-mask, for `attention_reference`."""
        pos = jnp.arange(length)
        return (pos[None, :] <= pos[:, None]) \
            & _window_visible(pos[:, None], pos[None, :], self)


def _window_visible(q_pos, k_pos, mask: CausalWindow):
    """The window's lower edge on a tile of positions (the causal mask is
    the upper one)."""
    return k_pos > q_pos - mask.window


def _structure_tile_mask(s, q0, k0, structure, q_axis=0):
    """The mask description's mask for a score tile whose first query is
    ``q0`` and first key ``k0``, [queries, keys] or, with ``q_axis=1``,
    transposed."""
    rows, cols = s.shape
    q_shape = (rows, 1) if q_axis == 0 else (1, cols)
    k_shape = (1, cols) if q_axis == 0 else (rows, 1)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, q_shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, k_shape, 1 - q_axis)
    visible = _window_visible if isinstance(structure, CausalWindow) \
        else _block_diffusion_visible
    return jnp.where(visible(q_pos, k_pos, structure), s, NEG_INF)


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
    `_blocks_run` counts. On Python ints and numpy arrays alike. A
    `CausalWindow` comes with the causal mask and adds its lower edge."""
    runs = None
    if causal:
        # Tiles strictly above the diagonal see nothing.
        runs = k0 < q0 + blk_q + offset
    if isinstance(structure, CausalWindow):
        # Nor do tiles wholly below the band: the tile's last key is past
        # its first query's window. (j - i takes every value between the
        # tile's least and its largest, so meeting both edges is meeting
        # the band.)
        seen = k0 + blk_k - 1 > q0 - structure.window
        runs = seen if runs is None else runs & seen
    elif structure is not None:
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
    if isinstance(structure, CausalWindow):
        # And its first key to its last query.
        whole = whole & (k0 > q0 + blk_q - 1 - structure.window)
    elif structure is not None:
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
    """The tiles of the kernels a layer runs, by the kernels' names: the
    forward, and the fused backward (``bwd``) or, where its accumulators
    over the whole lengths do not fit VMEM, the split pair (``dkdv``,
    ``dq``). A kernel the plan does not run is None."""
    fwd: KernelTiles
    dkdv: Optional[KernelTiles] = None
    dq: Optional[KernelTiles] = None
    bwd: Optional[KernelTiles] = None

    @classmethod
    def explicit(cls, blk_q: int, blk_k: int,
                 fused: bool = True) -> "FlashPlan":
        """The caller's own tiles in every kernel, one head a step: the
        forward and the fused backward, or the split pair (``fused``
        False)."""
        tiles = KernelTiles(blk_q, blk_k)
        return cls(tiles, bwd=tiles) if fused else cls(tiles, tiles, tiles)

    def kernels(self) -> list:
        """(name, tiles) of each kernel the plan runs, in order."""
        return [(name, tiles) for name, tiles in zip(self._fields, self)
                if tiles is not None]

    def describe(self) -> str:
        """``fwd q512 k512 h4 kq; bwd q512 k512 h4`` (or ``...; dkdv q512
        k512 h2; dq q512 k512 h4`` for the split pair): the forward's word
        ``kq`` says it takes its scores keys-major."""
        return "; ".join("{} q{} k{} h{}".format(name, *tiles)
                         + " kq" * (name == "fwd")
                         for name, tiles in self.kernels())


#: What a step's buffers may take of VMEM as `step_vmem_bytes` reckons them:
#: half of the 16 MiB a v5e kernel gets by default, because the reckoning
#: leaves out what Mosaic adds (relayouts, spills, the temporaries of more
#: than one pass where it overlaps unrolled passes).
VMEM_BUDGET = 8 * 2 ** 20
#: The VMEM the fused backward asks Mosaic for (`vmem_limit_bytes`; a v5e
#: core has 128 MiB): its dQ, dK and dV accumulate over the whole lengths.
#: The plan takes it where its step, accumulators included, leaves
#: `VMEM_BUDGET` of this for what Mosaic adds.
BWD_VMEM_LIMIT = 96 * 2 ** 20
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


#: Query columns one pass of the forward takes at most: it takes its scores
#: keys-major, every key of the tile against a run of query columns, and
#: that run is the N of both its products (K Q^T and V^T p^T). A v5e reads
#: 1.60 us a 512-tile and head at 128 columns, 1.41 at 256 and 1.19 at 512
#: (SmallThinker's global layer, 1024-tiles; PERF.md section 6).
_FWD_COLS = 512


def _fwd_cols(blk_q: int, blk_k: int, itemsize: int, spare: int) -> int:
    """Query columns of a forward pass: `_pass_rows`' run (what
    `step_vmem_bytes` reckons), widened to the most multiples of 128 that
    divide ``blk_q``, up to `_FWD_COLS`, whose wider scores, probabilities
    and rounded copy fit in ``spare`` bytes: what `VMEM_BUDGET` leaves
    beside the step."""
    base = _pass_rows(blk_q, blk_k)
    fit = [c for c in range(base, min(blk_q, _FWD_COLS) + 1, _LANES)
           if blk_q % c == 0
           and (c - base) * blk_k * (2 * 4 + itemsize) <= spare]
    return max(fit, default=base)


def _pass_rows(rows: int, cols: int) -> int:
    """Rows of a [rows, cols] tile one pass of a kernel's inner loop takes:
    the most multiples of 128 that divide ``rows`` and keep the pass within
    `_PASS_ELEMS`; 128 at least (a row of statistics is stored 128 lanes at
    a time)."""
    fit = [r for r in range(_LANES, rows + 1, _LANES)
           if rows % r == 0 and r * cols <= _PASS_ELEMS]
    return max(fit, default=_LANES)


def step_vmem_bytes(kernel: str, tiles: KernelTiles, D: int, itemsize: int,
                    kv_shared: bool, pack: int = 1,
                    group: Optional[int] = None,
                    lengths: Optional[tuple] = None) -> int:
    """VMEM one grid step of ``kernel`` holds, from the shapes alone: every
    operand and result tile twice (the pipeline's double buffer), the
    float32 accumulators once, and the float32 scores, probabilities and
    their rounded copies of one pass over one head (passes and heads run
    one after the other; the forward widens its passes into what
    `VMEM_BUDGET` leaves, `_fwd_cols`). The last dimension pads to 128
    lanes (``pack`` heads share them); a row of statistics or mask pads
    to 8 sublanes.
    Query heads that share K/V (``kv_shared``) hold one K/V head, or one a
    ``group`` of them where a step holds whole groups (`_step_heads`).
    The fused backward (``bwd``) keeps dQ^T, delta, dK and dV over the
    whole (Sq, Sk) ``lengths`` and writes them through two staging tiles
    of each side."""
    blk_q, blk_k, heads = tiles
    slabs = -(-heads // pack)  # [rows, lanes] tiles on the query side
    q_tile = slabs * blk_q * _lane_pad(pack * D)
    kv_slabs = max(1, heads // (group or heads)) if kv_shared else slabs
    kv_tile = kv_slabs * blk_k * _lane_pad(pack * D)
    rows = 2 * 8 * 4 * (slabs * blk_q + blk_k)  # lse (delta) and the mask
    if kernel == "bwd":      # q, dO, o | k, v | staging | s, dp, ds, dQ^T's
        Sq, Sk = lengths     # term | dQ^T, delta | dK, dV
        tiles_io = 2 * itemsize * (3 * q_tile + 2 * kv_tile) + rows
        staging = 2 * itemsize * (q_tile + kv_tile)
        temps = _pass_rows(blk_k, blk_q) * blk_q * (3 * 4 + 2 * itemsize) \
            + 4 * pack * D * blk_q
        scratch = 4 * (slabs * (pack * D + 8) * Sq
                       + 2 * kv_slabs * Sk * _lane_pad(pack * D))
        return tiles_io + staging + temps + scratch
    if kernel == "fwd":      # q, o | k, v | o^T, m, l | s, p and p rounded
        tiles_io = 2 * itemsize * (2 * q_tile + 2 * kv_tile) + rows
        scratch = 4 * (q_tile + 2 * 8 * slabs * blk_q)
        temps = _pass_rows(blk_q, blk_k) * blk_k * (2 * 4 + itemsize)
    elif kernel == "dkdv":   # q, dO | k, v, dk, dv | dk, dv | s, dp, ds
        tiles_io = 2 * itemsize * (2 * q_tile + 4 * kv_tile) + 2 * rows
        scratch = 4 * 2 * kv_tile
        temps = _pass_rows(blk_k, blk_q) * blk_q * (3 * 4 + 2 * itemsize)
    elif kernel == "dq":     # q, dO, o, dq | k, v | dq | s, dp, ds
        tiles_io = 2 * itemsize * (4 * q_tile + 2 * kv_tile) + 2 * rows
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
# opens and closes its accumulator. The fused backward's walk also marks the
# first step at each q-block of a rep block (`_OPEN`, where delta is made)
# and, from bit `_HELD` up, the q-block whose o the step holds: the last
# one opened.
_EMPTY, _PARTIAL, _WHOLE, _KIND = 0, 1, 2, 3
_FIRST, _LAST, _OPEN = 4, 8, 16
_HELD = 5


class TileWalk(NamedTuple):
    """The grid steps of one kernel under a mask that empties tiles: per
    step the q-block and k-block of its tile, the block of query heads it
    belongs to (``rep``; the dK/dV kernel and the fused backward stream a
    K/V group's rep blocks through one accumulator, the forward and dQ
    kernels have the rep axis on the grid) and its ``flags`` (`_KIND`,
    `_FIRST`, `_LAST`; the fused backward's `_OPEN` and `_HELD`). `table`
    is what the kernel and its index maps read from SMEM."""
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
    q-block; ``bwd`` (the fused backward) by rep block, then k-block, then
    ascending q-block, so that each q-block's dQ still takes its k-blocks
    in ascending order and each k-block's dK/dV its (rep block, q-block)
    pairs in the order ``dkdv`` gives them. A tile in which every pair is
    visible (`_tile_whole`) is marked whole, the others partial. An
    accumulator that no tile reaches (under a causal mask with Sq > Sk the
    first query rows see no key) still gets one step, marked empty, on the
    block the walk already holds, which only initialises and finalises it:
    its ``out`` / ``dq`` block is written (zeros, and the log-sum-exp of no
    key) as the dense grid wrote it. Every k-block is seen by some query
    under both mask kinds. The fused backward's accumulators span the whole
    lengths and are written whole, zeros where no tile reached: its walk
    has no empty step, its first and last flags open and close a rep
    block, and it marks where a rep block first reaches each q-block
    (`_OPEN`) and which q-block's o a step holds (`_HELD`)."""
    runs = _tiles_at(Sq, Sk, blk_q, blk_k, _tile_runs, causal, structure)
    if runs is None:
        return None
    whole = _tiles_at(Sq, Sk, blk_q, blk_k, _tile_whole, causal, structure)
    if kernel == "bwd":
        rows = []  # (q-block, k-block, flags, rep block)
        tiles = list(zip(*runs.T.nonzero()))  # by k-block, then q-block
        for rep in range(reps):
            opened, held = set(), 0
            for n, (kb, qi) in enumerate(tiles):
                flags = (_WHOLE if whole[qi, kb] else _PARTIAL) \
                    | _FIRST * (n == 0) | _LAST * (n == len(tiles) - 1)
                if qi not in opened:
                    opened.add(qi)
                    held, flags = qi, flags | _OPEN
                rows.append((int(qi), int(kb), flags | int(held) << _HELD,
                             rep))
        return TileWalk(*zip(*rows))
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
    tile; whole groups where one group's columns are too narrow for
    Mosaic to move: `_step_heads`) until stepping is under ``STEP_SHARE``
    of it or VMEM is full. ``has_mask`` moves nothing yet: a key-padding
    row is 4 KB a step. A mask description (``structure``) weighs in as
    the causal mask does, through the tiles it leaves to run.

    The backward is the fused kernel where it fits (`fused_fits`), else
    the split pair, each at tiles chosen the same way."""
    del has_mask
    kv_shared = H != Hkv
    pack = lane_pack(H, Hkv, D)
    step_heads = _step_heads(H, Hkv, D)

    def tiles_of(kernel):
        def cost(pair):
            blk_q, blk_k = pair
            along = blk_q if kernel == "dkdv" else blk_k
            return (_blocks_run(Sq, Sk, blk_q, blk_k, causal, structure)
                    * (STEP_COST + blk_q * blk_k), -along)

        def fits(blk_q, blk_k, heads):
            tiles = KernelTiles(blk_q, blk_k, heads)
            if kernel == "bwd":
                return fused_fits(Sq, Sk, D, H, Hkv, itemsize, tiles)
            return step_vmem_bytes(kernel, tiles, D, itemsize, kv_shared,
                                   pack, H // Hkv) <= VMEM_BUDGET

        pairs = [(bq, bk) for bq in _tiles_of(Sq) for bk in _tiles_of(Sk)
                 if fits(bq, bk, step_heads[0])]
        if not pairs:
            if kernel == "bwd":
                return None
            pairs = [(_LANES, _LANES)]
        blk_q, blk_k = min(pairs, key=cost)
        heads = step_heads[0]
        for h in step_heads:
            if not fits(blk_q, blk_k, h):
                break
            heads = h
            if STEP_COST / h <= STEP_SHARE * blk_q * blk_k:
                break
        return KernelTiles(blk_q, blk_k, heads)

    fwd, bwd = tiles_of("fwd"), tiles_of("bwd")
    if bwd is not None:
        return FlashPlan(fwd, bwd=bwd)
    return FlashPlan(fwd, tiles_of("dkdv"), tiles_of("dq"))


def fused_fits(Sq: int, Sk: int, D: int, H: int, Hkv: int, itemsize: int,
               tiles: KernelTiles) -> bool:
    """Whether the fused backward runs at ``tiles``: its passes are the
    dK/dV kernel's and one product more, so the dK/dV kernel's step at
    these tiles fits `VMEM_BUDGET`, and its own step, with its
    accumulators over the whole lengths, leaves `VMEM_BUDGET` of
    `BWD_VMEM_LIMIT` for what Mosaic adds (S 131,072 in GQA at D 128
    does not fit: dK and dV alone are 128 MiB of float32). Its results
    leave by DMA, from windows of rows of the results in HBM, which Mosaic
    cuts only where a row is whole 128-lane tiles: not at 4 heads of 72 or
    3 of 64."""
    if H * D % _LANES or Hkv * D % _LANES:
        return False
    args = (tiles, D, itemsize, H != Hkv, lane_pack(H, Hkv, D), H // Hkv)
    return step_vmem_bytes("dkdv", *args) <= VMEM_BUDGET \
        and step_vmem_bytes("bwd", *args, lengths=(Sq, Sk)) \
        <= BWD_VMEM_LIMIT - VMEM_BUDGET


def _remember(plan: FlashPlan, Sq: int, Sk: int, causal: bool,
              structure: Optional[BlockDiffusionMask]) -> None:
    said = plan.describe()
    if structure is not None:
        # The mask kind and its size (a window's, or the block length and
        # the data length), per kernel the tiles of a head that run of those
        # there are, and what its grid walks for them.
        kernels = plan.kernels()
        walks = [tile_walk(name, Sq, Sk, t.blk_q, t.blk_k, causal, structure)
                 for name, t in kernels]
        kind = "window w{}".format(structure.window) \
            if isinstance(structure, CausalWindow) \
            else "block_diffusion b{} L{}".format(structure.block,
                                                  structure.length)
        said += "; {} tiles {}; walk {}".format(
            kind,
            " ".join("{} {}/{}".format(
                name, walk.steps - walk.count(_EMPTY),
                (Sq // t.blk_q) * (Sk // t.blk_k))
                for (name, t), walk in zip(kernels, walks)),
            " ".join("{} {}".format(name, walk.describe())
                     for (name, _), walk in zip(kernels, walks)))
    remember_plan("flash", said)


# -------------------------------------------------------------- pallas kernel
#
# Every kernel reads its operands and writes its results where the model
# keeps them, [B, S, H*D] (`_columns`): a step's query-side blocks (q, o,
# dO, dQ) are [rows, slabs*width] and its K/V-side blocks (k, v, dK, dV)
# [rows, kv_slabs*width], a slab being ``width`` = pack*D columns of
# ``pack`` heads side by side in a tile's lanes (`lane_pack`; one head where
# pack is 1). Query slab h reads K/V slab h // (slabs / kv_slabs) (`_Slabs`):
# its own for MHA, the one K/V head for the query heads of one group. Row
# statistics (lse, delta) are [slabs, pack, rows]. Slabs, row chunks and a
# slab's heads run one after the other, so the [rows, blk_k] temporaries are
# one head's.

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    """MXU dot on the operands as they are, accumulated in float32."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _each(n, body, unroll=False):
    """``body(i)`` for i in range(n), in order (unrolled where asked)."""
    if n <= _UNROLL or unroll:
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


class _Slabs(NamedTuple):
    """How a step's heads lie in its blocks' columns: ``count`` query-side
    slabs of ``width`` columns each, and one K/V-side slab to each ``per``
    of them."""
    count: int
    width: int
    per: int

    @classmethod
    def of(cls, q_ref, k_ref, count):
        width = q_ref.shape[-1] // count
        return cls(count, width, count // (k_ref.shape[-1] // width))

    def cols(self, h):
        """The columns of slab ``h`` of a block."""
        return _chunk(h, self.width)

    def kv(self, h):
        """The columns of the K/V-side slab that query-side slab ``h``
        reads."""
        return _chunk(h // self.per, self.width)

    def each(self, body, n=None):
        """``body(h)`` for each of the first ``n`` slabs (all of the query
        side's), in order. A loop over them takes a traced ``h`` only where
        a slab is whole lane tiles: Mosaic takes a traced column offset in
        multiples of 128 alone."""
        _each(self.count if n is None else n, body,
              unroll=self.width % _LANES != 0)


def _each_chunk(slabs: _Slabs, chunks, body):
    """``body(h, c)`` for every row chunk c of every slab h of a step."""
    slabs.each(lambda h: _each(chunks, lambda c: body(h, c)))


def _scores(q, k_blk, q0, k0, causal, sm_scale, offset, keep,
            structure=None, q_axis=0):
    """[rows, cols] float32 logits of the queries from ``q0`` on against the
    keys from ``k0`` on: Q K^T on the operands as they are, or with
    ``q_axis=1`` K Q^T, keys-major; scaled after the dot (every kernel
    alike, so that the backward's p is the forward's), then the masks.
    ``keep`` is the key-padding mask as it broadcasts against the tile (a
    [1, keys] row, or a [keys, 1] column keys-major), or None."""
    s = (_dot(q, k_blk, _NT) if q_axis == 0 else _dot(k_blk, q, _NT)) \
        * sm_scale
    if causal:
        s = _causal_tile_mask(s, q0, k0, offset, q_axis)
    if structure is not None:
        s = _structure_tile_mask(s, q0, k0, structure, q_axis)
    if keep is not None:
        s = jnp.where(keep != 0, s, NEG_INF)
    return s


def _key_column(mask_ref, keys=slice(None)):
    """The key-padding mask's ``keys`` as the [keys, 1] column a keys-major
    tile takes (a lane-to-sublane relayout of the stored row), or None."""
    return None if mask_ref is None else mask_ref[:, keys][0][:, None]


class _Step(NamedTuple):
    """Where one grid step of a kernel stands: the q-block and the k-block
    of its tile, whether the step opens (``first()``) and closes
    (``last()``) its accumulator, and the tile's kind (`_KIND` of a walk's
    flags; None on the dense grid, where every tile runs under whatever
    mask the kernel has). ``first`` and ``last`` are asked where the kernel
    needs them, and in that order. The fused backward also asks for its
    rep block (``rep()``) and whether the step is the first of its rep
    block at its q-block (``opens()``)."""
    qi: jax.Array
    kb: jax.Array
    first: Callable[[], jax.Array]
    last: Callable[[], jax.Array]
    kind: Optional[jax.Array] = None
    rep: Optional[Callable[[], jax.Array]] = None
    opens: Optional[Callable[[], jax.Array]] = None


def _dense_step(q_axis: int, k_axis: int, streamed: tuple,
                rep_axis: Optional[int] = None) -> _Step:
    """A step of the dense grid: the blocks are program ids, and an
    accumulator opens at the first and closes at the last program of the
    ``streamed`` (sequential) axes; the rep block is program id
    ``rep_axis``, and a q-block is first reached at k-block 0. Ids, extents
    and comparisons are traced where the kernels always traced them, so a
    kernel with nothing to walk lowers to the Mosaic module it always
    did."""
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

    rep = None if rep_axis is None else pl.program_id(rep_axis)
    return _Step(ids[q_axis], ids[k_axis], first, last, rep=lambda: rep,
                 opens=lambda: ids[k_axis] == 0)


def _walk_step(walk_ref, axis: int) -> _Step:
    """A step of a `TileWalk`, read from its table in SMEM: grid axis
    ``axis`` counts the walk's steps."""
    from jax.experimental import pallas as pl

    t, steps = pl.program_id(axis), pl.num_programs(axis)
    flags = walk_ref[2 * steps + t]
    return _Step(walk_ref[t], walk_ref[steps + t],
                 lambda: flags & _FIRST != 0, lambda: flags & _LAST != 0,
                 flags & _KIND, rep=lambda: walk_ref[3 * steps + t],
                 opens=lambda: flags & _OPEN != 0)


def _step_and_refs(refs, causal, structure, q_axis, k_axis, streamed,
                   rep_axis=None):
    """(this grid step, the kernel's operand, result and scratch refs). A
    kernel walks where its mask kind empties tiles by their indices: the
    walk's table is then its first ref (scalar prefetch) and the walk's
    steps the grid axis that follows the parallel ones the dense grid
    begins with. Else the grid is dense, with the q-blocks, the k-blocks
    and the ``streamed`` (sequential) axes where the arguments say, and
    the rep blocks on ``rep_axis`` where the walk carries them (the fused
    backward)."""
    if causal or structure is not None:
        axes = (q_axis, k_axis) + (() if rep_axis is None else (rep_axis,))
        return _walk_step(refs[0], min(axes)), refs[1:]
    return _dense_step(q_axis, k_axis, streamed, rep_axis), refs


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


def _flash_fwd_kernel(*refs, causal, sm_scale, has_mask, offset, one_pass,
                      cols, structure=None):
    """One (b, head block, q-block, k-block) program: K/V stream through the
    grid's innermost (sequential) dimension, so VMEM holds one [blk_k, D]
    tile of K and V a head — sequence length is bounded by HBM, not VMEM.
    Under a causal mask or a description that dimension is a `TileWalk`'s
    steps, and the walk's table comes first among the refs.

    The scores are taken keys-major, as the dK/dV kernel takes them: a pass
    covers ``cols`` query columns (`_fwd_cols`) against every key of the
    tile, s^T = K Q^T as [blk_k, cols], so the row statistics (running max
    and sum) are reductions over sublanes, elementwise across vector
    registers, and rows [1, cols] as lse is stored; no lane reduction and
    no broadcast column. The output accumulates transposed, o^T += V^T p^T,
    the product taking V transposed (a transposed-LHS dot, which read as
    fast on the chip as a V^T made once a step in VMEM), and turns back
    once a q-block, when it is written; of a pair of packed heads, each
    owns half of o^T's rows. Online-softmax state (o^T, running max,
    running sum) lives in VMEM scratch that persists across the k-block
    steps of each program group; where one k-block is the whole row
    (``one_pass``) there is no state to keep and no scratch: the softmax
    is taken once.

    Refs: q [BLK_Q, slabs*width]; k/v [BLK_K, kv_slabs*width] (`_Slabs`);
    (mask [1, BLK_K] int32); o [BLK_Q, slabs*width]; lse
    [slabs, pack, BLK_Q] (q-rows on lanes — compact, no 128x pad); scratch
    acc (o^T) [slabs, width, BLK_Q], m/l [slabs, pack, BLK_Q] fp32.
    """
    from jax.experimental import pallas as pl

    step, refs = _step_and_refs(refs, causal, structure, 3, 4, (4,))
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    o_ref, lse_ref = refs[3 + has_mask:5 + has_mask]

    slabs, pack, blk_q = lse_ref.shape
    sl = _Slabs.of(q_ref, k_ref, slabs)
    blk_k = k_ref.shape[0]
    head = functools.partial(_chunk, rows=sl.width // pack)  # rows of o^T

    def scores(masked, keep, h, c, j):
        return _scores(_lanes_of(j, pack, q_ref[_chunk(c, cols), sl.cols(h)]),
                       k_ref[:, sl.kv(h)], step.qi * blk_q + c * cols,
                       step.kb * blk_k, causal and masked, sm_scale, offset,
                       keep, structure if masked else None, q_axis=1)

    def values(h, j, p):
        """V^T p^T: head j's rows of o^T, [D, cols], V taken transposed."""
        v_blk = v_ref[:, sl.kv(h)][:, head(j)]
        return _dot(v_blk, p.astype(v_blk.dtype), _TN)

    def write(h, c, heads):
        """o^T's rows of each head, [D, cols], as o's columns."""
        out = heads[0] if pack == 1 else jnp.concatenate(heads)
        o_ref[_chunk(c, cols), sl.cols(h)] = out.T.astype(o_ref.dtype)

    def on_tile(contribute):
        """``contribute(masked, keep, h, c)`` over the step's passes."""
        def body(masked):
            keep = _key_column(mask_ref)
            _each_chunk(sl, blk_q // cols,
                        functools.partial(contribute, masked, keep))

        _on_tile(step, body)

    if one_pass:
        def whole_columns(masked, keep, h, c):
            r = _chunk(c, cols)
            heads = []
            for j in range(pack):
                s = scores(masked, keep, h, c, j)
                m = jnp.max(s, axis=0, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=0, keepdims=True)  # >= 1: the max's
                heads.append(values(h, j, p) / l)
                lse_ref[h, j:j + 1, r] = m + jnp.log(l)
            write(h, c, heads)

        on_tile(whole_columns)
        return

    acc_ref, m_ref, l_ref = refs[5 + has_mask:]

    @pl.when(step.first())
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def contribute(masked, keep, h, c):
        r = _chunk(c, cols)
        for j in range(pack):
            s = scores(masked, keep, h, c, j)
            m_prev, l_prev = m_ref[h, j:j + 1, r], l_ref[h, j:j + 1, r]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h, j:j + 1, r] = alpha * l_prev \
                + jnp.sum(p, axis=0, keepdims=True)
            m_ref[h, j:j + 1, r] = m_new
            acc_ref[h, head(j), r] = acc_ref[h, head(j), r] * alpha \
                + values(h, j, p)

    on_tile(contribute)

    @pl.when(step.last())
    def _finalize():
        def finish(h, c):
            r = _chunk(c, cols)
            heads = []
            for j in range(pack):
                l_safe = jnp.maximum(l_ref[h, j:j + 1, r], 1e-30)
                heads.append(acc_ref[h, head(j), r] / l_safe)
                lse_ref[h, j:j + 1, r] = m_ref[h, j:j + 1, r] \
                    + jnp.log(l_safe)
            write(h, c, heads)

        _each_chunk(sl, blk_q // cols, finish)


def _head_blocks(Sq, Sk, H, Hkv, D, tiles):
    """How a step's heads lie in the columns: (G, R, hg, hr). A row holds G
    K/V-side slabs (K/V heads; for MHA, slabs of `lane_pack` heads) and R
    query-side slabs to each; a step holds hg of the first and hr of the
    second: MHA (R == 1) blocks slabs with their K/V, GQA the query heads
    of one group over its K/V head, or whole groups. A step takes the
    fewest heads Mosaic moves (`_moves`) that are as many as asked or a
    multiple of them. Tiles or heads that do not divide the shape are
    refused."""
    if Sq % tiles.blk_q or Sk % tiles.blk_k:
        raise ValueError("tiles q{} k{} do not divide Sq={}, Sk={}".format(
            tiles.blk_q, tiles.blk_k, Sq, Sk))
    pack, R = lane_pack(H, Hkv, D), H // Hkv
    heads = -(-tiles.heads // pack) * pack
    if R == 1:
        if H % heads:
            raise ValueError("{} heads a step do not divide H={}".format(
                tiles.heads, H))
    elif R % heads and (heads % R or H % heads):
        raise ValueError("{} heads a step do not divide the {} query heads "
                         "of a K/V group".format(tiles.heads, R))
    heads = next(h for h in range(heads, H + 1, heads)
                 if H % h == 0 and (R % h == 0 or h % R == 0)
                 and _moves(H, Hkv, D, h))
    if R == 1:
        return H // pack, 1, heads // pack, 1
    return Hkv, R, max(1, heads // R), min(heads, R)


# Where a tile's blocks lie in the kernels' operands, from its (b, g, r, qi,
# kb), ``reps`` blocks of query-side slabs to a K/V-side block: the query
# side's columns, the K/V side's, the row statistics, the key-padding row.
def _q_at(reps):
    return lambda b, g, r, qi, kb: (b, qi, g * reps + r)


def _kv_at(b, g, r, qi, kb):
    return b, kb, g


def _stat_at(reps):
    return lambda b, g, r, qi, kb: (b, g * reps + r, 0, qi)


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


@functools.partial(jax.jit, static_argnames=("causal", "tiles", "heads",
                                             "interpret", "structure"))
def _flash_fwd(q, k, v, mask, causal, tiles, heads, interpret,
               structure=None):
    """q: [B,Sq,H*D]; k/v: [B,Sk,Hkv*D] (`_columns`), ``heads`` (H, Hkv);
    mask: [B,1,Sk] int32 or None.
    Returns (out [B,Sq,H*D], lse [B,H/pack,pack,Sq] fp32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, _ = q.shape
    Sk = k.shape[1]
    H, Hkv = heads
    D = q.shape[2] // H
    pack = lane_pack(H, Hkv, D)
    blk_q, blk_k, _ = tiles
    G, R, hg, hr = _head_blocks(Sq, Sk, H, Hkv, D, tiles)
    slabs, width = hg * hr, pack * D
    offset = Sk - Sq
    sm_scale = 1.0 / (D ** 0.5)
    grid, semantics, prefetch, on = _kernel_grid(
        tile_walk("fwd", Sq, Sk, blk_q, blk_k, causal, structure),
        (B, G // hg, R // hr), (Sq // blk_q,), (Sk // blk_k,))

    q_spec = pl.BlockSpec((None, blk_q, slabs * width), on(_q_at(R // hr)))
    kv_spec = pl.BlockSpec((None, blk_k, hg * width), on(_kv_at))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k, v]
    if mask is not None:
        in_specs.append(pl.BlockSpec((None, 1, blk_k),
                                     on(_mask_at)))
        operands.append(mask)

    # One k-block a row, and every query row sees a key: the softmax is
    # whole in one step (under a causal mask with Sq > Sk the first rows see
    # none, and the stepping path's skip gives them the zeros it always did).
    one_pass = Sk == blk_k and not (causal and offset < 0)
    spare = VMEM_BUDGET - step_vmem_bytes(
        "fwd", KernelTiles(blk_q, blk_k, slabs * pack), D, q.dtype.itemsize,
        H != Hkv, pack, H // Hkv)
    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               sm_scale=sm_scale, has_mask=mask is not None,
                               offset=offset, one_pass=one_pass,
                               cols=_fwd_cols(blk_q, blk_k, q.dtype.itemsize,
                                              spare),
                               structure=structure)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                q_spec,
                pl.BlockSpec((None, slabs, pack, blk_q),
                             on(_stat_at(R // hr))),
            ],
            scratch_shapes=[] if one_pass else [
                pltpu.VMEM((slabs, width, blk_q), jnp.float32),
                pltpu.VMEM((slabs, pack, blk_q), jnp.float32),
                pltpu.VMEM((slabs, pack, blk_q), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H // pack, pack, Sq), jnp.float32),
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
    [blk_q, blk_k] tiles in every kernel, one head a step (ring attention,
    Ulysses and the tests; `multi_head_attention` plans its tiles from the
    shape). ``mask``: optional [B, Sk] (or [B,1,Sk]) keep-mask over keys.
    A query row whose keys are ALL masked outputs the uniform average of V
    (p = exp(NEG_INF-NEG_INF) per key — the same value the reference's
    softmax-of-all-masked produces); such rows are padding and must be
    excluded from the loss."""
    return flash_attention_planned(q, k, v, mask, causal,
                                   _explicit_plan(q, k, blk_q, blk_k),
                                   interpret)


def _explicit_plan(q, k, blk_q: int, blk_k: int) -> FlashPlan:
    """The caller's own tiles, with the fused backward where it fits the
    shape (`fused_fits`), else the split pair."""
    _, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    return FlashPlan.explicit(blk_q, blk_k, fused_fits(
        Sq, Sk, D, H, Hkv, q.dtype.itemsize, KernelTiles(blk_q, blk_k)))


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


def _flash_fwd_cols(q, k, v, mask, causal, tiles, interpret,
                    structure=None):
    """The forward kernel on q [B,Sq,H,D], k/v [B,Sk,Hkv,D], read in place:
    (out [B,Sq,H*D], lse [B,H/pack,pack,Sq] fp32)."""
    mask3 = _canon_mask(mask, q.shape[0], k.shape[1])
    return _flash_fwd(_columns(q), _columns(k), _columns(v), mask3, causal,
                      tiles, (q.shape[2], k.shape[2]), interpret, structure)


def _flash_fwd_4d(q, k, v, mask, causal, tiles, interpret, structure=None):
    """`_flash_fwd_cols` with out as [B,Sq,H,D]."""
    out, lse = _flash_fwd_cols(q, k, v, mask, causal, tiles, interpret,
                               structure)
    return out.reshape(q.shape), lse


def _flash_fwd_rule(q, k, v, mask, causal, plan, interpret, structure=None):
    out, lse = _flash_fwd_cols(q, k, v, mask, causal, plan.fwd, interpret,
                               structure)
    # Named here, so that the primal and the residuals are the named values:
    # naming the output in the caller would keep `out` and still run the
    # kernel again for `lse`. What is named and kept is the kernel's own
    # [B,Sq,H*D]: XLA lays out a [B,Sq,H,D] value kept across the pass on
    # its own, and would copy it to and from the kernels' layout.
    out, lse = map(checkpoint_name, (out, lse), REMAT_KEEP)
    return out.reshape(q.shape), (q, k, v, mask, out, lse)


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

    slabs, pack, blk_q = lse_ref.shape
    sl = _Slabs.of(q_ref, k_ref, slabs)
    blk_k = k_ref.shape[0]
    qi, kb = step.qi, step.kb
    rows = _pass_rows(blk_k, blk_q)

    @pl.when(step.first())
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def contribute(masked, h, c):
        keys = _chunk(c, rows)
        k_blk, v_blk = k_ref[keys, sl.kv(h)], v_ref[keys, sl.kv(h)]
        keep = _key_column(mask_ref, keys)
        dk = dv = 0.0
        for j in range(pack):
            q = _lanes_of(j, pack, q_ref[:, sl.cols(h)])
            do = _lanes_of(j, pack, do_ref[:, sl.cols(h)])
            s = _scores(q, k_blk, qi * blk_q, kb * blk_k + c * rows,
                        causal and masked, sm_scale, offset, keep,
                        structure if masked else None, q_axis=1)
            p = jnp.exp(s - lse_ref[h, j:j + 1])
            ds = p * (_dot(v_blk, do, _NT) - delta_ref[h, j:j + 1])
            dv += _dot(p.astype(do.dtype), do, _NN)  # head j's lanes alone
            dk += _dot(ds.astype(q.dtype), q, _NN)
        dv_acc[h // sl.per, keys] += dv
        dk_acc[h // sl.per, keys] += dk

    _on_tile(step, lambda masked: _each_chunk(
        sl, blk_k // rows, functools.partial(contribute, masked)))

    @pl.when(step.last())
    def _finalize():
        def write(g):
            # ds lacked the scale; dK takes it once, on [blk_k, width].
            dk_ref[:, sl.cols(g)] = (dk_acc[g] * sm_scale).astype(dk_ref.dtype)
            dv_ref[:, sl.cols(g)] = dv_acc[g].astype(dv_ref.dtype)

        sl.each(write, dk_acc.shape[0])


def _flash_bwd_dq_kernel(*refs, causal, sm_scale, has_mask, offset,
                         structure=None):
    """grid (B, head block, r, qi, kb): one Q tile a head per program group;
    stream K/V tiles through the sequential kb dimension, accumulating dQ
    in VMEM. When an accumulator opens, the kernel makes delta =
    rowsum(dO o) of each head from the forward's output o: a second
    result, which the steps that follow read back and the dK/dV kernel
    reads after this one."""
    from jax.experimental import pallas as pl

    step, refs = _step_and_refs(refs, causal, structure, 3, 4, (4,))
    q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref = refs[:6]
    mask_ref = refs[6] if has_mask else None
    dq_ref, delta_ref, dq_acc = refs[6 + has_mask:]

    slabs, pack, blk_q = lse_ref.shape
    sl = _Slabs.of(q_ref, k_ref, slabs)
    blk_k = k_ref.shape[0]
    qi, kb = step.qi, step.kb
    rows = _pass_rows(blk_q, blk_k)

    @pl.when(step.first())
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

        def make_delta(h, c):
            r = _chunk(c, rows)
            prod = do_ref[r, sl.cols(h)].astype(jnp.float32) \
                * o_ref[r, sl.cols(h)].astype(jnp.float32)
            for j in range(pack):
                delta_ref[h, j:j + 1, r] = _as_row(jnp.sum(
                    _lanes_of(j, pack, prod), axis=1, keepdims=True))

        _each_chunk(sl, blk_q // rows, make_delta)

    def contribute(masked, h, c):
        r = _chunk(c, rows)
        k_blk, v_blk = k_ref[:, sl.kv(h)], v_ref[:, sl.kv(h)]
        keep = None if mask_ref is None else mask_ref[...]
        dq = 0.0
        for j in range(pack):
            q = _lanes_of(j, pack, q_ref[r, sl.cols(h)])
            do = _lanes_of(j, pack, do_ref[r, sl.cols(h)])
            s = _scores(q, k_blk, qi * blk_q + c * rows, kb * blk_k,
                        causal and masked, sm_scale, offset, keep,
                        structure if masked else None)
            # lane->sublane relayout of the compact [1, rows] statistics
            p = jnp.exp(s - lse_ref[h, j:j + 1, r][0][:, None])
            ds = p * (_dot(do, v_blk, _NT)
                      - delta_ref[h, j:j + 1, r][0][:, None])
            dq += _dot(ds.astype(q.dtype), _lanes_of(j, pack, k_blk), _NN)
        dq_acc[h, r] += dq

    _on_tile(step, lambda masked: _each_chunk(
        sl, blk_q // rows, functools.partial(contribute, masked)))

    @pl.when(step.last())
    def _finalize():
        def write(h):
            dq_ref[:, sl.cols(h)] = (dq_acc[h] * sm_scale).astype(dq_ref.dtype)

        sl.each(write)


def _dma_out(n, fill, stages, flight, window, start=0) -> None:
    """A result held in VMEM to HBM in ``n`` chunks: ``fill(c, stage)``
    casts chunk c into one of the two staging refs ``stages``, and a DMA
    takes it to ``window(c)``, the chunk's place in the result. Chunk c
    takes slot ``(start + c) % 2``, so that a chunk's DMA runs while the
    next one is cast; a loop round takes a pair of chunks, so each knows
    its staging ref as it is traced (Mosaic slices no staging buffer whose
    rows are not whole lane tiles). A DMA is waited for when its slot is
    wanted again, here or in a later grid step (`_Flight`), so the last
    chunks' DMAs run on while the kernel goes on."""
    from jax.experimental.pallas import tpu as pltpu

    def chunk(c, slot):
        flight.wait(stages, window, slot)
        fill(c, stages[slot])
        pltpu.make_async_copy(stages[slot], window(c),
                              flight.sem(slot)).start()
        flight.pending[flight.base + slot] = 1

    def pair(i, _):
        for j in range(2):
            chunk(2 * i + j, (start + j) % 2)

    jax.lax.fori_loop(0, n // 2, pair, None)
    if n % 2:
        chunk(n - 1, (start + n - 1) % 2)


class _Flight(NamedTuple):
    """The DMAs in flight from one pair of staging slots: their semaphores
    (``sems``) and whether each slot has one (``pending``, in SMEM), at
    ``base`` and ``base + 1`` of both."""
    sems: object
    pending: object
    base: int

    def sem(self, slot):
        return self.sems.at[self.base + slot]

    def wait(self, stages, window, slot) -> None:
        """Wait for the DMA in flight from ``slot``, if any (the wait's
        window is any of the result's: only its size counts)."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        @pl.when(self.pending[self.base + slot] != 0)
        def _():
            pltpu.make_async_copy(stages[slot], window(0),
                                  self.sem(slot)).wait()
            self.pending[self.base + slot] = 0


def _flash_bwd_kernel(*refs, causal, sm_scale, has_mask, offset, reps,
                      structure=None):
    """The fused backward: grid (B, head block, r, kb, qi), the three inner
    dims sequential; under a causal mask or a description a `TileWalk`'s
    steps in that order. A step makes the keys-major scores of its tile
    once, s^T = K Q^T, and from p^T = exp(s^T - lse), dP^T = V dO^T and
    dS^T = p^T (dP^T - delta) all three results: dV += p^T dO, dK += dS^T
    Q (the dK/dV kernel's products, a chunk of key rows owning its rows of
    dK and dV) and dQ^T += K^T dS^T, K taken transposed as the forward
    takes V. Each accumulates in float32 VMEM over the whole length: dQ^T
    [slabs, width, Sq] over a rep block's walk, so a q-block takes its
    k-blocks in ascending order as the dQ kernel gave them; dK and dV
    [kv_slabs, Sk, width] over the group's rep blocks, so a k-block takes
    its (rep block, q-block) pairs in the dK/dV kernel's order. delta =
    rowsum(dO o) is made where a rep block first reaches a q-block (o's
    block index moves only there), into [slabs, pack, Sq]. A result is
    written once, when its accumulator closes, cast a chunk at a time and
    moved to HBM by DMA (`_dma_out`): dQ at the end of a rep block, dK and
    dV at the end of the group. The last chunks' DMAs run on into the
    steps that follow and are waited for where their staging slot is
    wanted again, or at the kernel's last step (at BERT's shape, one tile a
    head, a step that waited for its own took 1.94 ms a layer on a v5e
    where this takes 1.64).

    Refs: q/dO/o [BLK_Q, slabs*width]; k/v [BLK_K, kv_slabs*width]; lse
    [slabs, pack, BLK_Q]; (mask [1, BLK_K] int32); dq, dk, dv in HBM
    (memory space ANY); scratch dQ^T, dK, dV, delta, the two staging pairs,
    their DMA semaphores and which of them has a DMA in flight."""
    from jax.experimental import pallas as pl

    step, refs = _step_and_refs(refs, causal, structure, 4, 3, (3, 4), 2)
    q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref = refs[:6]
    mask_ref = refs[6] if has_mask else None
    (dq_hbm, dk_hbm, dv_hbm, dq_acc, dk_acc, dv_acc, delta_acc, *stages,
     sems, pending) = refs[6 + has_mask:]
    q_stages, kv_stages = stages[:2], stages[2:]
    q_flight, kv_flight = _Flight(sems, pending, 0), _Flight(sems, pending, 2)

    slabs, pack, blk_q = lse_ref.shape
    sl = _Slabs.of(q_ref, k_ref, slabs)
    blk_k = k_ref.shape[0]
    qi, kb = step.qi, step.kb
    rows = _pass_rows(blk_k, blk_q)
    head = functools.partial(_chunk, rows=sl.width // pack)  # rows of dQ^T
    queries = _chunk(qi, blk_q)  # the tile's lanes of dQ^T and delta
    b, g, r = pl.program_id(0), pl.program_id(1), step.rep()

    def zero(n, part):
        """Each of ``n`` parts of an accumulator to zeros, in a loop."""
        jax.lax.fori_loop(0, n, lambda c, _: part(c), None)

    @pl.when(step.first() & (r == 0) & (b == 0) & (g == 0))
    def _open_kernel():  # no DMA of a result is in flight yet
        for i in range(4):
            pending[i] = 0

    @pl.when(step.first())
    def _init():
        def dq_part(c):
            dq_acc[:, :, _chunk(c, blk_q)] = jnp.zeros(
                dq_acc.shape[:2] + (blk_q,), dq_acc.dtype)

        zero(dq_acc.shape[2] // blk_q, dq_part)

        @pl.when(r == 0)
        def _open_group():
            def kv_part(c):
                for acc in (dk_acc, dv_acc):
                    acc[:, _chunk(c, blk_k)] = jnp.zeros(
                        (acc.shape[0], blk_k, acc.shape[2]), acc.dtype)

            zero(dk_acc.shape[1] // blk_k, kv_part)

    @pl.when(step.opens())
    def _delta():
        d_rows = _pass_rows(blk_q, blk_k)

        def make_delta(h, c):
            part = _chunk(c, d_rows)
            prod = do_ref[part, sl.cols(h)].astype(jnp.float32) \
                * o_ref[part, sl.cols(h)].astype(jnp.float32)
            at = _chunk(qi * (blk_q // d_rows) + c, d_rows)
            for j in range(pack):
                delta_acc[h, j:j + 1, at] = _as_row(jnp.sum(
                    _lanes_of(j, pack, prod), axis=1, keepdims=True))

        _each_chunk(sl, blk_q // d_rows, make_delta)

    def contribute(masked, h, c):
        keys = _chunk(c, rows)
        k_blk, v_blk = k_ref[keys, sl.kv(h)], v_ref[keys, sl.kv(h)]
        keep = _key_column(mask_ref, keys)
        dk = dv = 0.0
        for j in range(pack):
            q = _lanes_of(j, pack, q_ref[:, sl.cols(h)])
            do = _lanes_of(j, pack, do_ref[:, sl.cols(h)])
            s = _scores(q, k_blk, qi * blk_q, kb * blk_k + c * rows,
                        causal and masked, sm_scale, offset, keep,
                        structure if masked else None, q_axis=1)
            p = jnp.exp(s - lse_ref[h, j:j + 1])
            ds = (p * (_dot(v_blk, do, _NT)
                       - delta_acc[h, j:j + 1, queries])).astype(q.dtype)
            dv += _dot(p.astype(do.dtype), do, _NN)  # head j's lanes alone
            dk += _dot(ds, q, _NN)
            dq_acc[h, head(j), queries] += _dot(k_blk[:, head(j)], ds, _TN)
        at = _chunk(kb * (blk_k // rows) + c, rows)
        dv_acc[h // sl.per, at] += dv
        dk_acc[h // sl.per, at] += dk

    _on_tile(step, lambda masked: _each_chunk(
        sl, blk_k // rows, functools.partial(contribute, masked)))

    @pl.when(step.last())
    def _finalize():
        # ds lacked the scale; dQ and dK take it once, as they are written.
        def window(result, stages, block):
            """Chunk c of a result: its rows as many as a staging ref's, in
            the step's column block, as wide as a staging ref."""
            rows, cols = stages[0].shape
            return lambda c: result.at[b, _chunk(c, rows),
                                       pl.ds(block * cols, cols)]

        def fill_dq(c, stage):
            def slab(h):
                stage[:, sl.cols(h)] = (
                    dq_acc[h, :, _chunk(c, blk_q)].T * sm_scale
                ).astype(stage.dtype)

            sl.each(slab)

        dq_window = window(dq_hbm, q_stages, g * reps + r)
        _dma_out(dq_acc.shape[2] // blk_q, fill_dq, q_stages, q_flight,
                 dq_window)
        kv_windows = [window(result, kv_stages, g)
                      for result in (dk_hbm, dv_hbm)]
        n_kv = dk_acc.shape[1] // blk_k

        @pl.when(r == reps - 1)
        def _close_group():
            for acc, at, scale, start in ((dk_acc, kv_windows[0], sm_scale, 0),
                                          (dv_acc, kv_windows[1], None, n_kv)):
                def fill(c, stage, acc=acc, scale=scale):
                    def slab(h):
                        x = acc[h, _chunk(c, blk_k)]
                        stage[:, sl.cols(h)] = (
                            x if scale is None else x * scale
                        ).astype(stage.dtype)

                    sl.each(slab, acc.shape[0])

                _dma_out(n_kv, fill, kv_stages, kv_flight, at, start)

        @pl.when((r == reps - 1) & (b == pl.num_programs(0) - 1)
                 & (g == pl.num_programs(1) - 1))
        def _close_kernel():  # the last step: every DMA done
            for slot in range(2):
                q_flight.wait(q_stages, dq_window, slot)
                kv_flight.wait(kv_stages, kv_windows[0], slot)


@functools.partial(jax.jit, static_argnames=("causal", "plan", "heads",
                                             "interpret", "structure"))
def _flash_bwd(q, k, v, do, lse, out, mask, causal, plan, heads,
               interpret, structure=None):
    """Pallas flash backward: the fused kernel where the plan has it, else
    dQ (which makes delta from dO and out), then dK/dV. q/do/out:
    [B,Sq,H*D]; k/v: [B,Sk,Hkv*D] (`_columns`), ``heads`` (H, Hkv); lse:
    [B,H/pack,pack,Sq] fp32 (compact); mask: [B,1,Sk] int32 or None.
    Returns (dq [B,Sq,H*D], dk/dv [B,Sk,Hkv*D])."""
    B, Sq, _ = q.shape
    Sk = k.shape[1]
    H, Hkv = heads
    D = q.shape[2] // H
    kernel_args = dict(causal=causal, sm_scale=1.0 / (D ** 0.5),
                       has_mask=mask is not None, offset=Sk - Sq,
                       structure=structure)
    if plan.bwd is not None:
        return _fused_bwd(q, k, v, do, lse, out, mask, plan.bwd, heads,
                          interpret, kernel_args)
    return _split_bwd(q, k, v, do, lse, out, mask, plan, heads, interpret,
                      kernel_args)


def _fused_bwd(q, k, v, do, lse, out, mask, tiles, heads, interpret,
               kernel_args):
    """The fused backward (`_flash_bwd_kernel`) as one `pallas_call`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, _ = q.shape
    Sk = k.shape[1]
    H, Hkv = heads
    D = q.shape[2] // H
    pack = lane_pack(H, Hkv, D)
    width = pack * D
    blk_q, blk_k, _ = tiles
    G, R, hg, hr = _head_blocks(Sq, Sk, H, Hkv, D, tiles)
    slabs, reps = hg * hr, R // hr
    walk = tile_walk("bwd", Sq, Sk, blk_q, blk_k, kernel_args["causal"],
                     kernel_args["structure"], reps)
    grid, _, prefetch, on = _kernel_grid(
        walk, (B, G // hg), (), (reps, Sk // blk_k, Sq // blk_q),
        lambda b, g, r, kb, qi: (b, g, r, qi, kb))
    if walk is None:  # o's block moves where a rep block opens a q-block
        def o_at(b, g, r, kb, qi):
            return b, jnp.where(kb == 0, qi, Sq // blk_q - 1), g * reps + r
    else:
        def o_at(b, g, t, table):
            return (b, table[2 * walk.steps + t] >> _HELD,
                    g * reps + table[3 * walk.steps + t])

    q_spec = pl.BlockSpec((None, blk_q, slabs * width), on(_q_at(reps)))
    kv_spec = pl.BlockSpec((None, blk_k, hg * width), on(_kv_at))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec,
                pl.BlockSpec((None, slabs, pack, blk_q), on(_stat_at(reps))),
                pl.BlockSpec((None, blk_q, slabs * width), o_at)]
    operands = [q, k, v, do, lse, out]
    if mask is not None:
        in_specs.append(pl.BlockSpec((None, 1, blk_k), on(_mask_at)))
        operands.append(mask)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, reps=reps, **kernel_args),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
            scratch_shapes=[
                pltpu.VMEM((slabs, width, Sq), jnp.float32),
                pltpu.VMEM((hg, Sk, width), jnp.float32),
                pltpu.VMEM((hg, Sk, width), jnp.float32),
                pltpu.VMEM((slabs, pack, Sq), jnp.float32),
                *[pltpu.VMEM((blk_q, slabs * width), q.dtype)] * 2,
                *[pltpu.VMEM((blk_k, hg * width), k.dtype)] * 2,
                pltpu.SemaphoreType.DMA((4,)),
                pltpu.SMEM((4,), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            # In order, every axis: a group's rep blocks share its dK/dV
            # accumulators, and a result's DMAs run on into the steps that
            # follow, to the kernel's last.
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=BWD_VMEM_LIMIT),
        interpret=interpret,
        name="flash_bwd",
    )(*prefetch, *operands)
    return dq, dk, dv


def _split_bwd(q, k, v, do, lse, out, mask, plan, heads, interpret,
               kernel_args):
    """The split backward: dQ (which makes delta from dO and out), then
    dK/dV, two `pallas_call`s (the plan's ``dq`` and ``dkdv``), where the
    fused kernel's accumulators do not fit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, _ = q.shape
    Sk = k.shape[1]
    H, Hkv = heads
    D = q.shape[2] // H
    pack = lane_pack(H, Hkv, D)
    width = pack * D
    causal, structure = kernel_args["causal"], kernel_args["structure"]
    has_mask = mask is not None

    def specs(tiles, on, R, hg, hr):
        """The blocks of the query side, the K/V side and the statistics."""
        return (pl.BlockSpec((None, tiles.blk_q, hg * hr * width),
                             on(_q_at(R // hr))),
                pl.BlockSpec((None, tiles.blk_k, hg * width), on(_kv_at)),
                pl.BlockSpec((None, hg * hr, pack, tiles.blk_q),
                             on(_stat_at(R // hr))))

    # --- dQ: grid (B, G, r, qi, kb); kb sequential, accumulating.
    blk_q, blk_k, _ = plan.dq
    G, R, hg, hr = _head_blocks(Sq, Sk, H, Hkv, D, plan.dq)
    grid, semantics, prefetch, on = _kernel_grid(
        tile_walk("dq", Sq, Sk, blk_q, blk_k, causal, structure),
        (B, G // hg, R // hr), (Sq // blk_q,), (Sk // blk_k,))
    q_spec, kv_spec, stat_spec = specs(plan.dq, on, R, hg, hr)
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, stat_spec, q_spec]
    operands = [q, k, v, do, lse, out]
    if has_mask:
        in_specs.append(pl.BlockSpec((None, 1, blk_k),
                                     on(_mask_at)))
        operands.append(mask)

    dq, delta = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kernel_args),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=[q_spec, stat_spec],
            scratch_shapes=[pltpu.VMEM((hg * hr, blk_q, width),
                                       jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*prefetch, *operands)

    # --- dK/dV: grid (B, G, kb, r, qi); r+qi sequential, accumulating.
    blk_q, blk_k, _ = plan.dkdv
    G, R, hg, hr = _head_blocks(Sq, Sk, H, Hkv, D, plan.dkdv)
    grid, semantics, prefetch, on = _kernel_grid(
        tile_walk("dkdv", Sq, Sk, blk_q, blk_k, causal, structure, R // hr),
        (B, G // hg), (Sk // blk_k,), (R // hr, Sq // blk_q),
        lambda b, g, kb, r, qi: (b, g, r, qi, kb))
    q_by_inner, kv_by_outer, stat_by_inner = specs(plan.dkdv, on, R, hg, hr)
    in_specs = [q_by_inner, kv_by_outer, kv_by_outer, q_by_inner,
                stat_by_inner, stat_by_inner]
    operands = [q, k, v, do, lse, delta]
    if has_mask:
        in_specs.append(pl.BlockSpec((None, 1, blk_k),
                                     on(_mask_at)))
        operands.append(mask)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, **kernel_args),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=[kv_by_outer, kv_by_outer],
            scratch_shapes=[
                pltpu.VMEM((hg, blk_k, width), jnp.float32),
                pltpu.VMEM((hg, blk_k, width), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(*prefetch, *operands)
    return dq, dk, dv


def _flash_bwd_rule(causal, plan, interpret, structure, res, g):
    """Flash backward as one Pallas kernel (or, where its accumulators do
    not fit, two: dQ, which makes delta on the way, then dK/dV),
    recomputing probabilities from the saved log-sum-exp — the S x S
    matrix never materializes."""
    q, k, v, mask, out, lse = res
    mask3 = _canon_mask(mask, q.shape[0], k.shape[1])
    dq, dk, dv = _flash_bwd(
        _columns(q), _columns(k), _columns(v), _columns(g), lse, out, mask3,
        causal, plan, (q.shape[2], k.shape[2]), interpret, structure)
    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype),
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


def flash_block_bwd(q, k, v, do, lse, out, causal: bool = True,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False):
    """One block of the ring-attention backward: given the GLOBAL per-row
    log-sum-exp and output, each (Q shard, K/V shard) pair's gradient
    contribution is independent and additive — p recomputed from the
    global lse is the true global probability for this block, and delta =
    sum(dO*O) is made from the global output. lse: [B,H,Sq] fp32; out:
    [B,Sq,H,D]. Returns (dq, dk, dv) fp32."""
    H, Hkv = q.shape[2], k.shape[2]
    dq, dk, dv = _flash_bwd(
        _columns(q), _columns(k), _columns(v), _columns(do),
        _slab_stats(lse, lane_pack(H, Hkv, q.shape[3])), _columns(out), None,
        causal, _explicit_plan(q, k, blk_q, blk_k), (H, Hkv), interpret)
    return (dq.reshape(q.shape).astype(jnp.float32),
            dk.reshape(k.shape).astype(jnp.float32),
            dv.reshape(v.shape).astype(jnp.float32))


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
    (`BlockDiffusionMask`; `CausalWindow`, with ``causal``) that the kernels
    compute from indices. ``force``
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
    structure = mask if isinstance(mask, (BlockDiffusionMask, CausalWindow)) \
        else None
    if isinstance(structure, CausalWindow):
        if not causal or Sq != Sk or structure.window < 1:
            raise ValueError(
                "{} is a causal mask over Sq = Sk with a window of at least "
                "one position; got causal={}, Sq={}, Sk={}".format(
                    structure, causal, Sq, Sk))
    elif structure is not None:
        if Sq != Sk or Sq != 2 * structure.length \
                or structure.length % structure.block:
            raise ValueError(
                "{} describes Sq = Sk = {} in whole blocks; got Sq={}, "
                "Sk={}".format(structure, 2 * structure.length, Sq, Sk))
    if structure is not None:
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
        if isinstance(structure, CausalWindow):
            mask = structure.dense(Sq)
        elif structure is not None:
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
