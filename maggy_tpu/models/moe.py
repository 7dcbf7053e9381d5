"""Mixture-of-experts MLPs: a capacity-bound one for GSPMD expert
parallelism, and a dropless one that holds a share of the experts.

`ExpertShareMLP` (second half of the file) is the dropless layer: it routes
over all ``num_experts`` (softmax, or sigmoid scores with a bias only the
choice sees), is TOLD which experts it holds, sorts the token-expert pairs
by expert and multiplies each held expert's rows by that expert's weights
(SwiGLU's or ReGLU's three matrices, relu^2's two) in Pallas grouped matrix
products (`moe_gmm_fwd`, `moe_gmm_dlhs`, `moe_gmm_drhs`), and adds the rows
back to their tokens in Pallas too where the shapes tile (`moe_combine_fwd`,
`moe_combine_bwd`), beside a shared expert where the model has one. No
capacity, no dropped token. On one chip it runs
without its exchange; nothing stands in for absent chips.

`MoEMLP` (first half) is the older layer, kept because the ``dp_ep``
strategy's tests (tests/test_parallel_strategies.py) shard it over an
"expert" mesh axis through GSPMD, which the dropless layer's kernels
cannot be (Mosaic kernels are not partitioned automatically):

Green-field TPU-first design (the reference has no model code, SURVEY.md
§5.7; expert parallelism is listed absent in §2.8). GShard-style top-k
routing with a static expert capacity so every shape is fixed under jit:

- router logits -> top-k experts per token, position-in-expert via cumsum
- dispatch/combine are ONE-HOT EINSUMS (dense [B,S,E,C] tensors), which XLA
  maps onto the MXU and — when the stacked expert dim of the weights is
  sharded over the "expert" mesh axis while tokens are sharded over "data" —
  lowers the dispatch into an all-to-all over ICI. No gather/scatter, no
  dynamic shapes, no sorting.
- load-balancing auxiliary loss (Shazeer et al. 2017 / GShard eq. 4) is
  sowed into the "losses" collection; train/trainer.py adds every sowed
  "losses" leaf to the objective when aux collections are enabled.

Weights are annotated with logical axis ("expert", embed, mlp) so
parallel/sharding.logical_axis_rules("..._ep") maps them onto the mesh.
"""

from __future__ import annotations

import functools
import operator
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

EXPERT = "expert"


def _top_k_mask(probs: jnp.ndarray, k: int) -> jnp.ndarray:
    """[*, E] -> 0/1 mask of the k largest entries per row."""
    top_vals = jax.lax.top_k(probs, k)[0]
    thresh = top_vals[..., -1:]
    return (probs >= thresh).astype(probs.dtype)


def routing_tensors(
    router_logits: jnp.ndarray, num_experts: int, capacity: int, top_k: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compute (dispatch [B,S,E,C] 0/1, combine [B,S,E,C], aux_loss).

    Tokens beyond an expert's capacity are dropped (their combine weight is
    zero — the residual connection carries them through unchanged).
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    mask = _top_k_mask(probs, top_k)  # [B,S,E]
    # Position of each token within each expert's buffer (tokens ordered by
    # sequence position), counted over the flattened (B,S) token stream per
    # batch row: capacity is per (batch row, expert).
    pos = jnp.cumsum(mask, axis=1) * mask - 1.0  # [B,S,E], -1 where unrouted
    keep = (pos >= 0) & (pos < capacity)
    pos = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    onehot_pos = jax.nn.one_hot(pos, capacity, dtype=probs.dtype)  # [B,S,E,C]
    dispatch = onehot_pos * keep.astype(probs.dtype)[..., None]
    gates = probs * mask
    # Renormalize kept gates so the combine weights of each token sum to ~1.
    denom = jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates / jnp.maximum(denom, 1e-9)
    combine = dispatch * gates[..., None]
    # Load-balancing aux loss: E * sum_e f_e * p_e  (f = fraction of tokens
    # routed to e, p = mean router prob of e). Minimized when uniform.
    f = jnp.mean(mask, axis=(0, 1))
    p = jnp.mean(probs, axis=(0, 1))
    aux_loss = num_experts * jnp.sum(f * p)
    return dispatch, combine, aux_loss


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU MLP with E stacked experts.

    x: [B, S, D] -> [B, S, D]. Expert weights are stacked on a leading
    expert dim with logical axis EXPERT, so under an "..._ep" strategy each
    device holds |E|/|expert axis| experts and XLA inserts the token
    all-to-all.
    """

    hidden_dim: int
    intermediate_dim: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 2.0
    aux_loss_weight: float = 0.01
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    embed_axis: str = "embed"
    mlp_axis: str = "mlp"

    @nn.compact
    def __call__(self, x):
        B, S, D = x.shape
        E = self.num_experts
        # A single-expert config degenerates to top-1 routing (top_k can't
        # exceed the number of experts).
        top_k = min(self.top_k, E)
        capacity = max(1, int(self.capacity_factor * S * top_k / E))

        router = self.param(
            "router", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (self.embed_axis, EXPERT)),
            (D, E), self.param_dtype)
        logits = jnp.dot(x.astype(jnp.float32), router)  # [B,S,E]
        dispatch, combine, aux = routing_tensors(logits, E, capacity, top_k)
        self.sow("losses", "moe_aux_loss", self.aux_loss_weight * aux)

        def expert_param(name, shape, axes):
            return self.param(name, nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (EXPERT,) + axes), shape,
                self.param_dtype)

        F = self.intermediate_dim
        w_gate = expert_param("gate_proj", (E, D, F), (self.embed_axis, self.mlp_axis))
        w_up = expert_param("up_proj", (E, D, F), (self.embed_axis, self.mlp_axis))
        w_down = expert_param("down_proj", (E, F, D), (self.mlp_axis, self.embed_axis))

        dispatch = dispatch.astype(self.dtype)
        combine = combine.astype(self.dtype)
        xd = x.astype(self.dtype)
        # Dispatch: [B,S,E,C] x [B,S,D] -> [E,B,C,D] expert inputs.
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, xd)
        gate = jnp.einsum("ebcd,edf->ebcf", expert_in, w_gate.astype(self.dtype))
        up = jnp.einsum("ebcd,edf->ebcf", expert_in, w_up.astype(self.dtype))
        act = nn.silu(gate) * up
        expert_out = jnp.einsum("ebcf,efd->ebcd", act, w_down.astype(self.dtype))
        # Combine back to token order, weighted by the (renormalized) gates.
        return jnp.einsum("bsec,ebcd->bsd", combine, expert_out)


# ---------------------------------------------------------------- dropless
#
# The held experts' rows live in a buffer in which every expert's rows start
# at a multiple of ``TILE_ROWS``, so a tile of rows belongs to one expert and
# a grouped product is a tiled matmul whose weight block is looked up per
# row tile. The buffer exists as INDICES only ([rows] int32); the rows
# themselves are gathered and multiplied a chunk at a time, and scattered
# back a chunk at a time or, where the combine kernels run, kept in buffer
# order for the layer and added back to the tokens once.

#: Rows of one tile of the grouped products.
TILE_ROWS = 512
#: Tiles one round of the layer's loop gathers, multiplies and scatters
#: back. The loop runs as many rounds as the routed rows need, so this is
#: the grain at which the work follows the routing; on the v5e a layer at
#: 16,384 positions took the same time at 4 tiles a round as at 34 (PERF.md
#: section 6, PR 26).
CHUNK_TILES = 4
#: The widest block of a weight's output (or, in `moe_gmm_drhs`, of either
#: of its dimensions) one grid step holds.
BLOCK_CAP = 512
#: VMEM a grouped product may take: the largest step ([512, 2048] rows
#: against a [2048, 512] weight block, double-buffered, and the float32
#: result) is 9 MiB; a v5e's default of 16 MiB is raised for headroom.
VMEM_LIMIT = 32 * 2 ** 20
#: Tokens one grid step of the combine kernels (`moe_combine_fwd`,
#: `moe_combine_bwd`) owns, where the tokens tile by it (`combine_plan`).
COMBINE_TOKENS = 256
#: Rows of the buffer one DMA of the combine kernels moves: a whole tile of
#: packed bfloat16 rows, so every window starts where such a tile does.
COMBINE_WINDOW = 16


#: The `jax.named_scope`s of the layer, in the order a token meets them.
#: The layer names them with its plan (`telemetry.plans.remember_plan`), so
#: whoever traces the step can note which of its instructions ran under
#: each (``moe_ops`` of the ``compiled`` record).
SCOPES = ("moe_routing", "moe_dispatch", "moe_experts", "moe_combine")
#: The scope a layer with a shared expert opens beside them, and names too.
SHARED_SCOPE = "moe_shared"

#: The name `ExpertShareMLP` gives what its routing hands to `expert_ffn`:
#: the pairs' gates, `grouped_layout`'s three and, where the combine is the
#: kernels', `combine_runs`' four, under 5 MB a layer at 16,384 positions.
#: A caller that rematerialises the layer and keeps it
#: (`save_only_these_names`) scores, selects and sorts once a step.
REMAT_KEEP = ("moe_route",)


def _block(dim: int, cap: int = BLOCK_CAP) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; ``dim`` itself where it is small or has none."""
    fit = [b for b in range(128, min(dim, cap) + 1, 128) if dim % b == 0]
    return max(fit, default=dim)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def route_top_k(x, router, top_k: int, renormalize: bool,
                scoring: str = "softmax", bias=None, scale: float = 1.0):
    """(expert ids [N, k] int32, gates [N, k] float32) of tokens x [N, D].
    The scores are taken over ALL experts in float32 (the logits at full
    matmul precision: a near-tie decides which expert a token takes).

    ``scoring="softmax"``: the ``top_k`` largest probabilities, renormalised
    over the chosen where ``renormalize``. ``scoring="sigmoid"`` (DeepSeek-V3's
    router, with one group): each expert's score is a sigmoid of its own
    logit; the CHOICE is of the ``top_k`` largest of ``score + bias`` (``bias``
    [E], which a balance rule moves, `balance_pull`, and no gradient of the
    model's loss does), the GATES are
    the chosen experts' scores without the bias, over their sum (plus 1e-20)
    where ``renormalize``, times ``scale``."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, top_k)
        if renormalize:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores if bias is None else scores + jax.lax.stop_gradient(
            bias.astype(jnp.float32))
        ids = jax.lax.top_k(choice, top_k)[1]
        gates = jnp.take_along_axis(scores, ids, axis=-1)
        if renormalize:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    else:
        raise ValueError("scoring is 'softmax' or 'sigmoid'; got {!r}".format(
            scoring))
    if scale != 1.0:
        gates = gates * scale
    return ids.astype(jnp.int32), gates


def balance_pull(ids, bias, num_experts: int):
    """The balance rule of a sigmoid router's bias, as a term for the
    objective: its VALUE is zero and its gradient with respect to ``bias``
    [E] is each expert's load error, ``pairs that chose it / (pairs / E) -
    1``, over ALL experts and this step's tokens (``ids`` [N, k]). Whatever
    optimizer trains the model then lowers the bias of an expert that took
    more than its share and raises that of one that took less, which is
    DeepSeek-V3's auxiliary-loss-free rule (arXiv:2412.19437, section 2.1.2:
    ``b_e -= gamma sign(load error)``) with the optimizer's step in gamma's
    place: under Adam a persistent error moves the bias a learning rate a
    step, one that changes sign hardly at all. The bias is outside the
    model's own loss (only the choice sees it), so nothing else pulls on it."""
    N, k = ids.shape
    load = jnp.sum(ids[:, :, None] == jnp.arange(num_experts), axis=(0, 1),
                   dtype=jnp.float32)
    # Named with the routing, so a rematerialised layer that keeps
    # `REMAT_KEEP` does not route again for the backward pass's sake.
    error = checkpoint_name(jax.lax.stop_gradient(
        load * (num_experts / (N * k)) - 1.0), REMAT_KEEP[0])
    bias = bias.astype(jnp.float32)
    return jnp.sum(error * (bias - jax.lax.stop_gradient(bias)))


def buffer_rows(tokens: int, top_k: int, held: int,
                tile_rows: int = TILE_ROWS) -> int:
    """Rows of the index buffer: a token's ``top_k`` experts are distinct,
    so at most ``min(top_k, held)`` of its pairs can name a held expert and
    at most ``tokens x min(top_k, held)`` rows are ever routed here,
    whatever the router does; each held expert's rows are padded to whole
    tiles, which adds under one tile an expert. No pair can overflow it."""
    worst = tokens * min(top_k, held)
    return -(-worst // tile_rows) * tile_rows + held * tile_rows


def grouped_layout(ids, first_expert: int, held: int, tile_rows: int):
    """Where each token-expert pair goes. ``ids`` [N, k] are the chosen
    experts; the held ones are ``first_expert .. first_expert + held - 1``.

    Returns ``row_pair`` [rows] (the flat pair index n * k + j that sits in
    each buffer row, or N * k for a padding row), ``tile_group`` [rows /
    tile_rows] (the held expert, 0-based, whose rows the tile holds; tiles
    past the last used one repeat the last used tile's expert, so that a
    kernel stepping onto them changes no block) and ``tiles_used`` (scalar).
    Pairs that chose an absent expert are in no row: they contribute
    nothing, here as in the reference."""
    N, k = ids.shape
    P = N * k
    rows = buffer_rows(N, k, held, tile_rows)
    local = ids.reshape(P) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    tiles = -(-counts // tile_rows)
    starts = jnp.cumsum(counts) - counts           # in sorted order
    tile_ends = jnp.cumsum(tiles)
    padded_starts = (tile_ends - tiles) * tile_rows
    group = key[order]
    g = jnp.minimum(group, held - 1)
    dest = jnp.where(group < held,
                     padded_starts[g] + jnp.arange(P) - starts[g], rows)
    row_pair = jnp.full((rows,), P, jnp.int32).at[dest].set(
        order, mode="drop", unique_indices=True)
    tiles_used = tile_ends[-1]
    tile = jnp.minimum(jnp.arange(rows // tile_rows),
                       jnp.maximum(tiles_used - 1, 0))
    tile_group = jnp.minimum(  # experts whose tiles end at or before it
        jnp.sum(tile[:, None] >= tile_ends[None, :], axis=1), held - 1)
    return row_pair, tile_group.astype(jnp.int32), tiles_used


def combine_plan(tokens: int, width: int, tile_rows: int):
    """(tokens, columns) one grid step of the combine kernels owns: up to
    `COMBINE_TOKENS` tokens and `_block` of the width. None where they do
    not tile by 128, or a window would cross a row tile, and the combine is
    XLA's scatter-add."""
    if tokens % 128 or width % 128 or tile_rows % COMBINE_WINDOW:
        return None
    return _block(tokens, COMBINE_TOKENS), _block(width)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def combine_runs(ids, first_expert: int, held: int, tile_rows: int,
                 token_block: int):
    """Where the rows of each block of ``token_block`` tokens lie in
    `grouped_layout`'s buffer. Its sort is stable, so one expert's rows
    stand in token order, and a token's experts are distinct: the rows of
    held expert g whose tokens lie in block b are ONE run of the buffer,
    at most a row a token.

    Returns ``row_of`` [held, N] int32 (the row of token n's pair with
    expert g, -1 where n did not choose g), ``slot_of`` [held, N] int32
    (which of n's ``k`` choices g is, ``k`` where none), and
    ``run_start``, ``run_len`` [N / token_block * held] int32 (the run of
    block b and expert g at ``b * held + g``). Dense work over tokens x
    held experts, no sort; jitted, so a step's layers trace and lower it
    once."""
    N, k = ids.shape
    chose = (ids - first_expert).T[None] == jnp.arange(held)[:, None, None]
    hit = jnp.any(chose, axis=1)
    slot_of = jnp.where(hit, jnp.sum(jnp.where(
        chose, jnp.arange(k)[:, None], 0), axis=1), k)
    per = hit.reshape(held, N // token_block, token_block).astype(jnp.int32)
    tiles = -(-jnp.sum(per, axis=(1, 2)) // tile_rows)
    first_row = (jnp.cumsum(tiles) - tiles) * tile_rows
    run_len = jnp.sum(per, axis=2)
    run_start = first_row[:, None] + jnp.cumsum(run_len, axis=1) - run_len
    row_of = jnp.where(per > 0,
                       run_start[..., None] + jnp.cumsum(per, axis=2) - per,
                       -1).reshape(held, N)
    return (row_of, slot_of.astype(jnp.int32), run_start.T.reshape(-1),
            run_len.T.reshape(-1))


def _gmm(lhs, rhs, tile_group, tiles_left, *, transpose_rhs: bool, name: str,
         tile_rows: int, interpret: bool):
    """Grouped product of a chunk: row tile m of ``lhs`` [C, K] times the
    weights of expert ``tile_group[m]``, ``rhs`` [G, K, N] (or, with
    ``transpose_rhs``, [G, N, K]: the product with W^T that the gradient
    with respect to the rows needs). Tiles from ``tiles_left`` on hold no
    routed row: no product is taken for them and their output is zeros.
    Grid (N blocks, row tiles): consecutive tiles of one expert reuse the
    weight block that is in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _block(N)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))

    def kernel(group_ref, left_ref, lhs_ref, rhs_ref, out_ref):
        del group_ref
        used = pl.program_id(1) < left_ref[0]

        @pl.when(used)
        def _():
            out_ref[...] = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[...], dims,
                preferred_element_type=jnp.float32).astype(out_ref.dtype)

        @pl.when(jnp.logical_not(used))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

    rhs_block = (None, tn, K) if transpose_rhs else (None, K, tn)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, C // tile_rows),
            in_specs=[
                pl.BlockSpec((tile_rows, K), lambda n, m, g, left: (m, 0)),
                pl.BlockSpec(rhs_block,
                             (lambda n, m, g, left: (g[m], n, 0))
                             if transpose_rhs else
                             (lambda n, m, g, left: (g[m], 0, n))),
            ],
            out_specs=pl.BlockSpec((tile_rows, tn),
                                   lambda n, m, g, left: (m, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((C, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(tile_group, tiles_left, lhs, rhs)


def _gmm_drhs(lhs, dout, tile_group, tiles_left, acc, *, tile_rows: int,
              interpret: bool):
    """``acc[g] += lhs_g^T dout_g`` for every expert g with row tiles in the
    chunk: the gradient of a grouped product with respect to its weights.
    ``lhs`` [C, K], ``dout`` [C, N], ``acc`` [G, K, N] float32, updated in
    place (experts without a row in the chunk are not touched). Grid (K
    blocks, N blocks, row tiles): the tiles of one expert follow each other,
    so its block is loaded at the first, summed in VMEM and stored at the
    last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, K = lhs.shape
    N = dout.shape[1]
    T = C // tile_rows
    tk, tn = _block(K), _block(N)

    def kernel(group_ref, left_ref, lhs_ref, dout_ref, acc_in_ref, out_ref,
               acc_ref):
        m = pl.program_id(2)
        last_used = jnp.minimum(left_ref[0], T) - 1
        g = group_ref[m]
        first = (m == 0) | (group_ref[jnp.maximum(m - 1, 0)] != g)
        final = (m == last_used) | (group_ref[jnp.minimum(m + 1, T - 1)] != g)
        used = m <= last_used

        @pl.when(used & first)
        def _():
            acc_ref[...] = acc_in_ref[...]

        @pl.when(used)
        def _():
            acc_ref[...] += jax.lax.dot_general(
                lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(used & final)
        def _():
            out_ref[...] = acc_ref[...]

    acc_spec = pl.BlockSpec((None, tk, tn),
                            lambda k, n, m, g, left: (g[m], k, n))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(K // tk, N // tn, T),
            in_specs=[
                pl.BlockSpec((tile_rows, tk), lambda k, n, m, g, left: (m, k)),
                pl.BlockSpec((tile_rows, tn), lambda k, n, m, g, left: (m, n)),
                acc_spec,
            ],
            out_specs=acc_spec,
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_drhs",
    )(tile_group, tiles_left, lhs, dout, acc)


def _split3(y):
    """float32 ``y`` as three bfloat16 terms whose sum, taken as
    ``(hi + mid) + lo`` in float32, is ``y`` exactly."""
    hi = y.astype(jnp.bfloat16)
    rest = y - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


@functools.partial(jax.jit,
                   static_argnames=("tokens", "out_dtype", "name", "interpret"))
def _combine(sources, scale, row_of, run_start, run_len, *, tokens: int,
             out_dtype, name: str, interpret: bool):
    """``out[n] = sum over n's rows r of scale[g, n] * sum_s sources[s][r]``
    (g the expert of row r), summed in float32 and written once: the
    scatter-add of the buffer's rows back to the tokens as a gather.

    ``sources`` [rows, D] in buffer order; ``scale`` [G, N] float32 or None
    (every factor 1); ``row_of``, ``run_start``, ``run_len`` as
    `combine_runs` gives them. Grid (token blocks, column blocks). A step
    walks its block's runs in a `fori_loop` over the held experts and,
    inside, over the `COMBINE_WINDOW`-row windows a run spans, starting a
    DMA of each into a slot; when the slots are full, and at the end, it
    waits for them and adds the batch into its float32 accumulator as one
    product on the MXU, a 0/1 matrix (``row_of`` against the window's rows)
    times the rows. Nothing in the body is repeated per held expert, so it
    lowers to the same program whatever their number.

    The 0/1 product is exact for bfloat16 rows. Rows that are scaled, or
    not bfloat16, are scaled in float32 and enter as three bfloat16 terms
    (`_split3`): a token with one row gets ``scale * row`` to the bit, one
    with several their sum up to float32 rounding.

    A `jax.jit` of its own: the layers of a step that call it at one shape
    share one trace and one lowering of the kernel, so the set-up a step
    costs does not grow with its layers."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D = sources[0].shape[1]
    G, N = row_of.shape
    tn, W = _block(D), COMBINE_WINDOW
    slots = tokens // W + G  # a row a token, and one partial window a run
    exact = scale is None and all(s.dtype == jnp.bfloat16 for s in sources)
    S, terms = len(sources), len(sources) if exact else 3
    f32 = jnp.float32

    def kernel(start_ref, len_ref, row_ref, *refs):
        scale_ref, refs = (None, refs) if scale is None else (refs[0],
                                                             refs[1:])
        src, (out_ref, acc, stack, mat, split, meta, sem) = refs[:S], refs[S:]
        b, c = pl.program_id(0), pl.program_id(1)
        col = pl.multiple_of(c * tn, tn)
        terms_ref = stack if exact else split

        def copies(i, start):
            return [pltpu.make_async_copy(
                src[s].at[pl.ds(start, W), pl.ds(col, tn)],
                stack.at[s, pl.ds(pl.multiple_of(i * W, W), W)], sem.at[s, i])
                for s in range(S)]

        def flush(used):
            def take(i, _):
                start, lo, hi, g = (meta[4 * i + f] for f in range(4))
                start = pl.multiple_of(start, W)
                for cp in copies(i, start):
                    cp.wait()
                rows = pl.ds(pl.multiple_of(i * W, W), W)
                match = row_ref[pl.ds(g, 1), :] == start + \
                    jax.lax.broadcasted_iota(jnp.int32, (W, tokens), 0)
                mat[rows, :] = match.astype(mat.dtype)
                r = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
                keep = (r >= lo) & (r < hi)  # the run's rows, nothing else
                if exact:
                    for s in range(S):
                        stack[s, rows, :] = jnp.where(keep, stack[s, rows, :],
                                                      0)
                    return 0
                y = functools.reduce(operator.add, (
                    stack[s, rows, :].astype(f32) for s in range(S)))
                if scale is not None:
                    y = y * jnp.sum(jnp.where(
                        match, scale_ref[pl.ds(g, 1), :], 0.0), axis=1,
                        keepdims=True)
                for t, part in enumerate(_split3(jnp.where(keep, y, 0.0))):
                    split[t, rows, :] = part
                return 0

            def blank(i, _):
                mat[pl.ds(pl.multiple_of(i * W, W), W), :] = jnp.zeros(
                    (W, tokens), mat.dtype)
                return 0

            jax.lax.fori_loop(0, used, take, 0)
            jax.lax.fori_loop(used, slots, blank, 0)
            m = mat[...].T
            acc[...] += functools.reduce(operator.add, (
                jnp.dot(m, terms_ref[t], preferred_element_type=f32)
                for t in range(terms)))

        @pl.when((b == 0) & (c == 0))
        def _():  # what a blank slot holds is finite from here on
            terms_ref[...] = jnp.zeros_like(terms_ref)

        acc[...] = jnp.zeros_like(acc)

        def run(g, k):
            start = start_ref[b * G + g]
            n = len_ref[b * G + g]
            first = start - start % W

            def window(j, k):
                at = pl.multiple_of(first + j * W, W)
                for cp in copies(k, at):
                    cp.start()
                meta[4 * k] = at
                meta[4 * k + 1] = jnp.maximum(start, at) - at
                meta[4 * k + 2] = jnp.minimum(start + n, at + W) - at
                meta[4 * k + 3] = g
                k = k + 1

                @pl.when(k == slots)
                def _():
                    flush(slots)

                return jnp.where(k == slots, 0, k)

            count = jnp.where(n > 0, (start + n - first + W - 1) // W, 0)
            return jax.lax.fori_loop(0, count, window, k)

        left = jax.lax.fori_loop(0, G, run, 0)

        @pl.when(left > 0)
        def _():
            flush(left)

        out_ref[...] = acc[...].astype(out_ref.dtype)

    by_block = pl.BlockSpec((G, tokens), lambda b, c, *_: (0, b))
    scratch = [
        pltpu.VMEM((tokens, tn), f32),
        pltpu.VMEM((S, slots * W, tn), sources[0].dtype),
        pltpu.VMEM((slots * W, tokens), jnp.bfloat16),
        pltpu.VMEM((1, W, tn) if exact else (3, slots * W, tn), jnp.bfloat16),
        pltpu.SMEM((4 * slots,), jnp.int32),
        pltpu.SemaphoreType.DMA((S, slots)),
    ]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tokens, D // tn),
            in_specs=[by_block] * (1 + (scale is not None))
            + [pl.BlockSpec(memory_space=pl.ANY)] * S,
            out_specs=pl.BlockSpec((tokens, tn), lambda b, c, *_: (b, c)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((N, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(run_start, run_len, row_of,
      *(() if scale is None else (scale,)), *sources)


class _Chunk:
    """One chunk of the index buffer, as the forward and the backward pass
    both need it: the rows' tokens and gates, and the chunk's tiles."""

    def __init__(self, c, pair_gate, row_pair, tile_group, tiles_used, top_k,
                 chunk_rows, tile_rows):
        P = pair_gate.shape[0]
        tiles = chunk_rows // tile_rows
        self.pair = jax.lax.dynamic_slice_in_dim(
            row_pair, c * chunk_rows, chunk_rows)
        self.valid = self.pair < P
        self.token = jnp.where(self.valid, self.pair // top_k, 0)
        self.gate = jnp.where(
            self.valid, pair_gate[jnp.minimum(self.pair, P - 1)], 0.0)
        self.tile_group = jax.lax.dynamic_slice_in_dim(
            tile_group, c * tiles, tiles)
        self.tiles_left = jnp.reshape(tiles_used - c * tiles, (1,))


def _silu_mul(gate, up):
    gate, up = gate.astype(jnp.float32), up.astype(jnp.float32)
    return gate * jax.nn.sigmoid(gate) * up


class _SwiGLU:
    """``act = silu(x W_gate) * (x W_up)``: two matrices into the width."""

    into = ("gate_proj", "up_proj")
    act = staticmethod(_silu_mul)

    @staticmethod
    def dpre(dact, gate, up):
        """``dact`` back through the activation, to each pre-activation
        (float32, as they come out of their products)."""
        sig = jax.nn.sigmoid(gate)
        return (dact * up * sig * (1.0 + gate * (1.0 - sig)),
                dact * gate * sig)


class _Relu2:
    """``act = relu(x W_up) ** 2``: one matrix into the width, no gate
    matrix."""

    into = ("up_proj",)

    @staticmethod
    def act(up):
        return jnp.square(jax.nn.relu(up.astype(jnp.float32)))

    @staticmethod
    def dpre(dact, up):
        return (dact * 2.0 * jax.nn.relu(up),)


class _ReGLU:
    """``act = relu(x W_gate) * (x W_up)``: two matrices into the width, the
    gate through a relu."""

    into = ("gate_proj", "up_proj")

    @staticmethod
    def act(gate, up):
        return jax.nn.relu(gate.astype(jnp.float32)) * up.astype(jnp.float32)

    @staticmethod
    def dpre(dact, gate, up):
        return dact * up * (gate > 0), dact * jax.nn.relu(gate)


#: The expert kinds `expert_ffn` knows: the matrices that lead into the
#: expert's width (by their parameter names), the activation over their
#: products and its way back. The matrix out of the width is every kind's
#: last weight.
EXPERT_KINDS = {"swiglu": _SwiGLU, "relu2": _Relu2, "reglu": _ReGLU}


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def expert_ffn(x, weights, pair_gate, row_pair, tile_group, tiles_used, runs,
               top_k: int, chunk_rows: int, tile_rows: int, interpret: bool,
               kind: str = "swiglu"):
    """``out[n] = sum over n's pairs in the buffer of gate * W_down_e
    act_e(x_n)``: x [N, D]; ``weights`` the held experts' matrices, those
    into the width [G, D, F] first and ``W_down`` [G, F, D] last (``kind``
    ``"swiglu"``: gate, up, down, ``act = silu(x W_gate) * (x W_up)``;
    ``"relu2"``: up, down, ``act = relu(x W_up) ** 2``; ``"reglu"``: gate,
    up, down, ``act = relu(x W_gate) * (x W_up)``); ``pair_gate``
    [N * k] float32, and `grouped_layout`'s three. The buffer's used rows
    are walked in chunks of ``chunk_rows`` (a `fori_loop` whose trip count
    is the used rows over the chunk, so the work follows the rows routed
    here and the live memory is one chunk's): gather the chunk's rows of x,
    the kind's grouped products, and a gate-weighted scatter-add back to the
    tokens. Where ``runs`` holds `combine_runs`' four, the products' rows
    are kept in buffer order instead (in their own dtype, in a buffer that
    is never cleared: only rows the loop wrote are read) and one kernel,
    `moe_combine_fwd`, adds them back to the tokens after the loop (and
    `moe_combine_bwd` the gradient's rows in the VJP); where it is empty,
    XLA's scatter-add does, a chunk at a time. Its own VJP (below)
    keeps x, the weights and the indices, and recomputes a chunk's
    activations beside their gradients."""
    out, _ = _expert_ffn_fwd(x, weights, pair_gate, row_pair, tile_group,
                             tiles_used, runs, top_k, chunk_rows, tile_rows,
                             interpret, kind)
    return out


def _chunks(tiles_used, chunk_rows, tile_rows):
    return -(-(tiles_used * tile_rows) // chunk_rows)


def _kept(row_pair, x, n):
    """``n`` buffers of the layer's rows, one a product, left uncleared."""
    return tuple(jax.lax.empty(row_pair.shape + x.shape[1:], x.dtype)
                 for _ in range(n))


def _keep(kept, rows, c, chunk_rows):
    """Chunk c's ``rows`` into the kept buffers, at the chunk's rows."""
    return tuple(jax.lax.dynamic_update_slice_in_dim(k, r, c * chunk_rows, 0)
                 for k, r in zip(kept, rows))


def _combine_kept(kept, scale, runs, out_dtype, name, interpret):
    row_of, _, run_start, run_len = runs
    G, N = row_of.shape
    return _combine(kept, scale, row_of, run_start, run_len,
                    tokens=N * G // run_start.shape[0], out_dtype=out_dtype,
                    name=name, interpret=interpret)


def _expert_ffn_fwd(x, weights, pair_gate, row_pair, tile_group, tiles_used,
                    runs, top_k, chunk_rows, tile_rows, interpret, kind):
    dtype = x.dtype
    act_of = EXPERT_KINDS[kind].act
    gmm = functools.partial(_gmm, transpose_rhs=False, name="moe_gmm_fwd",
                            tile_rows=tile_rows, interpret=interpret)
    *w_in, wd = (w.astype(dtype) for w in weights)

    def chunk(c, out):
        ck = _Chunk(c, pair_gate, row_pair, tile_group, tiles_used, top_k,
                    chunk_rows, tile_rows)
        with jax.named_scope("moe_dispatch"):
            xg = x[ck.token]
        with jax.named_scope("moe_experts"):
            act = act_of(*(gmm(xg, w, ck.tile_group, ck.tiles_left)
                           for w in w_in))
            y = gmm(act.astype(dtype), wd, ck.tile_group, ck.tiles_left)
        with jax.named_scope("moe_combine"):
            if runs:
                return _keep(out, (y,), c, chunk_rows)
            return out.at[ck.token].add(
                y.astype(jnp.float32) * ck.gate[:, None])

    out = jax.lax.fori_loop(
        0, _chunks(tiles_used, chunk_rows, tile_rows), chunk,
        _kept(row_pair, x, 1) if runs else jnp.zeros(x.shape, jnp.float32))
    if runs:
        with jax.named_scope("moe_combine"):
            slot_of = runs[1]  # each pair's gate where it lies, else 0
            scale = jnp.sum(jnp.where(
                slot_of[..., None] == jnp.arange(top_k),
                pair_gate.reshape(-1, top_k), 0.0), axis=-1)
            out = _combine_kept(out, scale, runs, dtype, "moe_combine_fwd",
                                interpret)
    return out.astype(dtype), (x, weights, pair_gate, row_pair, tile_group,
                               tiles_used, runs)


def _expert_ffn_bwd(top_k, chunk_rows, tile_rows, interpret, kind, res, dout):
    x, weights, pair_gate, row_pair, tile_group, tiles_used, runs = res
    dtype = x.dtype
    P = pair_gate.shape[0]
    expert = EXPERT_KINDS[kind]
    fwd = functools.partial(_gmm, transpose_rhs=False, name="moe_gmm_fwd",
                            tile_rows=tile_rows, interpret=interpret)
    dlhs = functools.partial(_gmm, transpose_rhs=True, name="moe_gmm_dlhs",
                             tile_rows=tile_rows, interpret=interpret)
    drhs = functools.partial(_gmm_drhs, tile_rows=tile_rows,
                             interpret=interpret)
    *w_in, wd = (w.astype(dtype) for w in weights)
    dout = dout.astype(dtype)

    def chunk(c, carry):
        dx, dw_in, dwd, dpair = carry
        ck = _Chunk(c, pair_gate, row_pair, tile_group, tiles_used, top_k,
                    chunk_rows, tile_rows)
        tiles = (ck.tile_group, ck.tiles_left)
        with jax.named_scope("moe_dispatch"):
            xg, dog = x[ck.token], dout[ck.token]
        with jax.named_scope("moe_experts"):
            pre = [fwd(xg, w, *tiles).astype(jnp.float32) for w in w_in]
            act = expert.act(*pre)
            # y = act W_down, so d/d(gate of the pair) <y, dout> is
            # <act, dout W_down^T>, and d/d(act) is that times the gate.
            u = dlhs(dog, wd, *tiles).astype(jnp.float32)
            dgate_of_pair = jnp.sum(act * u, axis=-1)
            dact = u * ck.gate[:, None]
            dpre = [d.astype(dtype) for d in expert.dpre(dact, *pre)]
            dxg = [dlhs(d, w, *tiles) for d, w in zip(dpre, w_in)]
            if not runs:  # summed here; the kernel sums what it keeps
                dxg = functools.reduce(operator.add, (
                    d.astype(jnp.float32) for d in dxg))
            dw_in = tuple(drhs(xg, d, *tiles, acc)
                          for d, acc in zip(dpre, dw_in))
            dwd = drhs(act.astype(dtype),
                       (dog.astype(jnp.float32) * ck.gate[:, None]).astype(
                           dtype), *tiles, dwd)
        with jax.named_scope("moe_combine"):
            dx = _keep(dx, dxg, c, chunk_rows) if runs \
                else dx.at[ck.token].add(dxg * ck.valid[:, None])
            dpair = dpair.at[ck.pair].set(dgate_of_pair, mode="drop",
                                          unique_indices=True)
        return dx, dw_in, dwd, dpair

    zeros = lambda w: jnp.zeros(w.shape, jnp.float32)  # noqa: E731
    dx, dw_in, dwd, dpair = jax.lax.fori_loop(
        0, _chunks(tiles_used, chunk_rows, tile_rows), chunk,
        (_kept(row_pair, x, len(w_in)) if runs
         else jnp.zeros(x.shape, jnp.float32),
         tuple(zeros(w) for w in weights[:-1]), zeros(weights[-1]),
         jnp.zeros((P,), jnp.float32)))
    if runs:  # the gate is in the rows already
        with jax.named_scope("moe_combine"):
            dx = _combine_kept(dx, None, runs, x.dtype, "moe_combine_bwd",
                               interpret)
    dweights = tuple(d.astype(w.dtype)
                     for d, w in zip(dw_in + (dwd,), weights))
    return (dx.astype(x.dtype), dweights, dpair.astype(pair_gate.dtype),
            None, None, None, None)


expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


class ExpertShareMLP(nn.Module):
    """Top-k routed experts, dropless, holding a share of them.

    x [B, S, D] -> [B, S, D]. The router scores all ``num_experts``; this
    layer holds ``experts_held`` of them from ``first_expert`` on (all, by
    default) and returns the held experts' part of the sum: the shares of
    the holders of all experts add up to the whole layer. Pairs that chose
    an expert held elsewhere contribute nothing here. ``renormalize``
    divides a token's ``top_k`` gates by their sum. There is no capacity
    and no auxiliary loss.

    The defaults are a softmax router over SwiGLU experts. ``scoring``
    ``"sigmoid"`` scores each expert by a sigmoid of its own logit, chooses
    by ``score + router_bias`` (a parameter that starts at zero and that no
    gradient of the model's loss reaches) and gates by the scores alone,
    times ``route_scale`` (`route_top_k`). ``balance_scale`` turns on the
    bias's balance rule: the layer sows `balance_pull`'s zero-valued term
    into the ``losses`` collection, which `Trainer` adds to the objective,
    so the optimizer steps each expert's bias against its load error; and
    the choice sees ``balance_scale x router_bias``, because an optimizer
    moves a parameter about a learning rate a step and a score lives on
    [0, 1]: at a learning rate of 3e-5, 512 makes a step of the bias at
    most 0.015. Where ``losses`` is not mutable (a plain ``apply``) nothing
    is sowed and the bias has no gradient at all. ``expert_kind`` ``"relu2"``
    makes an expert two matrices, ``W_down relu(W_up x) ** 2``; ``"reglu"``
    three, ``W_down (relu(x W_gate) * (x W_up))``. ``shared_dim``
    adds ONE shared expert of that width and the same kind, which every
    token takes with gate 1 and every holder computes alike (under the scope
    ``moe_shared``): where shares are added up it counts once.

    **A share held alone does not train the router.** Where the layer holds
    a part of the experts, its gates pass no gradient. Of a token's
    ``top_k`` gates only those of experts held here could get one (the
    other experts' outputs are with their holders); each of those says
    "more of this expert" as soon as the experts have learnt anything, and
    an optimizer that steps the router along that partial sum pulls the
    routing towards the held experts: the rows routed here grew 2.7 x in a
    hundred steps (PERF.md section 6, PR 26), where a layer that holds all
    its experts keeps its routing. The gates' gradient belongs where all of
    a token's expert outputs meet, after the exchange between the holders,
    which is not written (ROADMAP B-II). A layer that holds all its experts
    trains its router as any other weight.

    Shapes are static whatever the imbalance: the index buffer has
    `buffer_rows` rows, which no routing can overflow (see there), and the
    rows themselves are handled `CHUNK_TILES` tiles at a time, for as many
    rounds as the routed rows need: the time follows the rows routed here,
    not the buffer."""

    hidden_dim: int
    intermediate_dim: int
    num_experts: int
    top_k: int
    experts_held: Optional[int] = None
    first_expert: int = 0
    renormalize: bool = True
    scoring: str = "softmax"
    route_scale: float = 1.0
    balance_scale: Optional[float] = None
    expert_kind: str = "swiglu"
    shared_dim: Optional[int] = None
    tile_rows: int = TILE_ROWS
    #: Scale of ``down_proj``'s initial values (a model that scales its
    #: residual branches' output projections by depth passes it).
    down_init_scale: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    embed_axis: str = "embed"
    mlp_axis: str = "mlp"

    @nn.compact
    def __call__(self, x, route_from=None):
        """``route_from`` [B, S, D], where given, is what the router scores
        in place of ``x`` (a model whose router reads the layer's input
        before attention, `models.smallthinker`); the experts transform
        ``x`` either way."""
        from maggy_tpu.telemetry.plans import remember_plan

        B, S, D = x.shape
        E, F, k = self.num_experts, self.intermediate_dim, self.top_k
        G = E if self.experts_held is None else self.experts_held
        if not 0 <= self.first_expert <= E - G:
            raise ValueError("experts {}..{} are not among {}".format(
                self.first_expert, self.first_expert + G - 1, E))
        if self.expert_kind not in EXPERT_KINDS:
            raise ValueError("expert_kind is one of {}; got {!r}".format(
                sorted(EXPERT_KINDS), self.expert_kind))
        kind = EXPERT_KINDS[self.expert_kind]
        N = B * S
        tm = self.tile_rows
        rows = buffer_rows(N, k, G, tm)
        # Whole chunks cover the buffer, so no slice of it runs off its end.
        chunk_rows = tm * max(t for t in range(1, CHUNK_TILES + 1)
                              if (rows // tm) % t == 0)
        said = "experts {}+{}/{} top{} rows {} chunk {} tile {} pallas_gmm" \
            .format(self.first_expert, G, E, k, rows, chunk_rows, tm)
        if self.scoring != "softmax":
            said += " {}+bias x{:g}".format(self.scoring, self.route_scale)
        if self.balance_scale:
            if self.scoring != "sigmoid":
                raise ValueError("the balance rule moves a sigmoid router's "
                                 "bias; scoring is {!r}".format(self.scoring))
            said += " balance x{:g}".format(self.balance_scale)
        if self.expert_kind != "swiglu":
            said += " " + self.expert_kind
        if route_from is not None:
            said += " route pre-attn"
        scopes = SCOPES
        if self.shared_dim:
            said += " shared {}".format(self.shared_dim)
            scopes += (SHARED_SCOPE,)
        combine = combine_plan(N, D, tm) if _on_tpu() else None
        said += " combine " + ("pallas {}x{}".format(*combine) if combine
                               else "xla")
        remember_plan("moe", said, scopes)

        router = self.param("router", nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), (self.embed_axis, None)),
            (D, E), self.param_dtype)
        bias = None
        if self.scoring == "sigmoid":
            bias = self.param("router_bias", nn.with_logical_partitioning(
                nn.initializers.zeros_init(), (None,)), (E,),
                self.param_dtype)

        def matrix(name, shape, axes, scale=1.0, batch_axis=()):
            return self.param(name, nn.with_logical_partitioning(
                nn.initializers.variance_scaling(
                    scale ** 2, "fan_in", "truncated_normal",
                    batch_axis=batch_axis), axes), shape, self.param_dtype)

        def expert_weights(prefix, held, width):
            """The kind's matrices into the width, then the one out of it:
            stacked over the held experts, or one expert's (``held``
            None)."""
            lead = () if held is None else (held,)
            axes = () if held is None else (EXPERT,)
            batch = () if held is None else (0,)
            return tuple(
                matrix(prefix + name, lead + (D, width),
                       axes + (self.embed_axis, self.mlp_axis),
                       batch_axis=batch) for name in kind.into) + (
                matrix(prefix + "down_proj", lead + (width, D),
                       axes + (self.mlp_axis, self.embed_axis),
                       self.down_init_scale, batch),)

        weights = expert_weights("", G, F)

        xd = x.reshape(N, D).astype(self.dtype)
        scored = xd if route_from is None \
            else route_from.reshape(N, D).astype(self.dtype)
        with jax.named_scope("moe_routing"):
            ids, gates = route_top_k(
                scored, router, k, self.renormalize, self.scoring,
                bias * self.balance_scale if self.balance_scale else bias,
                self.route_scale)
            if self.balance_scale:
                self.sow("losses", "router_balance",
                         balance_pull(ids, bias, E))
            if G < E:  # a share held alone: see the class docstring
                gates = jax.lax.stop_gradient(gates)
            # Which experts each token took, for whoever asks for the
            # "intermediates" collection (nothing in a training step).
            self.sow("intermediates", "expert_ids", ids)
            route = [checkpoint_name(r, REMAT_KEEP[0]) for r in (
                gates.reshape(N * k),
                *grouped_layout(ids, self.first_expert, G, tm))]
            runs = tuple(checkpoint_name(r, REMAT_KEEP[0]) for r in
                         combine_runs(ids, self.first_expert, G, tm,
                                      combine[0])) if combine else ()
        out = expert_ffn(xd, weights, *route, runs, k, chunk_rows, tm,
                         not _on_tpu(), self.expert_kind)
        if self.shared_dim:
            *into, down = (w.astype(self.dtype) for w in expert_weights(
                "shared_", None, self.shared_dim))
            with jax.named_scope(SHARED_SCOPE):
                act = kind.act(*(
                    jnp.dot(xd, w, preferred_element_type=jnp.float32)
                    for w in into))
                out = out + jnp.dot(act.astype(self.dtype), down)
        return out.reshape(B, S, D)
