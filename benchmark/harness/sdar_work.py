"""The work a block-diffusion MoE step needs, as a function of shapes: the
visible pairs of the mask, attention's FLOPs and least HBM bytes from them,
the grouped expert products' FLOPs and least bytes, and the step's count by
part. Beside ``flops.py`` and ``attention_work.py``, whose conventions it
keeps (2 FLOPs a multiply-add, backward twice the forward, nothing
recomputed, no softmax / norms / look-ups / optimizer), for a model those
two cannot count: ``attention_work.of_cell`` assumes ``hidden // heads``
wide heads and all S^2 pairs, and neither holds here.

**The mask.** A data sequence of L tokens in blocks of b is 2 L positions,
a noised and a clean copy. A noised query of block c sees its own block and
the clean blocks before it, ``b + c b`` keys; a clean query of block c sees
the clean blocks up to its own, ``(c + 1) b``. Summed over the L / b blocks
of b queries each: ``L b + L (L - b) / 2 + L (L + b) / 2 = L^2 + L b``
visible pairs a sequence, of the ``4 L^2`` a dense mask over 2 L positions
would have (`visible_pairs`; a brute-force count is in
``benchmark/tests/test_sdar_work.py``).

**The experts.** A position routes ``top_k`` pairs over ``routed`` experts,
of which this chip holds ``held``: ``positions * top_k * held / routed``
rows are expected here. Routing makes the true count vary from step to
step; with 16,384 positions and 131,072 pairs a step its standard deviation
is under 1 % of the mean (a binomial's: sqrt(131072 * 1/8 * 7/8) = 120 of
16,384), so the expected rows are the work.
"""

from __future__ import annotations

from benchmark.harness import attention_work, flops

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def visible_pairs(length: int, block: int) -> int:
    """Query-key pairs of one sequence that the block-diffusion mask lets
    through."""
    return length * length + length * block


def attention(model: dict, batch: int, seq: int) -> dict:
    """One layer's attention work in a step: FLOPs over the visible pairs
    (forward ``4 D`` a pair and query head, backward twice that) and the
    least bytes (q, o and their gradients at all query heads, k, v and
    theirs at the K/V heads, each once)."""
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    d = model["head_dim"]
    itemsize = ITEMSIZE[model["activation_dtype"]]
    pairs = batch * visible_pairs(seq, model["block_length"])
    fwd = 4.0 * d * heads * pairs
    q_side = float(batch * 2 * seq * heads * d * itemsize)
    kv_side = float(batch * 2 * seq * kv_heads * d * itemsize)
    return {
        "layers": model["num_hidden_layers"],
        "forward": {"flops": fwd, "bytes": 2 * q_side + 2 * kv_side},
        "backward": {"flops": 2.0 * fwd, "bytes": 4 * q_side + 4 * kv_side},
    }


def expected_rows(model: dict, batch: int, seq: int) -> float:
    """Token-expert pairs a step is expected to route to the held experts."""
    return batch * 2 * seq * model["num_experts_per_tok"] \
        * model["num_experts"] / model["num_experts_routed"]


def grouped_products(model: dict, batch: int, seq: int) -> dict:
    """One layer's grouped expert products in a step, forward and backward
    together (what a step's ``moe_gmm_*`` time is read against): three
    products of ``rows x hidden x width`` forward and twice that backward;
    least bytes: the rows in and out of each product once (forward: x in
    twice, the gate and up results, the activation in, y out; backward the
    same again for the gradients, and the rows in once more for the weight
    gradients), the held weights read once in each direction, and their
    float32 gradients written once."""
    rows = expected_rows(model, batch, seq)
    hidden, width = model["hidden_size"], model["moe_intermediate_size"]
    itemsize = ITEMSIZE[model["activation_dtype"]]
    fwd = 3 * 2.0 * rows * hidden * width
    row_bytes = rows * itemsize * (3 * hidden + 3 * width)
    weights = model["num_experts"] * 3 * hidden * width
    return {
        "layers": model["num_hidden_layers"],
        "rows": rows,
        "flops": flops.train_flops(fwd),
        "bytes": 3 * row_bytes + weights * (2 * itemsize + 4),
    }


def forward_flops_per_position(model: dict, seq: int) -> dict:
    """Forward FLOPs of ONE layer for one of the 2 L positions, by part."""
    hidden, d = model["hidden_size"], model["head_dim"]
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    return {
        "projections": 2.0 * hidden * d * (2 * heads + 2 * kv_heads),
        "attention": 4.0 * d * heads * visible_pairs(
            seq, model["block_length"]) / (2 * seq),
        "experts": 3 * 2.0 * hidden * model["moe_intermediate_size"]
        * model["num_experts_per_tok"] * model["num_experts"]
        / model["num_experts_routed"],
        "router": 2.0 * hidden * model["num_experts_routed"],
    }


def train_flops_per_token(model: dict, seq: int) -> dict:
    """Forward + backward FLOPs per counted token, by part: two positions
    through every layer, one (the noised copy's) through the head."""
    layer = forward_flops_per_position(model, seq)
    out = {part: flops.train_flops(2 * model["num_hidden_layers"] * f)
           for part, f in layer.items()}
    out["head"] = flops.train_flops(
        2.0 * model["hidden_size"] * model["vocab_size"])
    return out


def least_ms(work: dict, peak_flops: float, device_kind: str):
    """(least milliseconds for ``work`` = {"flops", "bytes"}, which binds)."""
    seconds, bound = attention_work.least_seconds(
        work["flops"], work["bytes"], peak_flops,
        attention_work.hbm_bytes_per_s(device_kind))
    return seconds * 1e3, bound
