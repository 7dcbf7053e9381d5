"""The work a Nemotron-H next-token step needs, as a function of shapes: the
state-space scan's FLOPs and least HBM bytes, causal attention's visible
pairs, the grouped expert products' FLOPs and least bytes, and the step's
count by part. Beside ``flops.py``, ``attention_work.py`` and
``sdar_work.py``, whose conventions it keeps (2 FLOPs a multiply-add,
backward twice the forward, nothing recomputed, no softmax / norms /
look-ups / convolution taps / optimizer), for a model none of them can
count: its blocks are of three kinds and unequal cost, so every count here
is per KIND of block times how often the pattern holds it.

**The scan**, in the chunked form at the configuration's ``chunk_size`` L
(the form whose FLOPs are products; the recurrence itself needs fewer, all
of them elementwise, and nobody runs it on a matrix unit). Forward, a token:
``C B^T`` inside the chunk, once a group, ``2 L N G``; the decayed scores
times x, ``2 L P`` a head; a chunk's closing state, ``2 P N`` a head; the
carried state's part of the output, ``2 P N`` a head. The chunk's products
count the whole [L, L] tile, not its lower triangle: a matrix unit computes
the tile (`scan_forward_flops_per_token`; a brute-force count over the
products' shapes is in ``benchmark/tests/test_nemotron_h_work.py``).
Least bytes: x, B, C and dt read once and y written once forward; backward
those read again with y's gradient, and the four gradients written.

**Attention** is causal over S keys: ``S (S + 1) / 2`` visible pairs a
sequence and query head.

**The experts.** A token routes ``top_k`` pairs over ``routed`` experts, of
which this chip holds ``held``: ``tokens * top_k * held / routed`` rows are
expected here (6,144 of 98,304 pairs at 16,384 tokens; a binomial's standard
deviation is sqrt(98304 / 16 * 15 / 16) = 76, 1.2 %). A relu^2 expert is TWO
matrices. The shared expert is a dense two-matrix MLP that every token
takes, counted beside the routed share.
"""

from __future__ import annotations

from benchmark.harness import flops
from benchmark.harness.sdar_work import ITEMSIZE, least_ms  # noqa: F401


def kinds(model: dict) -> dict:
    """How many blocks of each kind the pattern holds."""
    pattern = model["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def scan_forward_flops_per_token(model: dict) -> float:
    """Forward FLOPs of ONE state-space block's scan for one token, chunked
    form."""
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N, L = model["n_groups"], model["ssm_state_size"], model["chunk_size"]
    return 2.0 * L * N * G + 2.0 * L * P * H + 2 * 2.0 * P * N * H


def scan(model: dict, batch: int, seq: int) -> dict:
    """One state-space block's scan in a step, forward and backward
    together (what a step's ``ssm_scan`` time is read against)."""
    tokens = batch * seq
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    itemsize = ITEMSIZE[model["activation_dtype"]]
    # x and y [H P], B and C [G N] in the activation dtype, dt [H] float32.
    ins = tokens * (itemsize * (H * P + 2 * G * N) + 4 * H)
    out = tokens * itemsize * H * P
    return {
        "layers": kinds(model)["M"],
        "flops": flops.train_flops(
            tokens * scan_forward_flops_per_token(model)),
        # Forward: inputs in, y out. Backward: inputs and dy in, the
        # inputs' gradients out.
        "bytes": float((ins + out) + (ins + out) + ins),
    }


def causal_pairs(seq: int) -> int:
    """Query-key pairs of one sequence and query head a causal mask lets
    through."""
    return seq * (seq + 1) // 2


def expected_rows(model: dict, batch: int, seq: int) -> float:
    """Token-expert pairs a step is expected to route to the held experts."""
    return batch * seq * model["num_experts_per_tok"] \
        * model["n_routed_experts"] / model["num_experts_routed"]


def grouped_products(model: dict, batch: int, seq: int) -> dict:
    """One expert block's grouped products in a step, forward and backward
    together (what a step's ``moe_gmm_*`` time is read against): TWO
    products of ``rows x hidden x width`` forward and twice that backward;
    least bytes: the rows in and out of each product once (forward: x in,
    the up result out, the activation in, y out; backward the same again
    for the gradients, and the rows in once more for the weight gradients),
    the held weights read once in each direction, and their float32
    gradients written once."""
    rows = expected_rows(model, batch, seq)
    hidden, width = model["hidden_size"], model["moe_intermediate_size"]
    itemsize = ITEMSIZE[model["activation_dtype"]]
    fwd = 2 * 2.0 * rows * hidden * width
    row_bytes = rows * itemsize * (2 * hidden + 2 * width)
    weights = model["n_routed_experts"] * 2 * hidden * width
    return {
        "layers": kinds(model)["E"],
        "rows": rows,
        "flops": flops.train_flops(fwd),
        "bytes": 3 * row_bytes + weights * (2 * itemsize + 4),
    }


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward FLOPs of ONE block of each kind for one token, by part."""
    hidden, d = model["hidden_size"], model["head_dim"]
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    inner = H * P
    return {
        "M": {
            "ssm_projections": 2.0 * hidden * (2 * inner + 2 * G * N + H)
            + 2.0 * inner * hidden,
            "ssm_scan": scan_forward_flops_per_token(model),
        },
        "E": {
            "experts_shared": 2 * 2.0 * hidden
            * model["moe_shared_expert_intermediate_size"],
            "experts_routed": 2 * 2.0 * hidden
            * model["moe_intermediate_size"] * model["num_experts_per_tok"]
            * model["n_routed_experts"] / model["num_experts_routed"],
            "router": 2.0 * hidden * model["num_experts_routed"],
        },
        "*": {
            "attention_projections": 2.0 * hidden * d
            * (2 * heads + 2 * kv_heads),
            "attention": 4.0 * d * heads * causal_pairs(seq) / seq,
        },
    }


def train_flops_per_token(model: dict, seq: int) -> dict:
    """Forward + backward FLOPs per counted token, by part: every block of
    the pattern and the head."""
    count = kinds(model)
    out = {part: flops.train_flops(count[kind] * f)
           for kind, parts in forward_flops_per_token(model, seq).items()
           for part, f in parts.items()}
    out["head"] = flops.train_flops(
        2.0 * model["hidden_size"] * model["vocab_size"])
    return out
