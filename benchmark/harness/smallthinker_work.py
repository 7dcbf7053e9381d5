"""The work a SmallThinker next-token step needs, as a function of shapes:
attention's visible pairs under each layer's mask, the windowed layers'
attention FLOPs and least HBM bytes, and the step's count by part. Beside
``flops.py``, ``attention_work.py`` and ``nemotron_h_work.py``, whose
conventions it keeps (2 FLOPs a multiply-add, backward twice the forward,
nothing recomputed, no softmax / norms / look-ups / optimizer), for a model
whose layers attend over different key sets: a global layer over the causal
past, a windowed one over the last ``sliding_window_size`` positions.

**Visible pairs** of one sequence and query head: ``S (S + 1) / 2`` under
the causal mask; under the window of W (query i sees key j iff ``i - W < j
<= i``) the first W queries see their whole past, ``W (W + 1) / 2``, and
each of the other ``S - W`` sees W keys. Attention FLOPs are ``4 D`` a pair
and query head forward (scores and values), twice that backward.

**The experts.** A token routes ``top_k`` pairs over ``routed`` experts, of
which this chip holds ``held``: ``tokens * top_k * held / routed`` rows are
expected here (0.75 a token and layer at 6 of 64 with 8 held). A ReGLU
expert is THREE matrices of ``hidden x width``.
"""

from __future__ import annotations

from benchmark.harness import flops
from benchmark.harness.sdar_work import ITEMSIZE, least_ms  # noqa: F401


def visible_pairs(seq: int, window=None) -> int:
    """Query-key pairs of one sequence and query head the mask lets
    through: causal, or causal under a window of ``window`` positions."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_kinds(model: dict) -> dict:
    """How many layers of each kind: ``global`` and ``window``."""
    layout = model["sliding_window_layout"]
    return {"global": layout.count(0), "window": layout.count(1)}


def expected_rows(model: dict, batch: int, seq: int) -> float:
    """Token-expert pairs a step is expected to route to one layer's held
    experts."""
    return batch * seq * model["moe_num_active_primary_experts"] \
        * model["moe_num_primary_experts"] / model["num_experts_routed"]


def window_attention(model: dict, batch: int, seq: int) -> dict:
    """The windowed layers' attention in a step, forward and backward
    together (what ``window_flash_roofline_pct`` reads the flash kernels'
    time under ``attn_window`` against): ``4 D`` FLOPs a visible pair and
    query head forward, twice that backward; least bytes: q and o at the
    query heads, k and v at the K/V heads, read or written once forward (q,
    k, v in, o out) and once backward (q, k, v, o and o's gradient in, the
    gradients of q, k and v out)."""
    d, heads = model["head_dim"], model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    layers = layer_kinds(model)["window"]
    pairs = visible_pairs(seq, model["sliding_window_size"])
    fwd = 4.0 * d * heads * pairs * batch
    row = batch * seq * d * ITEMSIZE[model["activation_dtype"]]
    fwd_bytes = row * (2 * heads + 2 * kv_heads)
    bwd_bytes = row * (3 * heads + 2 * kv_heads) + row * (heads + 2 * kv_heads)
    return {"layers": layers, "pairs": pairs,
            "flops": layers * flops.train_flops(fwd),
            "bytes": float(layers * (fwd_bytes + bwd_bytes))}


def forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward FLOPs of ONE layer of each kind for one token, by part."""
    hidden, d = model["hidden_size"], model["head_dim"]
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    shared = {
        "attention_projections": 2.0 * hidden * d * (2 * heads + 2 * kv_heads),
        "experts": 3 * 2.0 * hidden * model["moe_ffn_hidden_size"]
        * model["moe_num_active_primary_experts"]
        * model["moe_num_primary_experts"] / model["num_experts_routed"],
        "router": 2.0 * hidden * model["num_experts_routed"],
    }
    per_pair = 4.0 * d * heads / seq
    return {
        "global": dict(shared, attention_global=per_pair
                       * visible_pairs(seq)),
        "window": dict(shared, attention_window=per_pair * visible_pairs(
            seq, model["sliding_window_size"])),
    }


def train_flops_per_token(model: dict, seq: int) -> dict:
    """Forward + backward FLOPs per counted token, by part: every layer of
    the layout and the head."""
    count = layer_kinds(model)
    out: dict = {}
    for kind, parts in forward_flops_per_token(model, seq).items():
        for part, f in parts.items():
            out[part] = out.get(part, 0.0) + flops.train_flops(count[kind] * f)
    out["head"] = flops.train_flops(
        2.0 * model["hidden_size"] * model["vocab_size"])
    return out


def parameters(model: dict) -> dict:
    """Parameters by part, from the configuration's keys (what the chip
    holds: the layers held, their held experts, the vocabulary slice)."""
    hidden, d = model["hidden_size"], model["head_dim"]
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    attn = hidden * d * (2 * heads + 2 * kv_heads)
    expert = 3 * hidden * model["moe_ffn_hidden_size"]
    layer = attn + hidden * model["num_experts_routed"] + 2 * hidden \
        + model["moe_num_primary_experts"] * expert
    vocab = 2 * model["vocab_size"] * hidden + hidden
    layers = model["num_hidden_layers"]
    return {"attention": attn, "expert": expert, "layer": layer,
            "vocab": vocab, "all": layers * layer + vocab}
