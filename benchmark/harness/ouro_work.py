"""The work an Ouro training step needs, as a function of shapes: the
parameters this chip holds, the step's FLOPs by part and by pass, and the
least time of a step's exit heads. Beside ``flops.py``, ``sdar_work.py`` and
``nemotron_h_work.py``, whose conventions it keeps (2 FLOPs a multiply-add,
backward twice the forward, nothing recomputed, no softmax / norms /
look-ups / optimizer), for a model none of them can count: it COUNTS A
LOOP. A weight is stored once and used ``total_ut_steps`` times a token, so
parameters are per LAYER and work is per layer APPLICATION, ``passes x
layers`` of them, and the head runs once a pass.

**One layer application**, forward, a token: the four attention
projections ``2 hidden d (2 heads + 2 kv_heads)``, the SwiGLU's three
matrices ``3 x 2 hidden intermediate``, causal attention ``4 d heads x (S +
1) / 2`` (scores and values over the keys a causal mask lets through, a
mean of ``(S + 1) / 2`` a query).

**One exit**: the head, ``2 hidden vocab`` a token. The gate is ``2
hidden`` a token and counts nothing.

**The heads' least time** (`exit_heads`): a step's T heads, forward and the
two backward products (the state's gradient and the kernel's), ``3 T x 2
tokens hidden vocab`` FLOPs; least bytes: per exit and direction the states
read and written once, the kernel read once in each direction in the
activation dtype's width (it is cast on the way in), and its float32
gradient written once for all exits. The larger of the two bounds is the
least time; whatever computes the heads, XLA's chunk scan or a later kernel,
reads against this one count, and what an implementation recomputes (the
chunk scan makes every chunk's logits again in the backward pass) counts
against it.
"""

from __future__ import annotations

from benchmark.harness import flops
from benchmark.harness.sdar_work import ITEMSIZE, least_ms  # noqa: F401


def layer_parameters(model: dict) -> dict:
    """Parameters of ONE layer, by part."""
    hidden, d = model["hidden_size"], model["head_dim"]
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    return {
        "attention": hidden * d * (2 * heads + 2 * kv_heads),
        "mlp": 3 * hidden * model["intermediate_size"],
        "norms": 4 * hidden,
    }


def parameters(model: dict) -> dict:
    """Parameters this chip holds, by part: the held layers once each
    (however often they are applied), the embedding and the head whole, the
    final norm and the gate (a vector and a bias)."""
    hidden = model["hidden_size"]
    layer = sum(layer_parameters(model).values())
    out = {
        "layers": model["num_hidden_layers"] * layer,
        "embedding_and_head": 2 * model["vocab_size"] * hidden,
        "final_norm_and_gate": hidden + hidden + 1,
    }
    out["all"] = sum(out.values())
    return out


def causal_pairs(seq: int) -> int:
    """Query-key pairs of one sequence and query head a causal mask lets
    through."""
    return seq * (seq + 1) // 2


def application_forward_flops_per_token(model: dict, seq: int) -> dict:
    """Forward FLOPs of ONE application of one layer for one token."""
    hidden, d = model["hidden_size"], model["head_dim"]
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    return {
        "projections": 2.0 * hidden * d * (2 * heads + 2 * kv_heads),
        "mlp": 3 * 2.0 * hidden * model["intermediate_size"],
        "attention": 4.0 * d * heads * causal_pairs(seq) / seq,
    }


def applications(model: dict) -> int:
    """Layer applications a token goes through: passes x held layers."""
    return model["total_ut_steps"] * model["num_hidden_layers"]


def forward_flops_per_token_by_pass(model: dict, seq: int) -> list:
    """Forward FLOPs per token of each pass: its layers and its exit."""
    layer = sum(application_forward_flops_per_token(model, seq).values())
    one = {"layers": model["num_hidden_layers"] * layer,
           "head": 2.0 * model["hidden_size"] * model["vocab_size"]}
    return [dict(one) for _ in range(model["total_ut_steps"])]


def train_flops_per_token(model: dict, seq: int) -> dict:
    """Forward + backward FLOPs per counted token, by part: every layer
    application of every pass, and one head an exit."""
    out = {part: flops.train_flops(applications(model) * f)
           for part, f in application_forward_flops_per_token(
               model, seq).items()}
    out["head"] = flops.train_flops(
        model["total_ut_steps"] * 2.0 * model["hidden_size"]
        * model["vocab_size"])
    return out


def exit_heads(model: dict, batch: int, seq: int) -> dict:
    """A step's T exit heads, forward and backward together (what a step's
    ``exit_head`` time is read against)."""
    tokens, T = batch * seq, model["total_ut_steps"]
    hidden, vocab = model["hidden_size"], model["vocab_size"]
    itemsize = ITEMSIZE[model["activation_dtype"]]
    states = T * tokens * hidden * itemsize
    return {
        "exits": T,
        "flops": flops.train_flops(T * 2.0 * tokens * hidden * vocab),
        # Forward: the states and the kernel in. Backward: the states and
        # the kernel in again, the states' gradient and the kernel's
        # float32 gradient out.
        "bytes": float(3 * states + 2 * hidden * vocab * itemsize
                       + hidden * vocab * 4),
    }
