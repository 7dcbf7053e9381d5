"""Family ``smallthinker``: `maggy_tpu.models.SmallThinker`, a decoder of
windowed and global (NoPE) attention layers in one period over ReGLU experts
whose router reads the layer's input before attention, trained on the
causal next-token step, from a configuration file that carries the keys of
the published ``config.json``.

What the harness hands a family is the configuration's ``model`` dict and
nothing of the mix, so which experts this chip holds lives there too
(``moe_num_primary_experts`` of ``num_experts_routed``, from
``first_expert`` on).

One example is a sequence of ``seq`` tokens. ``inputs = (tokens, targets)``,
both [B, S], the target of position i being token i + 1: the module forms no
logits but the per-position negative log-likelihood of the target through
the fused head-and-loss (`ops.losses.chunked_token_nll`), so the targets go
IN, and it returns one float32 array [B, S], which the harness's check
compares as it would logits. ``labels`` carries the per-position weights,
``1 / (B (S - 1))``, zero at a sequence's last position, which has no next
token: the loss is the mean next-token cross-entropy.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import smallthinker_work


def build(model: dict):
    """(module, model config) from the configuration's ``model`` keys."""
    import jax.numpy as jnp

    from maggy_tpu.models import SmallThinker, SmallThinkerConfig

    layers = model["num_hidden_layers"]
    if len(model["sliding_window_layout"]) != layers \
            or len(model["rope_layout"]) != layers:
        raise ValueError("{} layers, but the layouts have {} and {} "
                         "entries".format(layers,
                                          len(model["sliding_window_layout"]),
                                          len(model["rope_layout"])))
    if not model["moe_primary_router_apply_softmax"] \
            or model["tie_word_embeddings"] or model["rope_scaling"]:
        raise ValueError("written down are a softmax router, an untied head "
                         "and plain rope")
    cfg = SmallThinkerConfig(
        vocab_size=model["vocab_size"], hidden_dim=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        moe_intermediate_dim=model["moe_ffn_hidden_size"],
        num_experts=model["num_experts_routed"],
        top_k=model["moe_num_active_primary_experts"],
        norm_topk_prob=model["norm_topk_prob"],
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        sliding_window=model["sliding_window_size"],
        sliding_window_layout=tuple(model["sliding_window_layout"]),
        rope_layout=tuple(model["rope_layout"]),
        experts_held=model["moe_num_primary_experts"],
        first_expert=model["first_expert"],
        residual_layers=model["residual_layers"],
        head_chunk=model["head_chunk"],
        dtype=jnp.dtype(model["activation_dtype"]),
        param_dtype=jnp.dtype(model["param_dtype"]), remat=model["remat"])
    return SmallThinker(cfg), cfg


def positions(model: dict, seq) -> int:
    """Tokens one example counts: every position of the sequence."""
    return int(seq)


def batches(model: dict, batch: int, seq, seed: int, n: int = 4):
    """``n`` seeded host batches of the next-token step, cycled by the trial
    as the other steady mixes' are: ids uniform over the vocabulary slice;
    the target of position i is token i + 1; weights ``1 / (batch (seq -
    1))``, zero at the last position."""
    rng = np.random.default_rng(seed)
    weights = np.full((batch, seq), 1.0 / (batch * (seq - 1)), np.float32)
    weights[:, -1] = 0.0
    out = []
    for _ in range(n):
        tokens = rng.integers(0, model["vocab_size"], size=(batch, seq))
        out.append({
            "inputs": (tokens.astype(np.int32),
                       np.roll(tokens, -1, axis=1).astype(np.int32)),
            "labels": {"weights": weights},
        })
    return out


def init_args(batch: dict):
    """(example_inputs, init_kwargs) for `Trainer.init`."""
    return batch["inputs"], {}


def loss(nll, batch):
    """The mean next-token cross-entropy, from the module's [B, S]."""
    import jax.numpy as jnp

    return jnp.sum(batch["labels"]["weights"] * nll)


#: Leaves of an expert layer that the check leaves out: a held expert's own
#: weight gradient is a sum over the some 1,536 rows routed to it, and ONE
#: row that the bfloat16 program and the float32 reference route
#: differently (a near-tie between a token's 6th and 7th of 64 scores)
#: moves its largest entry by tens of percent, as
#: ``families/nemotron_h.py`` found for its own. Their arithmetic is held
#: to float32 rounding on the CPU (``tests/test_smallthinker_model.py``).
FLIP_BOUND_LEAVES = ("gate_proj", "up_proj", "down_proj")


def checked_grads(grads):
    """The part of the gradient tree the reference check compares: the
    first global layer's and the first windowed layer's weights but
    `FLIP_BOUND_LEAVES` (their norms, attention, router), which the gradient
    reaches through every layer after them."""
    return {name: dict(grads[name], moe={
        k: v for k, v in grads[name]["moe"].items()
        if k not in FLIP_BOUND_LEAVES}) for name in ("layer_0", "layer_1")}


def flops_per_token(model: dict, seq) -> dict:
    """Forward + backward FLOPs one counted token needs, by part
    (``harness/smallthinker_work.py``)."""
    return smallthinker_work.train_flops_per_token(model, int(seq))
