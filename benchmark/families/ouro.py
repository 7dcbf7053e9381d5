"""Family ``ouro``: `maggy_tpu.models.Ouro`, a looped language model (one
stack of sandwich-norm decoder layers applied ``total_ut_steps`` times over
shared weights, an exit after every pass), trained on the next-token step
with the loss over ALL its exits, from a configuration file that carries the
keys of the published ``config.json``.

What the harness hands a family is the configuration's ``model`` dict and
nothing of the mix, so how many layers this chip holds lives there
(``num_hidden_layers`` of ``published_layers``), and the loss's ``beta``
too, which rides on into ``labels`` because neither `loss` nor the
reference's ``loss_from_logits`` sees the ``model`` dict.

One example is a sequence of ``seq`` tokens. ``inputs = (tokens, targets)``,
both [B, S], the target of position i being token i + 1: the module forms no
logits but the per-position negative log-likelihood of the target through
the fused head-and-loss, so the targets go IN, and it returns one float32
array ``[2, T, B, S]`` (``[0, t]`` exit t's likelihoods, ``[1, t]`` its gate
values), which the harness's check compares as it would logits. ``labels``
carries the per-position weights (``1 / (B (S - 1))``, zero at a sequence's
last position, which has no next token) and ``beta`` ([B, 1], so that a
batch sharded over its rows carries it along).

The loss: ``lambda_t = sigmoid(g_t)``; exit probabilities ``p_t = lambda_t
prod_{j < t} (1 - lambda_j)`` for t < T and ``p_T = prod_{j < T} (1 -
lambda_j)``, the last exit taking what is left; ``sum_i w_i [sum_t p_t(i)
l_t(i) - beta H(p(i))]``, the expected cross-entropy under the learned exit
distribution less an entropy term.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import ouro_work


def build(model: dict):
    """(module, model config) from the configuration's ``model`` keys."""
    import jax.numpy as jnp

    from maggy_tpu.models import Ouro, OuroConfig

    if model["hidden_act"] != "silu" or model["tie_word_embeddings"] \
            or model["use_sliding_window"] or model["rope_scaling"]:
        raise ValueError("written down are a SwiGLU of silu, an untied "
                         "head, full attention and plain rope")
    cfg = OuroConfig(
        vocab_size=model["vocab_size"], hidden_dim=model["hidden_size"],
        intermediate_dim=model["intermediate_size"],
        num_layers=model["published_layers"],
        layers_held=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], rope_theta=model["rope_theta"],
        norm_eps=model["rms_norm_eps"],
        total_ut_steps=model["total_ut_steps"],
        head_chunk=model["head_chunk"],
        dtype=jnp.dtype(model["activation_dtype"]),
        param_dtype=jnp.dtype(model["param_dtype"]), remat=model["remat"])
    return Ouro(cfg), cfg


def positions(model: dict, seq) -> int:
    """Tokens one example counts: every position of the sequence."""
    return int(seq)


def batches(model: dict, batch: int, seq, seed: int, n: int = 4):
    """``n`` seeded host batches of the next-token step, cycled by the trial
    as the other steady mixes' are: ids uniform over the whole vocabulary;
    the target of position i is token i + 1; weights ``1 / (batch (seq -
    1))``, zero at the last position."""
    rng = np.random.default_rng(seed)
    weights = np.full((batch, seq), 1.0 / (batch * (seq - 1)), np.float32)
    weights[:, -1] = 0.0
    beta = np.full((batch, 1), model["exit_entropy_beta"], np.float32)
    out = []
    for _ in range(n):
        tokens = rng.integers(0, model["vocab_size"], size=(batch, seq))
        out.append({
            "inputs": (tokens.astype(np.int32),
                       np.roll(tokens, -1, axis=1).astype(np.int32)),
            "labels": {"weights": weights, "beta": beta},
        })
    return out


def init_args(batch: dict):
    """(example_inputs, init_kwargs) for `Trainer.init`."""
    return batch["inputs"], {}


def exit_probabilities(gates):
    """gates [T, ...] -> (p [T, ...], ln p), through log-sigmoids: ``ln p_t
    = ln lambda_t + sum_{j < t} ln(1 - lambda_j)`` and the last exit's
    without its own ``ln lambda``."""
    import jax
    import jax.numpy as jnp

    stay = jax.nn.log_sigmoid(-gates)  # ln(1 - lambda_t)
    # sum over j < t; the last exit's own gate is in no term of it.
    before = jnp.concatenate([jnp.zeros_like(stay[:1]),
                              jnp.cumsum(stay[:-1], axis=0)])
    log_p = jnp.concatenate([
        (jax.nn.log_sigmoid(gates) + before)[:-1], before[-1:]])
    return jnp.exp(log_p), log_p


def loss(out, batch):
    """The expected cross-entropy over the exits less ``beta`` times the
    exit distribution's entropy, from the module's ``[2, T, B, S]``."""
    import jax.numpy as jnp

    labels = batch["labels"]
    p, log_p = exit_probabilities(out[1])
    entropy = -jnp.sum(p * log_p, axis=0)
    return jnp.sum(labels["weights"] * (
        jnp.sum(p * out[0], axis=0) - labels["beta"] * entropy))


def checked_grads(grads):
    """The part of the gradient tree the reference check compares: the
    first layer's weights, which the gradient reaches last and through
    every pass (each a sum over the T uses), and the exit gate's, which it
    reaches through the exit probabilities alone."""
    return {"layer_0": grads["stack"]["layer_0"],
            "exit_gate": grads["exit_gate"]}


def flops_per_token(model: dict, seq) -> dict:
    """Forward + backward FLOPs one counted token needs, by part
    (``harness/ouro_work.py``)."""
    return ouro_work.train_flops_per_token(model, int(seq))
