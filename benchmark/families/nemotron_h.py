"""Family ``nemotron_h``: `maggy_tpu.models.NemotronH`, a decoder built from
a pattern of state-space (``M``), expert (``E``) and attention (``*``)
blocks, trained on the causal next-token step, from a configuration file
that carries the keys of the published ``config.json``.

What the harness hands a family is the configuration's ``model`` dict and
nothing of the mix, so which experts this chip holds lives there too
(``n_routed_experts`` of ``num_experts_routed``, from ``first_expert`` on).

One example is a sequence of ``seq`` tokens (``inputs = (tokens [B, S],)``);
``labels`` carries the targets (the next token) and the per-position loss
weights together, because the harness's reference check hands the reference
``labels`` and nothing else. The logits are dense, [B, S, vocab]: the
position that has no next token weighs nothing.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import nemotron_h_work


def build(model: dict):
    """(module, model config) from the configuration's ``model`` keys."""
    import jax.numpy as jnp

    from maggy_tpu.models import NemotronH, NemotronHConfig

    pattern = model["hybrid_override_pattern"]
    if len(pattern) != model["num_hidden_layers"]:
        raise ValueError("{} blocks, but the pattern {!r} has {}".format(
            model["num_hidden_layers"], pattern, len(pattern)))
    if model["mlp_hidden_act"] != "relu2" or model["n_shared_experts"] != 1 \
            or model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("written down are relu2 experts, one shared expert "
                         "and a router of one group")
    cfg = NemotronHConfig(
        vocab_size=model["vocab_size"], hidden_dim=model["hidden_size"],
        pattern=pattern, num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], mamba_heads=model["mamba_num_heads"],
        mamba_head_dim=model["mamba_head_dim"],
        ssm_groups=model["n_groups"], ssm_state=model["ssm_state_size"],
        conv_kernel=model["conv_kernel"], chunk_size=model["chunk_size"],
        time_step_min=model["time_step_min"],
        time_step_max=model["time_step_max"],
        time_step_floor=model["time_step_floor"],
        moe_intermediate_dim=model["moe_intermediate_size"],
        shared_intermediate_dim=model["moe_shared_expert_intermediate_size"],
        num_experts=model["num_experts_routed"],
        top_k=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_eps=model["layer_norm_epsilon"],
        experts_held=model["n_routed_experts"],
        first_expert=model["first_expert"],
        balance_scale=model.get("router_balance_scale"),
        residual_blocks=model["residual_blocks"],
        dtype=jnp.dtype(model["activation_dtype"]),
        param_dtype=jnp.dtype(model["param_dtype"]), remat=model["remat"])
    return NemotronH(cfg), cfg


def positions(model: dict, seq) -> int:
    """Tokens one example counts: every position of the sequence."""
    return int(seq)


def batches(model: dict, batch: int, seq, seed: int, n: int = 4):
    """``n`` seeded host batches of the next-token step, cycled by the trial
    as the encoders' are: ids uniform over the vocabulary slice; the target
    of position i is token i + 1; weights ``1 / (batch (seq - 1))``, zero at
    the last position, so the loss is the mean over the pairs."""
    rng = np.random.default_rng(seed)
    weights = np.full((batch, seq), 1.0 / (batch * (seq - 1)), np.float32)
    weights[:, -1] = 0.0
    out = []
    for _ in range(n):
        tokens = rng.integers(0, model["vocab_size"], size=(batch, seq))
        out.append({
            "inputs": (tokens.astype(np.int32),),
            "labels": {
                "targets": np.roll(tokens, -1, axis=1).astype(np.int32),
                "weights": weights},
        })
    return out


def init_args(batch: dict):
    """(example_inputs, init_kwargs) for `Trainer.init`."""
    return batch["inputs"], {}


def loss(logits, batch):
    from maggy_tpu.ops.losses import weighted_token_xent

    labels = batch["labels"]
    return weighted_token_xent(logits, labels["targets"], labels["weights"])


#: Leaves of an expert block that the check leaves out: a held expert's own
#: weight gradient is a sum over the 768 rows routed to it, and ONE row that
#: the bfloat16 program and the float32 reference route differently (a
#: near-tie between a token's 6th and 7th score) moves its largest entry by
#: tens of percent: on the chip these two leaves read 0.33-0.57 whatever
#: stood in the program's place, float8 operands included (PERF.md section
#: 6, PR 30). Their arithmetic is held to float32 rounding on the CPU
#: (``tests/test_nemotron_h_model.py``).
FLIP_BOUND_LEAVES = ("up_proj", "down_proj")


def checked_grads(grads):
    """The part of the gradient tree the reference check compares: the
    first expert block's weights but `FLIP_BOUND_LEAVES` (its norm, router,
    shared expert) and the first state-space block's, which the gradient
    reaches through every block after them. A block's kind is told by a
    leaf only its mixer has."""
    blocks = sorted((k for k in grads if k.startswith("block_")),
                    key=lambda k: int(k.split("_")[1]))

    def first(leaf):
        return next(b for b in blocks if leaf in grads[b]["mixer"])

    experts, state_space = first("router"), first("A_log")
    return {
        experts: dict(grads[experts], mixer={
            k: v for k, v in grads[experts]["mixer"].items()
            if k not in FLIP_BOUND_LEAVES}),
        state_space: grads[state_space]}


def flops_per_token(model: dict, seq) -> dict:
    """Forward + backward FLOPs one counted token needs, by part
    (``harness/nemotron_h_work.py``)."""
    return nemotron_h_work.train_flops_per_token(model, int(seq))
