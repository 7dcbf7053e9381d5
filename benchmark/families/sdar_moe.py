"""Family ``sdar_moe``: `maggy_tpu.models.SdarMoe`, a Qwen3-MoE-shaped
decoder trained with the block-diffusion objective, from a configuration
file that carries the keys of the published ``config.json``.

What the harness hands a family is the configuration's ``model`` dict and
nothing of the mix, so the step's own parameters live there too:
``block_length``, ``mask_token_id``, the noise schedule, and which experts
this chip holds (``num_experts`` of ``num_experts_routed``, from
``first_expert`` on).

One example is a data sequence ``x0`` of L = ``seq`` tokens. The model's
input is 2 L positions, the noised copy ``xt`` and then ``x0``
(``inputs = (tokens [B, 2 L],)``); ``labels`` carries the targets ``x0`` and
the per-position loss weights together, because the harness's reference
check hands the reference ``labels`` and nothing else.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import sdar_work


def build(model: dict):
    """(module, model config) from the configuration's ``model`` keys."""
    import jax.numpy as jnp

    from maggy_tpu.models import SdarMoe, SdarMoeConfig

    if model["noise_schedule"] != "linear":
        raise ValueError("only the linear schedule (t ~ U(0, 1], weights "
                         "1/t) is written down; got {!r}".format(
                             model["noise_schedule"]))
    if model["mask_token_id"] != model["vocab_size"] - 1:
        raise ValueError("the mask id is the last id of the vocabulary slice")
    cfg = SdarMoeConfig(
        vocab_size=model["vocab_size"], hidden_dim=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        moe_intermediate_dim=model["moe_intermediate_size"],
        num_experts=model["num_experts_routed"],
        top_k=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"], block_length=model["block_length"],
        mask_token_id=model["mask_token_id"],
        experts_held=model["num_experts"], first_expert=model["first_expert"],
        dtype=jnp.dtype(model["activation_dtype"]),
        param_dtype=jnp.dtype(model["param_dtype"]), remat=model["remat"])
    return SdarMoe(cfg), cfg


def positions(model: dict, seq) -> int:
    """Tokens one example counts: the L data tokens. The clean copy that
    doubles the positions is the method's cost, not data."""
    return int(seq)


def batches(model: dict, batch: int, seq, seed: int, n: int = 4):
    """``n`` seeded host batches of the block-diffusion step, cycled by the
    trial as the encoders' are. ``x0`` uniform over the ids below the mask
    id; one ``t ~ U(0, 1]`` per sequence; each token replaced by the mask id
    with probability ``t``; weights ``masked / t / (batch * seq)``, so the
    loss is the sum over masked positions of the weighted cross-entropy."""
    rng = np.random.default_rng(seed)
    mask_id = model["mask_token_id"]
    out = []
    for _ in range(n):
        x0 = rng.integers(0, mask_id, size=(batch, seq))
        t = 1.0 - rng.random(size=(batch, 1))  # (0, 1]
        masked = rng.random(size=(batch, seq)) < t
        xt = np.where(masked, mask_id, x0)
        out.append({
            "inputs": (np.concatenate([xt, x0], axis=1).astype(np.int32),),
            "labels": {
                "targets": x0.astype(np.int32),
                "weights": (masked / t / (batch * seq)).astype(np.float32)},
        })
    return out


def init_args(batch: dict):
    """(example_inputs, init_kwargs) for `Trainer.init`."""
    return batch["inputs"], {}


def loss(logits, batch):
    from maggy_tpu.ops.losses import weighted_token_xent

    labels = batch["labels"]
    return weighted_token_xent(logits, labels["targets"], labels["weights"])


def checked_grads(grads):
    """The part of the gradient tree the reference check compares: the first
    layer's weights (attention, both norms, the router and the held
    experts), which the gradient reaches last."""
    return grads["layer_0"]


def flops_per_token(model: dict, seq) -> dict:
    """Forward + backward FLOPs one counted token needs, by part
    (``harness/sdar_work.py``): a token is two positions through the layers
    and one through the head."""
    return sdar_work.train_flops_per_token(model, int(seq))
