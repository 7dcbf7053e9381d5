"""Plain float32 reference of the ``sdar_moe`` family: forward, loss,
gradient of the block-diffusion training step.

`jax.numpy` only, no flax, no kernel, nothing imported from ``maggy_tpu``.
It reads the parameter tree the program's module makes (the flax names), so
that both can be fed the same seeded weights, and writes the equations out:

- layer: ``h = x + Attn(RMSNorm(x)); y = h + MoE(RMSNorm(h))``, RMSNorm with
  eps from the configuration;
- ``Attn``: q, k, v without bias; RMSNorm over each head's ``head_dim`` on q
  and k; rope (half-split) at positions 0..L-1 in BOTH copies; scores
  ``q k^T / sqrt(head_dim)`` under the mask, softmax, ``(P v) W_o``;
- the mask, built plainly from its three rules over the 2 L positions
  (noised copy first, then the clean one; b(i) the block of data position
  i): noised sees noised of its own block; noised sees clean of earlier
  blocks; clean sees clean up to its own block; clean never sees noised;
- ``MoE``: softmax of the router's logits over all routed experts, the
  ``num_experts_per_tok`` largest, renormalised over the chosen; EVERY held
  expert is applied to EVERY token and weighted by its gate, which is zero
  where the token did not choose it: no sort, no groups, no capacity. A
  pair that chose an expert this chip does not hold contributes nothing,
  and where only a part of the routed experts is held the gates pass no
  gradient (a share's partial sum would pull the router towards the held
  experts: ``assumed.router_gradient`` in the configuration); those are
  the two things left out, and the program leaves out the same;
- final RMSNorm and the untied head on the noised half only.

So that 8,192 positions fit a chip in float32: attention is computed a block
of queries at a time and the experts one at a time, each under
`jax.checkpoint` inside a `lax.map` / `lax.scan`, and every layer is
rematerialised. That changes where values are kept, not what is computed.

Every matmul runs under ``default_matmul_precision("highest")``: on a TPU a
float32 matmul is otherwise computed in bfloat16 passes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30
#: Queries a block of the attention holds: [heads, QUERY_BLOCK, 2 L] scores.
QUERY_BLOCK = 256


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, positions, theta):
    """x [S, H, D], positions [S]: rotate the two halves of D."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def visible(q_index, k_index, length: int, block: int):
    """[len(q_index), len(k_index)] bool: may query position q see key
    position k, both indices into the 2 L positions."""
    q_noised, k_noised = q_index < length, k_index < length
    q_block = (q_index % length) // block
    k_block = (k_index % length) // block
    qn, kn = q_noised[:, None], k_noised[None, :]
    qb, kb = q_block[:, None], k_block[None, :]
    return (qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb)) \
        | (~qn & ~kn & (kb <= qb))


def attention(x, p, model: dict):
    """x [S, hidden] of ONE sequence (S = 2 L) -> [S, hidden]."""
    S = x.shape[0]
    L = S // 2
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    positions = jnp.arange(S) % L
    q = (x @ p["q_proj"]["kernel"]).reshape(S, heads, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(S, kv_heads, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(S, kv_heads, d)
    q = rope(rms_norm(q, p["q_norm"]["scale"], eps), positions,
             model["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"]["scale"], eps), positions,
             model["rope_theta"])
    # Query head h reads K/V head h // (heads / kv_heads).
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    keys = jnp.arange(S)
    qb = min(QUERY_BLOCK, S)

    @jax.checkpoint
    def block(args):
        q_blk, q_index = args  # [qb, heads, d], [qb]
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k) / math.sqrt(d)
        keep = visible(q_index, keys, L, model["block_length"])
        probs = jax.nn.softmax(jnp.where(keep[None], scores, NEG_INF), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, (q.reshape(S // qb, qb, heads, d),
                              keys.reshape(S // qb, qb)))
    return out.reshape(S, heads * d) @ p["o_proj"]["kernel"]


def experts(x, p, model: dict):
    """x [N, hidden] -> the held experts' part of the routed sum."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, ids = jax.lax.top_k(probs, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    gates = jnp.zeros_like(probs).at[rows, ids].set(top)  # 0 where not chosen
    first = model["first_expert"]
    held = gates[:, first:first + model["num_experts"]]
    if model["num_experts"] < model["num_experts_routed"]:
        held = jax.lax.stop_gradient(held)

    @jax.checkpoint
    def one(out, expert):
        w_gate, w_up, w_down, gate = expert
        h = jax.nn.silu(x @ w_gate) * (x @ w_up)
        return out + gate[:, None] * (h @ w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["gate_proj"], p["up_proj"], p["down_proj"], held.T))
    return out


def layer(x, p, model: dict):
    """x [B, S, hidden]."""
    eps = model["rms_norm_eps"]
    h = x + jax.vmap(lambda s: attention(s, p["attn"], model))(
        rms_norm(x, p["attn_norm"]["scale"], eps))
    B, S, H = h.shape
    moe = experts(rms_norm(h, p["mlp_norm"]["scale"], eps).reshape(B * S, H),
                  p["moe"], model)
    return h + moe.reshape(B, S, H)


def forward(params, inputs, model: dict):
    """Float32 logits [B, L, vocab] at the noised half. ``inputs`` =
    (tokens [B, 2 L],): the noised copy, then the clean one."""
    (tokens,) = inputs
    with jax.default_matmul_precision("highest"):
        L = tokens.shape[1] // 2
        x = params["embedding"][tokens]
        for i in range(model["num_hidden_layers"]):
            x = jax.checkpoint(lambda x, p: layer(x, p, model))(
                x, params["layer_{}".format(i)])
        x = rms_norm(x[:, :L], params["final_norm"]["scale"],
                     model["rms_norm_eps"])
        return x @ params["lm_head"]


def loss_from_logits(logits, labels):
    """Sum over positions of ``weights`` x cross-entropy against
    ``targets``; the weights are 1/t / (B L) at masked positions, else 0."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels["targets"][..., None], axis=-1)[..., 0]
    return -jnp.sum(labels["weights"] * picked)
