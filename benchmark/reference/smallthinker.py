"""Plain float32 reference of the ``smallthinker`` family: forward, loss,
gradient of the causal next-token training step.

`jax.numpy` only, no flax, no kernel, nothing imported from ``maggy_tpu``.
It reads the parameter tree the program's module makes (the flax names), so
that both can be fed the same seeded weights, and writes the equations out:

- layer l: ``u = RMSNorm_in(x)``; the router's softmax over all routed
  experts of ``u W_r``, FROM THE LAYER'S INPUT BEFORE ATTENTION, the
  ``moe_num_active_primary_experts`` largest, renormalised over the chosen
  (``norm_topk_prob``);
- attention: q, k, v without bias (GQA, query head h reads K/V head ``h //
  (heads / kv_heads)``); rope on q and k (halves layout, positions 0..S-1,
  theta from the configuration) where ``rope_layout[l]`` is 1, none where it
  is 0; scores ``q k^T / sqrt(head_dim)`` under the mask built from indices,
  ``j <= i``, and where ``sliding_window_layout[l]`` is 1 also ``i -
  sliding_window_size < j``; softmax; ``h = x + (P v) W_o``;
- experts: ``w = RMSNorm_post(h)``; EVERY held expert is applied to EVERY
  token, ``W_down (relu(w W_gate) * (w W_up))``, and weighted by its gate,
  which is zero where the token did not choose it; ``y = h + sum``. A pair
  that chose an expert this chip does not hold contributes nothing, and
  where only a part of the routed experts is held the gates pass no
  gradient (``assumed.router_gradient``): the program leaves out the same;
- final RMSNorm, the untied head formed DENSE over the vocabulary slice, a
  block of positions at a time; `forward` returns each position's negative
  log-likelihood of its target, [B, S], as the program's module does;
  `loss_from_logits` is the weighted sum of it.

So that 16,384 positions fit a chip in float32: attention runs a block of
`QUERY_BLOCK` queries at a time (``lax.map``), the experts one at a time
(``lax.scan``), the head a block of `HEAD_POSITIONS` positions at a time,
each under `jax.checkpoint`, one sequence at a time, and every layer is
rematerialised. That changes where values are kept, not what is computed.

``knobs`` (`PLAIN` by default: nothing rounded, no fault) exist for
``harness/smallthinker_controls.py``, which puts these same equations in
the program's place with operands rounded to fewer bits, or with one term
wrong, to show that the check's limits refuse them. With `PLAIN` every knob
is the identity.

Every matmul runs under ``default_matmul_precision("highest")``: on a TPU a
float32 matmul is otherwise computed in bfloat16 passes.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30
#: Queries a block of the attention holds: [heads, QUERY_BLOCK, S] scores.
QUERY_BLOCK = 128
#: Positions one block of the head holds: [HEAD_POSITIONS, vocab] logits.
HEAD_POSITIONS = 1024

#: What a control can get wrong (``harness/smallthinker_controls.py``):
#: the windowed layers attend as global ones; the router reads the state
#: after attention (``RMSNorm_post(h)``, what the experts transform); the
#: global layers turn q and k too; the windowed layers do not.
FAULTS = {"none": 0, "no_window": 1, "route_after_attn": 2, "rope_global": 3,
          "no_rope": 4}


class Knobs(NamedTuple):
    """``r``: what rounds an operand or a kept activation; ``fault``: one of
    `FAULTS`' numbers (an int, or a traced scalar)."""
    r: Callable[[Any], Any]
    fault: Any


PLAIN = Knobs(lambda x: x, 0)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x [S, heads, d] at positions 0..S-1, halves layout."""
    S, d = x.shape[0], x.shape[2]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # [S, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, p, model: dict, windowed: bool, turned: bool,
              knobs: Knobs):
    """u [S, hidden] of ONE sequence -> ``(P v) W_o`` [S, hidden]."""
    r, fault = knobs
    S = u.shape[0]
    heads, d = model["num_attention_heads"], model["head_dim"]
    kv_heads = model["num_key_value_heads"]
    q = r(u @ r(p["q_proj"]["kernel"])).reshape(S, heads, d)
    k = r(u @ r(p["k_proj"]["kernel"])).reshape(S, kv_heads, d)
    v = r(u @ r(p["v_proj"]["kernel"])).reshape(S, kv_heads, d)
    if turned:
        turn = fault != FAULTS["no_rope"]
    else:
        turn = fault == FAULTS["rope_global"]
    q = r(jnp.where(turn, rope(q, model["rope_theta"]), q))
    k = r(jnp.where(turn, rope(k, model["rope_theta"]), k))
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    keys = jnp.arange(S)
    window = jnp.where(windowed & (fault != FAULTS["no_window"]),
                       model["sliding_window_size"], S)
    qb = min(QUERY_BLOCK, S)

    @jax.checkpoint
    def block(args):
        q_blk, q_index = args  # [qb, heads, d], [qb]
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k) / math.sqrt(d)
        keep = (keys[None, :] <= q_index[:, None]) \
            & (keys[None, :] > q_index[:, None] - window)
        probs = r(jax.nn.softmax(jnp.where(keep[None], scores, NEG_INF), -1))
        return r(jnp.einsum("hqk,khd->qhd", probs, v))

    out = jax.lax.map(block, (q.reshape(S // qb, qb, heads, d),
                              keys.reshape(S // qb, qb)))
    return r(out.reshape(S, heads * d) @ r(p["o_proj"]["kernel"]))


def experts(w, scored, p, model: dict, knobs: Knobs):
    """w [N, hidden] (what the experts transform), scored [N, hidden] (what
    the router reads) -> the held experts' part of the routed sum."""
    r = knobs.r
    probs = jax.nn.softmax(scored @ p["router"], axis=-1)
    top, ids = jax.lax.top_k(probs, model["moe_num_active_primary_experts"])
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(w.shape[0])[:, None]
    gates = jnp.zeros_like(probs).at[rows, ids].set(top)  # 0 if not chosen
    first = model["first_expert"]
    held = gates[:, first:first + model["moe_num_primary_experts"]]
    if model["moe_num_primary_experts"] < model["num_experts_routed"]:
        held = jax.lax.stop_gradient(held)

    @jax.checkpoint
    def one(out, expert):
        w_gate, w_up, w_down, gate = expert
        act = r(jnp.maximum(r(w @ w_gate), 0.0) * r(w @ w_up))
        return out + gate[:, None] * r(act @ w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(w), (
        r(p["gate_proj"]), r(p["up_proj"]), r(p["down_proj"]), held.T))
    return r(out)


def layer(x, p, model: dict, windowed: bool, turned: bool, knobs: Knobs):
    """x [B, S, hidden] through one layer."""
    r, fault = knobs
    eps = model["rms_norm_eps"]
    u = r(rms_norm(x, p["input_norm"]["scale"], eps))
    # One sequence after the other, each made again for its gradient.
    one = jax.checkpoint(
        lambda s: attention(s, p, model, windowed, turned, knobs))
    h = r(x + jnp.stack([one(s) for s in u]))
    w = r(rms_norm(h, p["post_norm"]["scale"], eps))
    scored = jnp.where(fault == FAULTS["route_after_attn"], w, u)
    B, S, H = h.shape
    return r(h + experts(w.reshape(B * S, H), scored.reshape(B * S, H),
                         p["moe"], model, knobs).reshape(B, S, H))


def token_nll(state, head, targets):
    """state [N, hidden], targets [N] -> the per-position negative
    log-likelihood [N], the logits dense over the whole slice, a block of
    positions at a time."""
    N = state.shape[0]
    blk = min(HEAD_POSITIONS, N)

    @jax.checkpoint
    def block(args):
        s_blk, t_blk = args
        logits = s_blk @ head  # [blk, vocab]
        picked = jnp.take_along_axis(logits, t_blk[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return jax.lax.map(block, (state.reshape(N // blk, blk, -1),
                               targets.reshape(N // blk, blk))).reshape(N)


def forward(params, inputs, model: dict, knobs: Knobs = PLAIN):
    """Float32 [B, S]: each position's negative log-likelihood of its
    target. ``inputs`` = (tokens [B, S], targets [B, S])."""
    tokens, targets = inputs
    B, S = tokens.shape
    with jax.default_matmul_precision("highest"):
        x = knobs.r(params["embedding"])[tokens]
        for i, (windowed, turned) in enumerate(zip(
                model["sliding_window_layout"], model["rope_layout"])):
            x = jax.checkpoint(
                lambda x, p, windowed=windowed, turned=turned: layer(
                    x, p, model, bool(windowed), bool(turned), knobs))(
                        x, params["layer_{}".format(i)])
        x = knobs.r(rms_norm(x, params["final_norm"]["scale"],
                             model["rms_norm_eps"]))
        return token_nll(x.reshape(B * S, -1), knobs.r(params["lm_head"]),
                         targets.reshape(-1)).reshape(B, S)


def loss_from_logits(nll, labels):
    """``sum_i weights_i nll_i``: the mean over the B (S - 1) next-token
    pairs, the last position weighing nothing."""
    return jnp.sum(labels["weights"] * nll)
