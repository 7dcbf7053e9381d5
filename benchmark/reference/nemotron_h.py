"""Plain float32 reference of the ``nemotron_h`` family: forward, loss,
gradient of the causal next-token training step.

`jax.numpy` only, no flax, no kernel, nothing imported from ``maggy_tpu``.
It reads the parameter tree the program's module makes (the flax names), so
that both can be fed the same seeded weights, and writes the equations out:

- block: ``x = x + mixer(RMSNorm(x))``, eps from the configuration; the
  mixer by the letter of ``hybrid_override_pattern``; no positional
  embedding anywhere;
- ``M`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC_t = silu(b + sum_j
  w_j xBC_{t-3+j})``, zero before the sequence's start; split into x [H, P],
  B [G, N], C [G, N]; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  THE RECURRENCE ITSELF, POSITION BY POSITION: ``h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t`` (head h reads group ``h // (H
  / G)``), never the chunked algorithm the program runs; ``y = y * silu(z)``,
  then an RMS norm over each of the G groups of channels with one learned
  scale a channel; ``y W_out``;
- ``*``: q, k, v without bias and without rope; scores ``q k^T /
  sqrt(head_dim)`` under the causal mask, softmax, ``(P v) W_o``;
- ``E``: ``s = sigmoid(n W_r)`` over all routed experts; the
  ``num_experts_per_tok`` largest of ``s + router_bias`` are chosen (the
  bias in the units the program's balance rule keeps it in:
  ``router_balance_scale x`` the parameter); gates
  ``s_chosen / (sum s_chosen + 1e-20) x routed_scaling_factor``; EVERY held
  expert is applied to EVERY token (``W_down relu(W_up n) ** 2``) and
  weighted by its gate, which is zero where the token did not choose it; the
  shared expert is added with gate one. A pair that chose an expert this
  chip does not hold contributes nothing, the bias gets no gradient from
  the loss (what moves it is the optimizer's balance rule, over steps), and
  where only a part of the routed experts is held the gates pass none
  (``assumed.router_gradient``): the program leaves out the same;
- final RMSNorm, the untied head; the loss is the weighted cross-entropy of
  ``labels`` (targets: the next token; weights: 1 / (B (S - 1)), zero at
  the last position).

So that 2 x 8,192 positions fit a chip in float32: the recurrence runs as an
outer scan over blocks of `SCAN_BLOCK` positions, each block under
`jax.checkpoint` (its backward holds one block's states), attention a block
of queries at a time, the experts one at a time, a mixer one sequence at a
time (a Python loop: a `lax.map` would carry every weight's gradient as a
second copy), and every block of the model is rematerialised. That changes
where values are kept, not what is computed.

``knobs`` (`PLAIN` by default: nothing rounded, no fault) exist for
``harness/nemotron_h_controls.py``, which puts these same equations in the
program's place with operands rounded to fewer bits, or with one term wrong,
to show that the check's limits refuse them. With `PLAIN` every knob is the
identity.

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
#: Positions one checkpointed block of the recurrence covers.
SCAN_BLOCK = 64

#: What a control can get wrong (``harness/nemotron_h_controls.py``).
FAULTS = {"none": 0, "no_skip": 1, "no_softplus": 2, "conv_ahead": 3,
          "no_shared": 4, "no_scale": 5, "uncausal": 6}


class Knobs(NamedTuple):
    """``r``: what rounds an operand or a kept activation; ``fault``: one of
    `FAULTS`' numbers (an int, or a traced scalar)."""
    r: Callable[[Any], Any]
    fault: Any


PLAIN = Knobs(lambda x: x, 0)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def conv(x, taps, bias, ahead):
    """x [S, C], taps [K, C]: ``y_t = bias + sum_j taps_j x_{t-(K-1)+j}``,
    zeros before the start. ``ahead`` (False, or True for the fault) hands
    every position its successor's input, so that each tap reaches one
    position into the future."""
    K, C = taps.shape
    S = x.shape[0]
    x = jnp.where(ahead, jnp.concatenate([x[1:], jnp.zeros((1, C))]), x)
    padded = jnp.concatenate([jnp.zeros((K - 1, C)), x])
    return bias + sum(taps[j] * padded[j:j + S] for j in range(K))


def recurrence(x, dt, A, B, C):
    """x [S, H P] (head-major), dt [S, H], A [H], B and C [S, G, N] (head h
    reads group ``h // (H / G)``) -> ``h_t C_t`` [S, H P], one position
    after the other."""
    S, H = dt.shape
    G, N = B.shape[1:]
    P = x.shape[1] // H
    blk = min(SCAN_BLOCK, S)

    def step(h, at):  # h [G, H / G, P, N]
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t * A).reshape(G, H // G, 1, 1) * h \
            + (dt_t[:, None] * x_t.reshape(H, P)).reshape(G, H // G, P, 1) \
            * b_t[:, None, None, :]
        return h, jnp.einsum("ghpn,gn->ghp", h, c_t).reshape(H * P)

    @jax.checkpoint
    def block(h, positions):
        return jax.lax.scan(step, h, positions)

    _, y = jax.lax.scan(block, jnp.zeros((G, H // G, P, N)), tuple(
        a.reshape((S // blk, blk) + a.shape[1:]) for a in (x, dt, B, C)))
    return y.reshape(S, H * P)


def mamba(u, p, model: dict, knobs: Knobs):
    """u [S, hidden] of ONE sequence -> [S, hidden]."""
    r, fault = knobs
    S = u.shape[0]
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    inner = H * P
    zxbcdt = r(u @ r(p["in_proj"]["kernel"]))
    z, xBC, dt = jnp.split(zxbcdt, (inner, 2 * inner + 2 * G * N), axis=-1)
    xBC = r(jax.nn.silu(conv(xBC, p["conv_kernel"], p["conv_bias"],
                             fault == FAULTS["conv_ahead"])))
    x, B, C = jnp.split(xBC, (inner, inner + G * N), axis=-1)
    raw = dt + p["dt_bias"]
    dt = jnp.where(fault == FAULTS["no_softplus"], raw, jax.nn.softplus(raw))
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), B.reshape(S, G, N),
                   C.reshape(S, G, N))
    skip = jnp.where(fault == FAULTS["no_skip"], 0.0, p["D"])
    y = r(y + jnp.repeat(skip, P) * x) * jax.nn.silu(z)
    y = y.reshape(S, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + model["layer_norm_epsilon"])
    y = r(y.reshape(S, inner) * p["norm_scale"])
    return r(y @ r(p["out_proj"]["kernel"]))


def attention(x, p, model: dict, knobs: Knobs):
    """x [S, hidden] of ONE sequence -> [S, hidden]."""
    r, fault = knobs
    S = x.shape[0]
    heads = model["num_attention_heads"]
    kv_heads = model["num_key_value_heads"]
    d = model["head_dim"]
    q = r(x @ r(p["q_proj"]["kernel"])).reshape(S, heads, d)
    k = r(x @ r(p["k_proj"]["kernel"])).reshape(S, kv_heads, d)
    v = r(x @ r(p["v_proj"]["kernel"])).reshape(S, kv_heads, d)
    # Query head h reads K/V head h // (heads / kv_heads).
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    keys = jnp.arange(S)
    qb = min(QUERY_BLOCK, S)

    @jax.checkpoint
    def block(args):
        q_blk, q_index = args  # [qb, heads, d], [qb]
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k) / math.sqrt(d)
        keep = (keys[None, :] <= q_index[:, None]) \
            | (fault == FAULTS["uncausal"])
        probs = r(jax.nn.softmax(jnp.where(keep[None], scores, NEG_INF), -1))
        return r(jnp.einsum("hqk,khd->qhd", probs, v))

    out = jax.lax.map(block, (q.reshape(S // qb, qb, heads, d),
                              keys.reshape(S // qb, qb)))
    return r(out.reshape(S, heads * d) @ r(p["o_proj"]["kernel"]))


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def experts(x, p, model: dict, knobs: Knobs):
    """x [N, hidden] -> the held experts' part of the routed sum, plus the
    shared expert."""
    r, fault = knobs
    scores = jax.nn.sigmoid(x @ p["router"])
    ids = jax.lax.top_k(
        scores + (model.get("router_balance_scale") or 1.0)
        * jax.lax.stop_gradient(p["router_bias"]),
        model["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if model["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * jnp.where(fault == FAULTS["no_scale"], 1.0,
                          model["routed_scaling_factor"])
    rows = jnp.arange(x.shape[0])[:, None]
    gates = jnp.zeros_like(scores).at[rows, ids].set(top)  # 0 if not chosen
    first = model["first_expert"]
    held = gates[:, first:first + model["n_routed_experts"]]
    if model["n_routed_experts"] < model["num_experts_routed"]:
        held = jax.lax.stop_gradient(held)

    @jax.checkpoint
    def one(out, expert):
        w_up, w_down, gate = expert
        return out + gate[:, None] * r(r(relu2(r(x @ w_up))) @ w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        r(p["up_proj"]), r(p["down_proj"]), held.T))
    shared = r(r(relu2(r(x @ r(p["shared_up_proj"]))))
               @ r(p["shared_down_proj"]))
    return r(out + jnp.where(fault == FAULTS["no_shared"], 0.0, 1.0) * shared)


def block(x, p, kind: str, model: dict, knobs: Knobs):
    """x [B, S, hidden] through one block of the pattern."""
    n = knobs.r(rms_norm(x, p["norm"]["scale"], model["layer_norm_epsilon"]))
    if kind == "E":
        B, S, H = n.shape
        out = experts(n.reshape(B * S, H), p["mixer"], model,
                      knobs).reshape(B, S, H)
    else:
        # One sequence after the other, each made again for its gradient.
        mixer = {"M": mamba, "*": attention}[kind]
        one = jax.checkpoint(lambda s: mixer(s, p["mixer"], model, knobs))
        out = jnp.stack([one(s) for s in n])
    return knobs.r(x + out)


def forward(params, inputs, model: dict, knobs: Knobs = PLAIN):
    """Float32 logits [B, S, vocab]. ``inputs`` = (tokens [B, S],)."""
    (tokens,) = inputs
    with jax.default_matmul_precision("highest"):
        x = knobs.r(params["embedding"])[tokens]
        for i, kind in enumerate(model["hybrid_override_pattern"]):
            x = jax.checkpoint(
                lambda x, p, kind=kind: block(x, p, kind, model, knobs))(
                    x, params["block_{}".format(i)])
        x = knobs.r(rms_norm(x, params["final_norm"]["scale"],
                             model["layer_norm_epsilon"]))
        return x @ knobs.r(params["lm_head"])


def loss_from_logits(logits, labels):
    """Sum over positions of ``weights`` x cross-entropy against
    ``targets``: the next token, at every position but the last."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels["targets"][..., None], axis=-1)[..., 0]
    return -jnp.sum(labels["weights"] * picked)
