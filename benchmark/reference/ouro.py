"""Plain float32 reference of the ``ouro`` family: forward, loss, gradient of
the looped language model's training step over all its exits.

`jax.numpy` only, no flax, no kernel, no scan over passes, nothing imported
from ``maggy_tpu``. It reads the parameter tree the program's module makes
(the flax names), so that both can be fed the same seeded weights, and
writes the equations out:

- norm: ``n(x) = w * x / sqrt(mean(x^2) + eps)``;
- layer l, four norms: ``h = x + n2(Attn(n1(x)))``; ``y = h + n4(MLP(n3(
  h)))``. ``Attn``: q, k, v without bias; rope on q and k at positions
  0..S-1, theta from the configuration, over the whole head, halves layout
  (``[x1 cos - x2 sin, x2 cos + x1 sin]``); scores ``q k^T /
  sqrt(head_dim)`` under the causal mask, softmax, ``(P v) W_o``. ``MLP``:
  ``W_down (silu(u W_gate) * (u W_up))``;
- model: ``s_0 = E[tokens]``; for t = 1..T, A PYTHON LOOP: ``s_t =
  n_f(Layer_L(.. Layer_1(s_{t-1})))``, the same parameter tree every pass,
  T x L calls of one layer function; exit t: logits ``s_t W_head`` formed
  DENSE, a block of positions at a time, ``l_t(i) = logsumexp(z_t(i)) -
  z_t(i)[target_i]``; gate ``g_t = s_t w_g + b_g``;
- `forward` returns ``[2, T, B, S]``: ``[0, t]`` the per-position ``l_t``,
  ``[1, t]`` the gate values, as the program's module does;
- `loss_from_logits`: ``lambda_t = sigmoid(g_t)``; ``p_t = lambda_t prod_{j
  < t} (1 - lambda_j)`` for t < T, ``p_T = prod_{j < T} (1 - lambda_j)``;
  ``sum_i w_i [sum_t p_t(i) l_t(i) - beta H(p(i))]``, ``H(p) = -sum_t p_t
  ln p_t``, the weights and beta from ``labels``.

So that 2 x 4,096 positions fit a chip in float32: every layer application
is rematerialised (`jax.checkpoint`), attention runs a block of `HEAD_BLOCK`
heads at a time ([B, HEAD_BLOCK, S, S] scores), the head a
block of `HEAD_POSITIONS` positions at a time ([HEAD_POSITIONS, vocab]
logits), each made again for its gradient. That changes where values are
kept, not what is computed.

``knobs`` (`PLAIN` by default: nothing rounded, no fault) exist for
``harness/ouro_controls.py``, which puts these same equations in the
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
#: Heads one block of the attention holds: [B, HEAD_BLOCK, S, S] scores.
HEAD_BLOCK = 2
#: Positions one block of an exit's head holds: [HEAD_POSITIONS, vocab].
HEAD_POSITIONS = 1024

#: What a control can get wrong (``harness/ouro_controls.py``).
FAULTS = {"none": 0, "three_passes": 1, "no_norm_between": 2,
          "no_after_norms": 3, "no_rope": 4, "uncausal": 5, "last_exit": 6,
          "first_passes_stopped": 7, "unshifted": 8, "beta_zero": 9}


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
    """x [B, S, heads, d] at positions 0..S-1, halves layout."""
    S, d = x.shape[1], x.shape[3]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # [S, d/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, p, model: dict, knobs: Knobs):
    """u [B, S, hidden] -> [B, S, hidden]."""
    r, fault = knobs
    B, S, _ = u.shape
    heads, d = model["num_attention_heads"], model["head_dim"]
    kv_heads = model["num_key_value_heads"]
    # The three projections as one product of their kernels side by side
    # (and gate and up likewise): 904 float32 products at full precision
    # make an executable of 1.1 GB, a third less this way.
    qkv = r(u @ r(jnp.concatenate([p[name]["kernel"] for name in (
        "q_proj", "k_proj", "v_proj")], axis=1)))
    q, k, v = jnp.split(qkv, (heads * d, (heads + kv_heads) * d), axis=-1)
    q = q.reshape(B, S, heads, d)
    k = k.reshape(B, S, kv_heads, d)
    v = v.reshape(B, S, kv_heads, d)
    turned = fault != FAULTS["no_rope"]
    q = r(jnp.where(turned, rope(q, model["rope_theta"]), q))
    k = r(jnp.where(turned, rope(k, model["rope_theta"]), k))
    # Query head h reads K/V head h // (heads / kv_heads).
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    at = jnp.arange(S)
    keep = (at[None, :] <= at[:, None]) | (fault == FAULTS["uncausal"])
    hb = min(HEAD_BLOCK, heads)

    @jax.checkpoint
    def block(args):
        q_blk, k_blk, v_blk = args  # [B, hb, S, d] each
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk) / math.sqrt(d)
        probs = r(jax.nn.softmax(jnp.where(keep, scores, NEG_INF), -1))
        return r(jnp.einsum("bhqk,bhkd->bhqd", probs, v_blk))

    def by_blocks(x):  # [B, S, heads, d] -> [heads / hb, B, hb, S, d]
        return x.reshape(B, S, heads // hb, hb, d).transpose(2, 0, 3, 1, 4)

    out = jax.lax.map(block, (by_blocks(q), by_blocks(k), by_blocks(v)))
    out = out.transpose(1, 3, 0, 2, 4).reshape(B, S, heads * d)
    return r(out @ r(p["o_proj"]["kernel"]))


def mlp(u, p, knobs: Knobs):
    r = knobs.r
    gate, up = jnp.split(r(u @ r(jnp.concatenate(
        [p["gate_proj"]["kernel"], p["up_proj"]["kernel"]], axis=1))), 2,
        axis=-1)
    gated = r(jax.nn.silu(gate) * up)
    return r(gated @ r(p["down_proj"]["kernel"]))


def layer(x, p, model: dict, knobs: Knobs):
    """x [B, S, hidden] through one application of one layer."""
    r, fault = knobs
    eps = model["rms_norm_eps"]

    def after(y, name):
        return jnp.where(fault == FAULTS["no_after_norms"], y,
                         rms_norm(y, p[name]["scale"], eps))

    u = r(rms_norm(x, p["attn_norm"]["scale"], eps))
    h = r(x + r(after(attention(u, p, model, knobs), "attn_after_norm")))
    u = r(rms_norm(h, p["mlp_norm"]["scale"], eps))
    return r(h + r(after(mlp(u, p, knobs), "mlp_after_norm")))


def exit_nll(state, head, targets):
    """state [N, hidden], targets [N] -> the per-position negative
    log-likelihood [N], the logits dense over the whole vocabulary, a block
    of positions at a time."""
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


def states(params, tokens, model: dict, knobs: Knobs = PLAIN):
    """The T exits' normed states, a list of [B, S, hidden]."""
    r, fault = knobs
    T, stack = model["total_ut_steps"], params["stack"]
    x = r(params["embedding"])[tokens]
    out = []
    for t in range(T):
        before = x
        for i in range(model["num_hidden_layers"]):
            x = jax.checkpoint(lambda x, p: layer(x, p, model, knobs))(
                x, stack["layer_{}".format(i)])
        normed = r(rms_norm(x, stack["final_norm"]["scale"],
                            model["rms_norm_eps"]))
        if t == T - 1:
            # Three passes for four: the last exit reads the third's state.
            normed = jnp.where(fault == FAULTS["three_passes"], before,
                               normed)
        else:
            # The weights' gradient from the last use only.
            normed = jnp.where(fault == FAULTS["first_passes_stopped"],
                               jax.lax.stop_gradient(normed), normed)
        out.append(normed)
        # The next pass reads the normed state.
        x = jnp.where(fault == FAULTS["no_norm_between"], x, normed)
    return out


def forward(params, inputs, model: dict, knobs: Knobs = PLAIN):
    """Float32 ``[2, T, B, S]``: ``[0, t]`` the per-position negative
    log-likelihood of exit t, ``[1, t]`` its gate values. ``inputs`` =
    (tokens [B, S], targets [B, S])."""
    tokens, targets = inputs
    targets = jnp.where(knobs.fault == FAULTS["unshifted"], tokens, targets)
    B, S = tokens.shape
    with jax.default_matmul_precision("highest"):
        head = knobs.r(params["lm_head"])
        nll, gates = [], []
        for state in states(params, tokens, model, knobs):
            nll.append(exit_nll(state.reshape(B * S, -1), head,
                                targets.reshape(-1)).reshape(B, S))
            gates.append(state @ params["exit_gate"]["kernel"]
                         + params["exit_gate"]["bias"])
        return jnp.stack([jnp.stack(nll), jnp.stack(gates)])


def exit_distribution(gates, fault=0):
    """gates [T, ...] -> the exit probabilities [T, ...]: ``p_t = lambda_t
    prod_{j < t} (1 - lambda_j)``, and the last exit takes what is left."""
    lam = jax.nn.sigmoid(gates)
    T = gates.shape[0]
    left = jnp.ones_like(lam[0])  # prod_{j < t} (1 - lambda_j)
    out = []
    for t in range(T - 1):
        out.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    out.append(jnp.where(fault == FAULTS["last_exit"], lam[-1] * left, left))
    return jnp.stack(out)


def loss_from_logits(out, labels, fault=0):
    """``sum_i w_i [sum_t p_t(i) l_t(i) - beta H(p(i))]`` from `forward`'s
    array."""
    nll, gates = out[0], out[1]
    p = exit_distribution(gates, fault)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    beta = jnp.where(fault == FAULTS["beta_zero"], 0.0, labels["beta"])
    return jnp.sum(labels["weights"] * (jnp.sum(p * nll, axis=0)
                                        - beta * entropy))
