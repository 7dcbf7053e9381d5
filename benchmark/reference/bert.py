"""Plain float32 reference of the ``bert`` family: forward, loss, gradient.

`jax.numpy` only, no flax, no kernel, nothing imported from ``maggy_tpu``.
It follows what ``maggy_tpu/models/bert.py`` computes, in mathematics, and
reads the same parameter tree (the flax names), so that the two can be fed
the same seeded weights.

Departures from the published BERT (Devlin et al. 2018), all of them the
program's and mirrored here so that the comparison tests the program's
arithmetic and not its architecture:

- pre-LN (layer norm before attention and before the MLP, and one final
  layer norm) where the original is post-LN with a layer norm on the
  embeddings;
- no token-type (segment) embedding;
- GELU in its tanh approximation (`flax.linen.gelu` default) where the
  original uses the erf form;
- layer-norm epsilon 1e-6 (flax default) where the original has 1e-12;
- dropout is never applied (`Trainer` feeds no dropout rng).

Every matmul runs under ``default_matmul_precision("highest")``: on a TPU a
float32 matmul is otherwise computed in bfloat16 passes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30
LN_EPS = 1e-6


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def encoder_layer(x, keep, p, num_heads: int):
    """One pre-LN encoder layer. ``x`` [B, S, H]; ``keep`` [B, S] bool,
    True where a key may be attended to."""
    B, S, H = x.shape
    d = H // num_heads
    h = layer_norm(x, p["ln_attn"])

    def heads(t):
        return t.reshape(B, S, num_heads, d).transpose(0, 2, 1, 3)

    q, k, v = (heads(dense(h, p[n])) for n in ("q_proj", "k_proj", "v_proj"))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(d)
    scores = jnp.where(keep[:, None, None, :], scores, NEG_INF)
    att = jax.nn.softmax(scores, axis=-1) @ v
    att = att.transpose(0, 2, 1, 3).reshape(B, S, H)
    x = x + dense(att, p["o_proj"])
    h = layer_norm(x, p["ln_mlp"])
    return x + dense(gelu_tanh(dense(h, p["fc_in"])), p["fc_out"])


def encoder(x, keep, params, num_layers: int, num_heads: int):
    for i in range(num_layers):
        x = encoder_layer(x, keep, params["layer_{}".format(i)], num_heads)
    return layer_norm(x, params["ln_final"])


def forward(params, inputs, model: dict):
    """Logits [B, num_labels] in float32. ``inputs`` = (tokens, keep)."""
    tokens, keep = inputs
    with jax.default_matmul_precision("highest"):
        S = tokens.shape[1]
        x = params["tok_embedding"][tokens] + params["pos_embedding"][None, :S]
        x = encoder(x, keep.astype(bool), params, model["num_hidden_layers"],
                    model["num_attention_heads"])
        pooled = jnp.tanh(dense(x[:, 0], params["pooler"]))
        return dense(pooled, params["classifier"])


def loss_from_logits(logits, labels):
    """Mean cross-entropy of the logits against integer labels."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
