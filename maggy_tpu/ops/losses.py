"""Memory-efficient losses: vocab-chunked softmax cross-entropy.

At flagship scale the lm-head logits are the single largest activation:
Llama-3's 128256-vocab head at [B=4, S=8192] is ~16.8 GB of fp32 logits —
more than half a v4 chip's HBM, and the full tensor is live across the
softmax forward AND stashed for the backward. The reference has no model
code at all (SURVEY.md §5.7); this is TPU-first design for the 8B LoRA
sweep (BASELINE configs[4]).

``chunked_softmax_xent`` computes the exact same loss while only ever
materializing ``[N, vocab_chunk]`` logits: a `lax.scan` over vocab chunks
maintains online logsumexp statistics (the flash-attention trick applied to
the classifier head), and the backward pass re-derives each chunk's logits
from the kept log-sum-exp instead of stashing them. Peak logits memory
drops from O(N·V) to O(N·chunk) in both passes; the matmuls stay MXU-shaped
([N,H] x [H,chunk], fp32 accumulation). It is the mean of
``chunked_token_nll``, the per-row likelihood, which a loss that weighs
rows differently reads (`models.ouro`: four exits, each position's
cross-entropy weighed by that position's exit probability).

Sharding: designed for dp/fsdp meshes (vocab replicated, embed sharded —
the flagship layout). Under tp the head's vocab dim is sharded over
"model"; prefer the dense path there (XLA's all-gather per chunk would
serialize the ring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _vocab_chunks(V: int, vocab_chunk: int):
    """(chunk width, the chunks' first global columns)."""
    vocab_chunk = int(min(vocab_chunk, V))
    num_chunks = -(-V // vocab_chunk)
    return vocab_chunk, jnp.arange(num_chunks, dtype=jnp.int32) * vocab_chunk


def _chunk_logits(h, kernel, c0, vocab_chunk: int):
    """One chunk's float32 logits [N, chunk], the slice's first column and
    which of its columns the chunk OWNS.

    The final ragged chunk slides its START back (dynamic_slice-style clamp)
    rather than padding the kernel: jnp.pad would materialize a second
    full-size [H, V'] copy of the head, defeating the HBM point. The owned
    mask keeps each column counted exactly once: the chunk owns global
    columns [c0, c0+chunk) intersected with [0, V)."""
    V = kernel.shape[1]
    cs = jnp.minimum(c0, V - vocab_chunk)
    Wk = jax.lax.dynamic_slice_in_dim(kernel, cs, vocab_chunk, axis=1)
    # bf16 MXU matmul with fp32 accumulation: same numerics contract as the
    # dense head (llama.py casts the head to activation dtype).
    logits = jnp.dot(h, Wk.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    gcol = cs + jnp.arange(vocab_chunk)  # global column of each slice column
    return logits, Wk, cs, (gcol >= c0) & (gcol < V)


def _token_nll_stats(h, kernel, tgt, vocab_chunk: int):
    """(log-sum-exp [N], target logit [N]) by the online scan over vocab
    chunks; at most one [N, chunk] block of logits is live."""
    N = h.shape[0]
    vocab_chunk, starts = _vocab_chunks(kernel.shape[1], vocab_chunk)

    def body(carry, c0):
        m, s, t = carry
        logits, _, cs, owned = _chunk_logits(h, kernel, c0, vocab_chunk)
        logits = jnp.where(owned[None, :], logits, -jnp.inf)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        s = s * jnp.exp(m - m_new) + \
            jnp.exp(logits - m_new[:, None]).sum(axis=-1)
        in_chunk = (tgt >= c0) & (tgt < c0 + vocab_chunk)
        picked = jnp.take_along_axis(
            logits, jnp.clip(tgt - cs, 0, vocab_chunk - 1)[:, None], axis=1
        )[:, 0]
        t = jnp.where(in_chunk, picked, t)
        return (m_new, s, t), None

    init = (jnp.full((N,), -jnp.inf, jnp.float32),
            jnp.zeros((N,), jnp.float32),
            jnp.zeros((N,), jnp.float32))
    (m, s, t), _ = jax.lax.scan(body, init, starts)
    return m + jnp.log(s), t


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _token_nll(h, kernel, tgt, vocab_chunk):
    lse, t = _token_nll_stats(h, kernel, tgt, vocab_chunk)
    return lse - t


def _token_nll_fwd(h, kernel, tgt, vocab_chunk):
    lse, t = _token_nll_stats(h, kernel, tgt, vocab_chunk)
    return lse - t, (h, kernel, tgt, lse)


def _token_nll_bwd(vocab_chunk, res, g):
    """Each chunk's logits are derived again from ``h`` and the kernel (the
    forward kept the log-sum-exp and no logits): ``d logits = g (softmax -
    onehot)``, in the activation dtype for the two products, ``dh`` summed in
    float32 over the chunks and each chunk's ``dW`` added into its columns."""
    h, kernel, tgt, lse = res
    vocab_chunk, starts = _vocab_chunks(kernel.shape[1], vocab_chunk)
    g = g.astype(jnp.float32)

    def body(carry, c0):
        dh, dW = carry
        logits, Wk, cs, owned = _chunk_logits(h, kernel, c0, vocab_chunk)
        hit = (tgt - cs)[:, None] == jnp.arange(vocab_chunk)[None, :]
        d = g[:, None] * (jnp.exp(logits - lse[:, None]) - hit)
        d = jnp.where(owned[None, :], d, 0.0).astype(h.dtype)
        dh = dh + jnp.dot(d, Wk.astype(h.dtype).T,
                          preferred_element_type=jnp.float32)
        dWk = jnp.dot(h.T, d, preferred_element_type=jnp.float32)
        seen = jax.lax.dynamic_slice_in_dim(dW, cs, vocab_chunk, axis=1)
        dW = jax.lax.dynamic_update_slice_in_dim(dW, seen + dWk, cs, axis=1)
        return (dh, dW), None

    (dh, dW), _ = jax.lax.scan(
        body, (jnp.zeros(h.shape, jnp.float32),
               jnp.zeros(kernel.shape, jnp.float32)), starts)
    return dh.astype(h.dtype), dW.astype(kernel.dtype), None


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def chunked_token_nll(h, kernel, targets, vocab_chunk: int = 16384):
    """Per-row negative log-likelihood ``logsumexp(h @ kernel) - (h @
    kernel)[i, targets[i]]``, float32 [N], without materializing the [N, V]
    logits in either pass.

    h: [N, H] activations (any float dtype; products accumulate fp32).
    kernel: [H, V] classifier weights.
    targets: [N] int class ids in [0, V).

    Forward, a `lax.scan` over vocab chunks keeps online logsumexp
    statistics and the target's logit; it keeps the log-sum-exp and nothing
    else, and the backward pass makes each chunk's logits again and takes
    the softmax straight from the kept log-sum-exp. That is a custom VJP
    because autodiff through the checkpointed scan differentiates the
    running maximum and the rescaled sums instead, and feeds float32
    cotangents to the two backward products: at 32,768 rows against 49,152
    columns on a v5e it took 211 ms where this takes 188 (PERF.md section 6,
    PR 32), for 0.26 GB more of temporaries (the float32 ``dh`` carry)."""
    return _token_nll(h, kernel, targets.astype(jnp.int32), int(vocab_chunk))


@jax.named_scope("chunked_ce")
def chunked_softmax_xent(h, kernel, targets, vocab_chunk: int = 16384):
    """Mean softmax cross-entropy of ``h @ kernel`` against ``targets``,
    without materializing the full [N, V] logits: the mean of
    `chunked_token_nll`.

    Numerically equivalent to
    ``-mean(log_softmax((h @ kernel).astype(f32))[i, targets[i]])``.
    """
    return jnp.mean(chunked_token_nll(h, kernel, targets, vocab_chunk))


def chunked_next_token_loss(hidden, kernel, tokens, vocab_chunk: int = 16384):
    """Causal-LM next-token loss from PRE-head activations.

    hidden: [B, S, H] final-norm outputs (`Llama(..., return_hidden=True)`
    yields exactly this plus the head kernel); kernel: [H, V]; tokens:
    [B, S]. Matches ``next_token_loss(hidden @ kernel, tokens)`` with
    O(B·S·vocab_chunk) instead of O(B·S·V) peak logits memory::

        trainer = Trainer(model, tx,
            lambda out, batch: chunked_next_token_loss(
                out[0], out[1], batch["tokens"]),
            mesh, strategy="fsdp",
            train_kwargs={"return_hidden": True})
    """
    B, S, H = hidden.shape
    h = hidden[:, :-1, :].reshape(-1, H)
    targets = tokens[:, 1:].reshape(-1)
    return chunked_softmax_xent(h, kernel, targets, vocab_chunk)


@jax.named_scope("weighted_ce")
def weighted_token_xent(logits, targets, weights):
    """``sum_i weights[i] * CE(logits[i], targets[i])``: the masked-token
    loss of a diffusion language model, whose weights carry everything the
    objective says beside the cross-entropy (which positions are masked, the
    1/t of the noise level, the normaliser), so that a position with weight
    0 counts nothing whatever its logits are.

    logits: [..., V] (any float dtype; the softmax is float32); targets:
    [...] int ids in [0, V); weights: [...] float."""
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(
        logits, targets.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - picked
    return jnp.sum(weights.astype(jnp.float32) * nll)
