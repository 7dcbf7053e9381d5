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
the classifier head), and `jax.checkpoint` on the scan body re-derives each
chunk's logits in the backward instead of stashing them. Peak logits
memory drops from O(N·V) to O(N·chunk) in both passes; the matmuls stay
MXU-shaped ([N,H] x [H,chunk], fp32 accumulation).

Sharding: designed for dp/fsdp meshes (vocab replicated, embed sharded —
the flagship layout). Under tp the head's vocab dim is sharded over
"model"; prefer the dense path there (XLA's all-gather per chunk would
serialize the ring).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("chunked_ce")
def chunked_softmax_xent(h, kernel, targets, vocab_chunk: int = 16384):
    """Mean softmax cross-entropy of ``h @ kernel`` against ``targets``,
    without materializing the full [N, V] logits.

    h: [N, H] activations (any float dtype; products accumulate fp32).
    kernel: [H, V] classifier weights.
    targets: [N] int class ids in [0, V).

    Numerically equivalent to
    ``-mean(log_softmax((h @ kernel).astype(f32))[i, targets[i]])``.
    """
    N, H = h.shape
    V = kernel.shape[1]
    vocab_chunk = int(min(vocab_chunk, V))
    num_chunks = -(-V // vocab_chunk)
    col = jnp.arange(vocab_chunk)
    tgt = targets.astype(jnp.int32)

    def body(carry, c0):
        m, s, t = carry
        # The final ragged chunk slides its START back (dynamic_slice-style
        # clamp) rather than padding the kernel — jnp.pad would materialize
        # a second full-size [H, V'] copy of the head, defeating the HBM
        # point. Masking below keeps each column counted exactly once: the
        # chunk OWNS global columns [c0, c0+chunk) ∩ [0, V).
        cs = jnp.minimum(c0, V - vocab_chunk)
        Wk = jax.lax.dynamic_slice_in_dim(kernel, cs, vocab_chunk, axis=1)
        # bf16 MXU matmul with fp32 accumulation — same numerics contract
        # as the dense head (llama.py casts the head to activation dtype).
        logits = jnp.dot(h, Wk.astype(h.dtype),
                         preferred_element_type=jnp.float32)
        gcol = cs + col  # global column index of each slice column
        owned = (gcol >= c0) & (gcol < V)
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
    starts = jnp.arange(num_chunks, dtype=jnp.int32) * vocab_chunk
    # checkpoint: the backward re-derives each chunk's logits instead of
    # keeping num_chunks * [N, chunk] residuals alive.
    (m, s, t), _ = jax.lax.scan(jax.checkpoint(body), init, starts)
    return jnp.mean(m + jnp.log(s) - t)


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
