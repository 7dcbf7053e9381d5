"""`ops.losses.chunked_token_nll`: the per-row negative log-likelihood from
pre-head states and the head's kernel, never forming ``[N, V]``, against
dense `log_softmax`, values and both gradients, at a ragged last chunk; and
`chunked_softmax_xent`, now its mean, bit for bit what it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.ops.losses import chunked_softmax_xent, chunked_token_nll

N, H, V = 14, 8, 50


def _setup(dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(N, H)), dtype)
    W = jnp.asarray(rng.normal(size=(H, V)), jnp.float32)
    # Targets straddling every chunk, first and last class among them.
    t = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)
    t = t.at[0].set(0).at[1].set(V - 1)
    w = jnp.asarray(rng.uniform(0.1, 2.0, size=(N,)), jnp.float32)
    return h, W, t, w


def _dense_nll(h, W, t):
    logits = jnp.dot(h, W.astype(h.dtype), preferred_element_type=jnp.float32)
    return -jnp.take_along_axis(jax.nn.log_softmax(logits), t[:, None],
                                axis=1)[:, 0]


def _xent_as_it_was(h, kernel, targets, vocab_chunk=16384):
    """`chunked_softmax_xent` as PR 31 left it, kept here so that its
    results can be held bit for bit."""
    n, _ = h.shape
    v = kernel.shape[1]
    vocab_chunk = int(min(vocab_chunk, v))
    num_chunks = -(-v // vocab_chunk)
    col = jnp.arange(vocab_chunk)
    tgt = targets.astype(jnp.int32)

    def body(carry, c0):
        m, s, t = carry
        cs = jnp.minimum(c0, v - vocab_chunk)
        wk = jax.lax.dynamic_slice_in_dim(kernel, cs, vocab_chunk, axis=1)
        logits = jnp.dot(h, wk.astype(h.dtype),
                         preferred_element_type=jnp.float32)
        gcol = cs + col
        owned = (gcol >= c0) & (gcol < v)
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

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    starts = jnp.arange(num_chunks, dtype=jnp.int32) * vocab_chunk
    (m, s, t), _ = jax.lax.scan(jax.checkpoint(body), init, starts)
    return jnp.mean(m + jnp.log(s) - t)


@pytest.mark.parametrize("chunk", [7, 16, 50, 64])
def test_each_rows_likelihood_matches_dense_log_softmax(chunk):
    """7 and 16 leave a ragged last chunk (50 = 7 x 7 + 1 = 3 x 16 + 2)."""
    h, W, t, _ = _setup()
    got = chunked_token_nll(h, W, t, chunk)
    assert got.shape == (N,) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _dense_nll(h, W, t), atol=5e-6)


@pytest.mark.parametrize("chunk", [7, 16, 64])
def test_gradients_of_a_weighted_sum_match_dense(chunk):
    """Each row's cotangent is its own: a loss that weighs rows differently
    reaches ``h`` and the kernel as the dense form's does."""
    h, W, t, w = _setup()
    dense = jax.grad(lambda h, W: jnp.sum(w * _dense_nll(h, W, t)), (0, 1))(
        h, W)
    got = jax.grad(lambda h, W: jnp.sum(
        w * chunked_token_nll(h, W, t, chunk)), (0, 1))(h, W)
    for a, b in zip(dense, got):
        assert a.shape == b.shape
        np.testing.assert_allclose(b, a, atol=2e-6)


def test_bfloat16_states_keep_a_float32_kernel_gradient():
    h, W, t, w = _setup(jnp.bfloat16)
    dh, dW = jax.grad(lambda h, W: jnp.sum(
        w * chunked_token_nll(h, W, t, 16)), (0, 1))(h, W)
    assert dh.dtype == jnp.bfloat16 and dW.dtype == jnp.float32
    ref_dh, ref_dW = jax.grad(lambda h, W: jnp.sum(
        w * _dense_nll(h, W, t)), (0, 1))(h, W)
    np.testing.assert_allclose(dh.astype(jnp.float32),
                               ref_dh.astype(jnp.float32), rtol=0.02,
                               atol=0.02)
    np.testing.assert_allclose(dW, ref_dW, rtol=0.02, atol=0.02)


def test_neither_pass_forms_the_whole_logits():
    """No value of the forward or the backward program has a row of V
    columns beside the kernel and its gradient ([H, V])."""
    h, W, t, w = _setup()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda h, W: jnp.sum(w * chunked_token_nll(h, W, t, 16)), (0, 1)))(
            h, W)

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield tuple(getattr(var.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    assert (N, 16) in set(shapes(jaxpr.jaxpr))
    assert not [s for s in shapes(jaxpr.jaxpr) if s[:1] == (N,) and V in s]


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_mean_is_bit_for_bit_what_it_was(chunk, dtype, jitted):
    h, W, t, _ = _setup(dtype, seed=chunk)
    now, was = chunked_softmax_xent, _xent_as_it_was
    if jitted:
        now, was = (jax.jit(f, static_argnums=3) for f in (now, was))
    assert float(now(h, W, t, chunk)) == float(was(h, W, t, chunk))


def test_the_means_gradients_are_what_they_were_to_rounding():
    h, W, t, _ = _setup()
    now = jax.grad(chunked_softmax_xent, (0, 1))(h, W, t, 16)
    was = jax.grad(_xent_as_it_was, (0, 1))(h, W, t, 16)
    for a, b in zip(now, was):
        np.testing.assert_allclose(a, b, atol=1e-6)
