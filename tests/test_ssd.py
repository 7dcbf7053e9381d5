"""`ops.ssd`: the chunked state-space scan against the recurrence itself
(position by position) and against the quadratic masked form: three forms,
one answer, forward and every gradient; and the causal depthwise
convolution against a shifted sum."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.ops import ssd

B, H, P, G, N = 2, 4, 8, 2, 16
ARGS = ("x", "dt", "A", "B", "C", "D")


def quadratic(x, dt, A, Bm, Cm, D):
    """``y_l = sum_{s <= l} exp(cum_l - cum_s) dt_s (C_l . B_s) x_s + D
    x_l``: one masked [S, S] product a head, no state at all."""
    S = x.shape[1]
    rep = x.shape[2] // Bm.shape[2]
    Bh, Ch = jnp.repeat(Bm, rep, axis=2), jnp.repeat(Cm, rep, axis=2)
    cum = jnp.cumsum(dt * A, axis=1)                        # [B, S, H]
    seg = cum[:, :, None, :] - cum[:, None, :, :]           # [B, l, s, H]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    weights = jnp.einsum("blhn,bshn->blsh", Ch, Bh) \
        * jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0) \
        * dt[:, None, :, :]
    return jnp.einsum("blsh,bshp->blhp", weights, x) + D[:, None] * x


def inputs(chunks: int, chunk: int, seed: int = 0):
    S = chunks * chunk
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, S, H)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (B, S, G, N)),
            jax.random.normal(k[4], (B, S, G, N)),
            jax.random.normal(k[5], (H,)))


@functools.lru_cache(maxsize=None)
def three_forms(chunks: int, chunk: int):
    """(value, gradients of a scalar of it) of each form."""
    args = inputs(chunks, chunk)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, form in (
                ("chunked", functools.partial(ssd.ssd_scan, chunk=chunk)),
                ("recurrence", ssd.ssd_reference), ("quadratic", quadratic)):
            out[name] = jax.value_and_grad(
                lambda *a, form=form: jnp.sum(form(*a) * w),
                argnums=tuple(range(6)))(*args) + (form(*args),)
    return out


def close(got, want, tol=2e-5):
    assert float(jnp.abs(got - want).max()) \
        <= tol * max(float(jnp.abs(want).max()), 1e-30)


SHAPES = [(3, 8), (8, 8), (3, 16), (8, 16)]  # S of 3 and of 8 chunks


@pytest.mark.parametrize("other", ["recurrence", "quadratic"])
@pytest.mark.parametrize("chunks,chunk", SHAPES)
def test_the_chunked_scan_is_the_other_forms_forward(chunks, chunk, other):
    forms = three_forms(chunks, chunk)
    assert forms["chunked"][2].shape == (B, chunks * chunk, H, P)
    close(forms["chunked"][2], forms[other][2])


@pytest.mark.parametrize("arg", range(6), ids=ARGS)
@pytest.mark.parametrize("chunks,chunk", SHAPES)
def test_every_gradient_of_the_chunked_scan_is_the_recurrences(chunks, chunk,
                                                               arg):
    forms = three_forms(chunks, chunk)
    close(forms["chunked"][1][arg], forms["recurrence"][1][arg], 1e-4)
    close(forms["quadratic"][1][arg], forms["recurrence"][1][arg], 1e-4)


def test_bfloat16_operands_stay_near_the_float32_scan():
    """What the model runs: x, B and C in bfloat16, the decays in float32."""
    x, dt, A, Bm, Cm, D = inputs(4, 8, seed=3)
    want = ssd.ssd_reference(x, dt, A, Bm, Cm, D)
    bf = jnp.bfloat16
    got = ssd.ssd_scan(x.astype(bf), dt, A, Bm.astype(bf), Cm.astype(bf), D,
                       chunk=8)
    assert got.dtype == bf
    close(got.astype(jnp.float32), want, 3e-2)


def test_the_scan_takes_whole_chunks_and_whole_groups():
    x, dt, A, Bm, Cm, D = inputs(3, 8)
    with pytest.raises(ValueError, match="whole chunks"):
        ssd.ssd_scan(x, dt, A, Bm, Cm, D, chunk=16)
    with pytest.raises(ValueError, match="whole groups"):
        ssd.ssd_scan(x, dt, A, Bm[:, :, :1].repeat(3, 2), Cm[:, :, :1].repeat(
            3, 2), D, chunk=8)


def test_the_backward_pass_keeps_inputs_and_no_per_position_state():
    """The gradient's jaxpr holds no value of a position's [P, N] state
    for every position, and nothing of [S, S]."""
    chunk, chunks = 8, 6
    S = chunk * chunks
    args = inputs(chunks, chunk)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=chunk)),
        argnums=(0, 1, 3, 4)))(*args)

    def sizes(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                yield int(np.prod(v.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    biggest = max(sizes(jaxpr.jaxpr))
    assert biggest < B * S * (H // G) * P * N          # per-position states
    assert biggest < B * (H // G) * S * S              # a dense [S, S]


@pytest.mark.parametrize("taps", [2, 4])
def test_the_convolution_is_a_shifted_sum(taps):
    rng = np.random.default_rng(taps)
    x = rng.normal(size=(2, 10, 6)).astype(np.float32)
    w = rng.normal(size=(taps, 6)).astype(np.float32)
    b = rng.normal(size=(6,)).astype(np.float32)
    want = np.zeros_like(x) + b
    for t in range(10):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                want[:, t] += w[j] * x[:, t - (taps - 1) + j]
    got = ssd.causal_conv1d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", [0, 4, 8])
def test_the_convolution_never_sees_the_next_position(t):
    """Position t's output moves with x_t and with nothing after it."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 10, 3)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)
    jac = jax.jacobian(
        lambda x: ssd.causal_conv1d(x, w, jnp.zeros(3))[0, t])(x)
    seen = np.abs(np.asarray(jac)[:, 0]).sum(axis=(0, 2)) > 0     # [S]
    assert seen[t] and not seen[t + 1:].any()
    assert not seen[:max(t - 3, 0)].any()
