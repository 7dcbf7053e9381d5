"""`ops.ssd`: the chunked state-space scan, as XLA products and as the
Pallas kernels (interpret mode), against the recurrence itself (position by
position) and against the quadratic masked form: three forms, one answer,
forward and every gradient; which of the two multiplies where; and the
causal depthwise convolution against a shifted sum."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.ops import ssd

B = 2
DIMS = (4, 8, 2, 16)  # H, P, G, N: toy widths, which only XLA's products take
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


def inputs(chunks: int, chunk: int, seed: int = 0, dims=DIMS):
    H, P, G, N = dims
    S = chunks * chunk
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, S, H)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (B, S, G, N)),
            jax.random.normal(k[4], (B, S, G, N)),
            jax.random.normal(k[5], (H,)))


def chunked(chunk: int, step):
    """The chunked form: XLA's products (``step`` None, what `ssd_scan` runs
    off a TPU), or the kernels with ``step`` chunks a grid step."""
    if step is None:
        return functools.partial(ssd.ssd_scan, chunk=chunk)
    return lambda *a: ssd.kernel_scan(*a, chunk, step, True)


@functools.lru_cache(maxsize=None)
def three_forms(chunks: int, chunk: int, dims=DIMS, step=None):
    """(value, gradients of a scalar of it) of each form."""
    args = inputs(chunks, chunk, dims=dims)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, form in (
                ("chunked", chunked(chunk, step)),
                ("recurrence", ssd.ssd_reference), ("quadratic", quadratic)):
            out[name] = jax.value_and_grad(
                lambda *a, form=form: jnp.sum(form(*a) * w),
                argnums=tuple(range(6)))(*args) + (form(*args),)
    return out


def close(got, want, tol=2e-5):
    assert float(jnp.abs(got - want).max()) \
        <= tol * max(float(jnp.abs(want).max()), 1e-30)


# chunks, chunk, (H, P, G, N), chunks a grid step. XLA's products at S of 3
# and of 8 chunks; then shapes the kernels tile, in interpret mode: two heads
# a 128-lane slab, a grid step of two chunks, four heads a slab and an odd
# count of chunks, a head a slab, one group and four chunks a step.
SHAPES = [(chunks, chunk, DIMS, None)
          for chunks, chunk in [(3, 8), (8, 8), (3, 16), (8, 16)]] + [
    (2, 128, (4, 64, 2, 128), 1), (4, 128, (4, 64, 2, 128), 2),
    (3, 128, (8, 32, 2, 128), 1), (2, 128, (4, 128, 2, 128), 2),
    (4, 128, (2, 64, 1, 128), 4)]


def shape_id(shape):
    chunks, chunk, (H, P, G, N), step = shape
    return "{}x{}_h{}x{}_g{}_n{}_{}".format(
        chunks, chunk, H, P, G, N,
        "xla" if step is None else "kernels{}".format(step))


@pytest.mark.parametrize("other", ["recurrence", "quadratic"])
@pytest.mark.parametrize("shape", SHAPES, ids=shape_id)
def test_the_chunked_scan_is_the_other_forms_forward(shape, other):
    forms = three_forms(*shape)
    chunks, chunk, (H, P, _, _), _ = shape
    assert forms["chunked"][2].shape == (B, chunks * chunk, H, P)
    # At S of 512 the quadratic form's own float32 sums are 2e-5 from the
    # recurrence; the kernels stay within 4e-6 of it.
    close(forms["chunked"][2], forms[other][2],
          2e-5 if chunks * chunk < 256 else 5e-5)


@pytest.mark.parametrize("arg", range(6), ids=ARGS)
@pytest.mark.parametrize("shape", SHAPES, ids=shape_id)
def test_every_gradient_of_the_chunked_scan_is_the_recurrences(shape, arg):
    forms = three_forms(*shape)
    close(forms["chunked"][1][arg], forms["recurrence"][1][arg], 1e-4)
    close(forms["quadratic"][1][arg], forms["recurrence"][1][arg], 1e-4)


@pytest.mark.parametrize("shape", [SHAPES[0], SHAPES[5]], ids=shape_id)
def test_bfloat16_operands_stay_near_the_float32_scan(shape):
    """What the model runs: x, B and C in bfloat16, the decays in float32."""
    chunks, chunk, dims, step = shape
    x, dt, A, Bm, Cm, D = inputs(chunks, chunk, seed=3, dims=dims)
    want = ssd.ssd_reference(x, dt, A, Bm, Cm, D)
    bf = jnp.bfloat16
    got = chunked(chunk, step)(x.astype(bf), dt, A, Bm.astype(bf),
                               Cm.astype(bf), D)
    assert got.dtype == bf
    close(got.astype(jnp.float32), want, 3e-2)


@pytest.mark.parametrize("shape", [SHAPES[0], SHAPES[5]], ids=shape_id)
def test_bfloat16_gradients_stay_near_the_float32_scans(shape):
    """... and every gradient, the cotangent in bfloat16 too."""
    chunks, chunk, dims, step = shape
    args = inputs(chunks, chunk, seed=3, dims=dims)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    bf = jnp.bfloat16
    low = tuple(a.astype(bf) if i in (0, 3, 4) else a
                for i, a in enumerate(args))
    want = jax.grad(lambda *a: jnp.sum(ssd.ssd_reference(*a) * w),
                    argnums=tuple(range(6)))(*args)
    got = jax.grad(
        lambda *a: jnp.sum(chunked(chunk, step)(*a).astype(jnp.float32) * w),
        argnums=tuple(range(6)))(*low)
    for g, w_ in zip(got, want):
        assert g.dtype == jnp.float32 or g.dtype == bf
        assert float(jnp.linalg.norm(g.astype(jnp.float32) - w_)) \
            <= 3e-2 * float(jnp.linalg.norm(w_))


TILING = (8192, 64, 64, 8, 128, 128)  # S, H, P, G, N, chunk: the cell's
NOT_TILING = [(32, 4, 8, 2, 16, 8),   # `NemotronHConfig.tiny`
              (8192, 64, 64, 8, 64, 128),    # a state of half a tile
              (8192, 64, 64, 8, 128, 64),    # a chunk of half a tile
              (8192, 8, 48, 8, 128, 128)]    # heads that fill no slab


def test_off_a_tpu_the_scan_is_xlas_products(monkeypatch):
    """The path rides on the backend and the shapes: here (a CPU) every
    shape takes XLA's products and says so; on a TPU the shapes that tile
    take the kernels, the others still the products."""
    assert ssd.scan_plan(*TILING) == "xla_products"
    assert ssd.chunks_a_step(*TILING) == 8
    args = inputs(4, 128, dims=(4, 64, 2, 128))
    text = str(jax.make_jaxpr(functools.partial(ssd.ssd_scan, chunk=128))(
        *args))
    assert "pallas_call" not in text and "custom_vjp" not in text
    assert (ssd.ssd_scan(*args) == ssd._xla_scan(*args, chunk=128)).all()

    monkeypatch.setattr(ssd, "_tpu_backend", lambda: True)
    assert ssd.scan_plan(*TILING) == "pallas 1024"
    assert ssd.scan_plan(3 * 128, 64, 64, 8, 128, 128) == "pallas 128"
    text = str(jax.make_jaxpr(functools.partial(ssd.ssd_scan, chunk=128))(
        *args))
    assert "pallas_call" in text and "ssd_fwd" in text
    tiny = inputs(4, 8)
    text = str(jax.make_jaxpr(functools.partial(ssd.ssd_scan, chunk=8))(
        *tiny))
    assert "pallas_call" not in text


@pytest.mark.parametrize("shape", NOT_TILING, ids=str)
def test_shapes_the_kernels_cannot_tile_take_xlas_products(shape, monkeypatch):
    monkeypatch.setattr(ssd, "_tpu_backend", lambda: True)
    assert ssd.chunks_a_step(*shape) is None
    assert ssd.scan_plan(*shape) == "xla_products"


def test_the_scan_takes_whole_chunks_and_whole_groups():
    x, dt, A, Bm, Cm, D = inputs(3, 8)
    with pytest.raises(ValueError, match="whole chunks"):
        ssd.ssd_scan(x, dt, A, Bm, Cm, D, chunk=16)
    with pytest.raises(ValueError, match="whole groups"):
        ssd.ssd_scan(x, dt, A, Bm[:, :, :1].repeat(3, 2), Cm[:, :, :1].repeat(
            3, 2), D, chunk=8)
    assert ssd.chunks_a_step(3 * 128, 64, 64, 8, 128, 256) is None
    assert ssd.chunks_a_step(8192, 64, 64, 6, 128, 128) is None


@pytest.mark.parametrize("shape", [(6, 8, DIMS, None),
                                   (4, 128, (4, 64, 2, 128), 2)],
                         ids=shape_id)
def test_the_backward_pass_keeps_inputs_and_no_per_position_state(shape):
    """The gradient's jaxpr holds no value of a position's [P, N] state
    for every position, and nothing of [S, S]; the kernels' rule keeps the
    op's inputs and nothing else."""
    chunks, chunk, dims, step = shape
    H, P, G, N = dims
    S = chunk * chunks
    args = inputs(chunks, chunk, dims=dims)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(chunked(chunk, step)(*a)),
        argnums=(0, 1, 3, 4)))(*args)
    if step is not None:
        _, kept = ssd._kernel_scan_fwd(*args, chunk, step, True)
        assert all(k is a for k, a in zip(kept, args)) and len(kept) == 6

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


# The convolution kernels (interpret mode) against `causal_conv1d`, its silu
# and the cast: x [B, S, width] holds the C channels from column ``start``.
# S, width, start, C, rows, cols: three blocks of four strips, at column 128
# of a wider array and two column blocks; two blocks of two strips reading
# the whole array; four blocks of one strip each.
CONV_SHAPES = [(192, 640, 128, 256, 64, 128), (64, 256, 0, 256, 32, 256),
               (64, 384, 256, 128, 16, 128)]


def conv_inputs(S, width, C, dtype=jnp.bfloat16, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], (B, S, width)).astype(dtype),
            jax.random.uniform(k[1], (4, C), minval=-0.5, maxval=0.5),
            0.1 * jax.random.normal(k[2], (C,)),
            jax.random.normal(k[3], (B, S, C)).astype(dtype))


def conv_forms(start, rows, cols):
    def xla(x, w, b):
        return jax.nn.silu(ssd.causal_conv1d(
            x[..., start:start + w.shape[1]], w, b)).astype(x.dtype)

    return xla, lambda x, w, b: ssd.kernel_conv(x, w, b, start, rows, cols,
                                                True)


def conv_id(shape):
    return "S{}_w{}_at{}_c{}_{}x{}".format(*shape)


def within_a_rounding(got, want):
    """Each entry within one bfloat16 rounding of the other form's."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    assert got.dtype == want.dtype
    assert bool((jnp.abs(got - want)
                 <= 2.0 ** -7 * jnp.abs(want) + 1e-6).all())


@pytest.mark.parametrize("shape", CONV_SHAPES, ids=conv_id)
def test_the_convolution_kernel_is_the_xla_forms_forward(shape):
    S, width, start, C, rows, cols = shape
    x, w, b, _ = conv_inputs(S, width, C)
    xla, kernel = conv_forms(start, rows, cols)
    got = kernel(x, w, b)
    assert got.shape == (B, S, C) and got.dtype == jnp.bfloat16
    within_a_rounding(got, xla(x, w, b))


@pytest.mark.parametrize("shape", CONV_SHAPES, ids=conv_id)
def test_every_gradient_of_the_convolution_kernel_is_the_xla_forms(shape):
    """dx (in x's dtype, the wider array's width, zero outside the
    columns), dw and db (float32) against autodiff of the XLA form."""
    S, width, start, C, rows, cols = shape
    x, w, b, dy = conv_inputs(S, width, C)
    xla, kernel = conv_forms(start, rows, cols)
    want = jax.vjp(xla, x, w, b)[1](dy)
    got = jax.vjp(kernel, x, w, b)[1](dy)
    assert got[0].shape == x.shape and got[0].dtype == x.dtype
    outside = np.ones(width, bool)
    outside[start:start + C] = False
    assert not np.asarray(got[0])[..., outside].any()
    within_a_rounding(got[0][..., start:start + C],
                      want[0][..., start:start + C])
    for g, w_ in zip(got[1:], want[1:]):
        assert g.dtype == jnp.float32
        close(g, w_, 1e-5)


@pytest.mark.parametrize("t", [15, 16, 31, 32])
def test_the_convolution_kernel_never_sees_the_next_position(t):
    """Position t's output moves with x_{t-3} .. x_t and with nothing else,
    of its own batch row: t beside the edge of a block of 16 rows. And
    dy_t's gradient reaches x_{t-3} .. x_t alone."""
    S, width, start, C, rows, cols = 48, 256, 128, 128, 16, 128
    x, w, b, _ = conv_inputs(S, width, C, jnp.float32)
    _, kernel = conv_forms(start, rows, cols)
    y = kernel(x, w, b)
    for moved, seen in [(t + 1, False), (t, True), (t - 3, True),
                        (t - 4, False)]:
        if not 0 <= moved < S:
            continue
        y2 = kernel(x.at[:, moved].add(1.0), w, b)
        assert bool((y2[:, t] != y[:, t]).any()) == seen, moved
    other_row = kernel(x.at[1].add(1.0), w, b)
    assert bool((other_row[0] == y[0]).all())
    dy = jnp.zeros((B, S, C)).at[0, t].set(1.0)
    dx = np.asarray(jax.vjp(kernel, x, w, b)[1](dy)[0])
    reached = np.abs(dx).sum(axis=2) > 0                      # [B, S]
    assert not reached[1].any()
    assert reached[0, max(t - 3, 0):t + 1].all()
    assert not reached[0, :max(t - 3, 0)].any()
    assert not reached[0, t + 1:].any()


def test_the_convolution_kernels_keep_their_inputs_alone():
    x, w, b, _ = conv_inputs(64, 384, 128)
    _, kept = ssd._kernel_conv_fwd(x, w, b, 256, 16, 128, True)
    assert len(kept) == 3 and all(k is a for k, a in zip(kept, (x, w, b)))


CONV_TILING = (8192, 6144, 4096)  # S, C, start: the cell's xBC in zxbcdt
CONV_NOT_TILING = [(8192, 6144 + 64, 4096),  # channels of half a tile
                   (8192, 6144, 4096 + 64),  # a start inside a tile
                   (8200, 6144, 4096),       # a length of no whole strips
                   (16, 96, 32)]             # `NemotronHConfig.tiny`


def test_off_a_tpu_the_convolution_is_xlas(monkeypatch):
    """The path rides on the backend and the shapes, as the scan's: here
    every shape takes XLA's form and says ``conv xla``; on a TPU, the
    cell's shape takes the kernels, a block of 512 rows by 1024 columns."""
    assert ssd.conv_plan(*CONV_TILING) == "conv xla"
    assert ssd.conv_tile(*CONV_TILING) == (512, 1024)
    x, w, b, _ = conv_inputs(64, 384, 128)
    text = str(jax.make_jaxpr(functools.partial(ssd.conv_silu, start=256))(
        x, w, b))
    assert "pallas_call" not in text and "custom_vjp" not in text
    xla, _ = conv_forms(256, 16, 128)
    assert bool((ssd.conv_silu(x, w, b, 256) == xla(x, w, b)).all())

    monkeypatch.setattr(ssd, "_tpu_backend", lambda: True)
    assert ssd.conv_plan(*CONV_TILING) == "conv pallas 512x1024"
    assert ssd.conv_plan(8192, 1024, 512) == "conv pallas 512x512"
    text = str(jax.make_jaxpr(functools.partial(ssd.conv_silu, start=256))(
        x, w, b))
    assert "pallas_call" in text and "ssd_conv_fwd" in text


@pytest.mark.parametrize("shape", CONV_NOT_TILING, ids=str)
def test_shapes_the_convolution_kernels_cannot_tile_take_xlas_form(
        shape, monkeypatch):
    monkeypatch.setattr(ssd, "_tpu_backend", lambda: True)
    assert ssd.conv_tile(*shape) is None
    assert ssd.conv_plan(*shape) == "conv xla"


def test_passes_of_fewer_lanes_give_the_kernels_answer(monkeypatch):
    """A strip's columns taken in passes of 128 lanes (four a block):
    forward and every gradient as the XLA form's. A shape of its own, so
    that no kernel traced at the default passes is reused."""
    monkeypatch.setattr(ssd, "_PASS_LANES", 128)
    S, width, start, C, rows, cols = 96, 1280, 512, 512, 32, 512
    x, w, b, dy = conv_inputs(S, width, C, seed=5)
    xla, kernel = conv_forms(start, rows, cols)
    within_a_rounding(kernel(x, w, b), xla(x, w, b))
    want = jax.vjp(xla, x, w, b)[1](dy)
    got = jax.vjp(kernel, x, w, b)[1](dy)
    within_a_rounding(got[0], want[0])
    for g, w_ in zip(got[1:], want[1:]):
        close(g, w_, 1e-5)
