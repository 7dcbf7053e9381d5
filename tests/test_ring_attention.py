"""Ring attention (sequence parallelism) tests on an 8-way seq mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from maggy_tpu.ops.attention import attention_reference
from maggy_tpu.parallel import make_mesh
from maggy_tpu.parallel.ring_attention import ring_attention

# Heavy module (e2e / sharded-compile tests): excluded from the fast lane
# (pytest -m 'not slow').
pytestmark = pytest.mark.slow


def qkv(B=2, S=64, H=2, D=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
                 for _ in range(3))


class TestRingAttention:
    def test_matches_reference_causal(self):
        mesh = make_mesh({"seq": 8})
        q, k, v = qkv()
        ref = attention_reference(q, k, v, causal=True)
        out = ring_attention(q, k, v, mesh, causal=True)
        assert float(jnp.abs(ref - out).max()) < 1e-5

    def test_matches_reference_full(self):
        mesh = make_mesh({"seq": 8})
        q, k, v = qkv(seed=1)
        ref = attention_reference(q, k, v, causal=False)
        out = ring_attention(q, k, v, mesh, causal=False)
        assert float(jnp.abs(ref - out).max()) < 1e-5

    def test_gradients_flow(self):
        mesh = make_mesh({"seq": 8})
        q, k, v = qkv(seed=2)
        g_ref = jax.grad(lambda q: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2))(q)
        g_ring = jax.grad(lambda q: jnp.sum(
            ring_attention(q, k, v, mesh, causal=True) ** 2))(q)
        assert float(jnp.abs(g_ref - g_ring).max()) < 1e-4

    def test_seq_not_divisible_raises(self):
        mesh = make_mesh({"seq": 8})
        q, k, v = qkv(S=60)
        with pytest.raises(ValueError, match="divide"):
            ring_attention(q, k, v, mesh)

    def test_composes_with_data_axis(self):
        """seq axis combined with a data axis: [data=2, seq=4] mesh."""
        mesh = make_mesh({"data": 2, "seq": 4})
        q, k, v = qkv(B=4, S=32, seed=3)
        ref = attention_reference(q, k, v, causal=True)
        out = ring_attention(q, k, v, mesh, causal=True)
        assert float(jnp.abs(ref - out).max()) < 1e-5

    def test_long_context_under_jit(self):
        """jit + seq-sharded inputs: the long-context training shape."""
        mesh = make_mesh({"seq": 8})
        q, k, v = qkv(B=1, S=512, H=2, D=32, seed=4)
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P(None, "seq", None, None))
        q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
        f = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=True))
        out = f(q, k, v)
        assert out.shape == (1, 512, 2, 32)
        ref = attention_reference(q, k, v, causal=True)
        assert float(jnp.abs(ref - out).max()) < 1e-5


class TestRingFlash:
    """Flash-within-ring: the Pallas kernel as the ring's inner block
    (interpret mode on the CPU mesh), with the ring-of-blocks custom VJP."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_ring_matches_reference(self, causal):
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = qkv(B=1, S=512, H=2, D=64, seed=5)
        ref = attention_reference(q, k, v, causal=causal)
        out = ring_attention(q, k, v, mesh, causal=causal, impl="flash",
                             interpret=True)
        assert float(jnp.abs(ref - out).max()) < 1e-4

    def test_flash_ring_gradients_match(self):
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = qkv(B=1, S=512, H=2, D=64, seed=6)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2), (0, 1, 2))(q, k, v)
        g_ring = jax.grad(lambda q, k, v: jnp.sum(ring_attention(
            q, k, v, mesh, causal=True, impl="flash", interpret=True) ** 2),
            (0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_ring):
            assert float(jnp.abs(a - b).max()) < 1e-3

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_ring_gqa_gradients_match(self, causal):
        """Every step of the ring's backward is the fused flash backward on
        a (Q shard, K/V shard) block, float32 partials summed around the
        ring: GQA gradients for q, k and v against the reference."""
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        rng = np.random.default_rng(9)
        B, S, H, Hkv, D = 1, 512, 4, 2, 64
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k, v = (jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
                for _ in range(2))
        g_ref = jax.grad(lambda q, k, v: jnp.sum(attention_reference(
            q, k, v, causal=causal) ** 2), (0, 1, 2))(q, k, v)
        g_ring = jax.grad(lambda q, k, v: jnp.sum(ring_attention(
            q, k, v, mesh, causal=causal, impl="flash", interpret=True) ** 2),
            (0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_ring):
            assert a.shape == b.shape
            assert float(jnp.abs(a - b).max()) < 1e-3

    def test_flash_ring_gqa(self):
        """GQA rides the ring without kv repetition: Hkv < H shards rotate
        and gradients (dk/dv summed over the head group) match."""
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        rng = np.random.default_rng(7)
        B, S, H, Hkv, D = 1, 512, 4, 2, 64
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
        ref = attention_reference(q, k, v, causal=True)
        out = ring_attention(q, k, v, mesh, causal=True, impl="flash",
                             interpret=True)
        assert float(jnp.abs(ref - out).max()) < 1e-4
        g_ref = jax.grad(lambda k, v: jnp.sum(
            attention_reference(q, k, v, causal=True) ** 2), (0, 1))(k, v)
        g_ring = jax.grad(lambda k, v: jnp.sum(ring_attention(
            q, k, v, mesh, causal=True, impl="flash", interpret=True) ** 2),
            (0, 1))(k, v)
        for a, b in zip(g_ref, g_ring):
            assert a.shape == b.shape
            assert float(jnp.abs(a - b).max()) < 1e-3

    def test_flash_ring_long_context_4k(self):
        """S=4096 over 8 shards (512/shard): the long-context shape the
        kernel advertises, forward-checked against the XLA reference."""
        mesh = make_mesh({"seq": 8})
        q, k, v = qkv(B=1, S=4096, H=1, D=64, seed=8)
        ref = attention_reference(q, k, v, causal=True)
        out = ring_attention(q, k, v, mesh, causal=True, impl="flash",
                             interpret=True)
        assert float(jnp.abs(ref - out).max()) < 1e-4

    def test_flash_requires_tiling(self):
        mesh = make_mesh({"seq": 8})
        q, k, v = qkv(B=1, S=64, H=2, D=16)
        with pytest.raises(ValueError, match="flash"):
            ring_attention(q, k, v, mesh, impl="flash", interpret=True)
