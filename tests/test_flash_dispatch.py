"""Attention dispatch: which path `multi_head_attention` takes is decided
by the backend, the shapes and the MAGGY_TPU_NO_FLASH kill switch alone.
On a TPU backend, shapes that tile take the Pallas kernel compiled (never
interpreted), and a kernel that fails to build is an error, not a silent
switch to the XLA reference (tests/test_flash_compile.py holds the compile
guarantee)."""

import jax.numpy as jnp
import numpy as np
import pytest

import maggy_tpu.ops.attention as att


def _qkv():
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.float32)
                 for _ in range(3))


class TestDispatch:
    def test_kill_switch_forces_reference(self, monkeypatch):
        monkeypatch.setenv("MAGGY_TPU_NO_FLASH", "1")
        monkeypatch.setattr(att, "_tpu_backend", lambda: True)
        called = {"flash": False}
        monkeypatch.setattr(
            att, "flash_attention_planned",
            lambda *a, **k: called.__setitem__("flash", True))
        q, k, v = _qkv()
        out = att.multi_head_attention(q, k, v, causal=True)
        assert not called["flash"]
        ref = att.attention_reference(q, k, v, causal=True)
        assert float(jnp.abs(out - ref).max()) < 1e-6

    def test_tpu_backend_takes_the_kernel_compiled(self, monkeypatch):
        monkeypatch.setattr(att, "_tpu_backend", lambda: True)
        seen = {}

        def stub(q, k, v, mask, causal, plan, interpret):
            seen["interpret"] = interpret
            seen["plan"] = plan
            return att.attention_reference(q, k, v, causal=causal)

        monkeypatch.setattr(att, "flash_attention_planned", stub)
        att.multi_head_attention(*_qkv(), causal=True)
        # Compiled, at the tiles the shape's plan gives.
        assert seen == {"interpret": False,
                        "plan": att.tile_plan(128, 128, 128, 2, 2, 4, True,
                                              False)}

    def test_kernel_build_failure_is_an_error(self, monkeypatch):
        monkeypatch.setattr(att, "_tpu_backend", lambda: True)

        def boom(*a, **k):
            raise RuntimeError("Mosaic lowering failed")

        monkeypatch.setattr(att, "flash_attention_planned", boom)
        with pytest.raises(RuntimeError, match="Mosaic lowering failed"):
            att.multi_head_attention(*_qkv(), causal=True)

    def test_shapes_that_do_not_tile_take_the_reference(self, monkeypatch):
        monkeypatch.setattr(att, "_tpu_backend", lambda: True)
        monkeypatch.setattr(
            att, "flash_attention_planned",
            lambda *a, **k: pytest.fail("kernel called for S=96"))
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.normal(size=(1, 96, 2, 128)), jnp.float32)
                   for _ in range(3))
        out = att.multi_head_attention(q, k, v, causal=True)
        ref = att.attention_reference(q, k, v, causal=True)
        assert float(jnp.abs(out - ref).max()) < 1e-6

    def test_force_flash_interprets_off_tpu(self):
        """force='flash' on the CPU backend runs the kernel in interpret
        mode (how the algorithm is tested without a chip)."""
        q, k, v = _qkv()
        out = att.multi_head_attention(q, k, v, causal=True, force="flash")
        ref = att.attention_reference(q, k, v, causal=True)
        assert float(jnp.abs(out - ref).max()) < 1e-4
