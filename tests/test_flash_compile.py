"""The Pallas flash kernels must COMPILE for the TPU at every shape class
auto-dispatch can reach. `multi_head_attention` has no fallback for a kernel
that fails to build (a compile error on the chip is an error), so the
guarantee lives here: libtpu compiles for a v5e without a chip, against a
topology description, through the real Mosaic/XLA:TPU compiler. Interpret
mode (tests/test_models.py) proves the algorithm; this proves the build.
"""

import jax
import jax.numpy as jnp
import pytest

from maggy_tpu.ops.attention import flash_attention


@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu, or one without AOT
        pytest.skip("libtpu cannot describe a v5e:2x2 topology: {!r}".format(e))
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "name,B,Sq,Sk,H,Hkv,D,causal,masked,dtype", [
        ("bert_d64_masked", 2, 128, 128, 12, 12, 64, False, True, jnp.bfloat16),
        ("llama_gqa_d128_causal", 1, 2048, 2048, 32, 8, 128, True, False,
         jnp.bfloat16),
        ("sq_ne_sk_causal", 2, 128, 512, 4, 4, 128, True, False, jnp.bfloat16),
        ("float32", 2, 256, 256, 4, 2, 128, True, True, jnp.float32),
        ("d72", 2, 128, 128, 4, 4, 72, False, False, jnp.bfloat16),
    ])
def test_forward_and_both_backward_kernels_compile(
        v5e_device, name, B, Sq, Sk, H, Hkv, D, causal, masked, dtype):
    q = jax.ShapeDtypeStruct((B, Sq, H, D), dtype, sharding=v5e_device)
    kv = jax.ShapeDtypeStruct((B, Sk, Hkv, D), dtype, sharding=v5e_device)
    keep = jax.ShapeDtypeStruct((B, Sk), jnp.bool_, sharding=v5e_device)

    def loss(q, k, v, keep):
        out = flash_attention(q, k, v, keep if masked else None, causal,
                              128, 128, False)  # compiled, never interpreted
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, kv, kv, keep).compile()
    # Forward, dK/dV and dQ: three Mosaic kernels in the executable.
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3
