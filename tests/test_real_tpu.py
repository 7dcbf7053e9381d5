"""Real-chip-gated tests of chip pinning, for a TPU VM.

The whole suite runs on virtual CPU devices (conftest sets
JAX_PLATFORMS=cpu), so these tests gate on an explicit opt-in. On a TPU VM::

    MAGGY_TPU_REAL_CHIP=1 python -m pytest tests/test_real_tpu.py -q

`python chip_smoke.py` (phase ``tpu``) checks the same pinning end to end on
every chip run; the virtual-device equivalents (same code paths, pinning
asserted through `TPU_VISIBLE_CHIPS` markers) run in every CI pass:
`tests/test_experiment.py::TestVirtualChipPinning` and
`TestElasticChipLeasing`.
"""

import os
import subprocess
import sys

import pytest

pytestmark = [
    pytest.mark.slow,
    pytest.mark.skipif(
        os.environ.get("MAGGY_TPU_REAL_CHIP") != "1",
        reason="real-chip tests need MAGGY_TPU_REAL_CHIP=1 on a TPU VM"),
]

_CHILD = """\
import os, sys
import jax
ds = jax.local_devices()
sys.stdout.write("{} {} {}".format(
    os.environ.get("TPU_VISIBLE_CHIPS", ""), len(ds), ds[0].platform))
"""


class TestRealChipPinning:
    def test_pinned_child_sees_exactly_its_chip(self):
        """A child spawned with the pool's pinning env must see ONE chip,
        and it must be the pinned one."""
        from maggy_tpu.core.runner_pool import chip_env

        env = dict(os.environ)
        env.update(chip_env(0, chips_per_trial=1))
        env.pop("JAX_PLATFORMS", None)
        out = subprocess.run(
            [sys.executable, "-c", _CHILD], env=env, timeout=300,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL).stdout.decode()
        visible, n_devices, platform = out.split()
        assert visible == "0"
        assert platform == "tpu"
        # One pinned chip -> its local devices only (1 on v4/v5e, 2 cores
        # on v2/v3); never the whole host inventory beyond one chip.
        assert int(n_devices) in (1, 2), out

    def test_overcommitted_pool_degrades_loudly(self):
        """2 one-chip workers on a 1-chip host must be a clear ValueError
        at pool construction, not a libtpu crash at runtime."""
        from maggy_tpu.core.runner_pool import (TPURunnerPool,
                                                _probe_local_devices)

        chips, _ = _probe_local_devices(timeout_s=300)
        with pytest.raises(ValueError, match="exceeds"):
            TPURunnerPool(num_workers=chips + 1, chips_per_trial=1,
                          total_chips=chips)
