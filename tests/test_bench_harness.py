"""bench.py without a chip: the headline and the extras are device
measurements, so where JAX finds no TPU the run exits non-zero and prints no
metric — not a zero, not a CPU number under the chip metric's name. The
orchestrator stays off JAX and runs every measurement in a child process,
one after the other, because one process owns the chip at a time.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(*argv, tmp_path):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "MAGGY_TPU_BASE_DIR": str(tmp_path)})
    return subprocess.run(
        [sys.executable, "bench.py", *argv], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)


def test_bench_without_tpu_exits_nonzero_and_prints_no_metric(tmp_path):
    proc = _bench(tmp_path=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "needs a TPU" in proc.stderr


def test_extra_without_tpu_exits_nonzero_and_prints_no_metric(tmp_path):
    proc = _bench("--extra", "flash_vs_xla", tmp_path=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "needs a TPU" in proc.stderr


def test_orchestrator_never_imports_jax():
    """Importing bench.py and building its orchestrator must not pull JAX
    in: a parent that touched JAX would hold the chip its children need."""
    code = ("import sys; sys.path.insert(0, {!r}); import bench; "
            "assert 'jax' not in sys.modules, 'bench import pulled jax in'"
            .format(REPO))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
