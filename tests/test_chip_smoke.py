"""chip_smoke.py off the chip: its sweep runs on the CPU at
`BertConfig.tiny()` (the rehearsal every chip run starts from), and the
script itself exits non-zero, in seconds and without a result, where JAX
finds no accelerator or the repository is not next to it."""

import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_sweep_runs_and_checks_itself_on_cpu_at_tiny(tmp_path):
    from maggy_tpu.models import BertConfig

    report = chip_smoke.run_sweep(BertConfig.tiny(), "thread", 2,
                                  str(tmp_path / "sweep"), platform="cpu",
                                  batch=8)
    assert report["trials_finalized"] == report["trials_scheduled"] >= 7
    assert set(report["rungs"]) == {"0", "1", "2"}
    assert report["forks_served"] >= 1 and report["warm_hits"] >= 1
    assert report["attention_path"] == "reference"  # no Pallas off the TPU
    buckets = report["goodput"]["buckets"]
    assert abs(sum(buckets.values()) - report["goodput"]["held_chip_s"]) < 0.1
    # Gigabytes of checkpoints at full size: the sweep removes them.
    assert not any("checkpoints" in dirs
                   for _, dirs, _ in os.walk(tmp_path / "sweep"))


def test_flash_check_runs_every_shape_at_planned_and_explicit_tiles():
    """Off the chip the same check runs interpreted, at a small shape of
    each kind; the chip's list holds the benchmark cell's class."""
    report = chip_smoke.check_flash_attention(shapes=(
        ("bert_b1_s256_h4_d64_masked", 1, 256, 4, 4, 64, False, True),
        ("gqa_b1_s256_h4_kv2_d128_causal", 1, 256, 4, 2, 128, True, False)))
    assert report["bert_b1_s256_h4_d64_masked"]["plan"] == \
        "fwd q256 k256 h4 kq; bwd q256 k256 h4"
    for entry in report.values():
        for tiles in ("planned", "explicit_128"):
            assert set(entry[tiles]) == {"out", "dq", "dk", "dv"}
            assert max(entry[tiles].values()) <= chip_smoke.FLASH_TOL
    assert chip_smoke.FLASH_TOL == 4 * 2.0 ** -8
    assert ("bert_b64_s512_h12_d64_masked", 64, 512, 12, 12, 64, False,
            True) in chip_smoke.FLASH_SHAPES
    assert len(chip_smoke.FLASH_SHAPES) == 3


def test_trial_refuses_another_platform_than_asked():
    import pytest

    from maggy_tpu.models import BertConfig

    with pytest.raises(RuntimeError, match="needs backend 'tpu'"):
        chip_smoke.train_fn(1e-4, model_cfg=BertConfig.tiny(), platform="tpu")


def test_verdict_line_has_exactly_the_keys_the_driver_reads():
    import json

    line = chip_smoke.verdict({"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 4, "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)
    return proc, time.time() - t0


def test_no_accelerator_exits_nonzero_in_seconds_without_a_result():
    proc, took = _run_smoke(REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "found no accelerator" in proc.stderr.strip().splitlines()[-1]
    assert took < 60


def test_alone_in_a_directory_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc, _ = _run_smoke(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "no maggy_tpu/" in proc.stderr.strip().splitlines()[-1]
