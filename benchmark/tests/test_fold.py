"""The windowed fold against the program's `compute_goodput`."""

import math

import pytest

from benchmark.harness.fold import BUCKETS, windowed_fold
from benchmark.tests.journals import T, sweep_journal


def test_bucket_names_are_the_programs():
    from maggy_tpu.telemetry.vocab import GOODPUT_BUCKETS

    assert tuple(BUCKETS) == tuple(GOODPUT_BUCKETS)


def test_whole_experiment_equals_compute_goodput():
    from maggy_tpu.telemetry.goodput import compute_goodput

    events = sweep_journal()
    want, got = compute_goodput(events), windowed_fold(events)
    assert got["held_chip_s"] == pytest.approx(want["held_chip_s"], abs=1e-9)
    for bucket in BUCKETS:
        assert got["buckets"][bucket] == pytest.approx(
            want["buckets"][bucket], abs=1e-9), bucket
    assert want["buckets"]["rework"] > 0  # the dead attempt and the re-train
    for pid, part in want["per_partition"].items():
        for bucket in BUCKETS:
            assert got["per_partition"][pid]["buckets"][bucket] == \
                pytest.approx(part["buckets"][bucket], abs=1e-9)


@pytest.mark.parametrize("windows", [
    {0: (21.0, 40.0), 1: (19.0, 40.0)},   # opens between trials, cuts two
    {0: (5.0, 25.0), 1: (22.0, 33.0)},    # opens inside init, inside a gap
    {0: (100.0, 110.0), 1: (0.0, 1.0)},   # outside the experiment
])
def test_clipped_fold_closes(windows):
    events = sweep_journal()
    got = windowed_fold(events, {p: (T + a, T + b)
                                 for p, (a, b) in windows.items()})
    if not got:
        assert all(a >= 46.0 or b <= 1.0 for a, b in windows.values())
        return
    assert math.isclose(sum(got["buckets"].values()), got["held_chip_s"],
                        abs_tol=1e-6)
    assert abs(got["buckets"]["unaccounted"]) < 1e-6
    for part in got["per_partition"].values():
        assert math.isclose(sum(part["buckets"].values()), part["held_s"],
                            abs_tol=1e-6)


def test_clip_places_the_pieces_in_order():
    """Partition 0, window [21, 31] holds all of the forked trial c:
    1.5 s staging, 0.2 s init, 1.2 s restore, then training, then a 5 s
    save that ends with the attempt."""
    got = windowed_fold(sweep_journal(), {0: (T + 21.0, T + 31.0)})
    b = got["buckets"]
    assert b["fork_stage"] == pytest.approx(1.5)
    assert b["ckpt_restore"] == pytest.approx(1.2)
    assert b["ckpt_save"] == pytest.approx(5.0)
    assert b["handoff"] == pytest.approx(0.1)
    assert b["train"] == pytest.approx(9.9 - 1.5 - 0.2 - 1.2 - 5.0)
    # Cut the window 2 s before the attempt's end: 2 s of the save go.
    cut = windowed_fold(sweep_journal(), {0: (T + 21.0, T + 29.0)})
    assert cut["buckets"]["ckpt_save"] == pytest.approx(3.0)
    assert cut["buckets"]["train"] == pytest.approx(b["train"])


def test_gangs_and_blocks_are_refused():
    events = sweep_journal() + [{"t": T + 2, "ev": "trial", "trial": "g",
                                 "phase": "gang_assembled", "partition": 0}]
    with pytest.raises(NotImplementedError):
        windowed_fold(events)
