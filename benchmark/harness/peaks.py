"""Peak rates of one chip, by ``device_kind`` prefix.

Source: Google Cloud TPU documentation, the "System architecture" page of
each generation ("TPU v5e": 197 TFLOP/s bf16 per chip). Copied from
``bench.py`` ``CHIP_PEAK_FLOPS`` so that no later PR can move the yardstick.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

#: (device_kind prefix, peak bf16 FLOP/s)
CHIP_PEAK_FLOPS = [
    ("TPU v5 lite", 197e12),  # v5e
    ("TPU v5e", 197e12),
    ("TPU v5p", 459e12),
    ("TPU v6", 918e12),
    ("TPU v4", 275e12),
    ("TPU v3", 123e12),
]


def chip_peaks(device_kind: str) -> dict:
    for prefix, flops in CHIP_PEAK_FLOPS:
        if device_kind.startswith(prefix):
            return {"flops": flops}
    raise ValueError(
        "no peak rates on record for device_kind {!r}; add it to "
        "benchmark/harness/peaks.py with its source".format(device_kind))
