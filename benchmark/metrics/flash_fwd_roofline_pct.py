"""Kernels: the least time the chip could take for a step's attention
forwards (every layer; the larger of 4 B H S^2 D FLOPs over the peak and
q, k, v, o once over the HBM bandwidth: ``harness/attention_work.py``) over
the device time of ``flash_fwd`` in a step."""

from benchmark.harness import annotated


def read(w):
    return annotated.roofline_pct(w, "forward")
