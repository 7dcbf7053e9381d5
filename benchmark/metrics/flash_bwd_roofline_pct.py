"""Kernels: the same for the backward pass as a whole (8 B H S^2 D FLOPs;
q, k, v, o, dO read and dQ, dK, dV written) over the device time of every
kernel named ``flash_bwd*`` in a step, so that two kernels and one fused
kernel read against the same work."""

from benchmark.harness import annotated


def read(w):
    return annotated.roofline_pct(w, "backward")
