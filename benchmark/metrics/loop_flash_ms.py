"""Kernels: device milliseconds a step spends in the flash kernels of the
looped stack's attention (``flash_fwd``, ``flash_bwd_dkdv``,
``flash_bwd_dq``: 24 applications of each), by the kernels' own names, over
the same one period of the traced span as ``loop_ms``, of which it is a
part (``harness/loop_trace.py``). The full report lists the three by name.
``flash_ms`` reads the same kernels over whole `train_step` programs, which
the span of this cell does not hold."""

from benchmark.harness import loop_trace


def read(w):
    by_kernel = (loop_trace.of_window(w) or {}).get("kernels_ms")
    return sum(by_kernel.values()) if by_kernel else None
