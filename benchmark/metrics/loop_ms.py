"""Looped stack: device milliseconds a step spends in the layer
applications, all passes: every operation of the `train_step` program that
ran under the scopes ``loop_attn`` (norms, projections, rope, the flash
kernels) and ``loop_mlp``, found by the instruction names the program notes
in its ``compiled`` record (``harness/loop_trace.py``). The full report
lists the time by scope."""

from benchmark.harness import loop_trace


def read(w):
    return loop_trace.ms_under(w, loop_trace.LOOP)
