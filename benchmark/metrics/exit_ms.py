"""Exits: device milliseconds a step spends closing the passes and reading
the exits: every operation of the `train_step` program that ran under the
scopes ``exit_norm``, ``exit_gate`` and ``exit_head``
(``harness/loop_trace.py``). The full report lists the time by scope."""

from benchmark.harness import loop_trace


def read(w):
    return loop_trace.ms_under(w, loop_trace.EXITS)
