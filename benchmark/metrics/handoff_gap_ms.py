"""Executor: median, over the trial boundaries inside the window, of one
trial function's return to the next one's entry on the same runner
(``fn_exit`` to ``fn_enter`` of the journal's ``trial`` spans)."""

from benchmark.harness import annotated
from benchmark.harness.window import median


def read(w):
    return median([b["gap_ms"] for b in annotated.trial_boundaries(w)])
