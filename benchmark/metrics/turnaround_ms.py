"""Executor: median, over the same boundaries, of one trial function's
return to the next trial's first step dispatch: the time the chip has
nothing of either trial queued."""

from benchmark.harness import annotated
from benchmark.harness.window import median


def read(w):
    return median([b["turnaround_ms"]
                   for b in annotated.trial_boundaries(w)])
