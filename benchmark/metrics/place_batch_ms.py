"""Trainer: median host duration of the ``place_batch`` annotations of the
loop's thread inside the traced span."""

from benchmark.harness import annotated
from benchmark.harness.window import median


def read(w):
    found = annotated.of_window(w)
    return median(found["place_batch_ms"] or []) if found else None
