"""Trainer: share of the traced span in which the device was idle while
the loop's thread was inside a ``place_batch`` annotation. The full report
lists the idle time under every annotation (``train_step``, ``report``,
``init``, ..., ``none``)."""

from benchmark.harness import annotated


def read(w):
    found = annotated.of_window(w)
    return found["input_wait_pct"] if found else None
