"""Scheduler: the ``train`` bucket's share of held chip time in the window
(host time inside the trial function that was first-run training)."""


def read(w):
    return 100.0 * w.fold["buckets"]["train"] / w.fold["held_chip_s"]
