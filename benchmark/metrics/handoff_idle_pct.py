"""Scheduler: share of held chip time in which a runner had no trial
(``handoff`` + ``queue_wait`` + ``idle``)."""


def read(w):
    b = w.fold["buckets"]
    return 100.0 * (b["handoff"] + b["queue_wait"] + b["idle"]) \
        / w.fold["held_chip_s"]
