"""Checkpoints: share of held chip time spent saving, restoring and
staging forks (``ckpt_save`` + ``ckpt_restore`` + ``fork_stage``)."""


def read(w):
    b = w.fold["buckets"]
    return 100.0 * (b["ckpt_save"] + b["ckpt_restore"] + b["fork_stage"]) \
        / w.fold["held_chip_s"]
