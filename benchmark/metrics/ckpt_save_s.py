"""Checkpoints: median seconds per save (``save_ms`` over ``saves``), over
trials started in the window."""

from benchmark.harness.window import median


def read(w):
    return median([t["ckpt"]["save_ms"] / t["ckpt"]["saves"] / 1e3
                   for t in w.in_window() if t["ckpt"].get("saves")])
