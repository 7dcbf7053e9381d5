"""Trainer: median ``init_ms`` of trials started in the window whose
``compiled`` record says they found the warm slot."""

from benchmark.harness.window import median


def read(w):
    return median([t["compiled"].get("init_ms") for t in w.in_window()
                   if t["compiled"].get("warm")])
