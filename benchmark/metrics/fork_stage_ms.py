"""Executor: median time to stage a parent's checkpoint into a forked
trial's directory (``fork_load_ms``), over forks started in the window."""

from benchmark.harness.window import median


def read(w):
    return median([t["compiled"].get("fork_load_ms")
                   for t in w.in_window() if t["compiled"].get("forked")])
