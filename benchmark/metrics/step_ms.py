"""Model: held time per first-run step in the window. In a cell whose one
trial trains from ``t0`` to ``t1`` this is the pipelined step time."""


def read(w):
    if not w.first_run_steps:
        return None
    return 1e3 * w.held_s / w.first_run_steps
