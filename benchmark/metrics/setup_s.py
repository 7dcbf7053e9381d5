"""Process start to ``t0``, the opening of the window on the last runner:
imports, device start-up, `lagom`'s own start, and each runner's warm-up
(init, trace, compile or cache load, first save)."""


def read(w):
    return w.setup_s
