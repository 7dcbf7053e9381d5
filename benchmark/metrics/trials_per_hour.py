"""Whole trials per hour: over the trials that both started and finalised
inside the window, ``3600 * n / (last finalise - first start)``, all runners
together. Over whole trials, so that it moves smoothly with their duration
and not in steps of one trial."""


def read(w):
    done = w.in_window(finalized=True)
    if not done:
        return None
    span = max(t["t_finalized"] for t in done) \
        - min(t["t_running"] for t in done)
    return 3600.0 * len(done) / span if span > 0 else None
