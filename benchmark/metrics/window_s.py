"""Harness: ``t1 - t0`` as measured, averaged over the runners. Equals
``--seconds`` plus the dispatch queue unless the sweep ran out of trials."""


def read(w):
    return w.held_s / len(w.runners)
