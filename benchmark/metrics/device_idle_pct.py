"""Device: 1 - union of device-operation intervals over the traced span,
from the `jax.profiler` trace taken inside the window."""


def read(w):
    return None if w.trace is None else w.trace["idle_pct"]
