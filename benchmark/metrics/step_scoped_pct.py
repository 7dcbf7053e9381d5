"""Harness: how much of a step the program's own names explain: the share
of one period's device-busy time that lies in parts other than
``unscoped:*`` and outside ``unnoted_ms`` (operations of the step program
with no `jax.named_scope` on their path, another program's, the runtime's
copies), after each fusion XLA made across parts was divided among them
(``harness/step_trace.py``). The full report lists the time by part, what
was divided and the longest unscoped and unnoted operations by name."""

from benchmark.harness import step_trace


def read(w):
    found = step_trace.of_window(w)
    if not found or not found["busy_ms"]:
        return None
    scoped = sum(ms for part, ms in found["parts_ms"].items()
                 if not part.startswith(step_trace.UNSCOPED))
    return 100.0 * scoped / found["busy_ms"]
