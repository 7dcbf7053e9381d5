"""Model: device milliseconds a step spends making a forward pass again in
its backward pass: ``parts_ms`` over every part whose pass is ``remat``
(operations whose ``op_name`` holds ``rematted_computation``: what a
rematerialised layer did not keep), a fusion of such work with the
gradient's divided by least time (``harness/step_trace.py``). The full
report lists it by scope."""

from benchmark.harness import step_trace


def read(w):
    return step_trace.ms_where(w, lambda part: part.endswith(":remat"))
