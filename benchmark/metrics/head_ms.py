"""Model: device milliseconds a step spends in the head, all passes:
``parts_ms`` over the parts under the scope ``head`` (final norm, the
head's product forward, and its two backward products, the weight's
gradient split from the optimizer's update it is fused with) and under
``loss`` (the loss function around it, ``weighted_ce`` inside)
(``harness/step_trace.py``). The Ouro cell's head runs under
``exit_head`` and keeps ``exit_ms``."""

from benchmark.harness import step_trace

HEAD = ("head", "loss")


def read(w):
    return step_trace.ms_where(
        w, lambda part: step_trace.scopes_of(part)[0] in HEAD)
