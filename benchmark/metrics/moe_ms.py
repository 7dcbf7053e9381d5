"""Experts: device milliseconds a step spends in the expert layers: every
operation of the `train_step` program that ran under the layer's scopes
(``moe_routing``, ``moe_dispatch``, ``moe_experts`` with the grouped
products ``moe_gmm_*``, ``moe_combine``), found by the instruction names the
program notes in its ``compiled`` record (``harness/moe_trace.py``). The
full report lists the time by scope and by kernel."""

from benchmark.harness import moe_trace


def read(w):
    found = moe_trace.of_window(w)
    if not found or not found["scopes_ms"]:
        return None
    return sum(found["scopes_ms"].values())
