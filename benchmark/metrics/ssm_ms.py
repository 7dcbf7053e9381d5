"""State space: device milliseconds a step spends in the state-space
blocks: every operation of the `train_step` program that ran under the
mixer's scopes (``ssm_proj``, ``ssm_conv``, ``ssm_scan``,
``ssm_gate_norm``), found by the instruction names the program notes in its
``compiled`` record (``harness/ssm_trace.py``). The full report lists the
time by scope and by kernel name."""

from benchmark.harness import ssm_trace


def read(w):
    found = ssm_trace.of_window(w)
    if not found or not found["scopes_ms"]:
        return None
    return sum(found["scopes_ms"].values())
