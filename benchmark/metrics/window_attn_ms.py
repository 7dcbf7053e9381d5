"""Windowed attention: device milliseconds a step spends under the scope
``attn_window`` (the windowed layers' input norm, projections, rope, the
flash kernels and the output projection), forward, remat and backward
together: ``parts_ms`` over the parts that hold the scope, over one period
of the traced span (``harness/step_trace.py``). The report lists the global
layers' ``attn_global`` beside it."""

from benchmark.harness import annotated, step_trace


def under(w, scope):
    return step_trace.ms_where(
        w, lambda part: scope in step_trace.scopes_of(part))


def read(w):
    took = {scope: under(w, scope) for scope in ("attn_window",
                                                 "attn_global")}
    annotated.note(w, "window_attn", took)
    return took["attn_window"]
