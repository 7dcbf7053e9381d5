"""Trainer: device milliseconds a step spends in the optimizer's update
alone: ``parts_ms`` under ``optimizer:update``, every operation under the
scope ``optimizer`` and, of each fusion XLA made of a weight's gradient
product and that weight's update, the update's share by its least time
(``harness/step_trace.py``). Its least is parameters x 28 B over the
bandwidth: adamw reads p, g, m, v and writes p, m, v."""

from benchmark.harness import step_trace


def read(w):
    return step_trace.ms_where(w, lambda part: part == "optimizer:update")
