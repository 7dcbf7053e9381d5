"""Trainer: compilations after ``t0``: trials started in the window whose
``compiled`` record carries a step-program compile, plus the runners'
persistent-cache misses between ``t0`` and ``t1``. Expected 0."""


def read(w):
    step_programs = sum(1 for t in w.in_window()
                        if t["compiled"].get("compile_ms"))
    misses = sum(r["counters1"]["misses"] - r["counters0"]["misses"]
                 for r in w.runners.values()
                 if r.get("counters0") and r.get("counters1"))
    return step_programs + misses
