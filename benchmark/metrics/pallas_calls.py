"""Kernels: ``tpu_custom_call`` count in the step executable that ran
(every runner must agree)."""


def read(w):
    counts = {r["pallas_calls"] for r in w.runners.values()}
    counts |= {t["out"]["pallas_calls"] for t in w.trials
               if t["out"] and "pallas_calls" in t["out"]}
    counts.discard(None)
    return counts.pop() if len(counts) == 1 else None
