"""Experts: the least time the chip could take for a step's grouped expert
products (every layer, forward and backward: the larger of their FLOPs over
the peak and their least HBM bytes over the bandwidth, at the rows a
balanced router sends here, ``harness/sdar_work.py``) over the device time
of the kernels named ``moe_gmm_*``. The work is the layer's, so whatever
multiplies the groups reads against the same count; what the program
recomputes counts against it."""

from benchmark.harness import annotated, moe_trace, sdar_work


def read(w):
    found = moe_trace.of_window(w)
    if not found or not found["gmm_ms"] or w.peak is None:
        return None
    took_ms = sum(found["gmm_ms"].values())
    mix = w.cell["mix"]
    work = sdar_work.grouped_products(w.cell["config"]["model"],
                                      mix["batch"], mix["seq"])
    least_ms, bound = sdar_work.least_ms(
        {k: work["layers"] * work[k] for k in ("flops", "bytes")},
        w.peak["flops"], w.device_kind)
    annotated.note(w, "moe_gmm_roofline", {
        "bound": bound, "least_ms": least_ms, "took_ms": took_ms,
        "rows_expected": work["rows"]})
    return 100.0 * least_ms / took_ms
