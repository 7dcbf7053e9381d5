"""Experts: as ``moe_gmm_roofline_pct``, for relu^2 experts of TWO
matrices: the least time the chip could take for a step's grouped expert
products (every ``E`` block, forward and backward, at the rows a balanced
router sends here; ``harness/nemotron_h_work.py``) over the device time of
the kernels named ``moe_gmm_*``."""

from benchmark.harness import annotated, moe_trace, nemotron_h_work


def read(w):
    found = moe_trace.of_window(w)
    if not found or not found["gmm_ms"] or w.peak is None:
        return None
    took_ms = sum(found["gmm_ms"].values())
    mix = w.cell["mix"]
    work = nemotron_h_work.grouped_products(w.cell["config"]["model"],
                                            mix["batch"], mix["seq"])
    least_ms, bound = nemotron_h_work.least_ms(
        {k: work["layers"] * work[k] for k in ("flops", "bytes")},
        w.peak["flops"], w.device_kind)
    annotated.note(w, "nh_moe_gmm_roofline", {
        "bound": bound, "least_ms": least_ms, "took_ms": took_ms,
        "rows_expected": work["rows"]})
    return 100.0 * least_ms / took_ms
