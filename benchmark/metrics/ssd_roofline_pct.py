"""Kernels: the least time the chip could take for a step's state-space
scans (every ``M`` block, forward and backward: the larger of the chunked
form's FLOPs over the peak and the least HBM bytes, x, B, C, dt in and y
out and their gradients, over the bandwidth; ``harness/nemotron_h_work.py``)
over the device time under the scope ``ssm_scan``. The work is the scan's,
so whatever computes it, XLA products or a kernel, reads against the same
count; what the program recomputes counts against it."""

from benchmark.harness import annotated, nemotron_h_work, ssm_trace


def read(w):
    found = ssm_trace.of_window(w)
    took_ms = found and (found["scopes_ms"] or {}).get("ssm_scan")
    if not took_ms or w.peak is None:
        return None
    mix = w.cell["mix"]
    work = nemotron_h_work.scan(w.cell["config"]["model"], mix["batch"],
                                mix["seq"])
    least_ms, bound = nemotron_h_work.least_ms(
        {k: work["layers"] * work[k] for k in ("flops", "bytes")},
        w.peak["flops"], w.device_kind)
    annotated.note(w, "ssd_roofline", {
        "bound": bound, "least_ms": least_ms, "took_ms": took_ms})
    return 100.0 * least_ms / took_ms
