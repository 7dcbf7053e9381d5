"""Kernels: the least time the chip could take for a step's exit heads (one
head a pass, forward and the two backward products: the larger of their
FLOPs over the peak and their least HBM bytes over the bandwidth;
``harness/ouro_work.py``) over the device time under the scope
``exit_head``. The work is the heads', so whatever computes them, XLA's
chunk scan or a kernel, reads against the same count; what the program
recomputes counts against it."""

from benchmark.harness import annotated, loop_trace, ouro_work


def read(w):
    took_ms = loop_trace.ms_under(w, ("exit_head",))
    if not took_ms or w.peak is None:
        return None
    mix = w.cell["mix"]
    work = ouro_work.exit_heads(w.cell["config"]["model"], mix["batch"],
                                mix["seq"])
    least_ms, bound = ouro_work.least_ms(work, w.peak["flops"],
                                         w.device_kind)
    annotated.note(w, "exit_head_roofline", {
        "bound": bound, "least_ms": least_ms, "took_ms": took_ms})
    return 100.0 * least_ms / took_ms
