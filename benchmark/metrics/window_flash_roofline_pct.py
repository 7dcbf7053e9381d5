"""Kernels: the least time the chip could take for a step's windowed
attention (every windowed layer, forward and backward: the larger of its
FLOPs, ``4 D`` a visible pair and query head forward and twice that
backward, over the peak, and its least HBM bytes over the bandwidth;
``harness/smallthinker_work.py``) over the device time of the flash kernels
under the scope ``attn_window`` (``harness/smallthinker_trace.py``). The
work is the pairs the window leaves visible, whatever implements the
window; what the program recomputes counts against it."""

from benchmark.harness import annotated, smallthinker_trace, smallthinker_work


def read(w):
    took_ms = smallthinker_trace.flash_ms(w, "attn_window")
    if not took_ms or w.peak is None:
        return None
    mix = w.cell["mix"]
    work = smallthinker_work.window_attention(w.cell["config"]["model"],
                                              mix["batch"], mix["seq"])
    least_ms, bound = smallthinker_work.least_ms(work, w.peak["flops"],
                                                 w.device_kind)
    annotated.note(w, "window_flash_roofline", {
        "bound": bound, "least_ms": least_ms, "took_ms": took_ms})
    return 100.0 * least_ms / took_ms
