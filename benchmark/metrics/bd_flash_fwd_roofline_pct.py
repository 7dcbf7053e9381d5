"""Kernels: the least time the chip could take for a step's attention
forwards under the block-diffusion mask (every layer: the larger of the
FLOPs of the VISIBLE pairs over the peak and q, k, v, o
once over the HBM bandwidth, ``harness/sdar_work.py``) over the device time
of the kernels named ``flash_fwd*`` in a step. A rematerialised layer runs
its forward kernel twice; the second run is the program's choice and counts
against it."""

from benchmark.harness import annotated, sdar_work


def read(w):
    took_ms = annotated.kernel_ms(w, "flash_fwd")
    if not took_ms or w.peak is None:
        return None
    mix = w.cell["mix"]
    work = sdar_work.attention(w.cell["config"]["model"], mix["batch"],
                               mix["seq"])
    least_ms, bound = sdar_work.least_ms(
        {key: work["layers"] * v for key, v in work["forward"].items()},
        w.peak["flops"], w.device_kind)
    annotated.note(w, "bd_forward_roofline", {
        "bound": bound, "least_ms": least_ms, "took_ms": took_ms})
    return 100.0 * least_ms / took_ms
