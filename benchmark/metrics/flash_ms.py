"""Kernels: device time a step spends in the three flash kernels
(``flash_fwd`` + ``flash_bwd_dkdv`` + ``flash_bwd_dq``, found by the names
their `pallas_call`s carry), per `train_step` program that ran whole inside
the traced span. The full report lists each kernel's time by name."""

from benchmark.harness import annotated


def read(w):
    return annotated.kernel_ms(w, "flash_")
