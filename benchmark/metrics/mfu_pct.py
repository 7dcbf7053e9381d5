"""Model: FLOPs the forward and backward passes require per token
(``benchmark/harness/flops.py``: matmuls and attention, no embedding
look-ups, nothing recomputed) times tokens per chip-second, over the chip's
peak (``benchmark/harness/peaks.py``). Not reported off the chip."""


def read(w):
    if w.peak is None:
        return None
    per_token = sum(w.flops_per_token.values())
    return 100.0 * per_token * w.tokens / w.held_s / w.peak["flops"]
