"""The work attention needs, as a function of shapes: FLOPs and the least
HBM traffic of the forward and of the backward pass, and the chip's memory
bandwidth. Beside ``flops.py``, whose ``attention`` term is the same count,
so that ``mfu_pct`` and a flash kernel's roofline share rest on one
reckoning.

Counted, for B sequences, H heads, S queries against S keys, head size D:

- forward: the scores (Q K^T) and the values (P V), two matmuls of
  2 B H S S D each: ``4 B H S^2 D``. Least traffic: q, k, v read once and o
  written once.
- backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q, four such
  matmuls: ``8 B H S^2 D``, twice the forward. The scores that a flash
  backward recomputes are work the algorithm chose, not work attention
  needs, and count nothing (as ``flops.py`` counts no recompute). Least
  traffic: q, k, v, o, dO read once and dQ, dK, dV written once.

Softmax, masks and the row statistics (log-sum-exp, delta) count nothing:
they are O(S) or elementwise beside the matmuls.
"""

from __future__ import annotations

#: (device_kind prefix, peak HBM bytes/s). Source: Google Cloud TPU
#: documentation, "TPU v5e" system architecture: 16 GB of HBM2e at 819 GB/s
#: per chip. A device that is not in the table is an error, never a default.
CHIP_HBM_BYTES_PER_S = [
    ("TPU v5 lite", 819e9),  # v5e
    ("TPU v5e", 819e9),
]


def hbm_bytes_per_s(device_kind: str) -> float:
    for prefix, rate in CHIP_HBM_BYTES_PER_S:
        if device_kind.startswith(prefix):
            return rate
    raise ValueError(
        "no HBM bandwidth on record for device_kind {!r}; add it to "
        "benchmark/harness/attention_work.py with its source".format(
            device_kind))


def forward_flops(batch: int, heads: int, seq: int, head_dim: int) -> float:
    return 4.0 * batch * heads * seq * seq * head_dim


def backward_flops(batch: int, heads: int, seq: int, head_dim: int) -> float:
    return 2.0 * forward_flops(batch, heads, seq, head_dim)


def _tensor_bytes(batch, heads, seq, head_dim, itemsize) -> float:
    return float(batch * heads * seq * head_dim * itemsize)


def forward_bytes(batch: int, heads: int, seq: int, head_dim: int,
                  itemsize: int) -> float:
    """q, k, v read and o written, each once."""
    return 4 * _tensor_bytes(batch, heads, seq, head_dim, itemsize)


def backward_bytes(batch: int, heads: int, seq: int, head_dim: int,
                   itemsize: int) -> float:
    """q, k, v, o, dO read and dQ, dK, dV written, each once."""
    return 8 * _tensor_bytes(batch, heads, seq, head_dim, itemsize)


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bytes_per_s: float):
    """(the least time the chip could take, which bound binds)."""
    compute, memory = flops / peak_flops, nbytes / peak_bytes_per_s
    return (compute, "flops") if compute >= memory else (memory, "hbm")


def of_cell(model: dict, batch: int, seq: int) -> dict:
    """One layer's attention work in a step of a cell, from the
    configuration's published keys and the mix's batch and sequence."""
    heads = model["num_attention_heads"]
    shape = (batch, heads, seq, model["hidden_size"] // heads)
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[
        model["activation_dtype"]]
    return {
        "layers": model["num_hidden_layers"],
        "forward": {"flops": forward_flops(*shape),
                    "bytes": forward_bytes(*shape, itemsize)},
        "backward": {"flops": backward_flops(*shape),
                     "bytes": backward_bytes(*shape, itemsize)},
    }
