"""FLOPs a training step requires, as a function of shapes.

Counted: every matrix multiplication of the forward pass (2 FLOPs per
multiply-add) and the two of the backward pass that each forward one needs
(gradient with respect to the input and to the weight), so 3x forward. Not
counted: embedding look-ups (a gather, no matmul), layer norms, softmax,
GELU, the optimizer, and anything recomputed. The backward pass of the very
first matmul needs no input gradient, which this over-counts by less than
0.5 % at the sizes in use; it is left in so that the function stays a plain
sum over matmuls.
"""

from __future__ import annotations


def encoder_forward_flops(tokens: int, seq: int, hidden: int,
                          intermediate: int, layers: int) -> dict:
    """Forward FLOPs of ``layers`` pre-LN encoder layers over ``tokens``
    positions arranged in sequences of ``seq``: q, k, v and output
    projections (4 of hidden x hidden), the two MLP matmuls, and attention
    (scores and values: 2 matmuls of seq x head_dim per head and query, in
    all 2 * 2 * seq * hidden per token)."""
    proj = 2 * tokens * 4 * hidden * hidden
    mlp = 2 * tokens * 2 * hidden * intermediate
    attn = 2 * tokens * 2 * seq * hidden
    return {"matmul": layers * (proj + mlp), "attention": layers * attn}


def train_flops(forward: float) -> float:
    """Forward plus backward: each matmul once forward, twice backward."""
    return 3.0 * forward
