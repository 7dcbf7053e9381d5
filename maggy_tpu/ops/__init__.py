"""TPU compute kernels: Pallas where it pays, XLA elsewhere."""

from maggy_tpu.ops.attention import multi_head_attention, flash_attention, attention_reference
from maggy_tpu.ops.losses import (chunked_next_token_loss, chunked_softmax_xent,
                                  chunked_token_nll, weighted_token_xent)

__all__ = ["multi_head_attention", "flash_attention", "attention_reference",
           "chunked_next_token_loss", "chunked_softmax_xent",
           "chunked_token_nll", "weighted_token_xent"]
