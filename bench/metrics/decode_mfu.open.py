"""Useful decode FLOPs (matmuls, attention at each token's context, its
logits) over the decode-chunk program's device time at the chip's peak."""
from harness.results import decode_mfu as read  # noqa: F401
