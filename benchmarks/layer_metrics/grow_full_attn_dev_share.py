"""Kernels (ops/ragged_paged_attention.py at heads of 256, 8 query heads a
KV head, under models/qwen3_next.py's gated-attention layers): device time
in the ragged kernels (``reduce/kernels/ragged_attention.json``) over device
busy time."""
from ._common import kernel_share


def read(ctx: dict):
    return kernel_share(ctx, "ragged_attention")
