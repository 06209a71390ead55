"""Kernels (ops/ragged_paged_attention.py, the plain form under the full
layers of a decoder with two kinds of layer: models/llama.py's beside
sliding ones, models/qwen3_next.py's gated-attention layers at heads of
256 beside GDN ones): device time in the ragged kernels
(``reduce/kernels/ragged_attention.json``, which matches the window form
too) less the window form's (``window_attention.json``) where the trace
holds any, over device busy time."""
from ._common import kernel_share


def read(ctx: dict):
    both = kernel_share(ctx, "ragged_attention")
    if both is None:
        return None
    return both - (kernel_share(ctx, "window_attention") or 0.0)
