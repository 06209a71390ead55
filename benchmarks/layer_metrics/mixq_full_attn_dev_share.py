"""Kernels (ops/ragged_paged_attention.py, the plain form under
models/llama.py's full layers): device time in the ragged kernels
(``reduce/kernels/ragged_attention.json``, which matches both forms) less
the window form's (``window_attention.json``), over device busy time."""
from ._common import kernel_share


def read(ctx: dict):
    both = kernel_share(ctx, "ragged_attention")
    window = kernel_share(ctx, "window_attention")
    if both is None or window is None:
        return None
    return both - window
