"""Kernels (ops/ragged_paged_attention.py, the window form under
models/llama.py's sliding layers): device time in its custom calls
(``reduce/kernels/window_attention.json``), decode and prefill shapes
together, over device busy time, from the device trace."""
from ._common import kernel_share


def read(ctx: dict):
    return kernel_share(ctx, "window_attention")
