"""Kernels (ops/grouped_matmul.py, called from models/llama.py
``_moe_ffn``): device time in the expert FFN's two Pallas kernels,
``grouped_swiglu`` and ``grouped_matmul`` (``reduce/kernels/moe_ffn.json``),
over device busy time, from the device trace. The router, the layout
arithmetic and the row gathers around the kernels are unnamed XLA fusions
and are not in it."""
from ._common import kernel_share


def read(ctx: dict):
    return kernel_share(ctx, "moe_ffn")
