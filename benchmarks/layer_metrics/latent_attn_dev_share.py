"""Kernels (ops/ragged_paged_attention.py ``ragged_latent_attention`` under
models/mla_moe.py): device time in the latent kernel's custom calls
(``reduce/kernels/latent_attention.json``), decode and prefill shapes
together, over device busy time, from the device trace. The absorbed
projections around it (``q_nope W_UK`` before, ``W_UV`` after) are XLA
matmuls and not in it."""
from ._common import kernel_share


def read(ctx: dict):
    return kernel_share(ctx, "latent_attention")
