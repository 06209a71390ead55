"""Kernels (ops/ragged_paged_attention.py): device time in the ragged
kernels' custom calls over device busy time, from the device trace."""
from ._common import kernel_share


def read(ctx: dict):
    return kernel_share(ctx, "ragged_attention")
