"""Kernels (ops/gated_delta.py, under models/qwen3_next.py's GDN layers):
device time in the chunked prefill kernel and the decode update
(``reduce/kernels/gdn_prefill.json`` / ``gdn_decode.json``) over device busy
time, from the device trace. None on a program without the kernels."""
from ._common import kernel_share


def read(ctx: dict):
    parts = [kernel_share(ctx, g) for g in ("gdn_prefill", "gdn_decode")]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
