"""Kernels (ops/gated_delta.py with a decay a key channel, under
models/ling_hybrid.py's KDA layers): device time in the chunked prefill
kernel and the decode update (``reduce/kernels/kda_prefill.json`` /
``kda_decode.json``) over device busy time, from the device trace. The
projections around them (Wq, Wk, Wv, Wf, Wg, Wo: XLA matmuls) are not in
it. None on a program without the kernels."""
from ._common import kernel_share


def read(ctx: dict):
    parts = [kernel_share(ctx, g) for g in ("kda_prefill", "kda_decode")]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
