"""Kernels (``kda_decode``, the single-token update in place with a decay
a key channel): ``gdn_decode_roofline``'s arithmetic — the least time
the chip could take to stream the live rows' states of one KDA layer once
in and once out (``decode_live_slots`` / ``decode_dispatches`` over the
traced slice x ``flops_kda.kda_decode_bytes`` over the peak HBM rate: 7
FLOPs an entry of a float32 state, memory-bound) over the time the kernel
took a call, one call a KDA layer a decode step. The kernel runs every one
of ``max_batch_size`` rows, idle rows on the sink: their time is what the
live rows pay for. None when the run was not traced or the program has no
such kernel or counter."""
from .. import flops, flops_kda
from ._common import trace
from ._engine import per, slice_deltas


def read(ctx: dict):
    t = trace(ctx)
    k = t and t["kernels"].get("kda_decode")
    live = per(ctx, "decode_live_slots", "decode_dispatches",
               over=slice_deltas)
    if not k or not k["count"] or not k["seconds"] or live is None \
            or ctx.get("rehearse"):
        return None
    least = flops_kda.kda_decode_bytes(ctx["config"], live) / flops.peaks(
        ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (k["seconds"] / k["count"])
