"""Kernels (``gated_delta_decode``, the single-token update in place): the
least time the chip could take to stream the live rows' states of one GDN
layer once in and once out over the time the kernel took a call (one call
a GDN layer a decode step). Least: ``decode_live_slots`` /
``decode_dispatches`` over the traced slice x
``flops_gdn.gdn_decode_bytes`` over the peak HBM rate: the update is
memory-bound (6 FLOPs an entry of a float32 state). The kernel runs every
one of ``max_batch_size`` rows, idle rows on the sink: their time is what
the live rows pay for. None when the run was not traced or the program has
no such kernel or counter."""
from .. import flops, flops_gdn
from ._common import trace
from ._engine import per, slice_deltas


def read(ctx: dict):
    t = trace(ctx)
    k = t and t["kernels"].get("gdn_decode")
    live = per(ctx, "decode_live_slots", "decode_dispatches",
               over=slice_deltas)
    if not k or not k["count"] or not k["seconds"] or live is None \
            or ctx.get("rehearse"):
        return None
    least = flops_gdn.gdn_decode_bytes(ctx["config"], live) / flops.peaks(
        ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (k["seconds"] / k["count"])
