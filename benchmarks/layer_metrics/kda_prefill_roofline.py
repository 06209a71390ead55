"""Kernels (``kda_prefill``, the chunked form of the gated delta rule with a
decay a key channel): the least time the chip could take for one KDA
layer's kernel call of one prefill dispatch over the time the kernel took a
call (one call a KDA layer a prefill dispatch, all the dispatch's rows).
Least, from the counters over the traced slice (``_engine.slice_deltas``):
the live tokens and live rows a dispatch (``prefill_tokens`` and
``prefill_rows_live`` / ``prefill_dispatches``) through
``flops_kda.kda_prefill_flops`` and ``flops_kda.kda_prefill_bytes`` — q, k,
v and the decay read, the output written, a row's state read and written —
and the larger of the two bounds (``flops.roofline_min_s``: the bytes, at
the chip's ridge). The kernel runs the padded rows and the padded tail of
each chunk too: their time is what the live tokens pay for. None when the
run was not traced, the program has no such kernel or counter, or the slice
holds no prefill dispatch."""
from .. import flops, flops_kda
from ._common import trace
from ._engine import per, slice_deltas


def read(ctx: dict):
    t = trace(ctx)
    k = t and t["kernels"].get("kda_prefill")
    tokens = per(ctx, "prefill_tokens", "prefill_dispatches",
                 over=slice_deltas)
    rows = per(ctx, "prefill_rows_live", "prefill_dispatches",
               over=slice_deltas)
    if not k or not k["count"] or not k["seconds"] or not tokens \
            or not rows or ctx.get("rehearse"):
        return None
    cfg = ctx["config"]
    least, _ = flops.roofline_min_s(
        flops_kda.kda_prefill_flops(cfg, tokens),
        flops_kda.kda_prefill_bytes(cfg, tokens, rows),
        flops.peaks(ctx["device"]["kind"]))
    return 100.0 * least / (k["seconds"] / k["count"])
