"""Kernels (ops/ragged_paged_attention.py at the decode shape, query window
1): the least time the chip could take to stream one decode step's live
cache over the time the kernel took per device step.

Least: the pages that hold the live sequences' tokens at a decode dispatch
(counters ``decode_live_pages`` / ``decode_dispatches``, over the traced
slice: ``_engine.slice_deltas``) x
``page_size`` x ``flops.kv_bytes_per_token`` (keys and values of every layer)
over the peak HBM bytes/s: decode attention reads each live key and value
once and is memory-bound. Measured: in the traced slice, the self time of
the ragged kernel's calls whose shape has a query window of 1, per call, x
``num_hidden_layers`` calls a step. None when the run was not traced, or
its trace carries no snapshots of the counters at the slice's ends.

Both halves cover the slice's seconds since PR 33. Before it the pages were
the whole window's mean and the kernel time the slice's, a tenth of it:
sessions cycle through documents, so the slice's live cache differs from
the window's mean, and a kernel near its roofline then read over 100 with
nothing miscounted (``ragged_prefill_roofline`` says how far)."""
import re

from .. import flops
from ._common import trace
from ._engine import per, slice_deltas

DECODE_SHAPE = re.compile(r"^ragged[^:]*:\w+\[\d+,1,")


def read(ctx: dict):
    t = trace(ctx)
    pages = per(ctx, "decode_live_pages", "decode_dispatches",
                over=slice_deltas)
    if t is None or pages is None or ctx.get("rehearse"):
        return None
    calls = [(s, n) for name, s, n, *_ in t["ops"] if DECODE_SHAPE.match(name)]
    seconds, count = sum(c[0] for c in calls), sum(c[1] for c in calls)
    if not seconds or not count:
        return None
    cfg = ctx["config"]
    live_bytes = (pages * cfg["engine"]["page_size"]
                  * flops.kv_bytes_per_token(cfg))
    least = live_bytes / flops.peaks(
        ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / count * cfg["num_hidden_layers"])
