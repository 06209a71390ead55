"""Kernels (the window form at the decode shape, query window 1): the
least time the chip could take to stream one decode step's live window of
one sliding layer over the time the kernel took per layer-step.

Least: the window pages the live sequences' last ``sliding_window`` keys
lie in at a decode dispatch (counters ``decode_live_wpages`` /
``decode_dispatches`` over the traced slice, ``_engine.slice_deltas``) x
``page_size`` x ``flops_window.kv_bytes_per_token_layer`` over the peak HBM
rate: decode attention reads each live key and value once and is
memory-bound. Measured: in the traced slice, the self time per call of the
window kernel's calls whose query window is 1; one call is one layer of
one step. None when the run was not traced, the program has no such kernel
or counter, or the trace carries no snapshots at the slice's ends."""
from .. import flops, flops_window
from . import _window
from ._engine import per, slice_deltas

KIND, PAGES = "window", "decode_live_wpages"


def read(ctx: dict, kind: str = KIND, pages_key: str = PAGES):
    pages = per(ctx, pages_key, "decode_dispatches", over=slice_deltas)
    got = _window.calls(ctx, kind, _window.DECODE)
    if pages is None or got is None:
        return None
    cfg = ctx["config"]
    live_bytes = (pages * cfg["engine"]["page_size"]
                  * flops_window.kv_bytes_per_token_layer(cfg))
    least = live_bytes / flops.peaks(
        ctx["device"]["kind"])["hbm_bytes_per_s"]
    seconds, count = got
    return 100.0 * least / (seconds / count)
