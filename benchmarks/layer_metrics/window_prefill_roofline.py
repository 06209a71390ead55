"""Kernels (the window form at the prefill shape, a query window of
``chunk_size``): the least time the chip could take for one live row's
attention in one sliding layer over the time the kernel took for it.

A prefill program calls the kernel once per row and layer. Least, per live
row and layer, from the counters over the traced slice
(``_engine.slice_deltas``): FLOPs = ``flops_window.attn_pair_flops`` x the
(query, key) pairs a row scores inside the window (``prefill_attn_wpairs``
/ ``prefill_rows_live``); bytes = the keys and values of the window pages
the row attends, read once (``prefill_ctx_wpages`` x ``page_size`` x
``flops_window.kv_bytes_per_token_layer``), plus the row's queries read and
outputs written (``prefill_tokens``); the larger of the two bounds.
Measured: the self time per call of the window kernel's calls whose query
window is above 1, x ``prefill_rows_padded`` / ``prefill_rows_live`` (a pad
row's call is time the live rows pay for). None when the run was not
traced, the program has no such kernel or counter, or the trace carries no
snapshots at the slice's ends."""
from .. import flops, flops_window
from . import _window
from ._engine import slice_deltas

NEEDS = ("prefill_rows_padded", "prefill_tokens", "prefill_ctx_wpages",
         "prefill_attn_wpairs")


def read(ctx: dict):
    d = slice_deltas(ctx)
    rows = d.get("prefill_rows_live")
    got = _window.calls(ctx, "window", _window.PREFILL)
    if got is None or not rows or not all(k in d for k in NEEDS):
        return None
    cfg = ctx["config"]
    fl = flops_window.attn_pair_flops(cfg) * d["prefill_attn_wpairs"] / rows
    kv = (d["prefill_ctx_wpages"] / rows * cfg["engine"]["page_size"]
          * flops_window.kv_bytes_per_token_layer(cfg))
    qo = (2.0 * d["prefill_tokens"] / rows * cfg["num_attention_heads"]
          * cfg["head_dim"] * 2)
    least = flops.roofline_min_s(
        fl, kv + qo, flops.peaks(ctx["device"]["kind"]))[0]
    seconds, count = got
    return 100.0 * least / (
        seconds / count * d["prefill_rows_padded"] / rows)
