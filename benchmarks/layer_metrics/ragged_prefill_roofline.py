"""Kernels (ops/ragged_paged_attention.py at the prefill shape, a query
window of ``chunk_size``): the least time the chip could take for one
row's attention in one layer over the time the kernel took for it.

A prefill program calls the kernel once per row and layer. Least, per live
row and layer, from the counters over the traced slice
(``_engine.slice_deltas``): FLOPs = 4 x heads x
head_dim x the causal (query, key) pairs a row scores (``prefill_attn_pairs``
/ ``prefill_rows_live``: QK^T and PV, 2 FLOPs each); bytes = the keys and
values of the pages the row attends, read once (``prefill_ctx_pages`` x
``page_size`` x one layer's share of ``flops.kv_bytes_per_token``), plus
the row's queries read and outputs written (``prefill_tokens``); the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s, taken on the slice's
means, which is never above the mean of the rows' own bounds. Measured: in
the traced slice, the self time per call of the ragged kernel's calls whose
query window is above 1, x ``prefill_rows_padded`` / ``prefill_rows_live``
(a pad row's call is time the live rows pay for). None when the run was not
traced, or its trace carries no snapshots of the counters at the slice's
ends: the window's counters are never taken in their place.

Until PR 33 the counters covered the 50 s window and the kernel time the 4 s
slice. A slice holds the chunks of three or four documents, whose mean
cache is 10-20% off the window's: the latent twin of this reader read 86.3
to 101.4 in five traced runs of a kernel that runs at 75-81% of the MXU's
peak alone (my chip runs, PR 32), and 105 is where the driver refuses a
run."""
import re

from .. import flops
from ._common import trace
from ._engine import slice_deltas

PREFILL_SHAPE = re.compile(r"^ragged[^:]*:\w+\[\d+,(?!1,)\d+,")


def read(ctx: dict):
    t, d = trace(ctx), slice_deltas(ctx)
    rows = d.get("prefill_rows_live")
    if t is None or not rows or ctx.get("rehearse") or not all(
            k in d for k in ("prefill_rows_padded", "prefill_tokens",
                             "prefill_ctx_pages", "prefill_attn_pairs")):
        return None
    calls = [(s, n) for name, s, n, *_ in t["ops"] if PREFILL_SHAPE.match(name)]
    seconds, count = sum(c[0] for c in calls), sum(c[1] for c in calls)
    if not seconds or not count:
        return None
    cfg = ctx["config"]
    hd, dtype_bytes = flops.head_dim(cfg), 2
    fl = 4.0 * cfg["num_attention_heads"] * hd * d["prefill_attn_pairs"] / rows
    kv = (d["prefill_ctx_pages"] / rows * cfg["engine"]["page_size"]
          * flops.kv_bytes_per_token(cfg, dtype_bytes)
          / cfg["num_hidden_layers"])
    qo = (2.0 * d["prefill_tokens"] / rows * cfg["num_attention_heads"]
          * hd * dtype_bytes)
    least = flops.roofline_min_s(
        fl, kv + qo, flops.peaks(ctx["device"]["kind"]))[0]
    per_live_row = seconds / count * d["prefill_rows_padded"] / rows
    return 100.0 * least / per_live_row
