"""Kernels (the latent kernel at the prefill shape, a query window of
``chunk_size``): the least time the chip could take for one live row's
attention in one layer over the time the kernel took for it.

A prefill program of models/mla_moe.py calls the kernel once a layer with
all its R rows (``[R, chunk, heads, rank]``: R is in the operation's
shape). Least, per live row and layer, from the counters over the traced
slice (``_engine.slice_deltas``; the window's until PR 33):
FLOPs = ``flops_mla.attn_pair_flops`` x the causal (query, key) pairs a
row scores (``prefill_attn_pairs`` / ``prefill_rows_live``); bytes = the
latents of the pages the row attends, read once (``prefill_ctx_pages`` x
``page_size`` x ``flops_mla.latent_bytes_per_token_layer``), plus the row's
queries read (``latent_dim`` wide a head) and outputs written
(``kv_lora_rank`` wide a head) (``prefill_tokens``); the larger of the two
bounds (compute, by ~25x at a 128-row chunk). Measured: the latent
kernel's self time at windows above 1, over the rows its calls ran (calls x
R), x ``prefill_rows_padded`` / ``prefill_rows_live`` (a pad row's share of
a call is time the live rows pay for). None when the run was not traced,
the program has no such kernel, or the trace carries no snapshots of the
counters at the slice's ends."""
import re

from .. import flops, flops_mla
from ._engine import slice_deltas
from .latent_decode_roofline import kernel_calls

PREFILL_SHAPE = re.compile(
    r"^ragged_paged_attention_latent[^:]*:\w+\[(\d+),(?!1,)\d+,")


def read(ctx: dict):
    d = slice_deltas(ctx)
    rows = d.get("prefill_rows_live")
    calls = kernel_calls(ctx, PREFILL_SHAPE)
    if calls is None or not rows or not all(
            k in d for k in ("prefill_rows_padded", "prefill_tokens",
                             "prefill_ctx_pages", "prefill_attn_pairs")):
        return None
    cfg = ctx["config"]
    heads, dtype_bytes = cfg["num_attention_heads"], 2
    fl = flops_mla.attn_pair_flops(cfg) * d["prefill_attn_pairs"] / rows
    kv = (d["prefill_ctx_pages"] / rows * cfg["engine"]["page_size"]
          * flops_mla.latent_bytes_per_token_layer(cfg, dtype_bytes))
    qo = (d["prefill_tokens"] / rows * heads * dtype_bytes
          * (flops_mla.latent_dim(cfg) + cfg["kv_lora_rank"]))
    least, _ = flops.roofline_min_s(
        fl, kv + qo, flops.peaks(ctx["device"]["kind"]))
    rows_run = sum(int(PREFILL_SHAPE.match(n).group(1)) * c
                   for n, _, c in calls)
    per_live_row = (sum(c[1] for c in calls) / rows_run
                    * d["prefill_rows_padded"] / rows)
    return 100.0 * least / per_live_row
