"""Kernels (ops/ragged_paged_attention.py at the decode shape): the pages
that hold a live sequence's tokens over the pages of the block table the
decode program sweeps — every one of ``max_batch_size`` rows, live or
padding, x the table's width (the power-of-two page bucket of the longest
live sequence). Counters ``decode_live_pages`` / ``decode_table_pages``
(PR 25), over the window. What is left of 100 is the sweep's dead grid
steps: nothing is fetched or computed in them, and each still costs its
launch (0.07 us a step of 8 pages in PR 25's kernel, PERF.md §6). None on
a program without the counter (every commit before PR 25)."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "decode_live_pages", "decode_table_pages", 100.0)
