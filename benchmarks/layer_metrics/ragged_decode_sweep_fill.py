"""Kernels (ops/ragged_paged_attention.py at the decode shape): the pages
that hold a live sequence's tokens over the pages of the key blocks the
decode call's sweeps step through for them — a live row's pages rounded up
to the call's key block; the keys of a last block's tail are scored under
a probability of 0 (and copied as duplicates of the last live page, or, in
the all-heads body, zeroed past the last live 128-key group). Counters
``decode_live_pages`` / ``decode_swept_pages`` (PR 48), over the window.
What a wider block costs: a live row wastes half a block on average, so the
fill falls as the block widens, and the decode roofline beside it says
whether the fall was worth paying (the block's width comes from the heads'
lanes and the table: ``window_step``). None on a program without the
counter (every commit before PR 48) or over a window with no decode."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "decode_live_pages", "decode_swept_pages", 100.0)
