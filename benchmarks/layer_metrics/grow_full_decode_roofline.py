"""Kernels (the plain ragged kernel at the decode shape, heads of 256):
``mixq_full_decode_roofline``'s arithmetic — the pages of every live token
(``decode_live_pages``) x one full layer's keys and values (2 KV heads of
256: 2,048 B a token) over the peak HBM rate, over the kernel's time a
call — where it moves this cell's own end-to-end metric."""
from . import mixq_window_decode_roofline as window


def read(ctx: dict):
    return window.read(ctx, "full", "decode_live_pages")
