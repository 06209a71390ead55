"""Kernels (the plain ragged kernel at the decode shape under the full
layers): ``mixq_window_decode_roofline`` for the other kind of layer — the
pages of every live token (``decode_live_pages``) x one layer's keys and
values over the peak HBM rate, over the time per call of the ragged kernel
that is not the window form."""
from . import mixq_window_decode_roofline as window


def read(ctx: dict):
    return window.read(ctx, "full", "decode_live_pages")
