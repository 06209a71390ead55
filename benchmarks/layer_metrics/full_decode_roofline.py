"""Kernels (the plain ragged kernel at the decode shape under the full
layers): ``window_decode_roofline`` for the other kind of layer — the
pages of every live token (``decode_live_pages``) x one layer's keys and
values over the peak HBM rate, over the time per call of the ragged kernel
that is not the window form (models/llama.py's full layers beside sliding
ones: 4 KV heads of 128; models/qwen3_next.py's gated-attention layers: 2 KV
heads of 256, 2,048 B a token a layer in both)."""
from . import window_decode_roofline as window


def read(ctx: dict):
    return window.read(ctx, "full", "decode_live_pages")
