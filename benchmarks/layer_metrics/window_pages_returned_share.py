"""Engine scheduler (llm/paged_engine.py ``_hand_back`` / ``_release``):
window pages given back to their pool over window pages claimed from it, in
the window (counters ``window_pages_returned`` / ``window_pages_claimed``).
Near 100 in steady state — every page claimed is returned, at the latest
when its sequence ends; the rest is what the live sequences hold at the
window's close. None for a program without the counters."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "window_pages_returned", "window_pages_claimed", 100.0)
