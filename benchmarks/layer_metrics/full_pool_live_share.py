"""Engine scheduler: ``window_pool_live_share`` for the full layers'
pool (``full_pool_live_pages`` over ``num_pages`` - 1)."""
from . import window_pool_live_share as window


def read(ctx: dict):
    return window.read(ctx, "full_pool_live_pages", "num_pages")
