"""Engine scheduler: pages of the sliding layers' pool that some sequence
holds, as a share of the pool, averaged over the window's decode
dispatches (counter ``window_pool_live_pages``, summed at every decode
booking, / ``decode_dispatches`` / (``num_window_pages`` - 1: page 0 is
the sink)). The rest is free or holds what the prefix cache published.
None for a program without the counter."""
from ._engine import per

COUNTER, POOL = "window_pool_live_pages", "num_window_pages"


def read(ctx: dict, counter: str = COUNTER, pool: str = POOL):
    live = per(ctx, counter, "decode_dispatches")
    if live is None:
        return None
    return 100.0 * live / (ctx["config"]["engine"][pool] - 1)
