"""Cache: snapshots the pool held (pinned or parked under their hash) as a
share of ``num_state_snapshots``, averaged over the window's decode
bookings (counter ``state_pool_live`` / ``decode_dispatches``). At 100 every
new snapshot reclaims the oldest. None for a program without the
counter."""
from ._engine import per


def read(ctx: dict):
    live = per(ctx, "state_pool_live", "decode_dispatches")
    if live is None:
        return None
    return 100.0 * live / ctx["config"]["engine"]["num_state_snapshots"]
