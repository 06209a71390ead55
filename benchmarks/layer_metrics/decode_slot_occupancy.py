"""Engine scheduler: slots with a live sequence over slots the decode
program runs (it computes all ``max_batch_size`` rows whatever is live).
Counters ``decode_live_slots`` / ``decode_dispatches``, and the
configuration's ``max_batch_size``."""
from ._engine import per


def read(ctx: dict):
    slots = per(ctx, "decode_live_slots", "decode_dispatches")
    if slots is None:
        return None
    return 100.0 * slots / ctx["config"]["engine"]["max_batch_size"]
