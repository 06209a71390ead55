"""Device: 1 - union of operation intervals / traced slice, on the chip
that was busy least. What the ``device`` key's busy_s and window_s give."""
from ._common import trace


def read(ctx: dict):
    t = trace(ctx)
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s_worst"] / t["window_s"])
