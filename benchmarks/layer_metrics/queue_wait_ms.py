"""Engine scheduler: mean time a request waited, submitted, for a slot and
pages (``admit_t - submit_t`` on the request, exact). Counters
``queue_wait_ns`` / ``admitted``."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "queue_wait_ns", "admitted", 1e-6)
