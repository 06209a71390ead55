"""Engine scheduler (llm/paged_engine.py): tokens produced per decode
dispatch over the window, i.e. batch occupancy times the decode window."""
from ._common import delta


def read(ctx: dict):
    n = delta(ctx, "decode_dispatches")
    return delta(ctx, "tokens_out") / n if n else None
