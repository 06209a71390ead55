"""Load generator (benchmark): how late requests were sent, send instant
minus due instant, 90th percentile. Above 5 ms the run is suspect: a
starved generator reads as a fast server."""
import numpy as np


def read(ctx: dict):
    rs = ctx.get("records")
    if not rs:
        return None
    return float(np.percentile([(r.sent - r.due) * 1e3 for r in rs], 90))
