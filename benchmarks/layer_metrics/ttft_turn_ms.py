"""Engine scheduler: client-side TTFT of a session's LATER turns
(``growing_sessions`` tags each record ``turn``; turn 0 prefills a whole
context), median: what a turn pays for the history it resends — a resumed
snapshot and ~0.5k tokens, or the whole history."""
import numpy as np


def read(ctx: dict):
    xs = [r.ttft_ms for r in ctx.get("records", [])
          if r.ok and r.tags.get("turn", 0) >= 1]
    return float(np.median(xs)) if xs else None
