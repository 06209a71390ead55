"""Engine scheduler: client-side TTFT of a session's first question (the
document is prefilled), median."""
import numpy as np


def read(ctx: dict, first: bool = True):
    xs = [r.ttft_ms for r in ctx.get("records", [])
          if r.ok and "question" in r.tags
          and (r.tags["question"] == 0) == first]
    return float(np.median(xs)) if xs else None
