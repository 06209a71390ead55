"""Engine scheduler: client-side TTFT of the requests of one class of
session (``closed_sessions_mixed`` tags each record ``cls``), median — the
short class: what a short request pays behind the long ones' prefill."""
import numpy as np

CLS = "short"


def read(ctx: dict, cls: str = CLS):
    xs = [r.ttft_ms for r in ctx.get("records", [])
          if r.ok and r.tags.get("cls") == cls]
    return float(np.median(xs)) if xs else None
