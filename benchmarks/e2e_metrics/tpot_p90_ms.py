"""Time per output token as the client sees it: per request (last token
chunk - first token chunk) / (tokens - 1), 90th percentile over the
requests due in the window. Gaps between single tokens are not used,
because the decode window delivers tokens in groups."""
import numpy as np


def read(ctx: dict, q: float = 90):
    rs = [r for r in ctx["records"] if r.want > 1]
    return float(np.percentile([r.tpot_ms for r in rs], q)) if rs else None
