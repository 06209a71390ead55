"""Time to first token as the client sees it: from the instant a request
was due to the first SSE chunk that carries a token; 90th percentile over
the requests due in the window. A failed request counts as infinite."""
import numpy as np


def read(ctx: dict, q: float = 90):
    rs = ctx["records"]
    return float(np.percentile([r.ttft_ms for r in rs], q)) if rs else None
