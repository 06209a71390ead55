"""Output tokens delivered to clients inside the window, per second of
window. In a closed loop this is the reciprocal of mean latency times the
number of callers."""


def read(ctx: dict):
    w = ctx["window"]
    tokens = sum(n for r in ctx["all_records"] if not r.error
                 for t, n in r.chunk_times if w.start <= t < w.end)
    return tokens / w.seconds
