"""HTTP front and router (serve/proxy.py, serve/handle.py,
llm/openai_api.py): mean client-side TTFT (first token chunk - send) minus
mean engine-side TTFT (``rtpu_llm_ttft_seconds`` sum/count, the exact part
of that histogram), both over every request of the run, warm-up included.
The histogram reaches the head on a ~2 s cadence, so it is read before the
first request and after the last has ended; unless its count then equals
the client's, the two means are of different requests and nothing is
reported."""


def read(ctx: dict):
    before, after = ctx.get("engine_ttft") or (None, None)
    rs = [r for r in ctx.get("all_records", []) if r.first]
    if not rs or not after:
        return None
    before = before or {"count": 0, "mean": 0.0}
    n = after["count"] - before["count"]
    if n != len(rs):
        return None
    engine_mean = (after["mean"] * after["count"]
                   - before["mean"] * before["count"]) / n
    client_mean = sum(r.first - r.sent for r in rs) / len(rs)
    return (client_mean - engine_mean) * 1e3
