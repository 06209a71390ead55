"""HTTP front and router (serve/handle.py ``DeploymentHandle``): the mean
time from a handle's call to the request's hand-over to a replica — the
replica-set refresh, a cold start — over the handle calls of the whole run,
warm-up included: the handle's share of ``front_overhead_ms``. From
``rtpu_serve_router_wait_seconds`` as ``serve.metrics_summary()`` folds it
(every process's handles: the proxy's to the router, the router's to the
model, the benchmark driver's own few): delta sum / delta count between
the reading before the first request and the one after the last has ended
and the ~2 s flush has passed (``ctx["serve_summary"]``, as ``engine_ttft``
travels). None unless the count covers the run's requests: the series of a
process that had not flushed would leave its requests out."""


def read(ctx: dict):
    before, after = ctx.get("serve_summary") or (None, None)
    rs = [r for r in ctx.get("all_records", []) if r.first]
    wait = (after or {}).get("router_wait")
    if not rs or not wait:
        return None
    base = (before or {}).get("router_wait") or {"count": 0, "mean": 0.0}
    n = wait["count"] - base["count"]
    if n < len(rs):
        return None
    return 1e3 * (wait["mean"] * wait["count"]
                  - base["mean"] * base["count"]) / n
