"""Arithmetic shared by the readers of the front path's clock
(``ray_tpu/serve/metrics.py``: ``rtpu_serve_front_stage_seconds``,
``rtpu_serve_chunk_*_total``, ``rtpu_serve_proxy_loop_lag_seconds``, PR 53)
as ``serve.metrics_summary()["requests"]`` folds them — ``front``:
{stage: {deployment: {count, mean, ..}}}, ``chunks``: the same of every
streamed item, ``loop_lag``: {count, mean, p99} — and as they travel:
``ctx["serve_summary"]``, the reading before the first request and the one
after the last has ended and the ~2 s flush has passed, so a value is the
difference of the two. A program without the series, as every commit before
PR 53, gives None and the metric is left out of the line."""


def client_requests(ctx: dict) -> int:
    """Requests of the whole run that got a first token chunk."""
    return sum(1 for r in ctx.get("all_records", []) if r.first)


def _group(ctx: dict, name: str):
    before, after = ctx.get("serve_summary") or (None, None)
    return tuple(((s or {}).get("requests") or {}).get(name) or {}
                 for s in (before, after))


def between(before, after):
    """(count, seconds) a {count, mean} reading grew by since ``before``
    (None: the series did not exist yet)."""
    before = before or {"count": 0, "mean": 0.0}
    return (after["count"] - before["count"],
            after["mean"] * after["count"]
            - before["mean"] * before["count"])


def stage_ms(ctx: dict, stage: str, group: str = "front"):
    """A stage's mean between the two readings in ms, summed over the
    deployments it is tagged with (a request crosses two handles and two
    rings: ``open``, ``first_hop`` and ``hop`` have two). None unless each
    of them counted at least the client's requests: a process that had not
    flushed would leave its requests out."""
    n_client = client_requests(ctx)
    before, after = _group(ctx, group)
    if not n_client or stage not in after:
        return None
    total, counted = 0.0, False
    for dep, stats in after[stage].items():
        n, seconds = between((before.get(stage) or {}).get(dep), stats)
        if not n:
            continue        # a deployment the run did not call
        if n < n_client:
            return None
        total += 1e3 * seconds / n
        counted = True
    return total if counted else None
