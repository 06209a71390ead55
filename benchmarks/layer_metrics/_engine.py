"""Arithmetic shared by the readers of the engine's phase times and
dispatch counters (``ray_tpu/llm/paged_engine.py`` ``PHASES`` and
``stats``). A program that has no such counter, as every commit before
PR 24, gives None and the metric is left out of the line.

Two spans: the measured window (``ctx["stats_before"]`` / ``["stats_after"]``)
and, since PR 33, the traced slice inside it (the same two keys of
``ctx["trace"]``)."""


def deltas(ctx: dict) -> dict:
    """{key: after - before} for every integer counter both snapshots
    hold; empty when a snapshot is missing. Over the measured WINDOW:
    what a reader of counters alone takes."""
    a, b = ctx.get("stats_before"), ctx.get("stats_after")
    if not a or not b:
        return {}
    return {k: b[k] - a[k] for k in b
            if k in a and isinstance(b[k], int) and isinstance(a[k], int)}


def slice_deltas(ctx: dict) -> dict:
    """The same over the traced SLICE: the snapshots the replica takes as
    the profiler starts and stops (``serve_app.py`` ``trace_start`` /
    ``trace_stop``). What a reader takes that divides a counter by a
    kernel's traced time, so that both halves cover the same seconds;
    empty for a trace without them — never the window's in their place."""
    return deltas(ctx.get("trace") or {})


def per(ctx: dict, num: str, den: str, scale: float = 1.0, over=deltas):
    """scale x delta ``num`` / delta ``den``; None where either counter is
    missing or nothing was counted. ``over=slice_deltas`` for the slice."""
    d = over(ctx)
    if num not in d or not d.get(den):
        return None
    return scale * d[num] / d[den]
