"""Arithmetic shared by the readers of the engine's phase times and
dispatch counters (``ray_tpu/llm/paged_engine.py`` ``PHASES`` and
``stats``). A program that has no such counter, as every commit before
PR 24, gives None and the metric is left out of the line."""


def deltas(ctx: dict) -> dict:
    """{key: after - before} for every integer counter both snapshots
    hold; empty when a snapshot is missing."""
    a, b = ctx.get("stats_before"), ctx.get("stats_after")
    if not a or not b:
        return {}
    return {k: b[k] - a[k] for k in b
            if k in a and isinstance(b[k], int) and isinstance(a[k], int)}


def per(ctx: dict, num: str, den: str, scale: float = 1.0):
    """scale x delta ``num`` / delta ``den``; None where either counter is
    missing or nothing was counted."""
    d = deltas(ctx)
    if num not in d or not d.get(den):
        return None
    return scale * d[num] / d[den]
