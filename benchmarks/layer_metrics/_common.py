"""Arithmetic shared by readers."""


def delta(ctx: dict, key: str):
    a, b = ctx.get("stats_before"), ctx.get("stats_after")
    if a is None or b is None:
        return None
    return b[key] - a[key]


def trace(ctx: dict):
    t = ctx.get("trace")
    return t if t and t.get("devices") else None


def family_median_ms(ctx: dict, family: str):
    """Median device duration of one execution of the programs of a family
    (``reduce/families/<family>.json``), in ms."""
    t = trace(ctx)
    if t is None or family not in t["families"]:
        return None
    return t["families"][family]["median_s"] * 1e3


def kernel_share(ctx: dict, group: str):
    t = trace(ctx)
    if t is None or group not in t["kernels"] or not t["busy_s"]:
        return None
    return 100.0 * t["kernels"][group]["seconds"] / t["busy_s"]
