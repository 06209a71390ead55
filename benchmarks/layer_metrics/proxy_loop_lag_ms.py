"""HTTP front and router (serve/proxy.py ``_watch_loop_lag``): how late a
100 ms sleep on the proxy's event loop woke, mean between the run's two
readings — what every ``await`` of a request's intake and of a chunk's
write waits behind. ``rtpu_serve_proxy_loop_lag_seconds``
(``requests["loop_lag"]``); None where the loop's watcher observed nothing
between the readings."""
from ._front import _group, between


def read(ctx: dict):
    before, after = _group(ctx, "loop_lag")
    if not after:
        return None
    n, seconds = between(before or None, after)
    return 1e3 * seconds / n if n > 0 else None
