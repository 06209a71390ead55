"""``stream_lag_ms`` where it moves this cell's own end-to-end metric."""
from .stream_lag_ms import read  # noqa: F401
