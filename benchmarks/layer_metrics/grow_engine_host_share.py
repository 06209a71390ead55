"""``engine_host_share`` where it moves this cell's own end-to-end metric."""
from .engine_host_share import read  # noqa: F401
