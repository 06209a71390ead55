"""``stream_cpu_share`` where it moves this cell's own end-to-end metric."""
from .stream_cpu_share import read  # noqa: F401
