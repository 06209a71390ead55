"""``device_idle_share`` where it moves this cell's own end-to-end metric."""
from .device_idle_share import read  # noqa: F401
