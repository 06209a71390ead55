"""``device_idle_share`` where it moves the chat cells' own end-to-end metric."""
from .device_idle_share import read  # noqa: F401
