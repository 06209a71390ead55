"""``front_overhead_ms`` where it moves the chat cells' own end-to-end metric."""
from .front_overhead_ms import read  # noqa: F401
