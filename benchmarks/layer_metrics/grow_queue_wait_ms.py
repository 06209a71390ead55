"""``queue_wait_ms`` where it moves this cell's own end-to-end metric."""
from .queue_wait_ms import read  # noqa: F401
