"""``prefill_launch_ms`` where it moves this cell's own end-to-end metric."""
from .prefill_launch_ms import read  # noqa: F401
