"""``decode_step_ms`` where it moves this cell's own end-to-end metric."""
from .decode_step_ms import read  # noqa: F401
