"""``decode_prog_dev_ms`` where it moves this cell's own end-to-end metric."""
from .decode_prog_dev_ms import read  # noqa: F401
