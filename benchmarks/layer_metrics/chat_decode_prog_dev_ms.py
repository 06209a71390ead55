"""``decode_prog_dev_ms`` where it moves the chat cells' own end-to-end metric."""
from .decode_prog_dev_ms import read  # noqa: F401
