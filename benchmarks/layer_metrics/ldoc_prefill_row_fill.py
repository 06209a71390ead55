"""``prefill_row_fill`` where it moves this cell's own end-to-end metric."""
from .prefill_row_fill import read  # noqa: F401
