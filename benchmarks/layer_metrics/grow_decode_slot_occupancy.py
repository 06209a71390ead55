"""``decode_slot_occupancy`` where it moves this cell's own end-to-end metric."""
from .decode_slot_occupancy import read  # noqa: F401
