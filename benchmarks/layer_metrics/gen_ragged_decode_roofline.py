"""``ragged_decode_roofline`` where it moves this cell's own end-to-end metric."""
from .ragged_decode_roofline import read  # noqa: F401
