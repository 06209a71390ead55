"""``ragged_decode_sweep_fill`` where it moves this cell's own end-to-end metric."""
from .ragged_decode_sweep_fill import read  # noqa: F401
