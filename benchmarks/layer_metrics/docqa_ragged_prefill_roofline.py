"""``ragged_prefill_roofline`` where it moves this cell's own end-to-end
metric."""
from .ragged_prefill_roofline import read  # noqa: F401
