"""``admit_ms_per_request`` where it moves this cell's own end-to-end metric."""
from .admit_ms_per_request import read  # noqa: F401
