"""``dispatch_overlap_share`` where it moves this cell's own end-to-end metric."""
from .dispatch_overlap_share import read  # noqa: F401
