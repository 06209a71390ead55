"""``step_thread_offcpu_share`` where it moves this cell's own end-to-end metric."""
from .step_thread_offcpu_share import read  # noqa: F401
