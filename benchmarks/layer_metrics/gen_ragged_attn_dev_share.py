"""``ragged_attn_dev_share`` where it moves this cell's own end-to-end metric."""
from .ragged_attn_dev_share import read  # noqa: F401
