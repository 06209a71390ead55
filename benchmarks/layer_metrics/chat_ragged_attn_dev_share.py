"""``ragged_attn_dev_share`` where it moves the chat cells' own end-to-end metric."""
from .ragged_attn_dev_share import read  # noqa: F401
