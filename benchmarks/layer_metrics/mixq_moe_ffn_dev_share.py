"""``gen_moe_ffn_dev_share`` where it moves this cell's own end-to-end metric."""
from .gen_moe_ffn_dev_share import read  # noqa: F401
