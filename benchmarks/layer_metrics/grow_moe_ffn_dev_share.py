"""``gen_moe_ffn_dev_share`` (ops/grouped_matmul.py's two kernels over
device busy time) where it moves this cell's own end-to-end metric: 128
held experts of 2048 x 512, the narrowest groups the kernels have met."""
from .gen_moe_ffn_dev_share import read  # noqa: F401
