"""``gen_moe_load_max_over_mean`` where it moves this cell's own end-to-end metric."""
from .gen_moe_load_max_over_mean import read  # noqa: F401
