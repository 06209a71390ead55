"""``mixq_prefix_hit_tok_share`` where it moves this cell's own end-to-end metric."""
from .mixq_prefix_hit_tok_share import read  # noqa: F401
