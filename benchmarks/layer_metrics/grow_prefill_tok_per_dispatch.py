"""``prefill_tok_per_dispatch`` where it moves this cell's own end-to-end metric."""
from .prefill_tok_per_dispatch import read  # noqa: F401
