"""``decode_tok_per_dispatch`` where it moves the chat cells' own end-to-end metric."""
from .decode_tok_per_dispatch import read  # noqa: F401
