"""``prefill_span_ms`` where it moves this cell's own end-to-end metric."""
from .prefill_span_ms import read  # noqa: F401
