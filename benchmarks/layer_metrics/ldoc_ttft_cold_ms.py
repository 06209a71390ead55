"""``docqa_ttft_cold_ms`` where it moves this cell's own end-to-end metric."""
from .docqa_ttft_cold_ms import read  # noqa: F401
