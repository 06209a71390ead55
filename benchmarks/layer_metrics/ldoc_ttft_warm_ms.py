"""``docqa_ttft_warm_ms`` where it moves this cell's own end-to-end metric."""
from .docqa_ttft_warm_ms import read  # noqa: F401
