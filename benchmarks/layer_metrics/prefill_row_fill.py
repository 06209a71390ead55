"""Engine scheduler (llm/paged_engine.py ``_prefill_step``): rows of the
prefill program that carried a chunk of a prompt over rows the program ran.
The row count is bucketed to a power of two, so that the ladder holds
O(log ``prefill_rows``) programs; a pad row writes to the sink page and
costs its attention call and its matmuls. Counters ``prefill_rows_live`` /
``prefill_rows_padded``."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "prefill_rows_live", "prefill_rows_padded", 100.0)
