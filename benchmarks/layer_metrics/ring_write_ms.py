"""HTTP front and router, the replica's end of it (llm/serving.py
``LLMServer._deliver``): the stream pump's wall time inside ``sink.put`` —
over the ring: serialise, seal, wake the readers — a chunk its sink took.
The part of ``stream_lag_ms`` that is the write itself, beside the part
that is the wait for a pass. Counters ``stream_write_ns`` /
``stream_chunks`` over the window."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "stream_write_ns", "stream_chunks", 1e-6)
