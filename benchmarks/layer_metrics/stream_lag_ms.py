"""HTTP front and router, the replica's end of it (llm/serving.py
``completions_stream``): a chunk's delivery time inside the replica, from
the booking that put its newest token on the host (the stepping thread's
stamp) to the moment its transport has taken the chunk — the wait for the
next launch's wake-up, the stream thread's turn at the interpreter, the
detokenisation and the transport's write together; the ring's reader, the
proxy and the socket come after it. Counters ``stream_lag_ns`` /
``stream_chunks`` over the window."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "stream_lag_ns", "stream_chunks", 1e-6)
