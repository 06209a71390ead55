"""HTTP front and router, the replica's end of it (llm/serving.py, the
stream pump: one thread for every stream since PR 39): a chunk's delivery
time inside the replica, from the booking that put its newest token on the
host (the stepping thread's stamp) to the moment its sink has taken the
chunk, which is the sink's write having returned — the wait for the next
launch's wake-up (since PR 41 the NEXT step's: a booking is followed by
telemetry, admit, build and launch, ~10 ms), the stream's turn in the
pump's pass, the detokenisation, the write, and every pass a ring without
credit held the text back; the ring's reader, the proxy and the socket come
after it. Counters ``stream_lag_ns`` / ``stream_chunks`` over the window."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "stream_lag_ns", "stream_chunks", 1e-6)
