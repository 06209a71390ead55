"""HTTP front and router, the replica's end of it (llm/serving.py, the
stream pump): ``stream_lag_ms`` for the chunk that carries a request's
first token, from that token's booking to the sink's write of the chunk
having returned (one pump thread serves every stream: the chunk waits its
turn in the pass). The engine's TTFT (``rtpu_llm_ttft_seconds``) ends at
the booking and the client's at the chunk's arrival: of
``front_overhead_ms``, their difference, this is the part spent inside the
replica; the rest is the ring, the proxy and the socket. Counters
``stream_first_lag_ns`` / ``stream_first_chunks`` over the window."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "stream_first_lag_ns", "stream_first_chunks", 1e-6)
