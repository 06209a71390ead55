"""HTTP front and router, the replica's end of it (llm/serving.py
``completions_stream``, one thread a stream): the stream threads' CPU time
over the window's seconds, in per cent of one core — so of the one
interpreter the stepping thread needs for its launches. A stream thread's
clock runs from one chunk's hand-over to the next, over its wake-ups, the
detokenisation of the whole answer and the transport's write
(``RingWriter.write`` on the replica's drain thread). Counters
``stream_cpu_ns`` / ``clock_ns`` over the window."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "stream_cpu_ns", "clock_ns", 100.0)
