"""HTTP front and router, the replica's end of it (llm/serving.py, the
stream pump: since PR 39 ONE thread delivers every stream's chunks, a pass
a wake-up): the pump thread's CPU time over the window's seconds, in per
cent of one core — so of the one interpreter the stepping thread needs for
its launches. The pump's clock (``thread_time_ns``, read once a pass)
runs over its wake-ups, empty ones too, the detokenisation of every answer
and every sink's write (``RingWriter.write``). Counters ``stream_cpu_ns`` /
``clock_ns`` over the window."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "stream_cpu_ns", "clock_ns", 100.0)
