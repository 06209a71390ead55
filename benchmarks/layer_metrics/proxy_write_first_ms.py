"""HTTP front and router (serve/proxy.py, the stream loop): for a stream's
first chunk, from the stream thread's read having returned to ``await
stream.write(chunk)`` having returned on the proxy's event loop — the hop
from the thread pool back to the loop, and the loop's queue. Stage
``first_write`` between the run's two readings; None unless it counted the
client's requests."""
from ._front import stage_ms


def read(ctx: dict):
    return stage_ms(ctx, "first_write")
