"""Engine scheduler: mean time from admission to the first token inside the
engine (``first_token_t - admit_t`` on the request, exact): the request's
own prefill chunks and every other session's chunks and decode windows it
waited behind. With ``queue_wait_ms`` and ``front_overhead_ms`` it splits the
client's TTFT. Counters ``prefill_span_ns`` / ``first_tokens``."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "prefill_span_ns", "first_tokens", 1e-6)
