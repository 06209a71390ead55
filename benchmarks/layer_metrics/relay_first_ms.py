"""HTTP front and router, the router's relay (serve/controller.py, the
``serve-stream-chan-<sid>`` drain thread that pulls ``llm/openai_api.py``
``_sse``): for a stream's first item, from the upstream ring's read having
returned to the stamp of the thread's own ring write — the SSE
re-encoding; the write itself lies in the second ring's hop
(``ring_hop_first_ms``), which begins at that stamp. Stage ``first_relay``
between the run's two readings; None unless it counted the client's
requests."""
from ._front import stage_ms


def read(ctx: dict):
    return stage_ms(ctx, "first_relay")
