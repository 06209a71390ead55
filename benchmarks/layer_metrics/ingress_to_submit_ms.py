"""HTTP front and router, the whole way in: a request's mean time from the
proxy's arrival stamp to the engine's submit stamp, the instant
``rtpu_llm_ttft_seconds`` starts at (``llm/telemetry.py`` ``on_submit``,
on the host's one clock). Client TTFT = [client send -> ``_dispatch``] +
this + the engine's TTFT + the way back. Stage ``to_submit`` between the
run's two readings; None unless it counted the client's requests."""
from ._front import stage_ms


def read(ctx: dict):
    return stage_ms(ctx, "to_submit")
