"""Engine scheduler (llm/paged_engine.py ``_launch_prefill``): prompt tokens
a prefill dispatch carried, over the window. Every layer's weights — of a
routed model, every expert's — are streamed once a dispatch whatever it
carries, so this is what the stream is paid by; it is at most the engine's
row budget x ``chunk_size`` (since PR 45 derived from the model's routing:
512 for a dense model, up to 2,048 for a routed one), and
``prefill_prog_dev_ms`` is the time of ONE dispatch: read the two
together. Counters ``prefill_tokens`` / ``prefill_dispatches``; None on a
program without them or over a window with no prefill."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "prefill_tokens", "prefill_dispatches")
