"""HTTP front and router, the rings: ``ring_hop_first_ms`` for EVERY item
of every stream — what a token waits between a writer's stamp and its
reader, both rings summed. ``rtpu_serve_chunk_seconds_total`` over
``rtpu_serve_chunk_events_total``, stage ``hop`` (``requests["chunks"]``),
between the run's two readings: the generators add their sums as a stream
settles. None unless both rings counted at least an item a request."""
from ._front import stage_ms


def read(ctx: dict):
    return stage_ms(ctx, "hop", "chunks")
