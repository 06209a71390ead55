"""Engine scheduler: ``ttft_short_ms`` for the long class (a document
prefilled, or its cached prefix mapped in and a suffix prefilled)."""
from . import ttft_short_ms as short


def read(ctx: dict):
    return short.read(ctx, "long")
