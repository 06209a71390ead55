"""Engine scheduler: client-side TTFT of a session's later questions (the
document's pages come from the prefix cache), median."""
from .ttft_cold_ms import read as _read


def read(ctx: dict):
    return _read(ctx, first=False)
