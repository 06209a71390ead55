"""Cache: prompt tokens an admitted request still had to prefill behind
the snapshot it resumed (counters ``state_rerun_tokens`` / ``admitted``):
near the previous answer + the new message + under a page (~0.5k) for a
later turn, the whole context for a first one. None for a program without
the counter."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "state_rerun_tokens", "admitted")
