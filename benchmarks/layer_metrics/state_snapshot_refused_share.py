"""Cache: snapshots a prefill row wanted to file and the pool refused
(every snapshot pinned) over those wanted (counters
``state_snapshots_refused`` / (+ ``state_snapshots_taken``)). The request is
never refused: its next turn re-runs more. None for a program without the
counters or a window that wanted none."""
from ._engine import deltas


def read(ctx: dict):
    d = deltas(ctx)
    if "state_snapshots_refused" not in d:
        return None
    wanted = d["state_snapshots_refused"] + d["state_snapshots_taken"]
    return 100.0 * d["state_snapshots_refused"] / wanted if wanted else None
