"""Cache (llm/kv_cache.py ``StateSlots``): the share of the admitted
requests' prompt tokens that a resumed state snapshot covered (counters
``state_hit_tokens`` / (``state_hit_tokens`` + ``state_rerun_tokens``) over
the window). A later turn resumes where the previous prompt's last whole
page ended, so with ~5-16k of history and ~0.5k new tokens a turn this
stands near 90 where snapshots work and near 0 where every turn re-runs its
history. None for a program without the counters."""
from ._engine import deltas


def read(ctx: dict):
    d = deltas(ctx)
    hit, rerun = d.get("state_hit_tokens"), d.get("state_rerun_tokens")
    if hit is None or rerun is None or not hit + rerun:
        return None
    return 100.0 * hit / (hit + rerun)
