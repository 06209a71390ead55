"""Engine scheduler (llm/paged_engine.py ``_match_window``): of the prompt
tokens the full layers' cached pages covered at admission, the share that
was prefilled all the same because the sliding layers' pages behind them
(the window tail) were gone — counter ``prefix_tail_tokens_lost`` over it
plus ``prefix_tokens_saved``. 0 while the window pool's LRU keeps the
tails of the documents in flight. None for a program without the counter,
or when the cache covered nothing."""
from ._engine import deltas


def read(ctx: dict):
    d = deltas(ctx)
    if "prefix_tail_tokens_lost" not in d:
        return None
    covered = d["prefix_tail_tokens_lost"] + d.get("prefix_tokens_saved", 0)
    return 100.0 * d["prefix_tail_tokens_lost"] / covered if covered else None
