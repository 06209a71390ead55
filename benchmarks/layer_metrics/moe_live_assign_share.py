"""Engine scheduler (llm/paged_engine.py, counters ``moe_assign_live`` /
``moe_assign_run``): of the token-expert assignments the programs routed
in the window, the share that belonged to live decode rows and real prompt
tokens; the rest were idle decode rows and the padding of prefill chunks
and rows, which an expert layer computes like any other token. None for a
program without the counters (a dense configuration, or a commit before
PR 27)."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "moe_assign_live", "moe_assign_run", 100.0)
