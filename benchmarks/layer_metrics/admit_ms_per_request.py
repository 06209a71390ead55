"""Engine scheduler (llm/paged_engine.py ``_admit``, span
``rtpu.engine.admit``): the stepping thread's time in admission per request
admitted: the wait for the pool lock, the prefix match over the prompt's
page hashes, promotion from the spill tier, the page claim. Counters
``ns_admit`` / ``admitted``.

Admission is pure Python. Until PR 33 the traced run's profiler session
traced every Python call and the value was about twice the untraced
program's (1.74 and 1.38 ms traced against 0.72 and 0.76 from the counters
of untraced runs on the same seeds); since PR 33 the slice is traced without
the Python tracer, and the level in the ledger breaks there."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "ns_admit", "admitted", 1e-6)
