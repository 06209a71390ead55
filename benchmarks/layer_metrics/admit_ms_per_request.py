"""Engine scheduler (llm/paged_engine.py ``_admit``, span
``rtpu.engine.admit``): the stepping thread's time in admission per request
admitted: the wait for the pool lock, the prefix match over the prompt's
page hashes, promotion from the spill tier, the page claim. Counters
``ns_admit`` / ``admitted``.

Admission is pure Python, and the benchmark prints per-layer metrics only
for a traced run, whose profiler session traces every Python call: on the
chip it read 1.74 and 1.38 ms traced against 0.72 and 0.76 from the counters
of untraced runs on the same seeds. Compare a traced value with traced
values only; it is not the untraced program's cost."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "ns_admit", "admitted", 1e-6)
