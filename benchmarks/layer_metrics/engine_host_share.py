"""Engine scheduler (llm/paged_engine.py ``step()``, llm/serving.py
``_loop``): what the host phases cost a step — the share of the stepping
thread's working time spent outside the ``*_device`` phases. The thread's
time is partitioned into the ``ns_*`` counters (spans ``rtpu.engine.*`` /
``rtpu.loop.*``); working time is all of them but ``ns_loop_idle`` (no
request to serve), and host time is working time outside the ``*_device``
phases (the jitted call through the blocking readback). Since PR 36 the
engine launches ahead of its readbacks, and since PR 41 a decode behind an
unbooked decode: the host phases run beside an outstanding dispatch, so
this is no longer time the device waits and it bounds nothing. It RISES as
``device_idle_share`` falls — a thread that waits less for the device
spends more of its working time in host phases — and the two are read
apart: the idle gaps are named by phase in the ledger's
``breakdown.idle_gaps``.

The benchmark prints per-layer metrics only for a traced run. Until PR 33
the profiler session traced every Python call, and the host phases are
Python: the value was 1.3-2.4x what an untraced run's counters give. Since
PR 33 the slice is traced without the Python tracer (``serve_app.py``
``trace_start``): the level in the ledger breaks there. The partition holds
for a server that steps one engine (a per-LoRA engine books its ``step()``
to its own stats)."""
from ._engine import deltas


def read(ctx: dict):
    work = {k: v for k, v in deltas(ctx).items()
            if k.startswith("ns_") and k != "ns_loop_idle"}
    total = sum(work.values())
    if not total:
        return None
    host = sum(v for k, v in work.items() if not k.endswith("_device"))
    return 100.0 * host / total
