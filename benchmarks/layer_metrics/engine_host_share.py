"""Engine scheduler (llm/paged_engine.py ``step()``, llm/serving.py
``_loop``): the share of the stepping thread's working time in which no
dispatch was outstanding. The thread's time is partitioned into the
``ns_*`` counters (spans ``rtpu.engine.*`` / ``rtpu.loop.*``); working time
is all of them but ``ns_loop_idle`` (no request to serve), and host time is
working time outside the ``*_device`` phases (the jitted call through the
blocking readback). Set beside ``device_idle_share``: equal means the host
phases explain the device's idle time; smaller means the rest is launch and
readback latency inside the ``*_device`` phases.

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
