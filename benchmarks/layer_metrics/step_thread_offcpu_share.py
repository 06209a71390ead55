"""Engine scheduler (llm/paged_engine.py ``step()``, llm/serving.py
``_loop``): the share of its working time the stepping thread stood
runnable without the interpreter. ``util/profiling.phase`` books every
phase's wall time (``ns_*``, the launches' also in ``launch_ns_*``), and
``engine_stats()`` reads the thread's CPU clock from outside the thread at
each snapshot (``step_thread_cpu_ns``). A host phase (admit, build, post,
telemetry, loop other) and a launch wait for nothing but the interpreter,
so their wall time less the thread's CPU time is time the thread wanted to
run and did not: the GIL held by a stream thread, a lock, the kernel's
scheduler. The readbacks' waits (the rest of the ``*_device`` phases) wait
for the device and are left out of the wall time; the little CPU they take
(the copy at a wait's end) is in the thread's clock all the same, so the
share errs low by that much. Working time, the denominator, is every
``ns_*`` but ``ns_loop_idle``, as in ``engine_host_share``."""
from ._engine import deltas


def read(ctx: dict):
    d = deltas(ctx)
    work = {k: v for k, v in d.items()
            if k.startswith("ns_") and k != "ns_loop_idle"}
    total = sum(work.values())
    if not total or "step_thread_cpu_ns" not in d:
        return None
    wall = sum(v for k, v in work.items() if not k.endswith("_device"))
    wall += sum(d.get(f"launch_ns_{f}", 0) for f in ("prefill", "decode"))
    return 100.0 * (wall - d["step_thread_cpu_ns"]) / total
