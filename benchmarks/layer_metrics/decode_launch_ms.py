"""Engine scheduler (llm/paged_engine.py ``_launch_decode`` and the verify
path of ``_spec_step``, span ``rtpu.engine.decode.launch``): the stepping
thread's time inside the jitted call that launches a decode (or verify)
program, per such dispatch; see ``prefill_launch_ms``. Counters
``launch_ns_decode`` / (``decode_dispatches`` + ``spec_dispatches``) over
the window."""
from ._engine import deltas


def read(ctx: dict):
    d = deltas(ctx)
    launches = d.get("decode_dispatches", 0) + d.get("spec_dispatches", 0)
    if "launch_ns_decode" not in d or not launches:
        return None
    return 1e-6 * d["launch_ns_decode"] / launches
