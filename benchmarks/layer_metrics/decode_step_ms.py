"""Engine scheduler (llm/paged_engine.py ``_decode_step``, span
``rtpu.engine.decode.device``): the stepping thread's time in a decode
dispatch, from the jitted call through the blocking readback, per device
step. A dispatch runs a window of 1 step while a prompt waits and of
``decode_window`` steps otherwise, so the time per dispatch (and
``decode_prog_dev_ms``, a median over both programs) moves with the mixture;
this does not. Launch and readback latency are inside it, so it sits a
little above the device's own time a step. Counters ``ns_decode_device`` /
``decode_steps``. Nearly all of it is the device's time, so the profiler
session of a traced run, which slows the Python around the call, moves it
little (63.6 ms traced, 65.0 from the counters of an untraced run on the
same seed, whose window held other documents)."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "ns_decode_device", "decode_steps", 1e-6)
