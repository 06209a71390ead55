"""Engine scheduler (llm/paged_engine.py ``_launch_prefill``, span
``rtpu.engine.prefill.launch``): the stepping thread's time inside the
jitted call that launches a prefill program (argument flattening, the
dispatch, and the wake-up of the streams), per prefill dispatch. A launch
waits for nothing but the interpreter: where it takes many times the
milliseconds it takes a thread that has the interpreter to itself, the
thread stood among awake stream threads, and the device, which the program
had not reached yet, stood idle meanwhile (``idle_gaps`` named
``rtpu.engine.prefill.launch``). Counters ``launch_ns_prefill`` /
``prefill_dispatches`` over the window; the readback's wait
(``rtpu.engine.prefill.wait``) is the rest of ``ns_prefill_device``."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "launch_ns_prefill", "prefill_dispatches", 1e-6)
