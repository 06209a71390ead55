"""Engine scheduler (llm/paged_engine.py ``step()`` / ``_launched``): the
share of dispatches launched while another was still outstanding — how
often the engine runs ahead of its readbacks (PR 36). A prefill beside a
decode counts, and since PR 41 so does a decode launched behind an
unbooked decode (its rows are fed on the device from the last one's
tokens): in a steady decode nearly every launch does. Counters
``dispatches_overlapped`` / (``prefill_dispatches`` + ``decode_dispatches``
+ ``spec_dispatches``) over the window."""
from ._engine import deltas


def read(ctx: dict):
    d = deltas(ctx)
    launches = sum(d.get(f"{f}_dispatches", 0)
                   for f in ("prefill", "decode", "spec"))
    if "dispatches_overlapped" not in d or not launches:
        return None
    return 100.0 * d["dispatches_overlapped"] / launches
