"""Program families, the prefill side in EVERY run's line (the traced
slice, the window's last 4 s, may hold no prefill dispatch: PERF.md §7):
the stepping thread's time in prefill programs — their launches and the
blocking readbacks of their tokens — a prompt token prefilled, over the
window. Counters ``ns_prefill_device`` / ``prefill_tokens``
(``paged_engine.PHASES``: the key is worn by ``rtpu.engine.prefill.wait``
and the prefill launch). An upper bound on the device's own time a token:
a readback also waits out the decode queued ahead of its prefill (the
readbacks run one dispatch behind). It moves with the chunked GDN kernel,
the held experts' stream a dispatch and the tokens a dispatch carries."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "ns_prefill_device", "prefill_tokens", 1e-3)
