"""Arithmetic shared by the readers of a decoder with two kinds of layer
(``window_*`` / ``full_*``): the two ragged kernels told apart by name in the traced
slice, and the engine's counters a pool kind (``ray_tpu/llm/paged_engine.py``
``stats``, the keys a model with sliding-window layers adds). A program
without the kernel or the counters gives None."""
import re

from ._common import trace

# ``name:shape`` of the traced slice's operations. The sliding layers'
# kernel carries ``_window`` in its name; the full layers' is the plain
# ragged kernel, neither that nor the latent form
KERNEL = {"window": r"^ragged_paged_attention_window",
          "full": r"^ragged_paged_attention(?!_window|_latent)"}
DECODE = r"[^:]*:\w+\[\d+,1,"            # a query window of 1
PREFILL = r"[^:]*:\w+\[\d+,(?!1,)\d+,"   # of chunk_size


def calls(ctx: dict, kind: str, shape: str):
    """(seconds, count) of the slice's calls of the ``kind`` layers' kernel
    at a shape; None when there is no trace or no such call."""
    t = trace(ctx)
    if t is None or ctx.get("rehearse"):
        return None
    rx = re.compile(KERNEL[kind] + shape)
    hit = [(s, n) for name, s, n, *_ in t["ops"] if rx.match(name)]
    seconds, count = sum(h[0] for h in hit), sum(h[1] for h in hit)
    return (seconds, count) if seconds and count else None
