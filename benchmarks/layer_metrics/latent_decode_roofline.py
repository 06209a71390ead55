"""Kernels (the latent kernel at the decode shape, query window 1): the
least time the chip could take for one layer's attention of one decode step
over the time the kernel took per layer-step.

Least: the larger of (a) bytes — the pages that hold the live sequences'
tokens at a decode dispatch (counters ``decode_live_pages`` /
``decode_dispatches`` over the traced slice, ``_engine.slice_deltas``) x
``page_size`` x ``flops_mla.latent_bytes_per_token_layer`` (the 1,152 B attention
has to read, not the 1,280 B row the pool stores) over the peak HBM rate — and (b) operations — those live tokens x
``flops_mla.attn_pair_flops`` (one query row a live sequence; 32 heads on
one key make it 60 FLOP a byte, a quarter of the chip's ridge) over the
peak bf16 rate. Measured: in the traced slice, the self time per call of
the latent kernel's calls whose window is 1; one call is one layer of one
step. None when the run was not traced, the program has no such kernel, or
the trace carries no snapshots of the counters at the slice's ends (PR 33:
both halves cover the slice; ``ragged_prefill_roofline`` says why). A call
is one layer of one step whatever the number of such layers: every layer of
models/mla_moe.py, ONE in seven of models/ling_hybrid.py."""
import re

from .. import flops, flops_mla
from ._common import trace
from ._engine import per, slice_deltas

DECODE_SHAPE = re.compile(r"^ragged_paged_attention_latent[^:]*:\w+\[\d+,1,")


def kernel_calls(ctx: dict, shape: re.Pattern):
    """[(name, seconds, count)] of the traced slice's operations whose
    ``name:shape`` matches; None when there is no trace or no match."""
    t = trace(ctx)
    if t is None or ctx.get("rehearse"):
        return None
    calls = [(n, s, c) for n, s, c, *_ in t["ops"] if shape.match(n)]
    return calls if sum(c[2] for c in calls) else None


def read(ctx: dict):
    pages = per(ctx, "decode_live_pages", "decode_dispatches",
                over=slice_deltas)
    calls = kernel_calls(ctx, DECODE_SHAPE)
    if pages is None or calls is None:
        return None
    cfg = ctx["config"]
    tokens = pages * cfg["engine"]["page_size"]
    least, _ = flops.roofline_min_s(
        tokens * flops_mla.attn_pair_flops(cfg),
        tokens * flops_mla.latent_bytes_per_token_layer(cfg),
        flops.peaks(ctx["device"]["kind"]))
    return 100.0 * least / (sum(c[1] for c in calls)
                            / sum(c[2] for c in calls))
