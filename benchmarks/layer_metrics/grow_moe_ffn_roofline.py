"""Kernels (ops/grouped_matmul.py at the decode shape, a share of the
experts held): ``gen_moe_ffn_roofline``'s measured half — the decode-shape
calls of ``grouped_swiglu`` and ``grouped_matmul``, a ``grouped_swiglu``
call — against the least time for what falls on the experts HELD: of
``max_batch_size`` rows x ``num_experts_per_tok`` assignments a quarter
reach the 128 of 512 (``flops_gdn.held_assignments``), so the bytes are
the weights of the held experts hit plus those assignments' rows
(``flops_gdn.held_ffn_bytes``). The experts hit are COUNTED, not
estimated: the program hands back the distinct held experts each layer of
each step reached (counter ``moe_held_hit_decode`` / (``decode_steps`` x
``num_hidden_layers``) over the traced slice) — routing of seeded random
weights is skewed and reaches fewer than uniform routing's ~91 of 128, and
a least time from that estimate read 108.7% of the kernels' own (my chip
run, PR 47). None when the run was not traced or the program has
no such kernel or counter."""
from .. import flops, flops_gdn
from ._common import trace
from ._engine import per, slice_deltas
from .gen_moe_ffn_roofline import CALL


def read(ctx: dict):
    t = trace(ctx)
    cfg = ctx.get("config") or {}
    hit = per(ctx, "moe_held_hit_decode", "decode_steps", over=slice_deltas)
    if t is None or ctx.get("rehearse") or hit is None:
        return None
    hit /= cfg["num_hidden_layers"]
    calls: dict = {}
    for name, seconds, count, *_ in t["ops"]:
        m = CALL.match(name)
        if m:
            calls.setdefault(m.group(1), []).append(
                (int(m.group(2)), seconds, count))
    if set(calls) != {"grouped_swiglu", "grouped_matmul"}:
        return None
    decode = {k: min(v) for k, v in calls.items()}      # fewest rows
    steps = decode["grouped_swiglu"][2]
    seconds = sum(v[1] for v in decode.values())
    if not steps or not seconds:
        return None
    rows = cfg["engine"]["max_batch_size"]
    least, _ = flops.roofline_min_s(
        flops_gdn.held_ffn_flops(cfg, rows),
        flops_gdn.held_ffn_bytes(cfg, rows, hit),
        flops.peaks(ctx["device"]["kind"]))
    return 100.0 * least / (seconds / steps)
