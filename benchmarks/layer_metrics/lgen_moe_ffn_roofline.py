"""Kernels (ops/grouped_matmul.py at the decode shape, a share of the
experts held, group-limited routing): ``grow_moe_ffn_roofline``'s
arithmetic with this configuration's counts — the decode-shape calls of
``grouped_swiglu`` and ``grouped_matmul``, a ``grouped_swiglu`` call,
against the least time for what falls on the experts HELD
(``flops_kda.held_assignments``: of ``max_batch_size`` rows x
``num_experts_per_tok`` assignments an eighth reach the 64 of 512) with the
experts hit COUNTED by the program (counter ``moe_held_hit_decode`` /
(``decode_steps`` x the EXPERT layers: the dense prefix routes nothing)
over the traced slice), not all 64 and not an estimate: the bytes are
those experts' weights read once plus the assignments' rows
(``flops_kda.held_ffn_bytes``). None when the run was not traced or the
program has no such kernel or counter."""
from .. import flops, flops_kda
from ._common import trace
from ._engine import per, slice_deltas
from .gen_moe_ffn_roofline import CALL


def read(ctx: dict):
    t = trace(ctx)
    cfg = ctx.get("config") or {}
    hit = per(ctx, "moe_held_hit_decode", "decode_steps", over=slice_deltas)
    if t is None or ctx.get("rehearse") or hit is None:
        return None
    hit /= flops_kda.expert_layers(cfg)
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
        flops_kda.held_ffn_flops(cfg, rows),
        flops_kda.held_ffn_bytes(cfg, rows, hit),
        flops.peaks(ctx["device"]["kind"]))
    return 100.0 * least / (seconds / steps)
