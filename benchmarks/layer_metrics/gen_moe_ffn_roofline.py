"""Kernels (ops/grouped_matmul.py at the decode shape): the least time the
chip could take for one layer's expert FFN of one decode step, over the
time its two kernels took per layer per step.

Least: the decode program routes all ``max_batch_size`` rows x
``num_experts_per_tok`` assignments, live or idle, so the experts hit are
``flops_moe.expected_experts_hit`` of that many tokens (uniform routing:
seeded random weights and tokens) and the bytes are their weights read once
plus the assignments' rows in and out (``flops_moe.expert_ffn_bytes``);
``flops.roofline_min_s`` takes the larger of bytes over the peak HBM rate
and operations over the peak bf16 rate — at these shapes the bytes, by 30x.
Measured: in the traced slice, the self time of the ``grouped_swiglu`` and
``grouped_matmul`` calls of the decode shape (the calls with the fewest
rows: a prefill chunk routes more tokens than the decode batch has rows),
per ``grouped_swiglu`` call, which is once a layer a step. None when the
run was not traced or the program has no such kernel."""
import re

from .. import flops, flops_moe
from ._common import trace

CALL = re.compile(r"^(grouped_swiglu|grouped_matmul)[^:]*:\w+\[(\d+),")


def read(ctx: dict):
    t = trace(ctx)
    if t is None or ctx.get("rehearse"):
        return None
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
    cfg = ctx["config"]
    rows = cfg["engine"]["max_batch_size"]
    least, _ = flops.roofline_min_s(
        flops_moe.expert_ffn_flops(cfg, rows),
        flops_moe.expert_ffn_bytes(cfg, rows),
        flops.peaks(ctx["device"]["kind"]))
    return 100.0 * least / (seconds / steps)
