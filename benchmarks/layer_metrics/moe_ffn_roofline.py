"""Kernels (ops/grouped_matmul.py at the decode shape): the least time the
chip could take for one layer's expert FFN of one decode step, over the
time its two kernels took per layer per step.

Measured, in every configuration: in the traced slice, the self time of the
``grouped_swiglu`` and ``grouped_matmul`` calls of the decode shape (the
calls with the fewest rows: a prefill chunk routes more tokens than the
decode batch has rows), per ``grouped_swiglu`` call, which is once a layer a
step. Least, by what the configuration's file says of its experts:

* every routed expert here (OLMoE, Mellum): the decode program routes all
  ``max_batch_size`` rows x ``num_experts_per_tok`` assignments, live or
  idle, so the experts hit are ``flops_moe.expected_experts_hit`` of that
  many tokens (uniform routing: seeded random weights and tokens) and the
  bytes are their weights read once plus the assignments' rows in and out
  (``flops_moe.expert_ffn_bytes``; the width of ONE expert is
  ``moe_intermediate_size`` where the file has that key, else
  ``intermediate_size``);
* a share of the experts held (``experts_routed`` in the file: Qwen3-Next's
  128 of 512, Ling's 64 of 512 behind group-limited routing): what falls on
  the experts HELD (``flops_gdn.held_assignments``), with the experts hit
  COUNTED, not estimated: the program hands back the distinct held experts
  each expert layer of each step reached (counter ``moe_held_hit_decode`` /
  (``decode_steps`` x the expert layers: a dense prefix routes nothing)
  over the traced slice) — routing of seeded random weights is skewed and
  reaches fewer than uniform routing's ~91 of 128, and a least time from
  that estimate read 108.7% of the kernels' own (my chip run, PR 47). The
  bytes are those experts' weights read once plus the assignments' rows
  (``flops_gdn.held_ffn_bytes``).

``flops.roofline_min_s`` takes the larger of bytes over the peak HBM rate
and operations over the peak bf16 rate — at these shapes the bytes, by 30x.
None when the run was not traced or the program has no such kernel or
counter."""
import re

from .. import flops, flops_gdn, flops_kda, flops_moe
from ._common import trace
from ._engine import per, slice_deltas

CALL = re.compile(r"^(grouped_swiglu|grouped_matmul)[^:]*:\w+\[(\d+),")


def _expert_layers(cfg: dict) -> int:
    if "first_k_dense_replace" in cfg:
        return flops_kda.expert_layers(cfg)
    return cfg["num_hidden_layers"]


def read(ctx: dict):
    t = trace(ctx)
    cfg = ctx.get("config") or {}
    if t is None or ctx.get("rehearse"):
        return None
    held = "experts_routed" in cfg
    if held:
        hit = per(ctx, "moe_held_hit_decode", "decode_steps",
                  over=slice_deltas)
        if hit is None:
            return None
        hit /= _expert_layers(cfg)
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
    if held:
        fl = flops_gdn.held_ffn_flops(cfg, rows)
        by = flops_gdn.held_ffn_bytes(cfg, rows, hit)
    else:
        one = dict(cfg, intermediate_size=cfg.get(
            "moe_intermediate_size", cfg["intermediate_size"]))
        fl = flops_moe.expert_ffn_flops(one, rows)
        by = flops_moe.expert_ffn_bytes(one, rows)
    least, _ = flops.roofline_min_s(
        fl, by, flops.peaks(ctx["device"]["kind"]))
    return 100.0 * least / (seconds / steps)
