"""Trainer (train/trainer.py + the user's step): model FLOP/s utilization,
tokens per second times model FLOPs per token (``benchmarks/flops.py``:
matmul weights only, causal attention, no recomputation) over chips times
the published peak."""
from .. import flops


def read(ctx: dict):
    m, cfg = ctx.get("train"), ctx.get("config")
    if not m or not cfg:
        return None
    if ctx.get("rehearse"):
        return 0.0
    peak = flops.peaks(ctx["device"]["kind"])["bf16_flops"]
    per_tok = flops.train_flops_per_token(cfg, ctx["traffic"]["seq"])
    return 100.0 * m["tokens"] / m["window_s"] * per_tok / (
        peak * ctx["cell"].chips)
