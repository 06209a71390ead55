"""Kernels (ops/flash_attention.py): the least time the chip could take for
the traced steps' attention calls (forward and backward, from
``benchmarks/flops.py`` and the peaks table; the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, which here is the compute bound) over
the device time spent in the flash kernels. With remat policy ``save_attn``
the forward runs once per step."""
from .. import flops
from ._common import trace


def read(ctx: dict):
    t = trace(ctx)
    if t is None or ctx.get("rehearse"):
        return None
    spent = t["kernels"].get("flash_attention", {}).get("seconds")
    if not spent:
        return None
    cfg = ctx["config"]
    b, s = ctx["traffic"]["batch"], ctx["traffic"]["seq"]
    peak = flops.peaks(ctx["device"]["kind"])
    least = 0.0
    for back in (False, True):
        fl = (flops.attention_bwd_flops if back
              else flops.attention_fwd_flops)(cfg, b, s)
        least += flops.roofline_min_s(
            fl, flops.attention_io_bytes(cfg, b, s, backward=back), peak)[0]
    least *= cfg["num_hidden_layers"] * t["steps"]
    return 100.0 * least / spent
