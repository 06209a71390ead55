"""``gen_moe_ffn_roofline`` where it moves this cell's own end-to-end
metric, over a configuration whose key for the width of ONE expert is
``moe_intermediate_size`` (``flops_moe.py`` reads ``intermediate_size``,
which this model's layers do not use)."""
from . import gen_moe_ffn_roofline


def read(ctx: dict):
    cfg = ctx.get("config") or {}
    if "moe_intermediate_size" not in cfg:
        return None
    return gen_moe_ffn_roofline.read(dict(ctx, config=dict(
        cfg, intermediate_size=cfg["moe_intermediate_size"])))
