"""Engine scheduler (llm/paged_engine.py, from the per-expert assignment
counts every dispatch of an MoE program hands back): the busiest expert's
assignments over the mean expert's, summed over dispatches. 1.0 is
perfectly even routing. Counters ``moe_expert_load_max`` /
``moe_expert_load_sum`` x the routed experts (``n_routed_experts`` where
the configuration's file has that key — a shared expert is not routed and
not counted — else ``num_experts``); where a share of the experts is held
(``experts_routed`` in the file), the busiest HELD expert's over the mean
held expert's: ``moe_held_load_max`` / ``moe_assign_held`` x
``num_experts``, the experts held. With seeded random weights and tokens
routing is skewed (1.8 to 3.2 in the cells, ledger, PR 51); it is here for
the day a mix skews it further. None for a program without the counters."""
from ._engine import per


def read(ctx: dict):
    cfg = ctx.get("config") or {}
    if "experts_routed" in cfg:
        ratio = per(ctx, "moe_held_load_max", "moe_assign_held")
    else:
        ratio = per(ctx, "moe_expert_load_max", "moe_expert_load_sum")
    if ratio is None:
        return None
    return ratio * (cfg.get("n_routed_experts") or cfg["num_experts"])
