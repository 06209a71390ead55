"""Engine scheduler (llm/paged_engine.py, counters ``moe_expert_load_max`` /
``moe_expert_load_sum``): the busiest routed expert's assignments over the
mean expert's, summed over dispatches; 1.0 is perfectly even routing.
``gen_moe_load_max_over_mean``'s arithmetic with this configuration's own
key for the number of routed experts (``n_routed_experts``; the shared
expert is not routed and not counted). None for a program without the
counters."""
from ._engine import per


def read(ctx: dict):
    ratio = per(ctx, "moe_expert_load_max", "moe_expert_load_sum")
    if ratio is None:
        return None
    return ratio * ctx["config"]["n_routed_experts"]
