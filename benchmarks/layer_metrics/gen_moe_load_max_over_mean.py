"""Engine scheduler (llm/paged_engine.py, counters
``moe_expert_load_max`` / ``moe_expert_load_sum``, from the per-expert
assignment counts every dispatch of an MoE program hands back): the busiest
expert's assignments over the mean expert's, summed over dispatches. 1.0 is
perfectly even routing. With seeded random weights and tokens routing is
near uniform and this sits a little above 1 (the maximum of 64 nearly equal
counts); it is here for the day a skewed mix is added. None for a program
without the counters."""
from ._engine import per


def read(ctx: dict):
    ratio = per(ctx, "moe_expert_load_max", "moe_expert_load_sum")
    if ratio is None:
        return None
    return ratio * ctx["config"]["num_experts"]
