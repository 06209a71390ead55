"""Engine scheduler (counters ``moe_held_load_max`` / ``moe_assign_held``,
from the per-expert assignment counts every dispatch hands back): the
busiest HELD expert's assignments over the mean held expert's, summed over
dispatches; 1.0 is perfectly even routing. None for a program without the
counters."""
from ._engine import per


def read(ctx: dict):
    ratio = per(ctx, "moe_held_load_max", "moe_assign_held")
    if ratio is None:
        return None
    return ratio * ctx["config"]["num_experts"]
