"""Engine scheduler: the share of the token-expert assignments the
programs routed (over all ``experts_routed``) that fell on the experts
held here (counters ``moe_assign_held`` / ``moe_expert_load_sum``): ~25% at
128 of 512 under near-uniform routing. The rest is what the absent chips
would compute. None for a program without the counters."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "moe_assign_held", "moe_expert_load_sum", 100.0)
