"""Engine scheduler (a router that selects groups first): the share of the
tokens x expert layers the programs routed whose chosen groups include a
group with experts held here (counters ``moe_group_hits`` /
(``moe_expert_load_sum`` / ``num_experts_per_tok``)) — ``topk_group`` /
``n_group`` = 50% under even routing where one whole group is held. Beside
``moe_assign_held`` in the window's counters it tells "half the tokens
bring two assignments" from "every token brings one": the rows the held
experts' grouped matmul sees are the same, the tokens gathered and
scattered around it are not. None for a program without the counter."""
from ._engine import deltas


def read(ctx: dict):
    d = deltas(ctx)
    routed = d.get("moe_expert_load_sum")
    if "moe_group_hits" not in d or not routed:
        return None
    return 100.0 * d["moe_group_hits"] * ctx["config"][
        "num_experts_per_tok"] / routed
