"""Operations and bytes of a mixture-of-experts layer, computed from the
configuration file's published keys (``num_experts``, ``num_experts_per_tok``,
``intermediate_size`` as the width of ONE expert). Kept with the benchmark,
beside ``flops.py``, whose ``layer_matmul_params`` reads ``intermediate_size``
as a dense MLP and is wrong for such a configuration.
"""
from __future__ import annotations


def expert_params(m: dict) -> int:
    """Weights of one expert: gate, up and down projections."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def layer_expert_params(m: dict) -> int:
    """Weights of all of one layer's experts (the router is apart)."""
    return m["num_experts"] * expert_params(m)


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["num_experts"]


def layer_params(m: dict) -> int:
    """Every stored parameter of one decoder layer: attention, the QK-norm
    and the two layer norms, the router and the experts."""
    d = m["hidden_size"]
    hd = m.get("head_dim") or d // m["num_attention_heads"]
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    return (d * q + 2 * d * kv + q * d + q + kv + 2 * d
            + router_params(m) + layer_expert_params(m))


def total_params(m: dict) -> int:
    """Layers, embedding, untied head and the final norm."""
    d = m["hidden_size"]
    return (m["num_hidden_layers"] * layer_params(m)
            + 2 * m["vocab_size"] * d + d)


def expected_experts_hit(m: dict, tokens: float) -> float:
    """Distinct experts that ``tokens`` tokens reach in one layer when
    routing is uniform (seeded random weights and tokens: nearly so):
    E x (1 - (1 - 1/E) ^ (tokens x top_k))."""
    e = m["num_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** (tokens * m["num_experts_per_tok"]))


def expert_ffn_flops(m: dict, tokens: float) -> float:
    """One layer's expert matmuls for ``tokens`` tokens: 2 FLOPs a weight
    of each of a token's top_k experts."""
    return 2.0 * tokens * m["num_experts_per_tok"] * expert_params(m)


def expert_ffn_bytes(m: dict, tokens: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one layer's expert FFN: the weights of the
    experts hit, read once, and each assignment's row in and out."""
    rows = tokens * m["num_experts_per_tok"] * 2 * m["hidden_size"]
    return dtype_bytes * (
        expected_experts_hit(m, tokens) * expert_params(m) + rows)
