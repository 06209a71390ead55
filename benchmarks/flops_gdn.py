"""Operations and bytes of a decoder whose layers are gated-delta-rule
(GDN) layers with a gated-attention layer among every few, each with a
routed mixture of experts of which this chip holds a share — computed from
the configuration file's published keys (``linear_*``,
``full_attention_interval``, ``num_experts`` = the experts HELD,
``experts_routed``). Kept with the benchmark, beside ``flops.py``, whose
``kv_bytes_per_token`` takes every layer to hold keys (three layers in
four here hold a fixed-size state and no key) and ``flops_moe.py``, which
takes every routed expert to be here.
"""
from __future__ import annotations


def full_layers(m: dict) -> int:
    """Gated-attention layers among the ``num_hidden_layers`` that run."""
    return m["num_hidden_layers"] // m["full_attention_interval"]


def gdn_layers(m: dict) -> int:
    return m["num_hidden_layers"] - full_layers(m)


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """Keys and values one cached token holds, over the full layers alone
    (4,096 B at 2 layers of 2 KV heads of 256 in bf16): ``page_size`` x
    this is the engine's ``page_nbytes``."""
    return (full_layers(m) * 2 * m["num_key_value_heads"] * m["head_dim"]
            * dtype_bytes)


def state_bytes_layer(m: dict) -> int:
    """One sequence's recurrent state in one GDN layer: float32 [value
    heads, key dim, value dim] (2,097,152 B at 32 x 128 x 128)."""
    return (m["linear_num_value_heads"] * m["linear_key_head_dim"]
            * m["linear_value_head_dim"] * 4)


def conv_dim(m: dict) -> int:
    """Channels of the GDN convolution: q, k and v side by side."""
    return (2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
            + m["linear_num_value_heads"] * m["linear_value_head_dim"])


def state_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """What one decode slot, and one snapshot, holds over the GDN layers:
    the state and the convolution's tail (the engine's ``state_nbytes``;
    12,877,824 B at six layers)."""
    tail = (m["linear_conv_kernel_dim"] - 1) * conv_dim(m) * dtype_bytes
    return gdn_layers(m) * (state_bytes_layer(m) + tail)


def gdn_token_flops(m: dict) -> int:
    """FLOPs the recurrence needs for one token in one GDN layer: S^T k,
    the rank-1 update and S^T q, 2 FLOPs an entry of the state each, every
    value head (the chunked form's extra products are its own cost)."""
    return 6 * m["linear_num_value_heads"] * m["linear_key_head_dim"] \
        * m["linear_value_head_dim"]


def gdn_prefill_bytes(m: dict, tokens: float, rows: float,
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one GDN layer's kernel call over ``rows`` rows
    of ``tokens`` tokens in all: q, k, v read (``conv_dim`` channels) and
    the float32 output written a token; a row's state read and written."""
    out = m["linear_num_value_heads"] * m["linear_value_head_dim"] * 4
    return (tokens * (conv_dim(m) * dtype_bytes + out)
            + rows * 2 * state_bytes_layer(m))


def gdn_decode_bytes(m: dict, rows: float) -> float:
    """Least HBM traffic of one GDN layer's decode update of ``rows`` live
    rows: each row's state read once and written once."""
    return rows * 2 * state_bytes_layer(m)


def expert_params(m: dict) -> int:
    """Weights of one routed expert: gate, up and down projections."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def held_assignments(m: dict, tokens: float) -> float:
    """Token-expert assignments of ``tokens`` tokens in one layer that
    fall on the experts held here, under uniform routing."""
    return (tokens * m["num_experts_per_tok"] * m["num_experts"]
            / m["experts_routed"])


def held_experts_hit(m: dict, tokens: float) -> float:
    """Distinct held experts those assignments reach (uniform routing)."""
    e = m["num_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** held_assignments(m, tokens))


def held_ffn_flops(m: dict, tokens: float) -> float:
    return 2.0 * held_assignments(m, tokens) * expert_params(m)


def held_ffn_bytes(m: dict, tokens: float, hit: float | None = None,
                   dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one layer's routed FFN here: the weights of
    the held experts hit, read once, and each assignment's row in and
    out. ``hit``: the distinct held experts the program counted (routing
    of seeded random weights is skewed — the busiest held expert gets
    twice the mean — and reaches fewer than ``held_experts_hit``'s
    uniform estimate, the default)."""
    rows = held_assignments(m, tokens) * 2 * m["hidden_size"]
    if hit is None:
        hit = held_experts_hit(m, tokens)
    return dtype_bytes * (hit * expert_params(m) + rows)


def total_params(m: dict) -> int:
    """Every stored parameter of the configuration as it is run."""
    d, hd = m["hidden_size"], m["head_dim"]
    nk, nv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    heads = m["num_attention_heads"]
    full = (d * heads * hd * 2 + 2 * d * m["num_key_value_heads"] * hd
            + heads * hd * d + 2 * hd + d)
    gdn = (d * (2 * nk * dk + 2 * nv * dv) + d * 2 * nv
           + m["linear_conv_kernel_dim"] * conv_dim(m) + 2 * nv + dv
           + nv * dv * d + d)
    moe = (d + d * m["experts_routed"] + m["num_experts"] * expert_params(m)
           + 3 * d * m["shared_expert_intermediate_size"] + d)
    return (full_layers(m) * full + gdn_layers(m) * gdn
            + m["num_hidden_layers"] * moe + 2 * m["vocab_size"] * d + d)
