"""Operations and bytes of a decoder whose layers are Kimi-delta-attention
(KDA) layers — the gated delta rule with a decay a key channel — with a
gated latent-attention (MLA) layer among every few, behind a dense prefix,
each later layer with a group-routed mixture of experts of which this chip
holds a share — computed from the configuration file's published keys
(``layer_group_size``, ``head_dim``, ``short_conv_kernel_size``,
``kv_lora_rank``, ``num_experts`` = the experts HELD, ``experts_routed``,
``dense_layers_kept``). Kept with the benchmark, beside ``flops_gdn.py``
(whose state is the same matrix a head, whose decay is a scalar, and whose
pages hold keys and values a head) and ``flops_mla.py`` (whose every layer
is latent and holds every expert).
"""
from __future__ import annotations

from . import flops_mla
# one chip's share of a layer's experts is counted as for Qwen3-Next: the
# same keys (``num_experts`` held of ``experts_routed``, top-k, one
# expert's width); under routing that is uniform over the groups and
# inside them an eighth of a token's top-8 falls on the one held group
from .flops_gdn import (expert_params, held_assignments,  # noqa: F401
                        held_ffn_bytes, held_ffn_flops)


def mla_layers(m: dict) -> int:
    """Latent-attention layers among the ``num_hidden_layers`` that run:
    layer i where (i + 1) % layer_group_size == 0."""
    return m["num_hidden_layers"] // m["layer_group_size"]


def kda_layers(m: dict) -> int:
    return m["num_hidden_layers"] - mla_layers(m)


def dense_layers(m: dict) -> int:
    """Leading layers with a dense MLP among those that run."""
    return min(m["first_k_dense_replace"],
               m.get("dense_layers_kept", m["first_k_dense_replace"]))


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"] - dense_layers(m)


def latent_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """What the device stores of one cached token, over the MLA layers
    alone: a row of ``c ‖ k_r`` padded to whole 128-lane tiles a layer
    (1,280 B at one layer of 576 values in bf16). ``page_size`` x this is
    the engine's ``page_nbytes``."""
    return mla_layers(m) * flops_mla.pool_bytes_per_token_layer(
        m, dtype_bytes)


def state_bytes_layer(m: dict) -> int:
    """One sequence's recurrent state in one KDA layer: float32 [heads,
    key dim, value dim] (2,097,152 B at 32 x 128 x 128)."""
    return m["num_attention_heads"] * m["head_dim"] * m["head_dim"] * 4


def conv_dim(m: dict) -> int:
    """Channels of the KDA convolution: q, k and v side by side."""
    return 3 * m["num_attention_heads"] * m["head_dim"]


def state_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """What one decode slot, and one snapshot, holds over the KDA layers:
    the state and the convolution's tail (the engine's ``state_nbytes``;
    13,025,280 B at six layers)."""
    tail = (m["short_conv_kernel_size"] - 1) * conv_dim(m) * dtype_bytes
    return kda_layers(m) * (state_bytes_layer(m) + tail)


def kda_decode_bytes(m: dict, rows: float) -> float:
    """Least HBM traffic of one KDA layer's decode update of ``rows`` live
    rows: each row's state read once and written once (the row's q, k, v,
    decay and beta are 0.05% of that)."""
    return rows * 2 * state_bytes_layer(m)


def kda_prefill_bytes(m: dict, tokens: float, rows: float,
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of one KDA layer's chunked kernel call over
    ``rows`` rows of ``tokens`` tokens in all: q, k, v read (``conv_dim``
    channels), the float32 decay a key channel read and the float32
    output written a token; a row's state read and written."""
    per_head = m["num_attention_heads"] * m["head_dim"] * 4
    return (tokens * (conv_dim(m) * dtype_bytes + 2 * per_head)
            + rows * 2 * state_bytes_layer(m))


def kda_prefill_flops(m: dict, tokens: float, chunk: int = 64) -> float:
    """FLOPs of the chunked form over ``tokens`` tokens of one KDA layer,
    a head a chunk of ``chunk``: the two [chunk, chunk] products over the
    key dim (2 x 2 chunk^2 dk), the inverse's log2(chunk) - 1 squarings
    and as many products (4 chunk^3 each pair), and the five products
    with the state or the new values (2 chunk dk dv each, the values'
    solve 2 chunk^2 (dk + dv))."""
    dk = dv = m["head_dim"]
    squarings = max((chunk - 1).bit_length() - 1, 0)
    a_chunk = (4 * chunk * chunk * dk + squarings * 4 * chunk ** 3
               + 2 * chunk * chunk * (dk + dv) + 2 * chunk * chunk * dv
               + 4 * 2 * chunk * dk * dv)
    return tokens / chunk * m["num_attention_heads"] * a_chunk


def group_hit_share(m: dict) -> float:
    """The share of tokens whose ``topk_group`` groups include the one
    held group (held = one group whole): topk_group / n_group."""
    return m["topk_group"] / m["n_group"]


def total_params(m: dict) -> int:
    """Every stored parameter of the configuration as it is run."""
    d, h, hd = m["hidden_size"], m["num_attention_heads"], m["head_dim"]
    kda = (6 * d * h * hd + d * h + m["short_conv_kernel_size"] * conv_dim(m)
           + h + h * hd + hd + d)
    mla = flops_mla.attention_params(m) + d * h + d
    moe = (d + (d + 1) * m["experts_routed"]
           + m["num_experts"] * expert_params(m)
           + m["num_shared_experts"] * 3 * d
           * m["moe_shared_expert_intermediate_size"])
    dense = d + 3 * d * m["intermediate_size"]
    return (kda_layers(m) * kda + mla_layers(m) * mla
            + expert_layers(m) * moe + dense_layers(m) * dense
            + 2 * m["vocab_size"] * d + d)
