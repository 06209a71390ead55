"""Operations, bytes and parameter counts of a latent-attention (MLA)
decoder with sigmoid-routed and shared experts behind a dense prefix,
computed from the configuration file's published keys (``kv_lora_rank``,
``qk_rope_head_dim``, ``n_routed_experts``, ``moe_intermediate_size``,
``n_shared_experts``, ``first_k_dense_replace``). Kept with the benchmark,
beside ``flops.py``, whose ``kv_bytes_per_token`` and ``layer_matmul_params``
read ``num_key_value_heads`` x ``head_dim`` and a dense MLP and are wrong for
such a configuration (``kinds/serve.py`` logs the former on an earlier line;
it decides nothing).
"""
from __future__ import annotations

LANES = 128     # a TPU vector register's lanes: an HBM row is whole tiles


def latent_dim(m: dict) -> int:
    """Values one cached token holds in one layer: the compressed latent
    and the one shared rope key."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def latent_bytes_per_token_layer(m: dict, dtype_bytes: int = 2) -> int:
    """What attention has to read of one cached token in one layer
    (1,152 B at rank 512 + rope 64 in bf16): the rooflines' bytes."""
    return latent_dim(m) * dtype_bytes


def pool_bytes_per_token_layer(m: dict, dtype_bytes: int = 2) -> int:
    """What the device stores for it: the row padded to whole 128-lane
    tiles (1,280 B), as the pool is laid out in HBM and as
    ``models/mla_moe.py`` allocates it. ``page_size`` x layers x this is
    the engine's ``page_nbytes``."""
    return -(-latent_dim(m) // LANES) * LANES * dtype_bytes


def attn_pair_flops(m: dict) -> int:
    """FLOPs of one (query, key) pair in one layer, all heads, in the
    absorbed form: every head's query of ``latent_dim`` against the one
    key (Dk), and its probability against the latent of ``kv_lora_rank``
    (Dv): 2 x heads x (Dk + Dv)."""
    return 2 * m["num_attention_heads"] * (latent_dim(m)
                                           + m["kv_lora_rank"])


def attention_params(m: dict) -> int:
    """Wq, Wkva (with the latent's norm), Wkvb and Wo of one layer."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    rank = m["kv_lora_rank"]
    return (d * h * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
            + d * latent_dim(m) + rank
            + rank * h * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + h * m["v_head_dim"] * d)


def expert_params(m: dict) -> int:
    """Gate, up and down projections of ONE routed expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_expert_params(m: dict) -> int:
    """The shared expert: one SwiGLU of n_shared_experts x the routed
    width."""
    return m["n_shared_experts"] * expert_params(m)


def router_params(m: dict) -> int:
    """The router's weights and its selection bias."""
    return (m["hidden_size"] + 1) * m["n_routed_experts"]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def layer_params(m: dict, dense: bool) -> int:
    """Every stored parameter of one decoder layer, of the dense prefix or
    of the expert layers; two norms each."""
    ffn = dense_mlp_params(m) if dense else (
        m["n_routed_experts"] * expert_params(m) + shared_expert_params(m)
        + router_params(m))
    return attention_params(m) + ffn + 2 * m["hidden_size"]


def total_params(m: dict) -> int:
    """Layers (``first_k_dense_replace`` dense ones first), embedding,
    untied head and the final norm."""
    n_dense = m["first_k_dense_replace"]
    d = m["hidden_size"]
    return (n_dense * layer_params(m, True)
            + (m["num_hidden_layers"] - n_dense) * layer_params(m, False)
            + 2 * m["vocab_size"] * d + d)
