"""Builder for ``model_type: qwen3_next`` (Qwen3-Next-80B-A3B): maps the
published keys onto ``ray_tpu.models.qwen3_next.Qwen3NextConfig`` and makes
the weights on the device from the seed. ``num_experts`` is the number of
routed experts HELD here (``experts_held`` = [lo, hi) of the
``experts_routed`` the router scores: one chip's share of a layer, the
configuration file's ``deployment``); the builder refuses by name what the
block does not compute.
"""
from __future__ import annotations

# published key -> the one value models/qwen3_next.py computes
_ONLY = {"hidden_act": "silu", "norm_topk_prob": True, "rope_scaling": None,
         "tie_word_embeddings": False, "use_sliding_window": False,
         "decoder_sparse_step": 1, "mlp_only_layers": []}


class Builder:
    def __init__(self, model: dict, **overrides):
        import jax.numpy as jnp

        from ray_tpu.models import qwen3_next
        for key, only in _ONLY.items():
            if model.get(key, only) != only:
                raise ValueError(f"{key}={model[key]!r}: models/"
                                 f"qwen3_next.py computes {key}={only!r}")
        lo, hi = model.get("experts_held") or (0, model["num_experts"])
        routed = model.get("experts_routed") or model["num_experts"]
        if hi - lo != model["num_experts"] or not 0 <= lo < hi <= routed:
            raise ValueError(
                f"experts_held={[lo, hi]}: num_experts="
                f"{model['num_experts']} of the experts_routed={routed}")
        self.model = model
        self.cfg = qwen3_next.Qwen3NextConfig(
            vocab_size=model["vocab_size"], dim=model["hidden_size"],
            n_layers=model["num_hidden_layers"],
            full_interval=model["full_attention_interval"],
            n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"],
            rotary_dim=int(model["partial_rotary_factor"]
                           * model["head_dim"]),
            rope_theta=float(model["rope_theta"]),
            gdn_k_heads=model["linear_num_key_heads"],
            gdn_v_heads=model["linear_num_value_heads"],
            gdn_k_dim=model["linear_key_head_dim"],
            gdn_v_dim=model["linear_value_head_dim"],
            conv_width=model["linear_conv_kernel_dim"],
            moe_experts=routed,
            moe_top_k=model["num_experts_per_tok"],
            mlp_dim=model["moe_intermediate_size"],
            shared_mlp_dim=model["shared_expert_intermediate_size"],
            experts_held=(lo, hi),
            max_seq_len=model["max_position_embeddings"],
            norm_eps=float(model["rms_norm_eps"]),
            dtype=jnp.dtype(model.get("torch_dtype", "bfloat16")).type,
            **overrides)

    def init_params(self, seed: int, shardings=None):
        """All weights in ONE jitted call on the device, in the type they
        are served in."""
        import jax

        from ray_tpu.models import qwen3_next
        make = jax.jit(lambda key: qwen3_next.init(key, self.cfg),
                       out_shardings=shardings)
        return jax.block_until_ready(make(jax.random.PRNGKey(seed)))

    def mesh_shardings(self, mesh_spec: dict, devices):
        from ray_tpu.models import qwen3_next
        qwen3_next.check_mesh(self.cfg, mesh_spec)
