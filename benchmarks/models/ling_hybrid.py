"""Builder for ``model_type: bailing_hybrid`` (Ling-3.0-flash): maps the
published keys onto ``ray_tpu.models.ling_hybrid.LingHybridConfig`` and
makes the weights on the device from the seed. ``num_experts`` is the
number of routed experts HELD here (``experts_held`` = [lo, hi) of the
``experts_routed`` the router scores: one chip's share of a layer, the
configuration file's ``deployment``); ``dense_layers_kept`` is how many of
the ``first_k_dense_replace`` leading dense layers the depth that runs
keeps (they count once, the model-configs guide's section 4). The builder
refuses by name every published key whose value the block does not
compute.
"""
from __future__ import annotations

# published key -> the one value models/ling_hybrid.py computes
_ONLY = {"use_nGPT": False, "value_norm": False, "up_proj_norm": False,
         "scale_router_input": False, "use_bias": False,
         "use_qkv_bias": False, "rope_scaling": None, "use_mla_nope": False,
         "mtp_use_kda": False, "q_lora_rank": None, "use_kda_lora": False,
         "no_kda_lora": True, "kda_safe_gate": True, "linear_silu": True,
         "group_norm_size": 1, "num_kv_heads_for_linear_attn": 0,
         "gated_attention_proj_granularity_type": "head_wise",
         "hidden_act": "silu", "norm_topk_prob": True,
         "moe_router_enable_expert_bias": True, "rope_interleave": True,
         "score_function": "sigmoid", "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "tie_word_embeddings": False,
         "use_qk_norm": True}
_LIMITS = ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")


class Builder:
    def __init__(self, model: dict, **overrides):
        import jax.numpy as jnp

        from ray_tpu.models import ling_hybrid
        for key, only in _ONLY.items():
            if model.get(key, only) != only:
                raise ValueError(f"{key}={model[key]!r}: models/"
                                 f"ling_hybrid.py computes {key}={only!r}")
        layers = model["num_hidden_layers"]
        for key in _LIMITS:
            if any(model.get(key, [])[:layers]):
                raise ValueError(
                    f"{key}={model[key][:layers]!r} over the {layers} "
                    "layers that run: models/ling_hybrid.py clamps no "
                    "SwiGLU (a limit of 0 alone)")
        if model["qk_head_dim"] != (model["qk_nope_head_dim"]
                                    + model["qk_rope_head_dim"]) \
                or model["rotary_dim"] != model["qk_rope_head_dim"]:
            raise ValueError("qk_head_dim is not nope + rope, or rotary_dim "
                             "not the rope part")
        lo, hi = model.get("experts_held") or (0, model["num_experts"])
        routed = model.get("experts_routed") or model["num_experts"]
        if hi - lo != model["num_experts"] or not 0 <= lo < hi <= routed:
            raise ValueError(
                f"experts_held={[lo, hi]}: num_experts="
                f"{model['num_experts']} of the experts_routed={routed}")
        self.model = model
        self.cfg = ling_hybrid.LingHybridConfig(
            vocab_size=model["vocab_size"], dim=model["hidden_size"],
            n_layers=layers,
            n_dense_layers=min(model["first_k_dense_replace"], model.get(
                "dense_layers_kept", model["first_k_dense_replace"])),
            mla_interval=model["layer_group_size"],
            n_heads=model["num_attention_heads"],
            qk_nope_dim=model["qk_nope_head_dim"],
            qk_rope_dim=model["qk_rope_head_dim"],
            v_head_dim=model["v_head_dim"],
            kv_lora_rank=model["kv_lora_rank"],
            kda_heads=model["num_attention_heads"],
            kda_k_dim=model["head_dim"], kda_v_dim=model["head_dim"],
            conv_width=model["short_conv_kernel_size"],
            kda_lower_bound=float(model["kda_lower_bound"]),
            dense_mlp_dim=model["intermediate_size"],
            moe_experts=routed, moe_top_k=model["num_experts_per_tok"],
            mlp_dim=model["moe_intermediate_size"],
            n_shared_experts=model["num_shared_experts"],
            routed_scale=float(model["routed_scaling_factor"]),
            n_group=model["n_group"], topk_group=model["topk_group"],
            experts_held=(lo, hi),
            max_seq_len=model["max_position_embeddings"],
            rope_theta=float(model["rope_theta"]),
            norm_eps=float(model["rms_norm_eps"]),
            dtype=jnp.dtype(model.get("torch_dtype", "bfloat16")).type,
            **overrides)
        if model["moe_shared_expert_intermediate_size"] != \
                model["moe_intermediate_size"]:
            raise ValueError("the shared expert is num_shared_experts x "
                             "moe_intermediate_size wide")

    def init_params(self, seed: int, shardings=None):
        """All weights in ONE jitted call on the device, in the type they
        are served in (router, its bias, a_log and dt_bias float32)."""
        import jax

        from ray_tpu.models import ling_hybrid
        make = jax.jit(lambda key: ling_hybrid.init(key, self.cfg),
                       out_shardings=shardings)
        return jax.block_until_ready(make(jax.random.PRNGKey(seed)))

    def mesh_shardings(self, mesh_spec: dict, devices):
        from ray_tpu.models import ling_hybrid
        ling_hybrid.check_mesh(self.cfg, mesh_spec)
