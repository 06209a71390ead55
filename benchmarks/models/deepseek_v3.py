"""Builder for ``model_type: deepseek_v3`` decoders without a compressed
query (kanana-2-30b-a3b: ``modeling_deepseek_v3.py`` with ``q_lora_rank``
null): latent attention (MLA) with one shared rope key, a dense prefix of
``first_k_dense_replace`` layers, then layers of ``n_routed_experts``
sigmoid-routed SwiGLU experts (top ``num_experts_per_tok`` by score + bias,
weighted by the scores alone, normalised and scaled) beside one shared
SwiGLU of ``n_shared_experts`` x ``moe_intermediate_size``. Maps the
published keys onto ``ray_tpu.models.mla_moe.MlaMoeConfig`` and makes the
weights on the device from the seed; what that module does not compute is
refused here by the key that asks for it.
"""
from __future__ import annotations

# published key -> the one value models/mla_moe.py computes
_ONLY = {"q_lora_rank": None, "rope_scaling": None, "attention_bias": False,
         "tie_word_embeddings": False, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
         "norm_topk_prob": True, "rope_interleave": True,
         "moe_layer_freq": 1, "hidden_act": "silu"}


class Builder:
    def __init__(self, model: dict, **overrides):
        import jax.numpy as jnp

        from ray_tpu.models import mla_moe
        for key, only in _ONLY.items():
            if model.get(key, only) != only:
                raise ValueError(
                    f"{key}={model[key]!r}: models/mla_moe.py computes "
                    f"{key}={only!r} alone")
        if model["qk_head_dim"] != (model["qk_nope_head_dim"]
                                    + model["qk_rope_head_dim"]):
            raise ValueError("qk_head_dim is not nope + rope")
        self.model = model
        self.cfg = mla_moe.MlaMoeConfig(
            vocab_size=model["vocab_size"], dim=model["hidden_size"],
            n_layers=model["num_hidden_layers"],
            n_dense_layers=model["first_k_dense_replace"],
            n_heads=model["num_attention_heads"],
            qk_nope_dim=model["qk_nope_head_dim"],
            qk_rope_dim=model["qk_rope_head_dim"],
            v_head_dim=model["v_head_dim"],
            kv_lora_rank=model["kv_lora_rank"],
            dense_mlp_dim=model["intermediate_size"],
            moe_experts=model["n_routed_experts"],
            moe_top_k=model["num_experts_per_tok"],
            mlp_dim=model["moe_intermediate_size"],
            n_shared_experts=model["n_shared_experts"],
            routed_scale=float(model["routed_scaling_factor"]),
            max_seq_len=model["max_position_embeddings"],
            rope_theta=float(model["rope_theta"]),
            norm_eps=float(model["rms_norm_eps"]),
            dtype=jnp.dtype(model.get("torch_dtype", "bfloat16")).type,
            **overrides)

    def init_params(self, seed: int, shardings=None):
        """All weights in ONE jitted call on the device, in the type they
        are served in (router and its bias float32; the bias drawn
        N(0, 0.1^2), not zero: mla_moe.init)."""
        import jax

        from ray_tpu.models import mla_moe
        make = jax.jit(lambda key: mla_moe.init(key, self.cfg),
                       out_shardings=shardings)
        return jax.block_until_ready(make(jax.random.PRNGKey(seed)))

    def mesh_shardings(self, mesh_spec: dict, devices):
        from ray_tpu.models import mla_moe
        return mla_moe.check_mesh(self.cfg, mesh_spec)   # raises: no mesh
