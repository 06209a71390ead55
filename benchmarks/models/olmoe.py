"""Builder for OLMoE-1B-7B (``modeling_olmoe.py``): the pre-norm decoder
``ray_tpu.models.llama`` expresses, with its two optional departures on —
QK-norm over the whole q and k projections before RoPE, and a dropless
top-k mixture of SwiGLU experts in the MLP's place (float32 softmax router,
``norm_topk_prob`` false: the eight probabilities are not renormalised).
Maps the published keys onto ``LlamaConfig``; weights, shardings and the
head-size check are the dense builder's.
"""
from __future__ import annotations

from .llama_dense import Builder as _DenseBuilder


class Builder(_DenseBuilder):
    def __init__(self, model: dict, **overrides):
        if model.get("clip_qkv") is not None or model.get("attention_bias") \
                or model.get("rope_scaling") is not None:
            raise ValueError("clip_qkv, attention biases and rope scaling "
                             "are not what models/llama.py computes")
        super().__init__(
            model, qk_norm=True, moe_experts=model["num_experts"],
            moe_top_k=model["num_experts_per_tok"],
            moe_renormalize=bool(model["norm_topk_prob"]), **overrides)
