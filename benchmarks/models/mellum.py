"""Builder for ``model_type: mellum`` (Mellum2-12B-A2.5B): the pre-norm
decoder ``ray_tpu.models.llama`` expresses with three of its optional
departures on — heads of ``head_dim`` that is not hidden / heads, layers of
two kinds (``layer_types``: sliding layers see ``sliding_window`` keys and
rotate by plain RoPE, full layers see every key and rotate by YaRN's
frequencies, ``rope_parameters``), and a dropless top-k mixture of SwiGLU
experts of ``moe_intermediate_size`` in every layer's MLP
(``norm_topk_prob`` true). Maps the published keys onto ``LlamaConfig``;
weights and shardings are the dense builder's. The dense builder refuses a
``sliding_window`` and a head size of its own: this one owns those keys,
and refuses by name what the block still lacks.
"""
from __future__ import annotations

from .llama_dense import Builder as _DenseBuilder

_KINDS = {"sliding_attention": "sliding", "full_attention": "full"}
# published key -> the one value models/llama.py computes
_ONLY = {"attention_bias": False, "tie_word_embeddings": False,
         "hidden_act": "silu"}


def rope_parameters(model: dict) -> dict:
    """The source's ``rope_parameters`` group. ``kinds/serve_child.py``
    hands the replica the configuration file's top-level numbers, strings
    and lists and none of its nested groups, so where the group is not in
    ``model`` it is read from the file that ``rope_parameters_file`` names
    (the configuration's own, from the checkout's root)."""
    if "rope_parameters" in model:
        return model["rope_parameters"]
    import json
    import os

    from ..spec import ROOT
    with open(os.path.join(ROOT, model["rope_parameters_file"])) as f:
        return json.load(f)["rope_parameters"]


class Builder(_DenseBuilder):
    def __init__(self, model: dict, **overrides):
        import jax.numpy as jnp

        from ray_tpu.models import llama
        for key, only in _ONLY.items():
            if model.get(key, only) != only:
                raise ValueError(f"{key}={model[key]!r}: models/llama.py "
                                 f"computes {key}={only!r} alone")
        n = model["num_hidden_layers"]
        if set(model["mlp_layer_types"][:n]) != {"sparse"}:
            raise ValueError("mlp_layer_types other than all 'sparse': "
                             "models/llama.py has one kind of MLP a model")
        rope = rope_parameters(model)
        full, sliding = rope["full_attention"], rope["sliding_attention"]
        if sliding["rope_type"] != "default" or full["rope_type"] != "yarn" \
                or sliding["rope_theta"] != full["rope_theta"]:
            raise ValueError(
                "rope_parameters: models/llama.py rotates sliding layers by "
                "plain RoPE and full layers by YaRN at one rope_theta")
        self.model = model
        self.cfg = llama.LlamaConfig(
            vocab_size=model["vocab_size"], dim=model["hidden_size"],
            n_layers=n, n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"],
            mlp_dim=model["moe_intermediate_size"],
            moe_experts=model["num_experts"],
            moe_top_k=model["num_experts_per_tok"],
            moe_renormalize=bool(model["norm_topk_prob"]),
            layer_types=tuple(_KINDS[k] for k in model["layer_types"][:n]),
            sliding_window=int(model["sliding_window"]),
            rope_theta=float(full["rope_theta"]),
            rope_yarn=llama.Yarn(
                factor=float(full["factor"]),
                original_max_position=int(
                    full["original_max_position_embeddings"]),
                beta_fast=float(full["beta_fast"]),
                beta_slow=float(full["beta_slow"]),
                attention_factor=float(full.get("attention_factor") or 0.0)),
            max_seq_len=model["max_position_embeddings"],
            norm_eps=float(model["rms_norm_eps"]),
            dtype=jnp.dtype(model.get("torch_dtype", "bfloat16")).type,
            **overrides)
