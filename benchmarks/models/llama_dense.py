"""Builder for dense pre-norm decoders that ``ray_tpu.models.llama``
expresses exactly (RMSNorm, RoPE by halves, GQA, SwiGLU, no biases, untied
head): maps a configuration file's published keys onto ``LlamaConfig`` and
makes the weights on the device from the seed.

A later architecture brings its own builder file and names it in its
configuration file; nothing else changes.
"""
from __future__ import annotations


class Builder:
    def __init__(self, model: dict, **overrides):
        import jax.numpy as jnp

        from ray_tpu.models import llama
        hd = model.get("head_dim") or (
            model["hidden_size"] // model["num_attention_heads"])
        if hd * model["num_attention_heads"] != model["hidden_size"]:
            raise ValueError("LlamaConfig derives head_dim as dim / n_heads; "
                             "this configuration needs its own builder")
        if model.get("sliding_window") or model.get("tie_word_embeddings"):
            raise ValueError("sliding windows and tied embeddings are not "
                             "what models/llama.py computes")
        self.model = model
        self.cfg = llama.LlamaConfig(
            vocab_size=model["vocab_size"], dim=model["hidden_size"],
            n_layers=model["num_hidden_layers"],
            n_heads=model["num_attention_heads"],
            n_kv_heads=model["num_key_value_heads"],
            mlp_dim=model["intermediate_size"],
            max_seq_len=model["max_position_embeddings"],
            rope_theta=float(model["rope_theta"]),
            norm_eps=float(model["rms_norm_eps"]),
            dtype=jnp.dtype(model.get("torch_dtype", "bfloat16")).type,
            **overrides)

    def init_params(self, seed: int, shardings=None):
        """All weights in ONE jitted call on the device, in the type they
        are served in; ``shardings`` places them straight onto a mesh (a
        full-depth model does not fit one chip on its way there)."""
        import jax

        from ray_tpu.models import llama
        make = jax.jit(lambda key: llama.init(key, self.cfg),
                       out_shardings=shardings)
        return jax.block_until_ready(make(jax.random.PRNGKey(seed)))

    def mesh_shardings(self, mesh_spec: dict, devices):
        """The placements ``PagedInferenceEngine._mesh_shardings`` commits
        the weights to, computed the same way, so that its own
        ``device_put`` finds them already in place."""
        from ray_tpu.models import llama
        from ray_tpu.parallel import sharding as shardlib
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh, use_mesh
        mesh = build_mesh(MeshSpec(**mesh_spec), devices=devices)
        with use_mesh(mesh):
            return shardlib.logical_sharding(llama.logical_axes(self.cfg))
