"""LoRA adapters for the llama family.

Reference parity: the multi-LoRA multiplexing surface of ray.llm
(llm/_internal/serve — LoRA adapters resolved per request and multiplexed
across replicas; vLLM applies them in-kernel). Serving applies adapters
per row from the engine's slot table (llm/multilora/, every tenant in one
dispatch). This module holds the adapter format and `merge`
(W' = W + (alpha/r)·A@B into a param copy): the single-tenant oracle the
slot-table path is tested against, and what LoRA training differentiates
through.

Adapter format: a dict of arrays ``<path>.A`` [L, d_in, r] and
``<path>.B`` [L, r, d_out] for each target in ("wq", "wk", "wv", "wo",
"lm_head"), plus scalars ``rank`` and ``alpha``; adapter_to_bytes /
adapter_from_bytes give its npz form.
"""
from __future__ import annotations

import io

import jax
import jax.numpy as jnp
import numpy as np

from ..models import llama

# param targets: layers/* are stacked [L, ...]; lm_head is unstacked
_LAYER_TARGETS = ("wq", "wk", "wv", "wo")


def random_adapter(rng: jax.Array, cfg: llama.LlamaConfig, rank: int = 4,
                   alpha: float = 8.0,
                   targets: tuple = ("wq", "wv")) -> dict:
    """A random adapter (B≠0 so it changes outputs — tests/demos; real
    adapters come from training where B starts at zero)."""
    out = {"rank": np.int32(rank), "alpha": np.float32(alpha)}
    L = cfg.n_layers
    for t in targets:
        if t == "lm_head":
            shapes = (cfg.dim, cfg.vocab_size)
            lead = ()
        elif t in ("wk", "wv"):
            shapes = (cfg.dim, cfg.n_kv_heads * cfg.head_dim)
            lead = (L,)
        elif t == "wq":
            shapes = (cfg.dim, cfg.n_heads * cfg.head_dim)
            lead = (L,)
        elif t == "wo":
            shapes = (cfg.n_heads * cfg.head_dim, cfg.dim)
            lead = (L,)
        else:
            raise ValueError(f"unknown LoRA target {t!r}")
        rng, ka, kb = jax.random.split(rng, 3)
        out[f"{t}.A"] = np.asarray(jax.random.normal(
            ka, lead + (shapes[0], rank)) * 0.05, np.float32)
        out[f"{t}.B"] = np.asarray(jax.random.normal(
            kb, lead + (rank, shapes[1])) * 0.05, np.float32)
    return out


def adapter_to_bytes(adapter: dict) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **adapter)
    return buf.getvalue()


def adapter_from_bytes(blob: bytes) -> dict:
    with np.load(io.BytesIO(blob)) as z:
        return {k: z[k] for k in z.files}


def merge(params: dict, adapter: dict) -> dict:
    """params' = params + scale·A@B per target. Returns a NEW pytree;
    untouched leaves are shared (no copy)."""
    rank = int(adapter.get("rank", 4))
    alpha = float(adapter.get("alpha", rank))
    scale = alpha / max(rank, 1)
    out = dict(params)
    layers = dict(params["layers"])
    for t in _LAYER_TARGETS:
        a, b = adapter.get(f"{t}.A"), adapter.get(f"{t}.B")
        if a is None or b is None:
            continue
        delta = jnp.einsum("ldr,lrk->ldk", jnp.asarray(a), jnp.asarray(b))
        layers[t] = (layers[t].astype(jnp.float32)
                     + scale * delta).astype(params["layers"][t].dtype)
    out["layers"] = layers
    if "lm_head.A" in adapter:
        delta = jnp.asarray(adapter["lm_head.A"]) @ jnp.asarray(
            adapter["lm_head.B"])
        out["lm_head"] = (params["lm_head"].astype(jnp.float32)
                          + scale * delta).astype(params["lm_head"].dtype)
    return out
