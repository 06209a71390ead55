"""Llama-family transformer, TPU-first.

The framework's flagship model (BASELINE.json north star: Llama-3-8B ≥45% MFU
on v5e). Design choices that are TPU-idiomatic rather than ports:

* pure-pytree params + pure functions — everything jit/pjit-friendly;
* `lax.scan` over layers with stacked parameters — O(1) HLO size, fast
  compiles at 80+ layers;
* every weight carries logical sharding axes (parallel.sharding rules map
  them to dp/fsdp/tp/sp mesh axes), activations are constrained at layer
  boundaries so XLA inserts exactly the Megatron-style collectives;
* attention = ops.flash_attention (Pallas on TPU); with an "sp" mesh axis the
  trainer swaps in parallel.ring.ring_attention for long context;
* bf16 params/activations, f32 RMSNorm accumulation and logits;
* two optional departures from the Llama block, each one config field and
  one branch at the block's single call site: QK-norm before RoPE (`_qkv`)
  and a dropless top-k mixture of SwiGLU experts in the MLP's place
  (`_moe_ffn`, over ops.grouped_matmul). OLMoE-1B-7B is LlamaConfig with
  both (benchmarks/models/olmoe.py).

Decode-time KV caching lives here too (used by the serving engine).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.flash_attention import _on_tpu, flash_attention, mha_reference
from ..parallel.sharding import constrain, shard_kernel


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # parallel/perf knobs
    remat: bool = True                # jax.checkpoint each layer
    # "full" recomputes everything in the backward; "save_attn" keeps the
    # flash-attention output+lse (ops/flash_attention.py checkpoint_name
    # tags) so attention's forward is NOT replayed — more memory, fewer
    # FLOPs: the right default for MFU on HBM-rich chips
    remat_policy: str = "save_attn"
    use_flash: bool = True            # Pallas flash attention (vs reference)
    attn_block_q: int = 512
    attn_block_k: int = 512
    # RMSNorm with a learned weight over the WHOLE q and k projections
    # (all heads together), before the reshape into heads and RoPE (OLMoE)
    qk_norm: bool = False
    # mixture-of-experts (0 = dense MLP): every token gets all top_k of
    # its experts, whoever else is in the batch (dropless, _moe_ffn).
    # Experts shard over the ep mesh axis ("expert" logical axis): each
    # shard runs its own experts over the tokens it sees and the shards'
    # results are summed.
    moe_experts: int = 0
    moe_top_k: int = 2
    # divide the top_k router probabilities by their sum (Mixtral: yes;
    # OLMoE's norm_topk_prob is false)
    moe_renormalize: bool = True
    moe_aux_weight: float = 0.01      # load-balance loss weight

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6·params + attention)."""
        n_params = self.num_params()
        attn = 12 * self.n_layers * self.dim * (seq_len or self.max_seq_len)
        return 6 * n_params + attn

    def num_params(self) -> int:
        d, v = self.dim, self.vocab_size
        if self.moe_experts:
            mlp = (3 * d * self.mlp_dim * self.moe_experts
                   + d * self.moe_experts)                           # + router
        else:
            mlp = 3 * d * self.mlp_dim
        kvd = self.n_kv_heads * self.head_dim
        per_layer = (
            d * d + 2 * d * kvd + d * d                              # qkvo
            + mlp                                                    # (swi)glu
            + 2 * d                                                  # norms
            + (d + kvd if self.qk_norm else 0))
        return v * d + self.n_layers * per_layer + d + d * v


# Reference-scale presets + test-scale configs.
def llama3_8b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama3_70b(**kw) -> LlamaConfig:
    return LlamaConfig(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                       mlp_dim=28672, **kw)


def llama_tiny(**kw) -> LlamaConfig:
    """CI-scale config: same topology, toy sizes."""
    defaults = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, mlp_dim=128, max_seq_len=128,
                    dtype=jnp.float32, remat=False)
    defaults.update(kw)
    return LlamaConfig(**defaults)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init(rng: jax.Array, cfg: LlamaConfig) -> dict:
    """Initialize parameters. Layer weights are stacked on a leading
    n_layers axis (scanned in apply)."""
    k_emb, k_layers, k_out = jax.random.split(rng, 3)
    d, hd = cfg.dim, cfg.head_dim
    kvd = cfg.n_kv_heads * hd
    L = cfg.n_layers

    def norm_init(*shape):
        return jnp.ones(shape, cfg.dtype)

    def dense_init(key, shape, fan_in):
        std = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(
            cfg.dtype)

    # 7 keys as in the dense-only original; the router key is derived via
    # fold_in so dense init for a given seed is unchanged by the MoE branch
    ks = jax.random.split(k_layers, 7)
    if cfg.moe_experts:
        E = cfg.moe_experts
        mlp = {
            "mlp_norm": norm_init(L, d),
            "w_router": (jax.random.normal(
                jax.random.fold_in(k_layers, 7), (L, d, E),
                jnp.float32) / math.sqrt(d)),
            "w_gate": dense_init(ks[4], (L, E, d, cfg.mlp_dim), d),
            "w_up": dense_init(ks[5], (L, E, d, cfg.mlp_dim), d),
            "w_down": dense_init(ks[6], (L, E, cfg.mlp_dim, d), cfg.mlp_dim),
        }
    else:
        mlp = {
            "mlp_norm": norm_init(L, d),
            "w_gate": dense_init(ks[4], (L, d, cfg.mlp_dim), d),
            "w_up": dense_init(ks[5], (L, d, cfg.mlp_dim), d),
            "w_down": dense_init(ks[6], (L, cfg.mlp_dim, d), cfg.mlp_dim),
        }
    qk = {"q_norm": norm_init(L, cfg.n_heads * hd),
          "k_norm": norm_init(L, kvd)} if cfg.qk_norm else {}
    return {
        "embed": dense_init(k_emb, (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": norm_init(L, d),
            "wq": dense_init(ks[0], (L, d, cfg.n_heads * hd), d),
            "wk": dense_init(ks[1], (L, d, kvd), d),
            "wv": dense_init(ks[2], (L, d, kvd), d),
            "wo": dense_init(ks[3], (L, cfg.n_heads * hd, d), cfg.dim),
            **qk,
            **mlp,
        },
        "final_norm": norm_init(d),
        "lm_head": dense_init(k_out, (d, cfg.vocab_size), d),
    }


def logical_axes(cfg: LlamaConfig) -> dict:
    """Logical sharding axes per param (leading None = scanned layer dim).
    Resolved against the mesh by parallel.sharding.logical_sharding."""
    if cfg.moe_experts:
        mlp = {
            "mlp_norm": (None, "norm"),
            "w_router": (None, "embed", None),
            "w_gate": (None, "expert", "embed", "mlp"),
            "w_up": (None, "expert", "embed", "mlp"),
            "w_down": (None, "expert", "mlp", "embed"),
        }
    else:
        mlp = {
            "mlp_norm": (None, "norm"),
            "w_gate": (None, "embed", "mlp"),
            "w_up": (None, "embed", "mlp"),
            "w_down": (None, "mlp", "embed"),
        }
    # the QK-norm weights span all heads: replicated ("norm"), and _qkv
    # normalises the whole projection before it constrains q and k by head
    qk = {"q_norm": (None, "norm"),
          "k_norm": (None, "norm")} if cfg.qk_norm else {}
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": (None, "norm"),
            "wq": (None, "embed", "heads"),
            "wk": (None, "embed", "heads"),
            "wv": (None, "embed", "heads"),
            "wo": (None, "heads", "embed"),
            **qk,
            **mlp,
        },
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope_freqs(cfg: LlamaConfig, positions: jax.Array):
    """positions [B, S] -> (cos, sin) each [B, S, head_dim/2], f32."""
    inv = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, cfg.head_dim, 2, dtype=jnp.float32) / cfg.head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv     # [B,S,hd/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [B, S, H, D]; rotate pairs (even, odd interleave by halves)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, cfg: LlamaConfig, causal: bool, attn_impl):
    if attn_impl is not None:
        return attn_impl(q, k, v)
    if cfg.use_flash:
        def flash(q, k, v):
            return flash_attention(q, k, v, causal, None,
                                   cfg.attn_block_q, cfg.attn_block_k)
        q_axes = ("batch", None, "heads", None)
        kv_axes = ("batch", None, "kv_heads", None)
        return shard_kernel(flash, (q_axes, kv_axes, kv_axes),
                            q_axes)(q, k, v)
    return mha_reference(q, k, v, causal=causal)


def _qkv(h, p, cfg: LlamaConfig, cos, sin, lora=None, slots=None):
    """Projections (+ QK-norm over the whole projected vector when
    cfg.qk_norm) + RoPE, shared by every forward mode. h [B, S, D].

    ``lora``/``slots``: optional per-layer adapter slot table
    (_lora_at_layer) and per-row slot ids — the batched multi-LoRA
    serving path adds scale·(h@A[slot])@B[slot] to each projection.
    None (every training/base path) leaves the math untouched."""
    b, s, _ = h.shape
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if lora is not None:
        q = _lora_add(q, h, lora, "wq", slots)
        k = _lora_add(k, h, lora, "wk", slots)
        v = _lora_add(v, h, lora, "wv", slots)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = constrain(q, ("batch", "sequence", "heads", "head_dim"))
    k = constrain(k, ("batch", "sequence", "kv_heads", "head_dim"))
    return q, k, v


# ---------------------------------------------------------------------------
# Batched multi-LoRA (llm/multilora): slot-table deltas on the serving paths
# ---------------------------------------------------------------------------
# The slot table is a fixed-shape pytree (llm/multilora/slots.py):
#   "<t>.A" [S, L, in_t, R]  "<t>.B" [S, L, R, out_t]   t in wq/wk/wv/wo
#   "lm_head.A" [S, d, R]    "lm_head.B" [S, R, V]
#   "scale" [S] f32 (alpha/rank per slot; slot 0 = base, all-zero A/B)
# so every dispatch keeps XLA-static shapes no matter which tenants are
# in the batch; per-row `slots` ids select each row's adapter. Padding
# (rank < R, missing targets, slot 0) contributes an exact +0.0, so the
# base path through a lora-enabled program is bit-identical to the
# plain program.

_LORA_LAYER_TARGETS = ("wq", "wk", "wv", "wo")


def _lora_at_layer(lora, layer: int):
    """Slice the [S, L, ...] layer-stacked tables at one layer (static
    index — the serving paths unroll layers in Python)."""
    if lora is None:
        return None
    out = {"scale": lora["scale"]}
    for t in _LORA_LAYER_TARGETS:
        a = lora.get(f"{t}.A")
        if a is not None:
            out[f"{t}.A"] = a[:, layer]
            out[f"{t}.B"] = lora[f"{t}.B"][:, layer]
    return out


def _lora_add(y, x, lora, target: str, slots):
    """y + scale[slot]·(x @ A[slot]) @ B[slot] for one projection.

    x [..., in]; slots is a scalar (single-sequence scan rows: prefill
    chunk / verify) or [B] (batched decode). The low-rank math runs in
    f32 — mirroring lora.merge, which merges in f32 before casting —
    and the delta is cast back to y.dtype. Absent targets return y
    unchanged."""
    a = lora.get(f"{target}.A")
    if a is None:
        return y
    b = lora[f"{target}.B"]
    sc = lora["scale"][slots]
    xf = x.astype(jnp.float32)
    if jnp.ndim(slots) == 0:
        d = ((xf @ a[slots]) @ b[slots]) * sc
    else:
        d = jnp.einsum("bsr,bro->bso",
                       jnp.einsum("bsi,bir->bsr", xf, a[slots]),
                       b[slots]) * sc[:, None, None]
    return y + d.astype(y.dtype)


def _mlp_block(x, p, cfg: LlamaConfig):
    """Post-attention MLP with residual: dense SwiGLU, or the top-k
    mixture of experts when cfg.moe_experts > 0. Returns (x, aux, load):
    the load-balance loss (0.0 when dense) and the [E] int32 count of
    assignments each expert got (None when dense)."""
    h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    if cfg.moe_experts:
        y, aux, load = _moe_ffn(h, p, cfg)
        x = x + y
        return constrain(x, ("batch", "sequence", "embed")), aux, load
    gate = jax.nn.silu(h @ p["w_gate"])
    x = x + (gate * (h @ p["w_up"])) @ p["w_down"]
    return (constrain(x, ("batch", "sequence", "embed")), jnp.float32(0.0),
            None)


def _moe_ffn(h, p, cfg: LlamaConfig, interpret: bool = False):
    """Dropless top-k mixture of SwiGLU experts, the one algorithm behind
    training, prefill, decode and verify. h [B, S, D] ->
    (out [B, S, D], aux_loss, load [E] int32).

    p = softmax(h Wr) in float32 over all experts, the top_k values and
    indices (divided by their sum when cfg.moe_renormalize), and
    out = sum_j p_j * expert_{e_j}(h): every token gets all of its
    experts, so a token's output does not depend on which other tokens
    (or idle decode rows, or pad tokens) share its dispatch. The T x k
    assignments are laid out by expert in whole row tiles
    (ops.grouped_matmul.group_layout), the three expert matmuls run as
    grouped matmuls over that layout, and each token gathers its k rows
    back, weighted. ``load`` counts the assignments per expert, the
    padding's included: what the dispatch actually routed.

    Under a mesh the expert part runs per shard (shard_kernel): a shard
    holds E / ep experts (and mlp_dim / tp of each), computes their part
    for the tokens it sees, and the parts are summed over ep and tp."""
    E, k = cfg.moe_experts, cfg.moe_top_k
    logits = jnp.einsum("bsd,de->bse", h.astype(jnp.float32), p["w_router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_k, idx_k = jax.lax.top_k(probs, k)                    # [B, S, k]
    if cfg.moe_renormalize:
        gate_k = gate_k / jnp.maximum(
            gate_k.sum(axis=-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux: E * sum(frac_routed * mean_prob)
    me = probs.mean(axis=(0, 1))                               # [E]
    ce = jax.nn.one_hot(idx_k[..., 0], E).mean(axis=(0, 1))    # [E]
    aux = E * jnp.sum(me * ce)
    load = (idx_k.reshape(-1, 1) == jnp.arange(E)).sum(0, dtype=jnp.int32)

    tok = ("batch", "sequence", None)
    # the serving paths hand over the layers' stacks and a layer index
    # (_layer_params): one more leading, unsharded axis
    layer = p.get("expert_layer")
    stack = () if layer is None else (None,)
    ffn = shard_kernel(
        functools.partial(_expert_ffn, n_experts=E, mlp_dim=cfg.mlp_dim,
                          layer=layer, kernel=interpret or _on_tpu(),
                          interpret=interpret),
        (tok, tok, tok, stack + ("expert", None, "mlp"),
         stack + ("expert", None, "mlp"), stack + ("expert", "mlp", None)),
        tok)
    out = ffn(h, idx_k, gate_k, p["w_gate"], p["w_up"], p["w_down"])
    return out, aux, load


def _expert_ffn(h, idx, gate, w_gate, w_up, w_down, *, n_experts: int,
                mlp_dim: int, layer: Optional[int], kernel: bool,
                interpret: bool):
    """The experts' part of _moe_ffn on one shard: h [B, S, D], idx and
    gate [B, S, k], weights [E_here, D, F_here] / [E_here, F_here, D], or
    the layers' stacks of them with ``layer`` naming the one to use."""
    from ..ops import grouped_matmul as gmm

    b, s, d = h.shape
    k = idx.shape[-1]
    t, e_here = b * s, w_gate.shape[-3]
    over_ep, over_tp = e_here != n_experts, w_gate.shape[-1] != mlp_dim
    flat = idx.reshape(t * k)
    owned = None
    if over_ep:
        flat = flat - jax.lax.axis_index("ep") * e_here
        owned = (flat >= 0) & (flat < e_here)
    tm = gmm.tile_rows(t * k, e_here)
    row_of, padded, tile_expert, n_live = gmm.group_layout(
        flat, owned, e_here, tm)
    rows = gmm.num_tiles(t * k, e_here, tm) * tm
    # the token each padded row holds (t, out of range, for padding: it
    # reads as zeros and its gradient is dropped)
    tok_of_row = jnp.full((rows,), t, jnp.int32).at[row_of].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    x = h.reshape(t, d).at[tok_of_row].get(mode="fill", fill_value=0)
    if kernel:
        y = gmm.grouped_ffn(x, w_gate, w_up, w_down, padded, tile_expert,
                            n_live, tm, layer, interpret)
    else:
        y = gmm.grouped_ffn_reference(x, w_gate, w_up, w_down, padded,
                                      layer)
    y = y.at[row_of].get(mode="fill", fill_value=0)            # [T*k, D]
    out = (y.reshape(t, k, d).astype(jnp.float32)
           * gate.reshape(t, k, 1)).sum(1).astype(h.dtype)
    axes = (("ep",) if over_ep else ()) + (("tp",) if over_tp else ())
    if axes:
        out = jax.lax.psum(out, axes)
    return out.reshape(b, s, d)


def _layer(x, layer_params, cfg: LlamaConfig, cos, sin, attn_impl,
           kv_cache=None, cache_idx=None):
    """One transformer block. x [B, S, D]. Returns (x, new_kv) where new_kv
    is None in training mode."""
    p = layer_params
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    b, s, _ = h.shape
    q, k, v = _qkv(h, p, cfg, cos, sin)

    new_kv = None
    if kv_cache is not None:
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, cache_idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, cache_idx, axis=1)
        new_kv = (ck, cv)
        # decode: attend over the cache prefix. The causal mask k_pos <=
        # q_pos also hides the not-yet-written cache tail (its positions
        # exceed every query position).
        k_pos = jnp.arange(ck.shape[1])                        # [K]
        q_pos = cache_idx + jnp.arange(s)                      # [S]
        mask = k_pos[None, :] <= q_pos[:, None]                # [S, K]
        groups = cfg.n_heads // cfg.n_kv_heads
        kr = jnp.repeat(ck, groups, axis=2)
        vr = jnp.repeat(cv, groups, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                            preferred_element_type=jnp.float32)
        scores = scores * (cfg.head_dim ** -0.5)
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vr.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vr)
    else:
        attn = _attention(q, k, v, cfg, causal=True, attn_impl=attn_impl)

    attn = attn.reshape(b, s, cfg.n_heads * cfg.head_dim)
    x = x + attn @ p["wo"]
    x = constrain(x, ("batch", "sequence", "embed"))
    x, aux, _ = _mlp_block(x, p, cfg)
    return x, aux, new_kv


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def apply(params: dict, tokens: jax.Array, cfg: LlamaConfig,
          attn_impl=None) -> jax.Array:
    """Training/prefill forward: tokens [B, S] int32 -> logits [B, S, V] f32.

    `attn_impl(q, k, v)` overrides attention (the trainer passes a
    ring-attention closure when an "sp" axis is active). MoE configs:
    use apply_with_aux to also get the load-balance loss.
    """
    return apply_with_aux(params, tokens, cfg, attn_impl)[0]


def apply_with_aux(params: dict, tokens: jax.Array, cfg: LlamaConfig,
                   attn_impl=None):
    """(logits, aux) — aux is the mean per-layer MoE load-balance loss
    (0.0 for dense configs); add cfg.moe_aux_weight * aux to the loss."""
    x = params["embed"][tokens].astype(cfg.dtype)
    x = constrain(x, ("batch", "sequence", "embed"))
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    cos, sin = rope_freqs(cfg, positions)

    def body(carry, layer_params):
        x, aux = carry
        y, a, _ = _layer(x, layer_params, cfg, cos, sin, attn_impl)
        return (y, aux + a), None

    if cfg.remat:
        if cfg.remat_policy == "save_attn":
            policy = jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse")
            body = jax.checkpoint(body, policy=policy)
        else:
            body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                               params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, aux / cfg.n_layers


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int) -> dict:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "idx": jnp.zeros((), jnp.int32)}


def apply_decode(params: dict, tokens: jax.Array, cache: dict,
                 cfg: LlamaConfig) -> tuple[jax.Array, dict]:
    """Incremental forward with KV cache: tokens [B, S_step] appended at
    cache['idx']. Returns (logits [B, S_step, V], updated cache)."""
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = cache["idx"] + jnp.broadcast_to(
        jnp.arange(tokens.shape[1]), tokens.shape)
    cos, sin = rope_freqs(cfg, positions)

    def body(x, scanned):
        layer_params, kv = scanned
        y, _, new_kv = _layer(x, layer_params, cfg, cos, sin, None,
                              kv_cache=kv, cache_idx=cache["idx"])
        return y, new_kv

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], (cache["k"], cache["v"])))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    new_cache = {"k": new_k, "v": new_v,
                 "idx": cache["idx"] + tokens.shape[1]}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Continuous-batching cache (slot-based; used by the llm engine)
# ---------------------------------------------------------------------------

def init_slot_cache(cfg: LlamaConfig, max_batch: int, max_len: int) -> dict:
    """Per-slot KV cache: each batch row is an independent request with its
    own length (unlike init_kv_cache's single shared position)."""
    shape = (cfg.n_layers, max_batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "lengths": jnp.zeros((max_batch,), jnp.int32)}


def apply_with_kv(params: dict, tokens: jax.Array, cfg: LlamaConfig):
    """Prefill forward returning per-layer rope'd K/V for cache seeding:
    tokens [B, S] -> (logits [B, S, V], k/v [L, B, S, KVH, D])."""
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    cos, sin = rope_freqs(cfg, positions)

    def body(x, layer_params):
        p = layer_params
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        b, s, _ = h.shape
        q, k, v = _qkv(h, p, cfg, cos, sin)
        attn = _attention(q, k, v, cfg, causal=True, attn_impl=None)
        x = x + attn.reshape(b, s, -1) @ p["wo"]
        x = constrain(x, ("batch", "sequence", "embed"))
        x, _, _ = _mlp_block(x, p, cfg)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return logits, ks, vs


def decode_batched(params: dict, tokens: jax.Array, cache: dict,
                   cfg: LlamaConfig) -> tuple[jax.Array, dict]:
    """One decode step for a batch of independent slots.

    tokens [B, 1] — next token per slot; cache rows advance at their own
    `lengths`. Returns (logits [B, V], updated cache). Inactive slots should
    carry any token; caller masks their outputs.
    """
    b = tokens.shape[0]
    rows = jnp.arange(b)
    x = params["embed"][tokens].astype(cfg.dtype)         # [B, 1, D]
    positions = cache["lengths"][:, None]                 # [B, 1]
    cos, sin = rope_freqs(cfg, positions)
    k_pos = jnp.arange(cache["k"].shape[2])[None, :]      # [1, S]
    mask = k_pos <= positions                             # [B, S]

    def body(x, scanned):
        p, (ck, cv) = scanned
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(h, p, cfg, cos, sin)
        ck = ck.at[rows, cache["lengths"]].set(k[:, 0].astype(ck.dtype))
        cv = cv.at[rows, cache["lengths"]].set(v[:, 0].astype(cv.dtype))
        groups = cfg.n_heads // cfg.n_kv_heads
        kr = jnp.repeat(ck, groups, axis=2)
        vr = jnp.repeat(cv, groups, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                            preferred_element_type=jnp.float32)
        scores = scores * (cfg.head_dim ** -0.5)
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vr.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vr)
        x = x + attn.reshape(b, 1, -1) @ p["wo"]
        x, _, _ = _mlp_block(x, p, cfg)
        return x, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], (cache["k"], cache["v"])))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)[:, 0]
    new_cache = {"k": new_k, "v": new_v, "lengths": cache["lengths"] + 1}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Pipeline-parallel forward (GPipe over the pp mesh axis)
# ---------------------------------------------------------------------------

def apply_pipelined(params: dict, tokens: jax.Array, cfg: LlamaConfig,
                    mesh, num_microbatches: int,
                    attn_impl=None, num_chunks: int = 1) -> jax.Array:
    """Training forward with transformer blocks pipelined over the mesh's
    `pp` axis (parallel.pipeline schedules: GPipe, or breadth-first
    interleaved virtual stages with num_chunks > 1 — bubble drops from
    (S-1)/(M+S-1) to (S-1)/(num_chunks*M+S-1)). Embedding and lm_head are
    pp-replicated and stay outside the pipeline; pp_size * num_chunks must
    divide cfg.n_layers. Matches `apply` numerically."""
    from ..parallel.pipeline import (interleave_stages, pipeline_apply,
                                     split_stages)

    if cfg.moe_experts:
        # the GPipe stage fn drops each layer's load-balance aux term; MoE
        # training must not lose it silently — use apply_with_aux (dense pp
        # for MoE needs an aux-accumulating pipeline, not yet built)
        raise NotImplementedError(
            "apply_pipelined does not propagate the MoE aux loss; "
            "train MoE configs with apply_with_aux (ep/dp sharding)")

    n_stages = mesh.shape.get("pp", 1)
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.arange(tokens.shape[1])[None, :]
    cos, sin = rope_freqs(cfg, positions)  # [1, S, hd/2]: broadcasts over mb

    def stage_fn(stage_layers, h):
        def body(h, layer_params):
            y, _, _ = _layer(h, layer_params, cfg, cos, sin, attn_impl)
            return y, None
        h, _ = jax.lax.scan(body, h, stage_layers)
        return h

    stages = split_stages(params["layers"], n_stages * num_chunks)
    if num_chunks > 1:
        stages = interleave_stages(stages, n_stages, num_chunks)
    x = pipeline_apply(stage_fn, stages, x, mesh, num_microbatches,
                       remat=cfg.remat, num_chunks=num_chunks)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Paged KV cache (block tables; used by the paged serving engine)
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: LlamaConfig, num_pages: int,
                     page_size: int) -> list[dict]:
    """Per-layer page pools: [{'k','v': [P, page, KVH, D]}] * n_layers.

    Kept as SEPARATE per-layer arrays (not a stacked [L, ...] tensor): the
    decode step is unrolled over layers so each Pallas paged-attention call
    consumes its layer's pool directly — a scan-sliced stacked tensor would
    materialize a full-layer copy per step.

    Convention: physical page 0 is a write SINK — allocators must never
    hand it to a sequence. decode_paged (idle rows) and prefill_paged_chunk
    (pad pages) dump never-attended writes there.
    """
    shape = (num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return [{"k": jnp.zeros(shape, cfg.dtype),
             "v": jnp.zeros(shape, cfg.dtype)}
            for _ in range(cfg.n_layers)]


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _layer_params(params: dict, layer: int) -> dict:
    """One layer's parameters out of the stacks, for the serving paths,
    which unroll their layers. The experts' weights stay stacked, with
    the layer's index beside them as ``expert_layer``: the grouped-matmul
    kernels read that layer's blocks in place, where a slice handed to a
    Pallas call is first copied whole (805 MB a layer a step at OLMoE's
    widths — as much as the expert matmuls themselves read)."""
    stacks = params["layers"]
    moe = "w_router" in stacks
    p = {k: a if moe and k in _EXPERT_WEIGHTS else a[layer]
         for k, a in stacks.items()}
    if moe:
        p["expert_layer"] = layer
    return p


def _add_load(total, routed):
    """Sum of the layers' per-expert assignment counts (_mlp_block's
    ``load``); None all the way for a dense config, which routes nothing
    and whose programs get no such output."""
    return routed if total is None or routed is None else total + routed


# logical axes of a page pool [P, page, KVH, D] (the engine commits it so)
_PAGES_AXES = (None, None, "kv_heads", None)


def _window_attend(scale: float, interpret: bool):
    """Attention of query windows [R, Q, H, D] over pages that already
    hold the windows' own K/V (prefill chunks, verify windows): the
    ragged Pallas kernel on TPU or under ``interpret`` — per shard under
    a mesh — and elsewhere its jnp oracle, which IS the fallback (one
    copy of the gather/mask/grouped-GQA math to keep in sync with the
    kernel). Call as attend(q, k_pages, v_pages, block_tables, starts,
    q_lens)."""
    from ..ops.ragged_paged_attention import (
        ragged_paged_attention, ragged_paged_reference,
    )
    if not (interpret or _on_tpu()):
        return functools.partial(ragged_paged_reference, scale=scale)
    q_axes = (None, None, "heads", None)
    return shard_kernel(
        functools.partial(ragged_paged_attention, scale=scale,
                          interpret=interpret),
        (q_axes, _PAGES_AXES, _PAGES_AXES, (), (), ()), q_axes)


def decode_paged(params: dict, tokens: jax.Array, caches: list[dict],
                 block_tables: jax.Array, lengths: jax.Array,
                 cfg: LlamaConfig, *, page_size: int,
                 interpret: bool = False, lora=None, slots=None):
    """One decode step over paged caches.

    tokens [B, 1]; block_tables [B, max_pages]; lengths [B] = tokens already
    WRITTEN (current token goes at position `lengths`). Returns
    (logits [B, V], updated caches, load) — load is the [E] int32 count of
    expert assignments over all layers, None for a dense config (the same
    third result on every paged forward below). Inactive rows: pass length 0 and mask
    the output — their token writes land in page block_tables[b, 0] slot 0
    and are overwritten on real use.

    ``lora``/``slots`` [B]: batched multi-LoRA — each row's projections
    (and logits, for lm_head adapters) get its slot's low-rank delta, so
    ONE dispatch serves a mixed-tenant batch (see _lora_add).
    """
    from ..ops.paged_attention import paged_decode_reference
    from ..ops.ragged_paged_attention import ragged_decode_attention

    b = tokens.shape[0]
    rows = jnp.arange(b)
    x = params["embed"][tokens].astype(cfg.dtype)          # [B, 1, D]
    cos, sin = rope_freqs(cfg, lengths[:, None])
    page_ids = block_tables[rows, lengths // page_size]    # [B]
    offsets = lengths % page_size                          # [B]
    # hoisted: the platform probe + partial are trace-time constants, so
    # selecting per layer just re-evaluated them n_layers times per step
    if interpret or _on_tpu():
        q_axes = (None, "heads", None)
        attend = shard_kernel(
            functools.partial(ragged_decode_attention, interpret=interpret),
            (q_axes, _PAGES_AXES, _PAGES_AXES, (), ()), q_axes)
    else:
        attend = paged_decode_reference

    new_caches, load = [], None
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer)
        ll = _lora_at_layer(lora, layer)
        cache = caches[layer]
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(h, p, cfg, cos, sin, ll, slots)     # q [B,1,H,D]
        k_pages = cache["k"].at[page_ids, offsets].set(
            k[:, 0].astype(cache["k"].dtype))
        v_pages = cache["v"].at[page_ids, offsets].set(
            v[:, 0].astype(cache["v"].dtype))
        attn = attend(q[:, 0], k_pages, v_pages, block_tables,
                      lengths + 1)                         # [B, H, D]
        proj = attn.reshape(b, 1, -1)
        y = proj @ p["wo"]
        if ll is not None:
            y = _lora_add(y, proj, ll, "wo", slots)
        x = x + y
        x, _, routed = _mlp_block(x, p, cfg)
        load = _add_load(load, routed)
        new_caches.append({"k": k_pages, "v": v_pages})

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    if lora is not None and "lm_head.A" in lora:
        logits = _lora_add(logits, x, lora, "lm_head", slots)
    return logits[:, 0], new_caches, load


def prefill_paged_chunk(params: dict, chunk: jax.Array, caches: list[dict],
                        block_table_row: jax.Array, start_pos: jax.Array,
                        cfg: LlamaConfig, *, page_size: int,
                        true_chunk_len: jax.Array | None = None,
                        interpret: bool = False, lora=None, slot=None):
    """Prefill ONE page-aligned chunk of one sequence.

    chunk [1, C] (C a multiple of page_size, right-padded with zeros);
    block_table_row [max_pages]; start_pos = tokens already prefilled
    (page-aligned); true_chunk_len = real tokens in this chunk (defaults to
    C). Attends over the already-written paged prefix plus causally within
    the chunk, writes the chunk's K/V into its pages, and returns
    (logits [C, V], updated caches, load) — caller picks the logit at the
    prompt's true last position.

    Attention dispatch: the chunk's K/V is scattered into its pages
    FIRST, so attention always reads pages only (prefix + causal window
    in one predicate). On TPU (or under ``interpret``) that is the
    ragged Pallas kernel (ops/ragged_paged_attention.py) with HBM
    traffic tracking the row's live page count; elsewhere it is the
    kernel's own jnp oracle (ragged_paged_reference), whose gather cost
    scales with the block-table row WIDTH — which the engine buckets to
    the live page count (power-of-two page buckets) at long tables.

    Pages past the chunk's real tokens (pad pages of the final chunk, or
    logical pages beyond the block table) are written to page 0 — the
    reserved sink page no sequence owns — so a short final chunk can never
    clobber pages the allocator has handed to another sequence.

    Chunked prefill exists so admission never stalls decode: the engine
    interleaves one bounded chunk per step (vLLM's chunked-prefill role).
    """
    c = chunk.shape[1]
    n_chunk_pages = c // page_size
    max_pages = block_table_row.shape[0]
    positions = start_pos + jnp.arange(c)[None, :]        # [1, C]
    cos, sin = rope_freqs(cfg, positions)
    attend = _window_attend(cfg.head_dim ** -0.5, interpret)
    if true_chunk_len is None:
        true_chunk_len = jnp.int32(c)
    # gather (not dynamic_slice: it clamps at the row end and would silently
    # shift the write window); invalid logical pages route to sink page 0
    logical = start_pos // page_size + jnp.arange(n_chunk_pages)
    valid_pages = (true_chunk_len + page_size - 1) // page_size
    valid = (jnp.arange(n_chunk_pages) < valid_pages) & (logical < max_pages)
    chunk_page_ids = jnp.where(
        valid, block_table_row[jnp.clip(logical, 0, max_pages - 1)], 0)

    x = params["embed"][chunk].astype(cfg.dtype)          # [1, C, D]
    new_caches, load = [], None
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer)
        ll = _lora_at_layer(lora, layer)
        cache = caches[layer]
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(h, p, cfg, cos, sin, ll, slot)     # [1,C,H/KVH,D]

        # write the chunk's K/V into its (page-aligned) pages
        k_w = k[0].reshape(n_chunk_pages, page_size,
                           cfg.n_kv_heads, cfg.head_dim)
        v_w = v[0].reshape(n_chunk_pages, page_size,
                           cfg.n_kv_heads, cfg.head_dim)
        k_pages = cache["k"].at[chunk_page_ids].set(
            k_w.astype(cache["k"].dtype))
        v_pages = cache["v"].at[chunk_page_ids].set(
            v_w.astype(cache["v"].dtype))

        # the scatter above already placed the window's K/V, so
        # attention reads pages only (prefix + causal window in one
        # predicate). Real queries (q < true_chunk_len) read only real
        # pages; pad queries read sink-routed garbage the caller discards.
        starts1 = jnp.reshape(start_pos, (1,)).astype(jnp.int32)
        qlens1 = jnp.reshape(true_chunk_len, (1,)).astype(jnp.int32)
        attn = attend(q, k_pages, v_pages, block_table_row[None], starts1,
                      qlens1).astype(cfg.dtype)
        proj = attn.reshape(1, c, -1)
        y = proj @ p["wo"]
        if ll is not None:
            y = _lora_add(y, proj, ll, "wo", slot)
        x = x + y
        x, _, routed = _mlp_block(x, p, cfg)
        load = _add_load(load, routed)
        new_caches.append({"k": k_pages, "v": v_pages})

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                        preferred_element_type=jnp.float32)
    if lora is not None and "lm_head.A" in lora:
        logits = _lora_add(logits, x, lora, "lm_head", slot)
    return logits[0], new_caches, load


def prefill_paged_rows(params: dict, chunks: jax.Array, caches: list[dict],
                       bt_rows: jax.Array, start_pos: jax.Array,
                       true_lens: jax.Array, cfg: LlamaConfig, *,
                       page_size: int, interpret: bool = False,
                       lora=None, slots=None):
    """Prefill up to R chunk-rows in ONE compiled program.

    chunks [R, C] (each row one page-aligned chunk, right-padded);
    bt_rows [R, max_pages]; start_pos/true_lens [R]. Rows run sequentially
    under lax.scan carrying the caches, so consecutive rows may be
    consecutive chunks of the SAME sequence — row i+1 sees row i's page
    writes. Rows with true_lens == 0 are padding: all their page writes
    route to sink page 0. Returns (last_logits [R, V] — the logit at each
    row's last real token — updated caches, and the rows' summed load).

    Exists to cut engine-step dispatch count: a burst of prompts prefills
    in ceil(n_chunks / R) dispatches instead of one dispatch per chunk
    (the batched-prefill scheduling role of the reference's vLLM engine,
    llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:180).
    """
    c = chunks.shape[1]
    # slots join the scanned operands only on the multi-LoRA path, so
    # lora=None traces exactly the pre-LoRA program
    if lora is not None and slots is None:
        slots = jnp.zeros((chunks.shape[0],), jnp.int32)

    def body(carry, row):
        chunk, bt, sp, tl = row[:4]
        sl = row[4] if lora is not None else None
        logits, carry, load = prefill_paged_chunk(
            params, chunk[None, :], carry, bt, sp, cfg,
            page_size=page_size, true_chunk_len=tl, interpret=interpret,
            lora=lora, slot=sl)
        last = logits[jnp.clip(tl - 1, 0, c - 1)]
        return carry, (last, load)

    xs = (chunks, bt_rows, start_pos, true_lens)
    if lora is not None:
        xs = xs + (slots,)
    caches, (last, load) = jax.lax.scan(body, caches, xs)
    return last, caches, None if load is None else load.sum(0)


def verify_paged_rows(params: dict, tokens: jax.Array, caches: list[dict],
                      bt_rows: jax.Array, starts: jax.Array,
                      cfg: LlamaConfig, *, page_size: int,
                      interpret: bool = False, lora=None, slots=None):
    """Speculative-verification forward (the scorer role of vLLM-style
    speculative decoding in the reference's serving engine): for each of
    R rows feed S1 = 1 + n_draft tokens at positions
    starts[r] .. starts[r]+S1-1 over that row's paged KV, writing their
    K/V in place, and return (logits [R, S1, V] for every fed position,
    updated caches, load) —
    the engine accepts the longest draft prefix the model agrees with,
    so one dispatch can emit up to S1 tokens.

    Attention dispatch mirrors prefill_paged_chunk: the ragged paged
    kernel on TPU / under ``interpret`` (the K/V scatter already happens
    before attention here), the plain-jnp gather as fallback/oracle.

    Position p's K/V lands in page bt_rows[r, p // page_size] at slot
    p % page_size; positions past the block table route to sink page 0
    (their logits are garbage and the engine discards them). Rejected
    drafts leave stale K/V beyond the accepted length — never attended,
    because attention is causal and the engine re-feeds real tokens at
    those same positions next dispatch, overwriting in place.

    Rows run under one lax.scan carrying the caches (same shape
    discipline as prefill_paged_rows; R and S1 are static).
    """
    maxp = bt_rows.shape[1]
    s1 = tokens.shape[1]
    attend = _window_attend(cfg.head_dim ** -0.5, interpret)
    if lora is not None and slots is None:
        slots = jnp.zeros((tokens.shape[0],), jnp.int32)

    def body(carry, row):
        toks, bt, start = row[:3]
        sl = row[3] if lora is not None else None
        positions = start + jnp.arange(s1)                 # [S1]
        cos, sin = rope_freqs(cfg, positions[None])
        pidx = positions // page_size
        page_ids = jnp.where(pidx < maxp,
                             bt[jnp.clip(pidx, 0, maxp - 1)], 0)
        offsets = positions % page_size
        x = params["embed"][toks][None].astype(cfg.dtype)  # [1, S1, D]
        new_caches, load = [], None
        for layer in range(cfg.n_layers):
            p = _layer_params(params, layer)
            ll = _lora_at_layer(lora, layer)
            cache = carry[layer]
            h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
            q, k, v = _qkv(h, p, cfg, cos, sin, ll, sl)    # [1,S1,H/KVH,D]
            k_pages = cache["k"].at[page_ids, offsets].set(
                k[0].astype(cache["k"].dtype))
            v_pages = cache["v"].at[page_ids, offsets].set(
                v[0].astype(cache["v"].dtype))
            # the scatter above already placed the window's K/V, so
            # attention reads pages only
            attn = attend(q, k_pages, v_pages, bt[None],
                          jnp.reshape(start, (1,)).astype(jnp.int32),
                          jnp.full((1,), s1, jnp.int32)).astype(cfg.dtype)
            proj = attn.reshape(1, s1, -1)
            y = proj @ p["wo"]
            if ll is not None:
                y = _lora_add(y, proj, ll, "wo", sl)
            x = x + y
            x, _, routed = _mlp_block(x, p, cfg)
            load = _add_load(load, routed)
            new_caches.append({"k": k_pages, "v": v_pages})
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
        if lora is not None and "lm_head.A" in lora:
            logits = _lora_add(logits, x, lora, "lm_head", sl)
        return new_caches, (logits[0], load)

    xs = (tokens, bt_rows, starts)
    if lora is not None:
        xs = xs + (slots,)
    caches, (logits, load) = jax.lax.scan(body, caches, xs)
    # logits [R, S1, V]
    return logits, caches, None if load is None else load.sum(0)


def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token NLL. logits [B,S,V] f32, targets [B,S] int32."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()
