"""Latent-attention (MLA) decoder with a sigmoid-routed mixture of experts
behind a dense prefix — the DeepSeek-V3 block, as kanana-2-30b-a3b and its
relatives publish it (``model_type: deepseek_v3``). The serving engine's
second model module: it gives ``llm/paged_engine.py`` the same functions
``models/llama.py`` does, over ONE latent page pool a layer.

    attention   q = Wq n1(x)                   [H, nope + rope]   (no q_lora)
                a = Wkva n1(x)                 [rank + rope]
                c = n_kv(a[:rank]);  k_r = RoPE(a[rank:])  — ONE rope key
                k_nope_h = W_UK_h c,  v_h = W_UV_h c       (Wkvb, per head)
                s_h = (q_nope_h . k_nope_h + RoPE(q_rope_h) . k_r)
                      * (nope + rope) ** -0.5
                x = x + Wo concat_h(softmax(s_h) v_h)
    dense layer x = x + SwiGLU(n2(x); dense_mlp_dim)   (the first
                n_dense_layers)
    MoE layer   z = n2(x);  s = sigmoid(z Wr) in float32
                E = the top_k experts by (s + b) — with n_group > 1 among
                the experts of the topk_group best groups alone, a group
                (E / n_group consecutive experts) scored by the sum of its
                two largest (s + b);  w_e = routed_scale * s_e
                / (sum_E s + 1e-20)              (weights from s WITHOUT b)
                x = x + sum_E w_e SwiGLU_e(z) + SwiGLU_shared(z), the sum
                over the chosen experts HELD here (``experts_held``: one
                chip's share of a layer; None: all of them)

What a token leaves in the cache is ``c ‖ k_r`` (rank + rope values, one
"kv head" for all query heads), and the paged forwards read it in the
ABSORBED form: ``s_h = ((q_nope_h W_UK_h) ‖ RoPE(q_rope_h)) . (c ‖ k_r)``
and ``o_h = (softmax(s_h) c) W_UV_h`` — a key as wide as the pool's lanes
whose first ``rank`` lanes are also the value
(ops/ragged_paged_attention.py ``ragged_latent_attention``). ``apply`` is
the plain full-sequence forward in the EXPANDED form (per-head keys and
values), which the tests hold the absorbed programs against.

RoPE is the published interleaved form — pairs ``(x[2i], x[2i+1])`` at
``theta ** (-2i / rope)`` — with the rotated pairs left de-interleaved
(all first members, then all second): the same permutation on q and on
the cached key, so every score is the published one and nothing is put
back in order. Reused, not copied, from models/llama.py: ``rms_norm``,
the grouped expert FFN (``routed_experts`` over ``_expert_ffn`` and
ops/grouped_matmul.py) and the per-expert load count.

Its pieces also serve ``models/ling_hybrid.py``, whose latent layers are
these behind a gate a head (``_attn_out``'s ``gate``) and whose expert
layers are `_ffn_block` with grouped routing over a held share.

Not built here, and refused by name where asked for: LoRA targets
(``lora_targets`` is empty, so ``PagedEngineConfig.max_adapters`` > 0
raises), a mesh (``check_mesh`` raises on ``PagedEngineConfig.mesh``) and
a compressed query (``q_lora_rank``): the builder refuses that key.
Training wants a flash kernel with unequal key and value widths (ROADMAP
R3); ``apply`` is plain jnp.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.flash_attention import _on_tpu
from .llama import (_add_load, chunk_pages, expert_load, held_load, rms_norm,
                    routed_experts)


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 128256
    dim: int = 2048
    n_layers: int = 48                # the dense prefix included
    n_dense_layers: int = 1           # first_k_dense_replace
    n_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    dense_mlp_dim: int = 6144         # intermediate_size
    moe_experts: int = 128            # n_routed_experts
    moe_top_k: int = 6
    mlp_dim: int = 768                # ONE routed expert's width
    n_shared_experts: int = 2         # one SwiGLU of n_shared x mlp_dim
    routed_scale: float = 2.448
    # group-limited selection: the experts in n_group groups of consecutive
    # experts, a token's top_k taken inside its topk_group best groups
    n_group: int = 1
    topk_group: int = 1
    # the experts this replica holds, [lo, hi) of moe_experts; None: all
    experts_held: Optional[tuple] = None
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def latent_dim(self) -> int:
        """What one token leaves in one layer's cache: c and the rope key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_lanes(self) -> int:
        """Lanes of a pool row: ``latent_dim`` rounded up to whole
        128-lane tiles (640 for 576). A 576-lane pool is laid out over
        640 lanes in HBM anyway, and the TPU compiler refuses a page copy
        that is not whole tiles (PERF.md §6, PR 31); the pad lanes are
        zeros in the pool and in q, exact +0.0 in every score."""
        return -(-self.latent_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held or (0, self.moe_experts))


def mla_moe_tiny(**kw) -> MlaMoeConfig:
    """CI-scale config: same topology, toy sizes."""
    defaults = dict(vocab_size=256, dim=64, n_layers=3, n_dense_layers=1,
                    n_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                    kv_lora_rank=32, dense_mlp_dim=128, moe_experts=8,
                    moe_top_k=2, mlp_dim=32, n_shared_experts=2,
                    max_seq_len=256, dtype=jnp.float32)
    defaults.update(kw)
    return MlaMoeConfig(**defaults)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init(rng: jax.Array, cfg: MlaMoeConfig) -> dict:
    """Two stacks of layers: ``dense_layers`` (the first n_dense_layers)
    and ``layers`` (the expert layers), each on a leading layer axis.
    ``w_uk`` / ``w_uv`` are Wkvb split per head: [H, nope, rank] maps a
    head's q_nope into the latent space (k_nope_h = w_uk_h c), [H, rank,
    v] maps attended latents to the head's values. The router, and the
    selection bias ``router_bias`` (e_score_correction_bias: drawn
    N(0, 0.1^2), a trained one is not zero), are float32."""
    d, nh = cfg.dim, cfg.n_heads
    qk, rank = cfg.qk_nope_dim + cfg.qk_rope_dim, cfg.kv_lora_rank
    n_moe = cfg.n_layers - cfg.n_dense_layers
    f_sh = cfg.n_shared_experts * cfg.mlp_dim
    k_emb, k_dense, k_moe, k_out = jax.random.split(rng, 4)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(cfg.dtype)

    def ones(*shape):
        return jnp.ones(shape, cfg.dtype)

    def attention(key, n):
        ks = jax.random.split(key, 5)
        return {
            "attn_norm": ones(n, d),
            "wq": dense(ks[0], (n, d, nh * qk), d),
            "wkv_a": dense(ks[1], (n, d, cfg.latent_dim), d),
            "kv_norm": ones(n, rank),
            "w_uk": dense(ks[2], (n, nh, cfg.qk_nope_dim, rank), rank),
            "w_uv": dense(ks[3], (n, nh, rank, cfg.v_head_dim), rank),
            "wo": dense(ks[4], (n, nh * cfg.v_head_dim, d),
                        nh * cfg.v_head_dim),
            "mlp_norm": ones(n, d),
        }

    def swiglu(key, lead, width, names):
        ks = jax.random.split(key, 3)
        return {names[0]: dense(ks[0], lead + (d, width), d),
                names[1]: dense(ks[1], lead + (d, width), d),
                names[2]: dense(ks[2], lead + (width, d), width)}

    ffn = ("w_gate", "w_up", "w_down")
    kd, km = jax.random.split(k_dense), jax.random.split(k_moe, 5)
    e = cfg.moe_experts
    held = cfg.held[1] - cfg.held[0]
    return {
        "embed": dense(k_emb, (cfg.vocab_size, d), d),
        "dense_layers": {
            **attention(kd[0], cfg.n_dense_layers),
            **swiglu(kd[1], (cfg.n_dense_layers,), cfg.dense_mlp_dim, ffn)},
        "layers": {
            **attention(km[0], n_moe),
            "w_router": jax.random.normal(km[1], (n_moe, d, e), jnp.float32)
            / math.sqrt(d),
            "router_bias": 0.1 * jax.random.normal(km[2], (n_moe, e),
                                                   jnp.float32),
            **swiglu(km[3], (n_moe, held), cfg.mlp_dim, ffn),
            **swiglu(km[4], (n_moe,), f_sh,
                     ("ws_gate", "ws_up", "ws_down"))},
        "final_norm": ones(d),
        "lm_head": dense(k_out, (d, cfg.vocab_size), d),
    }


# -- what the engine asks a model module beside its forwards --------------

_NO_MESH = ("PagedEngineConfig.mesh: models/mla_moe.py has no sharding "
            "rules yet (one latent head cannot split over tp; experts over "
            "ep need the all-to-all of ROADMAP R3) — serve it with "
            "mesh=None")


def cache_window(cfg: MlaMoeConfig) -> int:
    """Every layer keeps every key: one kind of page pool."""
    return 0


def cache_layers(cfg: MlaMoeConfig) -> list:
    """The cache kind each layer holds a sequence in: latent pages."""
    return ["full"] * cfg.n_layers


def check_mesh(cfg: MlaMoeConfig, sizes: dict) -> None:
    """The engine asks this before ``logical_axes`` and
    ``cache_logical_axes``, which this module therefore does not have."""
    raise NotImplementedError(_NO_MESH)


def lora_targets(cfg: MlaMoeConfig) -> tuple:
    """Projections a LoRA slot table may adapt: none yet (the absorbed
    products have no ``wk`` / ``wv`` to add a delta to)."""
    return ()


def routed_per_token(cfg: MlaMoeConfig) -> int:
    """Token-expert assignments one token makes through the whole depth
    (the shared expert is not routed and not counted)."""
    return cfg.moe_top_k * (cfg.n_layers - cfg.n_dense_layers)


def expert_routing(cfg: MlaMoeConfig) -> tuple[int, int, int]:
    """(routed experts a layer, experts a token goes to, experts held
    here): what sizes the groups of the grouped expert matmul; zeros where
    every layer is dense (llama.expert_routing's twin)."""
    return (cfg.moe_experts, cfg.moe_top_k, cfg.held[1] - cfg.held[0]) \
        if routed_per_token(cfg) else (0, 0, 0)


def experts_held(cfg: MlaMoeConfig) -> tuple[int, int]:
    """[lo, hi) of the routed experts whose weights this replica has."""
    return cfg.held if routed_per_token(cfg) else (0, 0)


def attn_step(cfg: MlaMoeConfig, q_window: int, page_size: int,
              table_pages: int, head_shards: int = 1) -> dict:
    """{'q_tile', 'block_keys'}: the query rows a tile and the keys a grid
    step of the kernel `_attend` calls under a window of ``q_window``
    queries a row (a prefill chunk; 1 = decode) over a table that wide —
    the kernel module's own derivation, for the engine's counts of the
    steps its prefill rows sweep and of the pages its decode rows sweep."""
    from ..ops.ragged_paged_attention import window_step
    if head_shards != 1:
        raise NotImplementedError(_NO_MESH)
    return window_step(
        q_window, cfg.n_heads, 1, cfg.latent_lanes, page_size=page_size,
        table_pages=table_pages, itemsize=jnp.dtype(cfg.dtype).itemsize,
        v_width=cfg.kv_lora_rank)


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _layer_params(params: dict, layer: int, cfg: MlaMoeConfig) -> dict:
    """One layer's parameters out of its stack. An expert layer's routed
    weights stay stacked with the index beside them (``expert_layer``),
    as llama._layer_params keeps them: the grouped kernels read that
    layer's blocks in place."""
    if layer < cfg.n_dense_layers:
        return {k: a[layer] for k, a in params["dense_layers"].items()}
    li = layer - cfg.n_dense_layers
    p = {k: a if k in _EXPERT_WEIGHTS else a[li]
         for k, a in params["layers"].items()}
    p["expert_layer"] = li
    return p


def rope_freqs(cfg: MlaMoeConfig, positions: jax.Array):
    """positions [B, S] -> (cos, sin) each [B, S, rope / 2], float32."""
    half = cfg.qk_rope_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [B, S, H, rope]: rotates the published pairs (x[2i], x[2i+1]) and
    returns them de-interleaved, [first members ‖ second members]."""
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s],
                           axis=-1).astype(x.dtype)


def _projections(h, p, cfg: MlaMoeConfig, cos, sin):
    """h [B, S, D] -> q_nope [B, S, H, nope], q_rope [B, S, H, rope]
    (rotated), c [B, S, rank] (after its norm), k_rope [B, S, rope]
    (rotated): what both attention forms start from."""
    b, s, _ = h.shape
    # the flat projection is finished before it is cut into heads: left
    # free, the TPU compiler folds the reshape into the matmul and
    # transposes all of wq for it in every layer of every dispatch
    # (llama._qkv's ``fence``)
    q = jax.lax.optimization_barrier(h @ p["wq"])
    q = q.reshape(b, s, cfg.n_heads, -1)
    a = h @ p["wkv_a"]
    c = rms_norm(a[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(a[:, :, None, cfg.kv_lora_rank:], cos, sin)[:, :, 0]
    q_rope = apply_rope(q[..., cfg.qk_nope_dim:], cos, sin)
    return q[..., :cfg.qk_nope_dim], q_rope, c, k_rope


def _absorbed(h, p, cfg: MlaMoeConfig, cos, sin):
    """The absorbed operands: queries [B, S, H, lanes] = (q_nope W_UK) ‖
    q_rope ‖ 0 and the cache entries [B, S, lanes] = c ‖ k_rope ‖ 0."""
    q_nope, q_rope, c, k_rope = _projections(h, p, cfg, cos, sin)
    q_lat = jnp.einsum("bshn,hnc->bshc", q_nope, p["w_uk"])

    def lanes(*parts):
        x = jnp.concatenate(parts, axis=-1)
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                       + [(0, cfg.latent_lanes - cfg.latent_dim)])
    return lanes(q_lat, q_rope), lanes(c, k_rope)


def _attn_out(o_lat, p, cfg: MlaMoeConfig, gate=None):
    """Attended latents [B, S, H, rank] -> the block's residual term.
    ``gate`` [B, S, H] float32: a factor a head on the attention's output —
    behind W_UV, which the absorbed form applies here, and ahead of Wo."""
    b, s = o_lat.shape[:2]
    o = jnp.einsum("bshc,hcv->bshv", o_lat.astype(cfg.dtype), p["w_uv"])
    if gate is not None:
        o = (o.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
    return o.reshape(b, s, -1) @ p["wo"]


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


def _select(z, p, cfg: MlaMoeConfig):
    """`route`, and with it the groups a token's selection was limited to
    ([B, S, n_group] bool; None without groups)."""
    logits = jnp.einsum("bsd,de->bse", z.astype(jnp.float32), p["w_router"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    biased, kept = scores + p["router_bias"], None
    if cfg.n_group > 1:
        groups = biased.reshape(biased.shape[:-1] + (cfg.n_group, -1))
        # a group's two largest as two reductions: its maximum, and the
        # maximum of the rest (a top_k of 2 is a whole sort on the TPU:
        # 7% of the device's time at 8 groups of 64, PERF.md §6, PR 50)
        first = groups.argmax(axis=-1, keepdims=True)
        rest = jnp.where(jnp.arange(groups.shape[-1]) == first, -jnp.inf,
                         groups)
        best = groups.max(axis=-1) + rest.max(axis=-1)          # [B, S, G]
        _, chosen = jax.lax.top_k(best, cfg.topk_group)
        kept = (chosen[..., None] == jnp.arange(cfg.n_group)).any(axis=-2)
        biased = jnp.where(kept[..., None], groups, -jnp.inf).reshape(
            biased.shape)
    _, idx = jax.lax.top_k(biased, cfg.moe_top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = cfg.routed_scale * picked / (
        picked.sum(axis=-1, keepdims=True) + 1e-20)
    return weights, idx, kept


def route(z, p, cfg: MlaMoeConfig):
    """z [B, S, D] -> (weights [B, S, k] float32, experts [B, S, k]):
    sigmoid scores in float32, the top_k by score + bias — among the
    experts of the ``topk_group`` best of ``n_group`` groups where the
    config has groups, a group scored by the sum of its two largest
    score + bias —, weighted by the scores alone, normalised over the
    selected and scaled."""
    return _select(z, p, cfg)[:2]


def _ffn_block(x, p, cfg: MlaMoeConfig, interpret: bool):
    """The block's second half with its residual. Returns (x, load): the
    [E] int32 assignment counts of an expert layer — behind them, where a
    share of the experts is held, llama.held_load's counts —, None for a
    layer of the dense prefix."""
    z = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    if "w_router" not in p:
        return x + _swiglu(z, p["w_gate"], p["w_up"], p["w_down"]), None
    weights, idx, kept = _select(z, p, cfg)
    y = routed_experts(z, idx, weights, p, cfg.moe_experts, cfg.mlp_dim,
                       interpret, held=cfg.experts_held)
    y = y + _swiglu(z, p["ws_gate"], p["ws_up"], p["ws_down"])
    return x + y, held_load(expert_load(idx, cfg.moe_experts), cfg.held,
                            cfg.moe_experts, kept)


def _head(params, x, cfg: MlaMoeConfig):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Full-sequence forward (expanded attention, plain jnp)
# ---------------------------------------------------------------------------

def _expanded_attention(h, p, cfg: MlaMoeConfig, cos, sin, causal):
    """h [B, S, D] (normed) -> [B, S, H, v] float32: per-head keys
    ``k_nope_h ‖ k_rope`` and values ``v_h`` expanded from the latents,
    causal softmax in float32 (``causal`` [S, S] bool)."""
    q_nope, q_rope, c, k_rope = _projections(h, p, cfg, cos, sin)
    k_nope = jnp.einsum("bsc,hnc->bshn", c, p["w_uk"])
    v = jnp.einsum("bsc,hcv->bshv", c, p["w_uv"])
    scores = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope,
                           preferred_element_type=jnp.float32)
              ) * cfg.softmax_scale
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhv->bqhv", w, v.astype(jnp.float32))


def apply(params: dict, tokens: jax.Array, cfg: MlaMoeConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32, no cache: per-head keys
    ``k_nope_h ‖ k_rope`` and values ``v_h`` expanded from the latents,
    causal softmax in float32. Differentiable but unkernelled."""
    s = tokens.shape[1]
    x = params["embed"][tokens].astype(cfg.dtype)
    cos, sin = rope_freqs(cfg, jnp.broadcast_to(jnp.arange(s), tokens.shape))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer, cfg)
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        o = _expanded_attention(h, p, cfg, cos, sin, causal)
        x = x + o.astype(cfg.dtype).reshape(x.shape[:2] + (-1,)) @ p["wo"]
        x, _ = _ffn_block(x, p, cfg, False)
    return _head(params, x, cfg)


# ---------------------------------------------------------------------------
# Paged latent cache (the serving engine's forwards)
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: MlaMoeConfig, num_pages: int,
                     page_size: int) -> list[dict]:
    """Per-layer pools [{'ckv': [P, page, latent_lanes]}] * n_layers: ONE
    pool a layer, a token's row = c ‖ k_rope ‖ zeros. Page 0 is the write
    sink, as in llama.init_paged_cache."""
    shape = (num_pages, page_size, cfg.latent_lanes)
    return [{"ckv": jnp.zeros(shape, cfg.dtype)}
            for _ in range(cfg.n_layers)]


def _attend(cfg: MlaMoeConfig, interpret: bool, block_tables, starts,
            q_lens):
    """attend(q [R, Q, H, lanes], pool) -> [R, Q, H, rank]: the ragged
    kernel's latent form on TPU or under ``interpret``, its jnp oracle
    elsewhere (the CPU fallback)."""
    from ..ops.ragged_paged_attention import (
        ragged_latent_attention, ragged_latent_reference,
    )
    kw = dict(v_width=cfg.kv_lora_rank, scale=cfg.softmax_scale)
    if interpret or _on_tpu():
        fn = functools.partial(ragged_latent_attention, interpret=interpret,
                               **kw)
    else:
        fn = functools.partial(ragged_latent_reference, **kw)
    return lambda q, pool: fn(q, pool, block_tables, starts, q_lens)


def _run_layers(params, tokens, caches, cfg: MlaMoeConfig, positions, write,
                attend, interpret: bool):
    """The one layer loop behind the three paged forwards. tokens and
    positions [R, S]; ``write(pool, entries [R, S, lanes])`` scatters the
    window's cache rows into the pool (BEFORE attention: the kernel reads
    pages only) and ``attend(q, pool)`` is _attend's closure. Returns
    (x [R, S, D], caches, load)."""
    x = params["embed"][tokens].astype(cfg.dtype)
    cos, sin = rope_freqs(cfg, positions)
    new_caches, load = [], None
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer, cfg)
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q_full, entry = _absorbed(h, p, cfg, cos, sin)
        pool = caches[layer]["ckv"]
        pool = write(pool, entry.astype(pool.dtype))
        x = x + _attn_out(attend(q_full, pool), p, cfg)
        x, routed = _ffn_block(x, p, cfg, interpret)
        load = _add_load(load, routed)
        new_caches.append({"ckv": pool})
    return x, new_caches, load


def _no_lora(lora):
    if lora is not None:
        raise NotImplementedError(
            "models/mla_moe.py takes no LoRA slot table (lora_targets is "
            "empty: PagedEngineConfig.max_adapters must stay 0)")


def decode_paged(params: dict, tokens: jax.Array, caches: list[dict],
                 block_tables: jax.Array, lengths: jax.Array,
                 cfg: MlaMoeConfig, *, page_size: int,
                 interpret: bool = False, lora=None, slots=None):
    """One decode step: llama.decode_paged's contract (tokens [B, 1],
    lengths [B] = tokens already written; returns logits [B, V], caches,
    load) over the latent pools. Decode is the kernel's window of 1."""
    _no_lora(lora)
    rows = jnp.arange(tokens.shape[0])
    lengths = lengths.astype(jnp.int32)
    page_ids = block_tables[rows, lengths // page_size]
    offsets = lengths % page_size
    x, caches, load = _run_layers(
        params, tokens, caches, cfg, lengths[:, None],
        lambda pool, e: pool.at[page_ids, offsets].set(e[:, 0]),
        _attend(cfg, interpret, block_tables, lengths,
                jnp.ones_like(lengths)), interpret)
    return _head(params, x, cfg)[:, 0], caches, load


def prefill_paged_rows(params: dict, chunks: jax.Array, caches: list[dict],
                       bt_rows: jax.Array, start_pos: jax.Array,
                       true_lens: jax.Array, cfg: MlaMoeConfig, *,
                       page_size: int, interpret: bool = False,
                       lora=None, slots=None):
    """Up to R page-aligned chunk-rows as one batched forward:
    llama.prefill_paged_rows's contract (chunks [R, C]; consecutive rows
    may be consecutive chunks of one sequence; true_lens == 0 rows are
    padding; pages past a row's real tokens are written to sink page 0;
    returns last_logits [R, V], caches, load). One kernel call a layer
    takes all R rows."""
    _no_lora(lora)
    r, c = chunks.shape
    n_chunk_pages = c // page_size
    starts = start_pos.astype(jnp.int32)
    q_lens = true_lens.astype(jnp.int32)
    chunk_page_ids = chunk_pages(bt_rows, starts, q_lens, n_chunk_pages,
                                 page_size)
    x, caches, load = _run_layers(
        params, chunks, caches, cfg,
        starts[:, None] + jnp.arange(c)[None, :],
        lambda pool, e: pool.at[chunk_page_ids].set(
            e.reshape(r, n_chunk_pages, page_size, -1)),
        _attend(cfg, interpret, bt_rows, starts, q_lens), interpret)
    last = jnp.clip(q_lens - 1, 0, c - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)     # [R, 1, D]
    return _head(params, x, cfg)[:, 0], caches, load


def verify_paged_rows(params: dict, tokens: jax.Array, caches: list[dict],
                      bt_rows: jax.Array, starts: jax.Array,
                      cfg: MlaMoeConfig, *, page_size: int,
                      interpret: bool = False, lora=None, slots=None):
    """Speculative verification: llama.verify_paged_rows's contract
    (tokens [R, S1] fed at positions starts[r] .. starts[r] + S1 - 1,
    written in place; positions past the block table go to sink page 0;
    returns logits [R, S1, V], caches, load), all rows in one batch."""
    _no_lora(lora)
    s1 = tokens.shape[1]
    max_pages = bt_rows.shape[1]
    starts = starts.astype(jnp.int32)
    positions = starts[:, None] + jnp.arange(s1)[None, :]
    pidx = positions // page_size
    page_ids = jnp.where(pidx < max_pages, jnp.take_along_axis(
        bt_rows, jnp.clip(pidx, 0, max_pages - 1), axis=1), 0)
    offsets = positions % page_size
    x, caches, load = _run_layers(
        params, tokens, caches, cfg, positions,
        lambda pool, e: pool.at[page_ids, offsets].set(e),
        _attend(cfg, interpret, bt_rows, starts,
                jnp.full_like(starts, s1)), interpret)
    return _head(params, x, cfg), caches, load
