"""Hybrid decoder of Kimi-delta-attention (KDA, linear attention) layers
and gated latent-attention (MLA) layers, five to one, behind a dense
prefix, with a group-routed mixture of experts and a shared expert — the
block ``model_type: bailing_hybrid`` publishes (Ling-3.0-flash). One of the
serving engine's model modules: it gives ``llm/paged_engine.py`` the
functions the others do, over layers of two cache kinds — an MLA layer's
``c ‖ k_r`` in LATENT pages (``models/mla_moe.py``'s pool), a KDA layer's
recurrent state, a float32 ``[H, dk, dv]`` matrix and the last
``conv_width - 1`` inputs of its convolution, in the sequence's decode slot
(``llm/kv_cache.py`` ``StateSlots``).

    norm(x; w) = x * rsqrt(mean(x^2) + eps) * w                (plain gain)
    block   x += mixer(norm(x));  x += mlp(norm(x))
    mixer   MLA where (i + 1) % mla_interval == 0, else KDA
    mlp     dense SwiGLU for i < n_dense_layers, else the experts

    KDA     h = norm(x);  q = Wq h, k = Wk h, v = Wv h    each [H, dk | dv]
            q | k | v through a causal depthwise convolution of conv_width
            (no bias) and SiLU; q, k L2-normalised a head, q scaled
            dk ** -0.5; no RoPE
            beta = sigmoid(Wb h)  [H];   a = Wf h  [H, dk]
            g = kda_lower_bound * sigmoid(exp(A_log) (a + dt_bias))
                float32, in (lower_bound, 0): a decay a key CHANNEL
            the gated delta rule with that decay (ops/gated_delta.py):
                S <- Diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t)
                S <- S + k_t u^T;  o_t = S^T q_t
            out = Wo (norm_head(o; w_o) * sigmoid(Wg h))
    MLA     models/mla_moe.py's attention (no q_lora; the cache entry
            c ‖ k_r, read in the absorbed form) and a gate a head:
            o_h <- o_h * sigmoid(w_h . h) on the attention's output —
            behind W_UV, ahead of Wo
    experts models/mla_moe.py's `_ffn_block`: sigmoid scores + a selection
            bias, the top_k among the experts of the topk_group best of
            n_group groups, weights from the scores alone, normalised and
            scaled; the sum over the chosen experts HELD here
            (``experts_held``) + the shared expert

Reused, not copied: the latent projections, the absorbed form, RoPE, the
router and the FFN block from ``models/mla_moe.py`` (this config IS an
``MlaMoeConfig`` with the KDA layers' sizes beside it); the state table's
columns, the convolution and `state_rows` from ``models/qwen3_next.py``.

Not built here, and refused by name where asked for: a mesh, LoRA targets,
speculative verification (a recurrent state cannot take back a rejected
draft), training (``apply`` is the plain forward for the tests).
Multi-token prediction is left out.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops.gated_delta import (gated_delta_decode, gated_delta_prefill,
                               gated_delta_scan)
from .llama import _add_load, chunk_pages, rms_norm
from .mla_moe import (MlaMoeConfig, _absorbed, _attend, _attn_out,
                      _expanded_attention, _ffn_block, _head, _no_lora,
                      rope_freqs)
# what the engine asks a model module beside its forwards: mla_moe's, over
# this config as over its own
from .mla_moe import (attn_step, expert_routing, experts_held,  # noqa: F401
                      lora_targets, routed_per_token)  # noqa: F401
from .qwen3_next import _conv, delta_qkv, state_rows


@dataclasses.dataclass(frozen=True)
class LingHybridConfig(MlaMoeConfig):
    mla_interval: int = 6             # layer i is MLA when (i+1) % 6 == 0
    kda_heads: int = 32
    kda_k_dim: int = 128
    kda_v_dim: int = 128
    conv_width: int = 4
    kda_lower_bound: float = -5.0     # a token's log decay lies above it

    def latent(self, layer: int) -> bool:
        return (layer + 1) % self.mla_interval == 0

    @property
    def conv_dim(self) -> int:
        """Channels of the KDA convolution: q, k and v side by side."""
        return self.kda_heads * (2 * self.kda_k_dim + self.kda_v_dim)


def ling_hybrid_tiny(**kw) -> LingHybridConfig:
    """CI-scale config: a dense layer and one period, toy sizes, groups of
    experts of which the first is held."""
    defaults = dict(vocab_size=256, dim=64, n_layers=4, n_dense_layers=1,
                    mla_interval=3, n_heads=4, qk_nope_dim=16, qk_rope_dim=8,
                    v_head_dim=16, kv_lora_rank=32, dense_mlp_dim=128,
                    kda_heads=4, kda_k_dim=16, kda_v_dim=16, moe_experts=16,
                    moe_top_k=2, mlp_dim=32, n_shared_experts=1,
                    routed_scale=2.5, n_group=4, topk_group=2,
                    experts_held=(0, 4), max_seq_len=512, rope_theta=6e6,
                    dtype=jnp.float32)
    defaults.update(kw)
    return LingHybridConfig(**defaults)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init(rng: jax.Array, cfg: LingHybridConfig) -> dict:
    """Four stacks on a leading layer axis: ``kda_layers`` and
    ``mla_layers`` (a layer's mixer, indexed among its own kind),
    ``dense_mlp`` and ``moe`` (its second half, likewise). The MLA and
    expert stacks are ``mla_moe.init``'s with the head-wise gate
    ``w_hgate`` beside them. Norm gains are drawn around 1 so that a test
    tells a gain from none. ``a_log`` / ``dt_bias``: a rate in [0.5, 2) a
    head and a bias in [-8, 0) a channel, so that a token's decay
    ``lower_bound * sigmoid(rate (a + bias))`` spans the bound's range
    over a head's channels — from forgetting in a token to carrying
    hundreds (a state that forgot in one step would hide an error in
    it)."""
    d, nh = cfg.dim, cfg.n_heads
    qk, rank = cfg.qk_nope_dim + cfg.qk_rope_dim, cfg.kv_lora_rank
    hk, dk, dv = cfg.kda_heads, cfg.kda_k_dim, cfg.kda_v_dim
    n_mla = sum(cfg.latent(i) for i in range(cfg.n_layers))
    n_kda = cfg.n_layers - n_mla
    n_moe = cfg.n_layers - cfg.n_dense_layers
    lo, hi = cfg.held
    ks = iter(jax.random.split(rng, 40))

    def dense(shape, fan_in, dtype=None):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype or cfg.dtype)

    def gain(*shape):
        return (1.0 + 0.1 * jax.random.normal(
            next(ks), shape, jnp.float32)).astype(cfg.dtype)

    def swiglu(lead, width, names):
        return {names[0]: dense(lead + (d, width), d),
                names[1]: dense(lead + (d, width), d),
                names[2]: dense(lead + (width, d), width)}
    ffn = ("w_gate", "w_up", "w_down")
    return {
        "embed": dense((cfg.vocab_size, d), d),
        "kda_layers": {
            "attn_norm": gain(n_kda, d),
            "wq": dense((n_kda, d, hk * dk), d),
            "wk": dense((n_kda, d, hk * dk), d),
            "wv": dense((n_kda, d, hk * dv), d),
            "w_f": dense((n_kda, d, hk * dk), d),
            "w_g": dense((n_kda, d, hk * dv), d),
            "w_b": dense((n_kda, d, hk), d),
            "conv_w": dense((n_kda, cfg.conv_width, cfg.conv_dim),
                            cfg.conv_width),
            "a_log": jnp.log(jax.random.uniform(
                next(ks), (n_kda, hk), jnp.float32, 0.5, 2.0)),
            "dt_bias": jax.random.uniform(
                next(ks), (n_kda, hk, dk), jnp.float32, -8.0, 0.0),
            "o_norm": gain(n_kda, dv),
            "wo": dense((n_kda, hk * dv, d), hk * dv)},
        "mla_layers": {
            "attn_norm": gain(n_mla, d),
            "wq": dense((n_mla, d, nh * qk), d),
            "wkv_a": dense((n_mla, d, cfg.latent_dim), d),
            "kv_norm": gain(n_mla, rank),
            "w_uk": dense((n_mla, nh, cfg.qk_nope_dim, rank), rank),
            "w_uv": dense((n_mla, nh, rank, cfg.v_head_dim), rank),
            "w_hgate": dense((n_mla, d, nh), d),
            "wo": dense((n_mla, nh * cfg.v_head_dim, d),
                        nh * cfg.v_head_dim)},
        "dense_mlp": {
            "mlp_norm": gain(cfg.n_dense_layers, d),
            **swiglu((cfg.n_dense_layers,), cfg.dense_mlp_dim, ffn)},
        "moe": {
            "mlp_norm": gain(n_moe, d),
            "w_router": dense((n_moe, d, cfg.moe_experts), d, jnp.float32),
            "router_bias": 0.1 * jax.random.normal(
                next(ks), (n_moe, cfg.moe_experts), jnp.float32),
            **swiglu((n_moe, hi - lo), cfg.mlp_dim, ffn),
            **swiglu((n_moe,), cfg.n_shared_experts * cfg.mlp_dim,
                     ("ws_gate", "ws_up", "ws_down"))},
        "final_norm": gain(d),
        "lm_head": dense((d, cfg.vocab_size), d),
    }


# -- what the engine asks a model module beside its forwards --------------
# (``expert_routing``, ``experts_held``, ``routed_per_token``,
# ``lora_targets`` and ``attn_step`` — the latent kernel's steps — are
# models/mla_moe.py's, over this config)

_NO_MESH = ("PagedEngineConfig.mesh: models/ling_hybrid.py has no sharding "
            "rules yet (expert shares over chips need their exchange, "
            "ROADMAP R9) — serve it with mesh=None")


def cache_window(cfg: LingHybridConfig) -> int:
    """No sliding layers: a latent layer keeps every key."""
    return 0


def cache_layers(cfg: LingHybridConfig) -> list:
    """The cache kind each layer holds a sequence in: ``full`` (latent)
    pages, or the ``state`` of a KDA layer (llm/kv_cache.py)."""
    return ["full" if cfg.latent(i) else "state"
            for i in range(cfg.n_layers)]


def check_mesh(cfg: LingHybridConfig, sizes: dict) -> None:
    raise NotImplementedError(_NO_MESH)


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _layer_params(params: dict, layer: int, cfg: LingHybridConfig) -> dict:
    """One layer's parameters: its mixer's out of its kind's stack, its
    second half out of ``dense_mlp`` or ``moe`` — the routed weights left
    stacked with the index beside them, as llama._layer_params keeps
    them."""
    kind = "mla_layers" if cfg.latent(layer) else "kda_layers"
    own = sum(cfg.latent(i) == cfg.latent(layer) for i in range(layer))
    p = {k: a[own] for k, a in params[kind].items()}
    if layer < cfg.n_dense_layers:
        p.update({k: a[layer] for k, a in params["dense_mlp"].items()})
        return p
    li = layer - cfg.n_dense_layers
    p.update({k: a if k in _EXPERT_WEIGHTS else a[li]
              for k, a in params["moe"].items()})
    p["expert_layer"] = li
    return p


def _head_gate(h, p):
    """h [B, S, D] -> the latent layer's gate a head [B, S, H], float32."""
    return jax.nn.sigmoid((h @ p["w_hgate"]).astype(jnp.float32))


def _kda_inputs(h, p, cfg: LingHybridConfig):
    """h [B, S, D] -> (the convolution's input [B, S, conv_dim] = q | k |
    v, the output's gate [B, S, H, dv], beta [B, S, H] and g [B, S, H, dk]
    float32). The flat projections are finished before they are cut into
    heads (llama._qkv's ``fence``: no projection weight is transposed)."""
    b, s, _ = h.shape
    hk, dk = cfg.kda_heads, cfg.kda_k_dim
    q, k, v, a, gate, beta = jax.lax.optimization_barrier(tuple(
        h @ p[n] for n in ("wq", "wk", "wv", "w_f", "w_g", "w_b")))
    a = a.reshape(b, s, hk, dk).astype(jnp.float32)
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[:, None] * (a + p["dt_bias"]))
    return (jnp.concatenate([q, k, v], axis=-1),
            gate.reshape(b, s, hk, cfg.kda_v_dim),
            jax.nn.sigmoid(beta.astype(jnp.float32)), g)


def _kda_qkv(x, cfg: LingHybridConfig):
    return delta_qkv(x, cfg.kda_heads, cfg.kda_k_dim, cfg.kda_heads,
                     cfg.kda_v_dim)


def _kda_out(o, gate, p, cfg: LingHybridConfig):
    """o (float32), gate [B, S, H, dv] -> the block's residual term."""
    b, s = o.shape[:2]
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    y = (o * jax.lax.rsqrt(var + cfg.norm_eps)).astype(cfg.dtype) \
        * p["o_norm"]
    y = y.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return y.astype(cfg.dtype).reshape(b, s, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# Full-sequence forward (plain jnp, no cache): what the tests hold the
# paged programs' pieces against beside the benchmark's reference
# ---------------------------------------------------------------------------

def apply(params: dict, tokens: jax.Array, cfg: LingHybridConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32: the recurrence token by
    token (ops.gated_delta.gated_delta_scan), latent attention in the
    EXPANDED form (keys and values a head) as a masked softmax in
    float32."""
    b, s = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    cos, sin = rope_freqs(cfg, jnp.broadcast_to(jnp.arange(s), tokens.shape))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    hk, dk, dv = cfg.kda_heads, cfg.kda_k_dim, cfg.kda_v_dim
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer, cfg)
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        if cfg.latent(layer):
            o = _expanded_attention(h, p, cfg, cos, sin, causal)
            o = (o * _head_gate(h, p)[..., None]).astype(cfg.dtype)
            x = x + o.reshape(b, s, -1) @ p["wo"]
        else:
            qkv, gate, beta, g = _kda_inputs(h, p, cfg)
            ext = jnp.pad(qkv, ((0, 0), (cfg.conv_width - 1, 0), (0, 0)))
            q, k, v = _kda_qkv(_conv(ext, p["conv_w"]), cfg)
            o, _ = jax.vmap(gated_delta_scan)(
                q, k, v, g, beta, jnp.zeros((b, hk, dk, dv), jnp.float32))
            x = x + _kda_out(o, gate, p, cfg)
        x, _ = _ffn_block(x, p, cfg, False)
    return _head(params, x, cfg)


# ---------------------------------------------------------------------------
# Paged caches (the serving engine's forwards)
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: LingHybridConfig, num_pages: int, page_size: int,
                     state_slots: int = 0, state_snapshots: int = 0
                     ) -> list[dict]:
    """One dict a layer. An MLA layer: mla_moe's latent pool ``ckv``
    [P, page, latent_lanes], page 0 the write sink. A KDA layer:
    qwen3_next's four arrays — ``S`` [state_slots + 1, H, dk, dv] float32
    and ``conv`` [state_slots + 1, W - 1, conv_dim], decode slot s at row
    s + 1, and ``snap_S`` / ``snap_conv``, the pool of ``state_snapshots``
    + 1 snapshots; row 0 of each is a sink no sequence owns."""
    state = (cfg.kda_heads, cfg.kda_k_dim, cfg.kda_v_dim)
    tail = (cfg.conv_width - 1, cfg.conv_dim)
    out = []
    for layer in range(cfg.n_layers):
        if cfg.latent(layer):
            out.append({"ckv": jnp.zeros(
                (num_pages, page_size, cfg.latent_lanes), cfg.dtype)})
        else:
            out.append({
                "S": jnp.zeros((state_slots + 1,) + state, jnp.float32),
                "conv": jnp.zeros((state_slots + 1,) + tail, cfg.dtype),
                "snap_S": jnp.zeros((state_snapshots + 1,) + state,
                                    jnp.float32),
                "snap_conv": jnp.zeros((state_snapshots + 1,) + tail,
                                       cfg.dtype)})
    return out


def _latent_layer(x, h, p, cfg: LingHybridConfig, cache, cos, sin, write,
                  attend):
    """An MLA layer of a paged forward (mla_moe._run_layers' body, with the
    gate): the window's entries written, then attended in the absorbed
    form. Returns (x, the layer's cache)."""
    q_full, entry = _absorbed(h, p, cfg, cos, sin)
    pool = write(cache["ckv"], entry.astype(cache["ckv"].dtype))
    return x + _attn_out(attend(q_full, pool), p, cfg,
                         gate=_head_gate(h, p)), {"ckv": pool}


def decode_paged(params: dict, tokens: jax.Array, caches: list[dict],
                 block_tables, lengths: jax.Array, cfg: LingHybridConfig, *,
                 page_size: int, interpret: bool = False, lora=None,
                 slots=None):
    """One decode step: qwen3_next.decode_paged's contract (``block_tables``
    the pair (the latent layers' table [B, max_pages], the state rows
    [B]); returns logits [B, V], caches, load) over latent pages and KDA
    states. A live row's state is advanced by this token where it lies,
    and no snapshot is taken in decode."""
    _no_lora(lora)
    table, rows = block_tables
    rows = rows.reshape(-1).astype(jnp.int32)
    b = tokens.shape[0]
    lengths = lengths.astype(jnp.int32)
    page_ids = table[jnp.arange(b), lengths // page_size]
    offsets = lengths % page_size
    cos, sin = rope_freqs(cfg, lengths[:, None])
    attend = _attend(cfg, interpret, table, lengths, jnp.ones_like(lengths))

    def write(pool, e):
        return pool.at[page_ids, offsets].set(e[:, 0])
    x = params["embed"][tokens].astype(cfg.dtype)
    new_caches, load = [], None
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer, cfg)
        cache = caches[layer]
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        if cfg.latent(layer):
            x, cache = _latent_layer(x, h, p, cfg, cache, cos, sin, write,
                                     attend)
        else:
            qkv, gate, beta, g = _kda_inputs(h, p, cfg)
            ext = jnp.concatenate([cache["conv"][rows], qkv], axis=1)
            q, k, v = _kda_qkv(_conv(ext, p["conv_w"]), cfg)
            with jax.named_scope("kda_decode"):
                o, states = gated_delta_decode(
                    cache["S"], rows, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                    beta[:, 0], interpret=interpret)
            x = x + _kda_out(o[:, None], gate, p, cfg)
            cache = dict(cache, S=states,
                         conv=cache["conv"].at[rows].set(ext[:, 1:]))
        new_caches.append(cache)
        x, routed = _ffn_block(x, p, cfg, interpret)
        load = _add_load(load, routed)
    return _head(params, x, cfg)[:, 0], new_caches, load


def _kda_prefill(h, p, cfg: LingHybridConfig, cache: dict, st, q_lens,
                 interpret: bool):
    """A KDA layer over R chunk-rows [R, C, D] (qwen3_next.state_rows).
    Returns (the residual term, the layer's cache)."""
    qkv, gate, beta, g = _kda_inputs(h, p, cfg)

    def recur(x, s0, chain, live):
        q, k, v = _kda_qkv(x, cfg)
        with jax.named_scope("kda_prefill"):
            return gated_delta_prefill(
                q, k, v, jnp.where(live[..., None], g, 0.0),
                jnp.where(live, beta, 0.0), s0, chain, interpret=interpret)
    o, new = state_rows(qkv, p["conv_w"], cache, st, q_lens, recur)
    return _kda_out(o, gate, p, cfg), new


def prefill_paged_rows(params: dict, chunks: jax.Array, caches: list[dict],
                       bt_rows, start_pos: jax.Array, true_lens: jax.Array,
                       cfg: LingHybridConfig, *, page_size: int,
                       interpret: bool = False, lora=None, slots=None):
    """Up to R page-aligned chunk-rows as one batched forward:
    qwen3_next.prefill_paged_rows's contract (``bt_rows`` the pair (the
    latent layers' table [R, max_pages], the state table [R, 5] of
    `state_rows`); returns last_logits [R, V], caches, load)."""
    _no_lora(lora)
    table, st = bt_rows
    r, c = chunks.shape
    n_chunk_pages = c // page_size
    starts = start_pos.astype(jnp.int32)
    q_lens = true_lens.astype(jnp.int32)
    cos, sin = rope_freqs(cfg, starts[:, None] + jnp.arange(c)[None, :])
    attend = _attend(cfg, interpret, table, starts, q_lens)
    chunk_page_ids = chunk_pages(table, starts, q_lens, n_chunk_pages,
                                 page_size)

    def write(pool, e):
        return pool.at[chunk_page_ids].set(
            e.reshape(r, n_chunk_pages, page_size, -1))
    x = params["embed"][chunks].astype(cfg.dtype)
    new_caches, load = [], None
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer, cfg)
        cache = caches[layer]
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        if cfg.latent(layer):
            x, cache = _latent_layer(x, h, p, cfg, cache, cos, sin, write,
                                     attend)
        else:
            y, cache = _kda_prefill(h, p, cfg, cache, st, q_lens, interpret)
            x = x + y
        new_caches.append(cache)
        x, routed = _ffn_block(x, p, cfg, interpret)
        load = _add_load(load, routed)
    last = jnp.clip(q_lens - 1, 0, c - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)     # [R, 1, D]
    return _head(params, x, cfg)[:, 0], new_caches, load


def verify_paged_rows(*args, **kwargs):
    raise NotImplementedError(
        "models/ling_hybrid.py: speculative verification over a recurrent "
        "state (a rejected draft has already moved it) — "
        "PagedEngineConfig.spec_tokens must stay 0")
