"""Hybrid decoder of gated-delta-rule (GDN, linear attention) layers and
gated softmax-attention layers, three to one, with a routed mixture of
experts and a gated shared expert in every layer — the block
``model_type: qwen3_next`` publishes (Qwen3-Next-80B-A3B). One of the
serving engine's model modules: it gives ``llm/paged_engine.py`` the
functions ``models/llama.py`` and ``models/mla_moe.py`` do, over layers of
TWO cache kinds (``models/ling_hybrid.py``, whose recurrence has a decay a
key channel and whose pages are latent, takes from here the state table's
columns, the convolution and `state_rows`, the way a state travels through
a prefill dispatch): a full layer's keys and values live in pages, a GDN layer's
recurrent state — a float32 ``[nv, dk, dv]`` matrix and the last
``conv_width - 1`` inputs of its convolution — in the sequence's decode
slot (``llm/kv_cache.py`` ``StateSlots``).

    norm(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)      (zero-centred)
    block       x += mixer(norm(x));  x += moe(norm(x))
    full layer  (layer + 1) % full_interval == 0
                [q | gate] = Wq h per head (2 x head_dim), k = Wk h, v = Wv h
                q = norm(q), k = norm(k) per head; RoPE on the first
                rotary_dim of head_dim dims (rotation by halves), the rest
                pass; causal softmax attention, scale head_dim ** -0.5
                out = Wo (attn * sigmoid(gate))
    GDN layer   [q k v z] = W_qkvz h, rows grouped by key head as
                transformers keeps them: (q dk | k dk | v r*dv | z r*dv)
                a key head, r = nv / nk;  [b a] = W_ba h, (b r | a r)
                [q | k | v] through a causal depthwise convolution of
                conv_width (no bias) and SiLU; q, k L2-normalised, q scaled
                dk ** -0.5; beta = sigmoid(b), g = -exp(A_log) softplus(a +
                dt_bias) in float32; the gated delta rule
                (ops/gated_delta.py); y = w * rmsnorm(o) * silu(z) per head
                (this weight is NOT zero-centred); out = W_out y
    MoE         p = softmax(Wr z) in float32 over ALL experts, top_k,
                renormalised over the chosen; sum_e p_e SwiGLU_e(z) over
                the experts HELD here (``experts_held``: one chip's share
                of a layer's experts; what the absent ones would add is
                left out) + sigmoid(w_sg z) SwiGLU_shared(z)

Not built here, and refused by name where asked for: a mesh, LoRA targets,
speculative verification (a recurrent state cannot take back a rejected
draft), training (``apply`` is the plain forward for the tests; the chunked
scan has no backward). Multi-token prediction is left out.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.flash_attention import _on_tpu
from ..ops.gated_delta import (gated_delta_decode, gated_delta_prefill,
                               gated_delta_scan)
from .llama import (_add_load, _window_attend, chunk_pages, expert_load,
                    held_load, routed_experts)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    full_interval: int = 4            # layer i is full when (i+1) % 4 == 0
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64              # partial_rotary_factor x head_dim
    rope_theta: float = 1e7
    gdn_k_heads: int = 16
    gdn_v_heads: int = 32
    gdn_k_dim: int = 128
    gdn_v_dim: int = 128
    conv_width: int = 4
    moe_experts: int = 512            # what the router scores
    moe_top_k: int = 10
    mlp_dim: int = 512                # ONE routed expert's width
    shared_mlp_dim: int = 512
    # the experts this replica holds, [lo, hi) of moe_experts; None: all
    experts_held: Optional[tuple] = None
    max_seq_len: int = 262144
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def full(self, layer: int) -> bool:
        return (layer + 1) % self.full_interval == 0

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held or (0, self.moe_experts))

    @property
    def conv_dim(self) -> int:
        """Channels of the GDN convolution: q, k and v side by side."""
        return (2 * self.gdn_k_heads * self.gdn_k_dim
                + self.gdn_v_heads * self.gdn_v_dim)


def qwen3_next_tiny(**kw) -> Qwen3NextConfig:
    """CI-scale config: one period, toy sizes, half the experts held."""
    defaults = dict(vocab_size=256, dim=64, n_layers=4, n_heads=4,
                    n_kv_heads=2, head_dim=16, rotary_dim=4, gdn_k_heads=2,
                    gdn_v_heads=4, gdn_k_dim=16, gdn_v_dim=16,
                    moe_experts=8, moe_top_k=2, mlp_dim=32,
                    shared_mlp_dim=32, experts_held=(0, 4), max_seq_len=512,
                    dtype=jnp.float32)
    defaults.update(kw)
    return Qwen3NextConfig(**defaults)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init(rng: jax.Array, cfg: Qwen3NextConfig) -> dict:
    """Three stacks on a leading layer axis: ``full_layers`` and
    ``gdn_layers`` (a layer's mixer, indexed among its own kind) and
    ``moe`` (every layer's second half). Norm gains are drawn around their
    neutral value (0 where zero-centred, 1 for the GDN output norm) so
    that a test tells ``1 + w`` from ``w``. ``a_log`` / ``dt_bias`` are
    drawn as state-space layers usually are — a decay rate in [1, 16), a
    step in [1e-3, 1e-1] — so that a token's decay exp(g) spans e^-2 ..
    1 - 1e-3 over the heads and the state carries hundreds of tokens (a
    state that forgets in one step would hide an error in it)."""
    d, hd = cfg.dim, cfg.head_dim
    nk, nv, dk, dv = (cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_k_dim,
                      cfg.gdn_v_dim)
    n_full = sum(cfg.full(i) for i in range(cfg.n_layers))
    n_gdn = cfg.n_layers - n_full
    lo, hi = cfg.held
    ks = iter(jax.random.split(rng, 32))

    def dense(shape, fan_in, dtype=None):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype or cfg.dtype)

    def gain(shape, centre):
        return (centre + 0.1 * jax.random.normal(
            next(ks), shape, jnp.float32)).astype(cfg.dtype)

    def swiglu(lead, width, names):
        return {names[0]: dense(lead + (d, width), d),
                names[1]: dense(lead + (d, width), d),
                names[2]: dense(lead + (width, d), width)}
    rate = jax.random.uniform(next(ks), (n_gdn, nv), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(next(ks), (n_gdn, nv), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    L = cfg.n_layers
    return {
        "embed": dense((cfg.vocab_size, d), d),
        "full_layers": {
            "attn_norm": gain((n_full, d), 0.0),
            "wq": dense((n_full, d, cfg.n_heads * 2 * hd), d),
            "wk": dense((n_full, d, cfg.n_kv_heads * hd), d),
            "wv": dense((n_full, d, cfg.n_kv_heads * hd), d),
            "q_norm": gain((n_full, hd), 0.0),
            "k_norm": gain((n_full, hd), 0.0),
            "wo": dense((n_full, cfg.n_heads * hd, d), cfg.n_heads * hd)},
        "gdn_layers": {
            "attn_norm": gain((n_gdn, d), 0.0),
            "w_qkvz": dense((n_gdn, d, 2 * nk * dk + 2 * nv * dv), d),
            "w_ba": dense((n_gdn, d, 2 * nv), d),
            "conv_w": dense((n_gdn, cfg.conv_width, cfg.conv_dim),
                            cfg.conv_width),
            "a_log": jnp.log(rate),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "gdn_norm": gain((n_gdn, dv), 1.0),
            "w_out": dense((n_gdn, nv * dv, d), nv * dv)},
        "moe": {
            "mlp_norm": gain((L, d), 0.0),
            "w_router": dense((L, d, cfg.moe_experts), d, jnp.float32),
            **swiglu((L, hi - lo), cfg.mlp_dim, _EXPERT_WEIGHTS),
            **swiglu((L,), cfg.shared_mlp_dim,
                     ("ws_gate", "ws_up", "ws_down")),
            "w_sg": dense((L, d, 1), d)},
        "final_norm": gain((d,), 0.0),
        "lm_head": dense((d, cfg.vocab_size), d),
    }


# -- what the engine asks a model module beside its forwards --------------

_NO_MESH = ("PagedEngineConfig.mesh: models/qwen3_next.py has no sharding "
            "rules yet (expert shares over chips need their exchange, "
            "ROADMAP R9) — serve it with mesh=None")


def cache_window(cfg: Qwen3NextConfig) -> int:
    """No sliding layers: a full layer keeps every key."""
    return 0


def cache_layers(cfg: Qwen3NextConfig) -> list:
    """The cache kind each layer holds a sequence in: ``full`` pages, or
    the ``state`` of a GDN layer (llm/kv_cache.py)."""
    return ["full" if cfg.full(i) else "state" for i in range(cfg.n_layers)]


def check_mesh(cfg: Qwen3NextConfig, sizes: dict) -> None:
    raise NotImplementedError(_NO_MESH)


def lora_targets(cfg: Qwen3NextConfig) -> tuple:
    return ()


def routed_per_token(cfg: Qwen3NextConfig) -> int:
    """Token-expert assignments one token makes through the whole depth,
    over ALL the experts the router scores."""
    return cfg.moe_top_k * cfg.n_layers


def expert_routing(cfg: Qwen3NextConfig) -> tuple:
    """(experts the router scores, experts a token goes to, experts held
    here): what sizes the groups of the grouped expert matmul."""
    lo, hi = cfg.held
    return cfg.moe_experts, cfg.moe_top_k, hi - lo


def experts_held(cfg: Qwen3NextConfig) -> tuple:
    """[lo, hi) of the router's experts whose weights this replica has."""
    return cfg.held


def attn_step(cfg: Qwen3NextConfig, q_window: int, page_size: int,
              table_pages: int, head_shards: int = 1) -> dict:
    """llama.attn_step for the full layers' kernel call."""
    from ..ops.ragged_paged_attention import window_step
    if head_shards != 1:
        raise NotImplementedError(_NO_MESH)
    return window_step(
        q_window, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
        page_size=page_size, table_pages=table_pages,
        itemsize=jnp.dtype(cfg.dtype).itemsize)


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _layer_params(params: dict, layer: int, cfg: Qwen3NextConfig) -> dict:
    """One layer's parameters: its mixer's out of its kind's stack, its
    MoE half out of ``moe`` — the routed weights left stacked with the
    index beside them, as llama._layer_params keeps them."""
    kind = "full_layers" if cfg.full(layer) else "gdn_layers"
    own = sum(cfg.full(i) == cfg.full(layer) for i in range(layer))
    p = {k: a[own] for k, a in params[kind].items()}
    p.update({k: a if k in _EXPERT_WEIGHTS else a[layer]
              for k, a in params["moe"].items()})
    p["expert_layer"] = layer
    return p


def norm(x, w, eps: float):
    """RMSNorm with a zero-centred gain: x / rms(x) * (1 + w)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (
        1.0 + w.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(cfg: Qwen3NextConfig, positions: jax.Array):
    """positions [B, S] -> (cos, sin) each [B, S, rotary_dim / 2]."""
    rot = cfg.rotary_dim
    inv = cfg.rope_theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [B, S, H, D]: the first rotary_dim dims rotated by halves, the
    rest passed through."""
    rot = 2 * cos.shape[-1]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([(x1 * c - x2 * s).astype(x.dtype),
                            (x2 * c + x1 * s).astype(x.dtype),
                            x[..., rot:]], axis=-1)


def _full_qkv(h, p, cfg: Qwen3NextConfig, cos, sin):
    """h [B, S, D] -> q [B, S, H, hd], k, v [B, S, KVH, hd], gate [B, S, H,
    hd]. The flat projections are finished before they are cut into heads
    (llama._qkv's ``fence``: no projection weight is transposed)."""
    b, s, _ = h.shape
    hd = cfg.head_dim
    qg, k, v = jax.lax.optimization_barrier(
        (h @ p["wq"], h @ p["wk"], h @ p["wv"]))
    qg = qg.reshape(b, s, cfg.n_heads, 2 * hd)
    q = norm(qg[..., :hd], p["q_norm"], cfg.norm_eps)
    k = norm(k.reshape(b, s, cfg.n_kv_heads, hd), p["k_norm"], cfg.norm_eps)
    return (apply_rope(q, cos, sin), apply_rope(k, cos, sin),
            v.reshape(b, s, cfg.n_kv_heads, hd), qg[..., hd:])


def _full_out(attn, gate, p, cfg: Qwen3NextConfig):
    """attn, gate [B, S, H, hd] -> the block's residual term."""
    b, s = attn.shape[:2]
    y = attn.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return y.astype(cfg.dtype).reshape(b, s, -1) @ p["wo"]


def _gdn_inputs(h, p, cfg: Qwen3NextConfig):
    """h [B, S, D] -> (the convolution's input [B, S, conv_dim] = q | k |
    v, z [B, S, nv, dv], beta and g [B, S, nv] float32)."""
    b, s, _ = h.shape
    nk, nv, dk, dv = (cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_k_dim,
                      cfg.gdn_v_dim)
    r = nv // nk
    mixed, ba = jax.lax.optimization_barrier((h @ p["w_qkvz"], h @ p["w_ba"]))
    mixed = mixed.reshape(b, s, nk, 2 * dk + 2 * r * dv)
    ba = ba.reshape(b, s, nk, 2 * r).astype(jnp.float32)
    qkv = jnp.concatenate([
        mixed[..., :dk].reshape(b, s, nk * dk),
        mixed[..., dk:2 * dk].reshape(b, s, nk * dk),
        mixed[..., 2 * dk:2 * dk + r * dv].reshape(b, s, nv * dv)], axis=-1)
    z = mixed[..., 2 * dk + r * dv:].reshape(b, s, nv, dv)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(b, s, nv))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(b, s, nv) + p["dt_bias"])
    return qkv, z, beta, g


def _conv(ext, w):
    """Causal depthwise convolution and SiLU: ext [B, S + W - 1, ch] (the
    W - 1 inputs before the window first), w [W, ch] -> [B, S, ch]."""
    width = w.shape[0]
    s = ext.shape[1] - width + 1
    acc = sum(ext[:, j:j + s].astype(jnp.float32) * w[j].astype(jnp.float32)
              for j in range(width))
    return jax.nn.silu(acc).astype(ext.dtype)


def delta_qkv(x, nk: int, dk: int, nv: int, dv: int):
    """The convolution's output [B, S, 2 nk dk + nv dv] -> q, k [B, S, nk,
    dk] (L2-normalised, q scaled) and v [B, S, nv, dv]: what a delta-rule
    layer hands its recurrence (models/ling_hybrid.py's too)."""
    b, s, _ = x.shape

    def l2(t):
        tf = t.astype(jnp.float32)
        return tf * jax.lax.rsqrt(jnp.sum(tf * tf, -1, keepdims=True) + 1e-6)
    q = l2(x[..., :nk * dk].reshape(b, s, nk, dk)) * dk ** -0.5
    k = l2(x[..., nk * dk:2 * nk * dk].reshape(b, s, nk, dk))
    v = x[..., 2 * nk * dk:].reshape(b, s, nv, dv)
    return q.astype(x.dtype), k.astype(x.dtype), v


def _gdn_qkv(x, cfg: Qwen3NextConfig):
    return delta_qkv(x, cfg.gdn_k_heads, cfg.gdn_k_dim, cfg.gdn_v_heads,
                     cfg.gdn_v_dim)


def _gdn_out(o, z, p, cfg: Qwen3NextConfig):
    """o (float32), z [B, S, nv, dv] -> the block's residual term."""
    b, s = o.shape[:2]
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    y = (o * jax.lax.rsqrt(var + cfg.norm_eps)).astype(cfg.dtype) \
        * p["gdn_norm"]
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(cfg.dtype).reshape(b, s, -1) @ p["w_out"]


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


def route(z, p, cfg: Qwen3NextConfig):
    """z [B, S, D] -> (weights [B, S, k] float32, experts [B, S, k]): a
    float32 softmax over every expert the router scores, the top_k,
    renormalised over the chosen whoever holds them."""
    logits = jnp.einsum("bsd,de->bse", z.astype(jnp.float32), p["w_router"],
                        precision=jax.lax.Precision.HIGHEST)
    top_p, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.moe_top_k)
    return top_p / top_p.sum(axis=-1, keepdims=True), idx


def _moe_block(x, p, cfg: Qwen3NextConfig, interpret: bool):
    """The block's second half with its residual -> (x, load int32): the
    assignments each of the E experts routed over got, [E]; where a share
    of them is held, [E + 1] (llama.held_load)."""
    z = norm(x, p["mlp_norm"], cfg.norm_eps)
    weights, idx = route(z, p, cfg)
    y = routed_experts(z, idx, weights, p, cfg.moe_experts, cfg.mlp_dim,
                       interpret, held=cfg.experts_held)
    shared = _swiglu(z, p["ws_gate"], p["ws_up"], p["ws_down"])
    sg = jax.nn.sigmoid((z @ p["w_sg"]).astype(jnp.float32))
    y = y + (sg * shared.astype(jnp.float32)).astype(cfg.dtype)
    load = held_load(expert_load(idx, cfg.moe_experts), cfg.held,
                     cfg.moe_experts)
    return x + y, load


def _head(params, x, cfg: Qwen3NextConfig):
    x = norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Full-sequence forward (plain jnp, no cache): what the tests hold the
# paged programs' pieces against beside the benchmark's reference
# ---------------------------------------------------------------------------

def apply(params: dict, tokens: jax.Array, cfg: Qwen3NextConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32: the recurrence token by
    token (ops.gated_delta.gated_delta_scan), attention as a masked
    softmax in float32."""
    b, s = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    cos, sin = rope_freqs(cfg, jnp.broadcast_to(jnp.arange(s), tokens.shape))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    nv, dk, dv = cfg.gdn_v_heads, cfg.gdn_k_dim, cfg.gdn_v_dim
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer, cfg)
        h = norm(x, p["attn_norm"], cfg.norm_eps)
        if cfg.full(layer):
            q, k, v, gate = _full_qkv(h, p, cfg, cos, sin)
            g = cfg.n_heads // cfg.n_kv_heads
            qg = q.reshape(b, s, cfg.n_kv_heads, g, -1)
            sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                            preferred_element_type=jnp.float32
                            ) * cfg.head_dim ** -0.5
            w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            attn = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
            x = x + _full_out(attn.reshape(q.shape), gate, p, cfg)
        else:
            qkv, z, beta, g = _gdn_inputs(h, p, cfg)
            ext = jnp.pad(qkv, ((0, 0), (cfg.conv_width - 1, 0), (0, 0)))
            q, k, v = _gdn_qkv(_conv(ext, p["conv_w"]), cfg)
            o, _ = jax.vmap(gated_delta_scan)(
                q, k, v, g, beta, jnp.zeros((b, nv, dk, dv), jnp.float32))
            x = x + _gdn_out(o, z, p, cfg)
        x, _ = _moe_block(x, p, cfg, False)
    return _head(params, x, cfg)


# ---------------------------------------------------------------------------
# Paged caches (the serving engine's forwards)
# ---------------------------------------------------------------------------

# columns of a prefill row's entry in the state table (kv_cache.StateSlots)
LOAD, MODE, SNAP_FROM, STORE, SNAP_TO = range(5)
# MODE: where a row's state comes from
CONTINUE, FRESH, RESUME, CHAIN = range(4)


def init_paged_cache(cfg: Qwen3NextConfig, num_pages: int, page_size: int,
                     state_slots: int = 0, state_snapshots: int = 0
                     ) -> list[dict]:
    """One dict a layer. A full layer: llama's ``k`` / ``v`` page pools
    [P, page, KVH * D], page 0 the write sink. A GDN layer: ``S``
    [state_slots + 1, nv, dk, dv] float32 and ``conv`` [state_slots + 1,
    W - 1, conv_dim], decode slot s at row s + 1, and ``snap_S`` /
    ``snap_conv``, the pool of ``state_snapshots`` + 1 snapshots; row 0 of
    each is a sink no sequence owns (idle and pad rows write there)."""
    lanes = cfg.n_kv_heads * cfg.head_dim
    state = (cfg.gdn_v_heads, cfg.gdn_k_dim, cfg.gdn_v_dim)
    tail = (cfg.conv_width - 1, cfg.conv_dim)
    out = []
    for layer in range(cfg.n_layers):
        if cfg.full(layer):
            out.append({n: jnp.zeros((num_pages, page_size, lanes),
                                     cfg.dtype) for n in ("k", "v")})
        else:
            out.append({
                "S": jnp.zeros((state_slots + 1,) + state, jnp.float32),
                "conv": jnp.zeros((state_slots + 1,) + tail, cfg.dtype),
                "snap_S": jnp.zeros((state_snapshots + 1,) + state,
                                    jnp.float32),
                "snap_conv": jnp.zeros((state_snapshots + 1,) + tail,
                                       cfg.dtype)})
    return out


def _no_lora(lora):
    if lora is not None:
        raise NotImplementedError(
            "models/qwen3_next.py takes no LoRA slot table (lora_targets is "
            "empty: PagedEngineConfig.max_adapters must stay 0)")


def decode_paged(params: dict, tokens: jax.Array, caches: list[dict],
                 block_tables, lengths: jax.Array, cfg: Qwen3NextConfig, *,
                 page_size: int, interpret: bool = False, lora=None,
                 slots=None):
    """One decode step: llama.decode_paged's contract (tokens [B, 1],
    lengths [B] = tokens already written; returns logits [B, V], caches,
    load) with ``block_tables`` the pair (the full layers' table [B,
    max_pages], the state rows [B]: row b's slot in ``S`` / ``conv``, 0
    for an idle row, whose step lands in the sink). A live row's state is
    advanced by this token where it lies: a row the host later finds had
    already stopped (a dispatch runs ahead of its booking) has advanced a
    state nobody reads again — its slot is released, the slot's next
    tenant starts FRESH or from a snapshot, and no snapshot is taken in
    decode."""
    from ..ops.ragged_paged_attention import (
        paged_decode_reference, ragged_decode_attention,
    )
    _no_lora(lora)
    table, rows = block_tables
    rows = rows.reshape(-1).astype(jnp.int32)
    b = tokens.shape[0]
    lengths = lengths.astype(jnp.int32)
    page_ids = table[jnp.arange(b), lengths // page_size]
    offsets = lengths % page_size
    cos, sin = rope_freqs(cfg, lengths[:, None])
    if interpret or _on_tpu():
        attend = functools.partial(ragged_decode_attention,
                                   interpret=interpret)
    else:
        attend = paged_decode_reference
    x = params["embed"][tokens].astype(cfg.dtype)
    new_caches, load = [], None
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer, cfg)
        cache = caches[layer]
        h = norm(x, p["attn_norm"], cfg.norm_eps)
        if cfg.full(layer):
            q, k, v, gate = _full_qkv(h, p, cfg, cos, sin)
            k_pages = cache["k"].at[page_ids, offsets].set(
                k.reshape(b, -1).astype(cache["k"].dtype))
            v_pages = cache["v"].at[page_ids, offsets].set(
                v.reshape(b, -1).astype(cache["v"].dtype))
            attn = attend(q[:, 0], k_pages, v_pages, table, lengths + 1)
            x = x + _full_out(attn[:, None], gate, p, cfg)
            new_caches.append({"k": k_pages, "v": v_pages})
        else:
            qkv, z, beta, g = _gdn_inputs(h, p, cfg)
            ext = jnp.concatenate([cache["conv"][rows], qkv], axis=1)
            q, k, v = _gdn_qkv(_conv(ext, p["conv_w"]), cfg)
            with jax.named_scope("gdn_decode"):
                o, states = gated_delta_decode(
                    cache["S"], rows, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                    beta[:, 0], interpret=interpret)
            x = x + _gdn_out(o[:, None], z, p, cfg)
            new_caches.append(dict(
                cache, S=states,
                conv=cache["conv"].at[rows].set(ext[:, 1:])))
        x, routed = _moe_block(x, p, cfg, interpret)
        load = _add_load(load, routed)
    return _head(params, x, cfg)[:, 0], new_caches, load


def state_rows(qkv, conv_w, cache: dict, st, q_lens, recur):
    """How a recurrent state travels through a prefill dispatch of R
    chunk-rows that lie flat one after another, whatever the recurrence:
    ``qkv`` [R, C, channels] is the convolution's input, ``st`` [R, 5]
    says for each row where its state comes from (MODE: CONTINUE what row
    LOAD of ``S`` holds, FRESH zeros, RESUME snapshot SNAP_FROM, CHAIN the
    state the row before it leaves: the next chunk of the same prompt) and
    where its end state goes (row STORE of ``S``, snapshot SNAP_TO; 0, the
    sink, for neither). The convolution's tail crosses row bounds the same
    way. ``recur(x, s0, chain, live)`` — the convolution's output [R, C,
    channels], the rows' start states, which rows chain, which tokens are
    real [R, C, 1] — returns (o, the states at the rows' ends): a row's
    q_lens real tokens alone may move its state. Returns (o, the layer's
    cache)."""
    r, c, _ = qkv.shape
    width = conv_w.shape[0]
    mode = st[:, MODE]

    def start(pool, snaps):
        def rows(flag):
            return flag.reshape((r,) + (1,) * (pool.ndim - 1))
        x = jnp.where(rows(mode == RESUME), snaps[st[:, SNAP_FROM]],
                      pool[st[:, LOAD]])
        return jnp.where(rows(mode == FRESH), jnp.zeros_like(x), x)
    s0 = start(cache["S"], cache["snap_S"])
    tail0 = start(cache["conv"], cache["snap_conv"])
    chain = (mode == CHAIN).astype(jnp.int32)
    # the tails row by row: a chained row's is what the row before it
    # leaves behind its last real token
    tails, ends, prev = [], [], tail0[0]
    for i in range(r):
        tail = jnp.where(chain[i] != 0, prev, tail0[i])
        prev = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([tail, qkv[i]], axis=0), q_lens[i], width - 1)
        tails.append(tail)
        ends.append(prev)
    ext = jnp.concatenate([jnp.stack(tails), qkv], axis=1)
    live = (jnp.arange(c)[None, :] < q_lens[:, None])[..., None]
    o, s_end = recur(_conv(ext, conv_w), s0, chain, live)
    ends = jnp.stack(ends)
    new = {"S": cache["S"].at[st[:, STORE]].set(s_end),
           "conv": cache["conv"].at[st[:, STORE]].set(ends),
           "snap_S": cache["snap_S"].at[st[:, SNAP_TO]].set(s_end),
           "snap_conv": cache["snap_conv"].at[st[:, SNAP_TO]].set(ends)}
    return o, new


def _gdn_prefill(h, p, cfg: Qwen3NextConfig, cache: dict, st, q_lens,
                 interpret: bool):
    """A GDN layer over R chunk-rows [R, C, D] (`state_rows`). Returns
    (the residual term, the layer's cache)."""
    qkv, z, beta, g = _gdn_inputs(h, p, cfg)

    def recur(x, s0, chain, live):
        q, k, v = _gdn_qkv(x, cfg)
        with jax.named_scope("gdn_prefill"):
            return gated_delta_prefill(
                q, k, v, jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0),
                s0, chain, interpret=interpret)
    o, new = state_rows(qkv, p["conv_w"], cache, st, q_lens, recur)
    return _gdn_out(o, z, p, cfg), new


def prefill_paged_rows(params: dict, chunks: jax.Array, caches: list[dict],
                       bt_rows, start_pos: jax.Array, true_lens: jax.Array,
                       cfg: Qwen3NextConfig, *, page_size: int,
                       interpret: bool = False, lora=None, slots=None):
    """Up to R page-aligned chunk-rows as one batched forward:
    llama.prefill_paged_rows's contract (chunks [R, C]; consecutive rows
    may be consecutive chunks of one sequence; true_lens == 0 rows are
    padding; returns last_logits [R, V], caches, load) with ``bt_rows``
    the pair (the full layers' table [R, max_pages], the state table [R,
    5] of `state_rows`)."""
    _no_lora(lora)
    table, st = bt_rows
    r, c = chunks.shape
    n_chunk_pages = c // page_size
    starts = start_pos.astype(jnp.int32)
    q_lens = true_lens.astype(jnp.int32)
    cos, sin = rope_freqs(cfg, starts[:, None] + jnp.arange(c)[None, :])
    attend = _window_attend(cfg.head_dim ** -0.5, interpret)
    chunk_page_ids = chunk_pages(table, starts, q_lens, n_chunk_pages,
                                 page_size)
    paged = (r, n_chunk_pages, page_size, cfg.n_kv_heads * cfg.head_dim)
    x = params["embed"][chunks].astype(cfg.dtype)
    new_caches, load = [], None
    for layer in range(cfg.n_layers):
        p = _layer_params(params, layer, cfg)
        cache = caches[layer]
        h = norm(x, p["attn_norm"], cfg.norm_eps)
        if cfg.full(layer):
            q, k, v, gate = _full_qkv(h, p, cfg, cos, sin)
            k_pages = cache["k"].at[chunk_page_ids].set(
                k.reshape(paged).astype(cache["k"].dtype))
            v_pages = cache["v"].at[chunk_page_ids].set(
                v.reshape(paged).astype(cache["v"].dtype))
            attn = attend(q, k_pages, v_pages, table, starts, q_lens)
            x = x + _full_out(attn, gate, p, cfg)
            new_caches.append({"k": k_pages, "v": v_pages})
        else:
            y, cache = _gdn_prefill(h, p, cfg, cache, st, q_lens, interpret)
            x = x + y
            new_caches.append(cache)
        x, routed = _moe_block(x, p, cfg, interpret)
        load = _add_load(load, routed)
    last = jnp.clip(q_lens - 1, 0, c - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)     # [R, 1, D]
    return _head(params, x, cfg)[:, 0], new_caches, load


def verify_paged_rows(*args, **kwargs):
    raise NotImplementedError(
        "models/qwen3_next.py: speculative verification over a recurrent "
        "state (a rejected draft has already moved it) — "
        "PagedEngineConfig.spec_tokens must stay 0")
