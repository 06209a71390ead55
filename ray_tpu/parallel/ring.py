"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has NO sequence parallelism (verified in SURVEY.md §5.7 — zero
hits for ring_attention/ulysses/context_parallel; long context lives in
external engines). On TPU it is ours to own, and the idiomatic design is
in-program: the sequence axis is a mesh axis ("sp"), K/V blocks rotate around
the ICI ring via `jax.lax.ppermute` while each step's partial attention is
computed blockwise with a streaming-softmax accumulator, so communication
overlaps compute and the full sequence never materializes on one chip.

Two schemes, matching the literature (see PAPERS.md):
* `ring_attention` — Liu et al. blockwise ring attention: K/V circulate,
  O(seq/n) memory per chip, exact result.
* `ulysses_attention` — DeepSpeed-Ulysses: all-to-all re-shards
  [B, S/n, H, D] -> [B, S, H/n, D], runs ordinary (flash) attention over the
  full sequence per head group, then re-shards back. Cheaper collectives for
  moderate sequence lengths; requires heads % n == 0.

Both are meant to be called inside `jax.shard_map` over the "sp" mesh axis;
`ring_attention_sharded` / `ulysses_attention_sharded` wrap that for callers
holding globally-sharded arrays.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, scale: float, mask: Optional[jax.Array]):
    """One q-block × kv-block attention step -> (unnormalized_out, max, sum).

    Returns the pieces a streaming-softmax accumulator needs. Shapes:
    q [B, Sq, H, D], k/v [B, Sk, H, D]; out [B, Sq, H, D], m/l [B, Sq, H].
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                       # [B, H, Sq]
    p = jnp.exp(scores - m[..., None])
    if mask is not None:
        # fully-masked rows: exp(NEG_INF - NEG_INF) = 1 — zero them instead
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                            # noqa: E741
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     preferred_element_type=jnp.float32)
    return out, jnp.moveaxis(m, 1, -1), jnp.moveaxis(l, 1, -1)


def _merge(acc_out, acc_m, acc_l, out, m, l):  # noqa: E741
    """Merge a new block into the streaming accumulator (flash-attention
    rescaling identity)."""
    new_m = jnp.maximum(acc_m, m)
    a = jnp.exp(acc_m - new_m)
    b = jnp.exp(m - new_m)
    new_out = acc_out * a[..., None] + out * b[..., None]
    new_l = acc_l * a + l * b
    return new_out, new_m, new_l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "sp",
                   causal: bool = False,
                   scale: Optional[float] = None) -> jax.Array:
    """Exact attention over a sequence sharded on `axis_name`.

    Call inside shard_map. q/k/v: [B, S_local, H, D] (the local sequence
    shard). K/V blocks rotate ring-wise via ppermute; `causal` masks with
    *global* positions derived from each block's ring offset.
    """
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5

    q_pos = my * s_local + jnp.arange(s_local)          # global q positions

    def step(carry, i):
        k_blk, v_blk, acc_out, acc_m, acc_l = carry
        src = (my - i) % n                               # who produced k_blk
        if causal:
            k_pos = src * s_local + jnp.arange(s_local)
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None]  # [1,1,q,k]
        else:
            mask = None
        out, m, l = _block_attn(q, k_blk, v_blk, scale, mask)  # noqa: E741
        acc_out, acc_m, acc_l = _merge(acc_out, acc_m, acc_l, out, m, l)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, acc_out, acc_m, acc_l), None

    acc_out = jnp.zeros(q.shape, jnp.float32)
    acc_m = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    acc_l = jnp.zeros(q.shape[:-1], jnp.float32)
    (_, _, acc_out, _, acc_l), _ = jax.lax.scan(
        step, (k, v, acc_out, acc_m, acc_l), jnp.arange(n))
    return (acc_out / jnp.maximum(acc_l, 1e-30)[..., None]).astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = "sp",
                      causal: bool = False,
                      scale: Optional[float] = None,
                      attn_fn=None) -> jax.Array:
    """Ulysses all-to-all attention; call inside shard_map.

    Re-shards seq→heads with one all_to_all, runs full-sequence attention on
    H/n heads (any `attn_fn(q, k, v, causal, scale)`, default streaming-exact
    jnp), re-shards back.
    """
    n = jax.lax.axis_size(axis_name)
    if q.shape[2] % n:
        raise ValueError(f"heads {q.shape[2]} % sp size {n} != 0")

    def s2h(x):  # [B, S/n, H, D] -> [B, S, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def h2s(x):  # [B, S, H/n, D] -> [B, S/n, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = s2h(q), s2h(k), s2h(v)
    if attn_fn is None:
        sc = scale if scale is not None else q.shape[-1] ** -0.5
        s = qg.shape[1]
        mask = (jnp.tril(jnp.ones((s, s), bool))[None, None]
                if causal else None)
        out, _, l = _block_attn(qg, kg, vg, sc, mask)  # noqa: E741
        og = (out / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    else:
        og = attn_fn(qg, kg, vg, causal=causal, scale=scale)
    return h2s(og)


def _sharded(fn, mesh, q_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=q_specs,
                         out_specs=q_specs[0], check_vma=False)


def _seq_spec(mesh, axis_name, batch_axes, head_axis) -> P:
    if axis_name not in mesh.axis_names:
        raise ValueError(
            f"mesh {tuple(mesh.axis_names)} has no {axis_name!r} axis; "
            f"build it with MeshSpec(sp=...) to use sequence parallelism")
    return P(tuple(a for a in batch_axes if a in mesh.axis_names) or None,
             axis_name,
             head_axis if head_axis in mesh.axis_names else None)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "sp",
                           causal: bool = False,
                           batch_axes=("dp", "fsdp"), head_axis="tp"):
    """Ring attention over globally-sharded [B, S, H, D] arrays: batch over
    dp/fsdp, sequence over sp, heads over tp."""
    spec = _seq_spec(mesh, axis_name, batch_axes, head_axis)
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal)
    return _sharded(fn, mesh, (spec, spec, spec))(q, k, v)


def ulysses_attention_sharded(q, k, v, mesh, axis_name: str = "sp",
                              causal: bool = False,
                              batch_axes=("dp", "fsdp"), head_axis="tp"):
    spec = _seq_spec(mesh, axis_name, batch_axes, head_axis)
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                           causal=causal)
    return _sharded(fn, mesh, (spec, spec, spec))(q, k, v)
