"""Pipeline parallelism over the `pp` mesh axis (GPipe schedule, SPMD).

Reference role: the reference has NO pipeline schedule of its own — PP runs
inside vLLM over Ray workers coordinated by compiled graphs
(dag/compiled_dag_node.py:808; SURVEY.md §2.4). On TPU the idiomatic
construction is the inverse: the schedule lives INSIDE one compiled SPMD
program. Each pp shard holds one stage's parameters; every schedule tick,
all stages run the same stage function on their current microbatch and
activations hop to the next stage with `lax.ppermute`. Autodiff flows
through the whole schedule (ppermute transposes to the reverse rotation),
so the backward pipeline needs no extra code — this is the
compiled-graph-channels analog with XLA owning the transfers (PAPERS.md
JaxPP-style, original implementation).

Schedules:
  - GPipe (num_chunks=1): M microbatches through S stages in M + S - 1
    ticks; bubble fraction (S-1)/(M+S-1).
  - Breadth-first interleaved virtual stages (num_chunks=V>1, the
    schedule Megatron calls interleaved 1F1B, bubble-wise): each device
    holds V stage CHUNKS (device d owns logical stages {c*S+d}), a
    microbatch makes V loops around the ring, and stage k=c*S+d runs
    microbatch m at tick (m//S)*S*V + c*S + (m%S) + d — conflict-free,
    every activation still hops d->d+1 each tick, and the bubble shrinks
    to (S-1)/(V*M+S-1) ticks. Requires M % S == 0.

Activation-memory trade is handled by jax.checkpoint over the stage fn.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array,
                   mesh: Mesh, num_microbatches: int,
                   remat: bool = True, x_spec: P = P(),
                   num_chunks: int = 1) -> jax.Array:
    """Run `x` through a chain of pp-sharded stages.

    stage_fn(params_one_stage, h) -> h : one stage's computation (e.g. a
        `lax.scan` over its transformer layers).
    stage_params : pytree whose leaves have leading dim S*num_chunks,
        sharded P("pp") and ordered DEVICE-MAJOR (use interleave_stages to
        go from logical stage order to this layout) — device d holds
        chunks for logical stages {c*S+d | c < num_chunks}.
    x [B, ...] : input activations, replicated over pp (embedding and head
        stay outside the pipeline: they're pp-replicated). `x_spec` shards
        the activation dims over OTHER mesh axes (e.g. P("dp") to compose
        pp with data parallelism — each (pp, dp) shard pipelines its local
        batch slice).
    Returns y [B, ...] — the last stage's output, replicated over pp,
    sharded per x_spec elsewhere.

    The per-shard batch must divide into num_microbatches equal
    microbatches; interleaving additionally needs num_microbatches % S == 0.
    """
    S = mesh.shape.get("pp", 1)
    V = num_chunks
    if S == 1:
        # single device: chunks run back to back (device-major order with
        # d=0 IS logical order)
        if V == 1:
            return stage_fn(jax.tree.map(lambda a: a[0], stage_params), x)
        h = x
        for c in range(V):
            h = stage_fn(jax.tree.map(lambda a: a[c], stage_params), h)
        return h
    M = num_microbatches
    if V > 1 and M % S:
        raise ValueError(
            f"interleaved schedule needs microbatches ({M}) divisible by "
            f"pipeline stages ({S})")
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    def inner(params, xs):
        # params: this shard's V chunks, leading dims [1, V] — squeeze
        sp = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index("pp")
        b = xs.shape[0]
        mb = b // M
        xs = xs.reshape(M, mb, *xs.shape[1:])
        state = jnp.zeros_like(xs[0])
        outputs = jnp.zeros_like(xs)
        fwd = [(i, (i + 1) % S) for i in range(S)]
        SV = S * V

        def entry_tick(m):        # logical stage 0 consumes m at this tick
            return (m // S) * SV + (m % S)

        exits = {entry_tick(m) + SV - 1: m for m in range(M)}
        enters = {entry_tick(m): m for m in range(M)}
        for t in range(M * V + S - 1):
            # device 0 injects microbatch m when the schedule says stage 0
            # starts it this tick (static: t is a Python int)
            m_in = enters.get(t)
            inject = xs[m_in] if m_in is not None else jnp.zeros_like(xs[0])
            h = jnp.where(idx == 0, inject, state) if m_in is not None \
                else state
            # which chunk is this device running this tick? c such that
            # (t - d) mod SV lies in [c*S, c*S + S)
            if V == 1:
                h = fn(jax.tree.map(lambda a: a[0], sp), h)
            else:
                c = jnp.mod(t - idx, SV) // S
                h = jax.lax.switch(
                    c, [lambda hh, cc=cc: fn(
                        jax.tree.map(lambda a: a[cc], sp), hh)
                        for cc in range(V)], h)
            m_out = exits.get(t)
            if m_out is not None:   # last device finished logical stage SV-1
                outputs = outputs.at[m_out].set(
                    jnp.where(idx == S - 1, h, outputs[m_out]))
            state = jax.lax.ppermute(h, "pp", fwd)
        # replicate the last stage's outputs to every pp shard
        outputs = jnp.where(idx == S - 1, outputs, 0.0)
        outputs = jax.lax.psum(outputs, "pp")
        return outputs.reshape(b, *outputs.shape[2:])

    per_shard = x.shape[0]
    for ax in (x_spec[0] if len(x_spec) else None,) :
        if ax is not None:
            names = (ax,) if isinstance(ax, str) else tuple(ax)
            for n in names:
                per_shard //= mesh.shape.get(n, 1)
    if per_shard % M:
        raise ValueError(
            f"per-shard batch {per_shard} must divide microbatches {M}")

    # leaves arrive [S*V, ...] device-major; shard_map slices the leading
    # dim over pp leaving [V, ...] per shard — regroup as [1, V, ...] so
    # inner's squeeze-one convention holds for every V
    grouped = jax.tree.map(
        lambda a: a.reshape(S, V, *a.shape[1:]), stage_params)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P("pp"), x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(grouped, x)


def interleave_stages(stacked_stage_params, n_stages: int, n_chunks: int):
    """Logical stage order [S*V, ...] (stage k runs k-th) -> the
    device-major layout pipeline_apply(num_chunks=V) expects: device d
    holds logical stages {c*S+d}, stored as g = d*V + c."""
    S, V = n_stages, n_chunks

    def rearr(a):
        if a.shape[0] != S * V:
            raise ValueError(
                f"leading dim {a.shape[0]} != stages*chunks {S * V}")
        a = a.reshape(V, S, *a.shape[1:])   # [c, d, ...] (k = c*S + d)
        a = jnp.swapaxes(a, 0, 1)           # [d, c, ...]
        return a.reshape(S * V, *a.shape[2:])

    return jax.tree.map(rearr, stacked_stage_params)


def split_stages(stacked_layer_params, n_stages: int):
    """[L, ...] layer-stacked params -> [S, L/S, ...] stage-major params
    (shard dim 0 over pp)."""
    def reshape(a):
        L = a.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers not divisible by {n_stages} stages")
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])

    return jax.tree.map(reshape, stacked_layer_params)


def stage_sharding(mesh: Mesh):
    """NamedSharding placing stage-major params on the pp axis."""
    return NamedSharding(mesh, P("pp"))
