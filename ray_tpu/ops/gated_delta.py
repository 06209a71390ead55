"""The gated delta rule (Gated DeltaNet's recurrence) as two Pallas kernels
for TPU, and the token-by-token scan both are held against.

Per value head, a state ``S`` in R^{dk x dv} (key x value), float32:

    S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;
    o_t = S^T q_t

``g_t <= 0`` is the log of the decay and ``beta_t`` in (0, 1) the write
strength; ``q`` arrives scaled, ``q`` and ``k`` L2-normalised. A key head
serves ``nv // nk`` consecutive value heads.

* ``gated_delta_prefill`` — chunked (64 tokens a chunk, the WY / UT-transform
  form): inside a chunk ``T = (I - A)^-1`` for the strictly lower-triangular
  ``A = -(beta k k^T) * decay`` turns the 64 sequential rank-1 updates into
  matmuls, and the state is touched once a chunk. ``A`` is nilpotent, so
  ``T = (I + A)(I + A^2)(I + A^4)...``: five squarings at 64. Grid (value
  head, chunk), chunks innermost, the state carried in VMEM scratch. The
  dispatch's rows lie flat one after another: a row loads its own initial
  state, or (``chain``) takes the state the row before it leaves — the
  consecutive chunks of one prompt packed into one prefill dispatch — and
  every row hands back the state at its end. Float32 state; the operands
  of the matmuls against the state and of ``k k^T`` / ``q k^T`` go into
  the MXU in the activations' type (bf16), accumulated in float32; the
  small products that build ``T`` stay float32 at the highest precision
  (their entries cancel).
* ``gated_delta_decode`` — one token for every decode row, in place on the
  slots' state array: grid (row), the row's state block picked by a
  scalar-prefetched slot index and written back where it was read
  (``input_output_aliases``), all on the VPU in float32. An idle row names
  the sink row 0. Bound by streaming the state: read once, written once.

Vectors a kernel needs down the sublanes (a column) are made in the kernel
from lane vectors by a masked lane reduction, or handed over transposed
with the heads on the lanes: a ``[n, 1]`` array would be padded to 128
lanes in HBM.

Off the TPU (and not under ``interpret``) both run the scan: the CPU
fallback and the numerical contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _on_tpu

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def _expand(x, nv: int):
    """[..., nk, d] -> [..., nv, d]: key head i serves value heads
    i * (nv // nk) .. (i + 1) * (nv // nk) - 1."""
    return jnp.repeat(x, nv // x.shape[-2], axis=-2)


def gated_delta_scan(q, k, v, g, beta, s0):
    """The recurrence token by token, float32: q, k [T, nk, dk], v [T, nv,
    dv], g, beta [T, nv], s0 [nv, dk, dv] -> (o [T, nv, dv], S)."""
    nv = v.shape[-2]
    f32 = jnp.float32
    q, k = _expand(q.astype(f32), nv), _expand(k.astype(f32), nv)

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, s,
                                           precision=_HIGHEST))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s, precision=_HIGHEST)
    s, o = jax.lax.scan(step, s0.astype(f32), (
        q, k, v.astype(f32), g.astype(f32), beta.astype(f32)))
    return o, s


def gated_delta_rows_reference(q, k, v, g, beta, s0, chain):
    """``gated_delta_prefill``'s contract through the scan, a row at a
    time (R is static)."""
    outs, finals, s = [], [], None
    for r in range(q.shape[0]):
        init = s0[r] if s is None else jnp.where(chain[r] != 0, s, s0[r])
        o, s = gated_delta_scan(q[r], k[r], v[r], g[r], beta[r], init)
        outs.append(o)
        finals.append(s)
    return jnp.stack(outs), jnp.stack(finals)


# ---------------------------------------------------------------------------
# Prefill: chunked
# ---------------------------------------------------------------------------

def _chunk_kernel(chain_ref, q_ref, k_ref, kt_ref, v_ref, gb_ref, s0_ref,
                  o_ref, sf_ref, s_scr, *, chunks_per_row: int):
    from jax.experimental import pallas as pl

    c = pl.program_id(1)
    row = c // chunks_per_row

    @pl.when((c % chunks_per_row == 0) & (chain_ref[row] == 0))
    def _load():
        s_scr[...] = s0_ref[0, 0]

    f32 = jnp.float32
    q, k, kt, v = q_ref[0, 0], k_ref[0, 0], kt_ref[0, 0], v_ref[0, 0]
    cd = q.dtype                      # what goes into the MXU
    n = q.shape[0]

    def mm(a, b):                     # operands in the activations' type
        return jnp.dot(a.astype(cd), b.astype(cd),
                       preferred_element_type=f32)

    def mm32(a, b):                   # the products that build T
        return jnp.dot(a, b, preferred_element_type=f32, precision=_HIGHEST)

    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    lower, diag = jj <= ii, jj == ii

    def column(row_vec):              # [1, n] down the sublanes: [n, 1]
        return jnp.sum(jnp.where(diag, jnp.broadcast_to(row_vec, (n, n)),
                                 0.0), axis=1, keepdims=True)
    g, beta = gb_ref[0, 0, 0:1, :], gb_ref[0, 0, 1:2, :]       # [1, n]
    # cumulative log decay inside the chunk, as a column and as a row
    gc_col = jnp.sum(jnp.where(lower, jnp.broadcast_to(g, (n, n)), 0.0),
                     axis=1, keepdims=True)
    gc_row = jnp.sum(jnp.where(ii <= jj, jnp.broadcast_to(
        column(g), (n, n)), 0.0), axis=0, keepdims=True)
    beta_col = column(beta)
    # decay[i, j] = exp(gc_i - gc_j) for j <= i (never above 1)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gc_col - gc_row, 0.0)),
                      0.0)
    kb = k.astype(f32) * beta_col                               # [n, dk]
    a = jnp.where(jj < ii, -mm(kb, kt) * decay, 0.0)
    t = jnp.where(diag, 1.0, 0.0) + a
    power = a
    for _ in range(max((n - 1).bit_length() - 1, 0)):
        power = mm32(power, power)
        t = t + mm32(t, power)
    s = s_scr[...]                                              # [dk, dv]
    e_col = jnp.exp(gc_col)
    v_new = mm32(t, v.astype(f32) * beta_col) - mm(
        mm32(t, kb * e_col), s)                                 # [n, dv]
    o = mm(q.astype(f32) * e_col, s) + mm(mm(q, kt) * decay, v_new)
    g_last = gc_col[n - 1:n, :]                                 # [1, 1]
    s = s * jnp.exp(g_last) + mm(
        kt.astype(f32) * jnp.exp(g_last - gc_row), v_new)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    s_scr[...] = s
    sf_ref[0, 0] = s


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _chunk_call(q, k, v, g, beta, s0, chain, *, chunk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, c, nk, dk = q.shape
    nv, dv = v.shape[-2:]
    nc, per_row, rep = r * c // chunk, c // chunk, nv // nk
    # heads outermost, the rows' tokens flat behind them, cut in chunks
    qh = q.transpose(2, 0, 1, 3).reshape(nk, nc, chunk, dk)
    kh = k.transpose(2, 0, 1, 3).reshape(nk, nc, chunk, dk)
    vh = v.transpose(2, 0, 1, 3).reshape(nv, nc, chunk, dv)
    gb = jnp.zeros((nv, nc, 8, chunk), jnp.float32)
    gb = gb.at[:, :, 0].set(g.astype(jnp.float32).transpose(2, 0, 1).reshape(
        nv, nc, chunk))
    gb = gb.at[:, :, 1].set(beta.astype(jnp.float32).transpose(
        2, 0, 1).reshape(nv, nc, chunk))

    def key_head(h, c, ch):
        return (h // rep, c, 0, 0)

    def own(h, c, ch):
        return (h, c, 0, 0)

    def state(h, c, ch):
        return (c // per_row, h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nv, nc),
        in_specs=[pl.BlockSpec((1, 1, chunk, dk), key_head),
                  pl.BlockSpec((1, 1, chunk, dk), key_head),
                  pl.BlockSpec((1, 1, dk, chunk), key_head),
                  pl.BlockSpec((1, 1, chunk, dv), own),
                  pl.BlockSpec((1, 1, 8, chunk), own),
                  pl.BlockSpec((1, 1, dk, dv), state)],
        out_specs=[pl.BlockSpec((1, 1, chunk, dv), own),
                   pl.BlockSpec((1, 1, dk, dv), state)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)])
    o, s_final = pl.pallas_call(
        functools.partial(_chunk_kernel, chunks_per_row=per_row),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nv, nc, chunk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((r, nv, dk, dv), jnp.float32)],
        interpret=interpret, name="gated_delta_prefill",
    )(chain.astype(jnp.int32), qh, kh, kh.swapaxes(2, 3), vh, gb,
      s0.astype(jnp.float32))
    return o.reshape(nv, r, c, dv).transpose(1, 2, 0, 3), s_final


def gated_delta_prefill(q, k, v, g, beta, s0, chain, *,
                        interpret: bool = False):
    """R rows of C tokens, flat one after another: q, k [R, C, nk, dk]
    (q scaled), v [R, C, nv, dv], g, beta [R, C, nv] float32 — 0 at a pad
    token, which then leaves the state as it was — s0 [R, nv, dk, dv]
    float32, chain [R] (row r starts from the state row r - 1 ends with,
    not from s0[r]; never set on row 0). Returns (o [R, C, nv, dv] float32,
    the state at each row's end [R, nv, dk, dv] float32)."""
    if not (interpret or _on_tpu()):
        return gated_delta_rows_reference(q, k, v, g, beta, s0, chain)
    c = q.shape[1]
    return _chunk_call(q, k, v, g, beta, s0, chain,
                       chunk=CHUNK if c % CHUNK == 0 else c,
                       interpret=interpret)


# ---------------------------------------------------------------------------
# Decode: one token a row, in place
# ---------------------------------------------------------------------------

def _decode_kernel(rows_ref, s_ref, qt_ref, kt_ref, v_ref, dec_ref, beta_ref,
                   o_ref, s_out_ref):
    for h in range(s_ref.shape[1]):
        kc, qc = kt_ref[0, :, h:h + 1], qt_ref[0, :, h:h + 1]   # [dk, 1]
        s = s_ref[0, h] * dec_ref[0, h:h + 1, :]                # [dk, dv]
        u = beta_ref[0, h:h + 1, :] * (
            v_ref[0, h:h + 1, :] - jnp.sum(s * kc, axis=0, keepdims=True))
        s = s + kc * u
        o_ref[0, h:h + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)
        s_out_ref[0, h] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_call(states, rows, q, k, v, g, beta, *, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, nv, dv = v.shape
    dk = q.shape[-1]
    f32 = jnp.float32
    lanes = lambda x: jnp.broadcast_to(                    # noqa: E731
        x.astype(f32)[..., None], (b, nv, dv))

    def per_row(*tail):
        return pl.BlockSpec((1,) + tail, lambda i, rows: (i,) + (0,) * len(
            tail))
    slot = pl.BlockSpec((1, nv, dk, dv), lambda i, rows: (rows[i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b,),
        in_specs=[slot, per_row(dk, nv), per_row(dk, nv), per_row(nv, dv),
                  per_row(nv, dv), per_row(nv, dv)],
        out_specs=[per_row(nv, dv), slot])
    block = nv * dk * dv * 4
    o, states = pl.pallas_call(
        _decode_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, nv, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        # the slots' array is updated where it lies (operand 1, counting
        # the prefetched rows, is output 1)
        input_output_aliases={1: 1},
        # a row's state block, in and out, double-buffered
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(4 * block + (8 << 20), 16 << 20)),
        interpret=interpret, name="gated_delta_decode",
    )(rows.astype(jnp.int32), states,
      _expand(q.astype(f32), nv).swapaxes(1, 2),
      _expand(k.astype(f32), nv).swapaxes(1, 2), v.astype(f32),
      lanes(jnp.exp(g)), lanes(beta))
    return o, states


def gated_delta_decode(states, rows, q, k, v, g, beta, *,
                       interpret: bool = False):
    """One token for each of B rows over the slots' states [N, nv, dk, dv]
    float32, row b's at ``states[rows[b]]`` (several idle rows may name
    the sink row 0, whose content is then undefined): q, k [B, nk, dk]
    (q scaled), v [B, nv, dv], g, beta [B, nv]. Returns (o [B, nv, dv]
    float32, the states with the rows' updated)."""
    if interpret or _on_tpu():
        return _decode_call(states, rows, q, k, v, g, beta,
                            interpret=interpret)
    one = jax.vmap(lambda s, *x: gated_delta_scan(
        *(a[None] for a in x), s))
    o, new = one(states[rows], q, k, v, g, beta)
    return o[:, 0], states.at[rows].set(new)
