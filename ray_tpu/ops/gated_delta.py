"""The gated delta rule as Pallas kernels for TPU, and the token-by-token
scan they are held against: Gated DeltaNet's recurrence, whose decay is one
scalar a head, and Kimi delta attention's (KDA), whose decay is a vector a
head — one factor a key channel.

Per value head, a state ``S`` in R^{dk x dv} (key x value), float32:

    S <- Diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;
    o_t = S^T q_t

``g_t <= 0`` is the log of the decay — ``[nv]`` a token (GDN: every row of
a head's state by the same factor) or ``[nv, dk]`` (KDA: row c by
``exp(g_t[c])``) — and ``beta_t`` in (0, 1) the write strength; ``q``
arrives scaled, ``q`` and ``k`` L2-normalised. A key head serves
``nv // nk`` consecutive value heads. Every entry point tells the two by
``g``'s rank; each has its own kernel body and ``pallas_call`` name
(``gated_delta_*`` / ``kda_*``), so a program of scalar decays is what it
was before the vector form existed.

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
* the same with a decay a channel (``kda_prefill``): inside a chunk
  ``A_ij = -beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` with ``G`` the
  running sum of ``g`` a channel is no longer ``(k k^T) * decay``: it is a
  product of ``k_i * exp(G_i - G_ref)`` and ``k_j * exp(G_ref - G_j)``, and
  against one reference a chunk the second factor reaches ``exp(64 x 5)``
  at the decay's bound of -5 a token — float32 ends at ``exp(88.7)``. So a
  second level of chunking (Kimi Linear, arXiv:2510.26692): the rows of a
  16-token sub-block take the cumulative decay at their sub-block's middle
  row as reference. Both factors then lie within ``exp(+-8 x 5)`` inside
  the sub-block — far from float32's largest AND from its smallest normal
  number, which a reference at the sub-block's start would leave the last
  row's ``k * exp(-80)`` under — and against an earlier sub-block the
  keys' factor is below 1 (where it underflows, so does the true entry).
  The caller's bound ``g >= -5`` a token (``SUB / 2 * |g|`` well inside 88)
  is the kernel's contract. ``q`` and the terms between chunks carry
  ``exp(G) <= 1``.
* ``gated_delta_decode`` — one token for every decode row, in place on the
  slots' state array: grid (row), the row's state block picked by a
  scalar-prefetched slot index and written back where it was read
  (``input_output_aliases``), all on the VPU in float32. An idle row names
  the sink row 0. Bound by streaming the state: read once, written once.
  With a decay a channel (``kda_decode``) one operand changes: a head's
  ``[dk, 1]`` column of factors where the scalar form broadcasts one
  factor over the ``[nv, dv]`` lanes.

Vectors a kernel needs down the sublanes (a column) are made in the kernel
from lane vectors by a masked lane reduction, or handed over transposed
with the heads on the lanes: a ``[n, 1]`` array would be padded to 128
lanes in HBM.

Off the TPU (and not under ``interpret``) both run the scan: the CPU
fallback and the numerical contract.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .flash_attention import _on_tpu

CHUNK = 64
# rows of a chunk that share one reference for a decay a channel: SUB x the
# caller's bound on |g| stays inside float32's exponent (16 x 5 = 80 < 88.7)
SUB = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def _expand(x, nv: int):
    """[..., nk, d] -> [..., nv, d]: key head i serves value heads
    i * (nv // nk) .. (i + 1) * (nv // nk) - 1."""
    return jnp.repeat(x, nv // x.shape[-2], axis=-2)


def gated_delta_scan(q, k, v, g, beta, s0):
    """The recurrence token by token, float32: q, k [T, nk, dk], v [T, nv,
    dv], beta [T, nv], g [T, nv] (a decay a head) or [T, nv, dk] (a decay
    a key channel), s0 [nv, dk, dv] -> (o [T, nv, dv], S)."""
    nv = v.shape[-2]
    f32 = jnp.float32
    q, k = _expand(q.astype(f32), nv), _expand(k.astype(f32), nv)

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * (jnp.exp(gt)[:, None, None] if gt.ndim == 1
                 else jnp.exp(gt)[:, :, None])
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

def _unit_lower_inverse(a, diag, mm32):
    """(I - A)^-1 of a strictly lower-triangular ``a`` [n, n]: nilpotent,
    so (I + A)(I + A^2)(I + A^4)..., ``log2 n`` squarings."""
    n = a.shape[0]
    t = jnp.where(diag, 1.0, 0.0) + a
    power = a
    for _ in range(max((n - 1).bit_length() - 1, 0)):
        power = mm32(power, power)
        t = t + mm32(t, power)
    return t


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
    t = _unit_lower_inverse(a, diag, mm32)
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


def _kda_chunk_kernel(chain_ref, q_ref, k_ref, kt_ref, v_ref, g_ref, gt_ref,
                      beta_ref, s0_ref, o_ref, sf_ref, s_scr, *,
                      chunks_per_row: int, sub: int):
    """`_chunk_kernel` with a decay a key channel: ``g`` [n, dk] and its
    transpose ``gt`` [dk, n] in place of the row of scalars. The two
    [n, n] products of the chunk — ``A`` from ``beta k`` and the causal
    ``q k^T`` — are built a sub-block of ``sub`` rows at a time, each
    against the cumulative decay at its middle row (the module's header
    says why)."""
    from jax.experimental import pallas as pl

    c = pl.program_id(1)
    row = c // chunks_per_row

    @pl.when((c % chunks_per_row == 0) & (chain_ref[row] == 0))
    def _load():
        s_scr[...] = s0_ref[0, 0]

    f32 = jnp.float32
    q, k, kt, v = q_ref[0, 0], k_ref[0, 0], kt_ref[0, 0], v_ref[0, 0]
    cd = q.dtype
    n, dk = q.shape

    def mm(a, b):
        return jnp.dot(a.astype(cd), b.astype(cd),
                       preferred_element_type=f32)

    def mm32(a, b):
        return jnp.dot(a, b, preferred_element_type=f32, precision=_HIGHEST)

    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    lower, diag = jj <= ii, jj == ii
    beta = beta_ref[0, 0, 0:1, :]                               # [1, n]
    beta_col = jnp.sum(jnp.where(diag, jnp.broadcast_to(beta, (n, n)), 0.0),
                       axis=1, keepdims=True)
    # the running sum of g a channel, rows down ([n, dk]) and across
    # ([dk, n]): a product with a triangle of ones
    gc = mm32(jnp.where(lower, 1.0, 0.0), g_ref[0, 0])
    gct = mm32(gt_ref[0, 0], jnp.where(ii <= jj, 1.0, 0.0))
    starts = range(0, n, sub)
    mid = (sub - 1) // 2        # a sub-block's reference: its middle row
    # each sub-block's reference under its rows: [n, dk]
    ref = jnp.concatenate([jnp.broadcast_to(
        gc[at + mid:at + mid + 1], (sub, dk)) for at in starts], axis=0)
    inside = jnp.exp(gc - ref)                  # a row against its reference
    kb = k.astype(f32) * beta_col                               # [n, dk]
    kb_in, q_in = kb * inside, q.astype(f32) * inside
    col = jax.lax.broadcasted_iota(jnp.int32, (dk, n), 1)
    ktf = kt.astype(f32)
    a_rows, qk_rows = [], []
    for at in starts:
        # the reference against every key up to the sub-block's end:
        # below 0 ahead of the sub-block, within sub / 2 x |g| of 0 inside
        # it, as the rows' factor is; keys behind the sub-block are masked
        # below and take no exponent here
        k_ref_t = ktf * jnp.exp(jnp.where(
            col < at + sub, gct[:, at + mid:at + mid + 1] - gct, 0.0))
        a_rows.append(mm(kb_in[at:at + sub], k_ref_t))
        qk_rows.append(mm(q_in[at:at + sub], k_ref_t))
    a = jnp.where(jj < ii, -jnp.concatenate(a_rows, axis=0), 0.0)
    qk = jnp.where(lower, jnp.concatenate(qk_rows, axis=0), 0.0)
    t = _unit_lower_inverse(a, diag, mm32)
    s = s_scr[...]                                              # [dk, dv]
    e = jnp.exp(gc)                                             # [n, dk]
    v_new = mm32(t, v.astype(f32) * beta_col) - mm(mm32(t, kb * e), s)
    o = mm(q.astype(f32) * e, s) + mm(qk, v_new)
    g_last = gct[:, n - 1:n]                                    # [dk, 1]
    s = s * jnp.exp(g_last) + mm(ktf * jnp.exp(g_last - gct), v_new)
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

    def row_of_8(rows):             # {row: [R, C, nv]} -> [nv, nc, 8, chunk]
        out = jnp.zeros((nv, nc, 8, chunk), jnp.float32)
        for at, x in rows.items():
            out = out.at[:, :, at].set(x.astype(jnp.float32).transpose(
                2, 0, 1).reshape(nv, nc, chunk))
        return out

    def key_head(h, c, ch):
        return (h // rep, c, 0, 0)

    def own(h, c, ch):
        return (h, c, 0, 0)

    def state(h, c, ch):
        return (c // per_row, h, 0, 0)
    if g.ndim == 3:
        kernel, name = _chunk_kernel, "gated_delta_prefill"
        decay = [row_of_8({0: g, 1: beta})]
        decay_specs = [pl.BlockSpec((1, 1, 8, chunk), own)]
    else:
        kernel = functools.partial(_kda_chunk_kernel, sub=(
            SUB if chunk % SUB == 0 else math.gcd(chunk, SUB)))
        name = "kda_prefill"
        gh = g.astype(jnp.float32).transpose(2, 0, 1, 3).reshape(
            nv, nc, chunk, dk)
        decay = [gh, gh.swapaxes(2, 3), row_of_8({0: beta})]
        decay_specs = [pl.BlockSpec((1, 1, chunk, dk), own),
                       pl.BlockSpec((1, 1, dk, chunk), own),
                       pl.BlockSpec((1, 1, 8, chunk), own)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nv, nc),
        in_specs=[pl.BlockSpec((1, 1, chunk, dk), key_head),
                  pl.BlockSpec((1, 1, chunk, dk), key_head),
                  pl.BlockSpec((1, 1, dk, chunk), key_head),
                  pl.BlockSpec((1, 1, chunk, dv), own),
                  *decay_specs,
                  pl.BlockSpec((1, 1, dk, dv), state)],
        out_specs=[pl.BlockSpec((1, 1, chunk, dv), own),
                   pl.BlockSpec((1, 1, dk, dv), state)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)])
    o, s_final = pl.pallas_call(
        functools.partial(kernel, chunks_per_row=per_row),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nv, nc, chunk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((r, nv, dk, dv), jnp.float32)],
        interpret=interpret, name=name,
    )(chain.astype(jnp.int32), qh, kh, kh.swapaxes(2, 3), vh, *decay,
      s0.astype(jnp.float32))
    return o.reshape(nv, r, c, dv).transpose(1, 2, 0, 3), s_final


def gated_delta_prefill(q, k, v, g, beta, s0, chain, *,
                        interpret: bool = False):
    """R rows of C tokens, flat one after another: q, k [R, C, nk, dk]
    (q scaled), v [R, C, nv, dv], beta [R, C, nv] and g [R, C, nv] (a
    decay a head) or [R, C, nv, dk] (a decay a key channel: then at least
    -88 / SUB a token) float32 — 0 at a pad token, which then leaves the
    state as it was — s0 [R, nv, dk, dv]
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
                   o_ref, s_out_ref, *, channel: bool = False):
    """``dec_ref``: the decay's factors, one a head over the ``dv`` lanes
    ([nv, dv]) or, with ``channel``, one a key channel with the heads on
    the lanes ([dk, nv], as q and k arrive)."""
    for h in range(s_ref.shape[1]):
        kc, qc = kt_ref[0, :, h:h + 1], qt_ref[0, :, h:h + 1]   # [dk, 1]
        s = s_ref[0, h] * (dec_ref[0, :, h:h + 1] if channel
                           else dec_ref[0, h:h + 1, :])         # [dk, dv]
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
    channel = g.ndim == 3
    kernel, name = _decode_kernel, "gated_delta_decode"
    if channel:
        kernel = functools.partial(_decode_kernel, channel=True)
        name = "kda_decode"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b,),
        in_specs=[slot, per_row(dk, nv), per_row(dk, nv), per_row(nv, dv),
                  per_row(dk, nv) if channel else per_row(nv, dv),
                  per_row(nv, dv)],
        out_specs=[per_row(nv, dv), slot])
    block = nv * dk * dv * 4
    o, states = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, nv, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        # the slots' array is updated where it lies (operand 1, counting
        # the prefetched rows, is output 1)
        input_output_aliases={1: 1},
        # a row's state block, in and out, double-buffered
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(4 * block + (8 << 20), 16 << 20)),
        interpret=interpret, name=name,
    )(rows.astype(jnp.int32), states,
      _expand(q.astype(f32), nv).swapaxes(1, 2),
      _expand(k.astype(f32), nv).swapaxes(1, 2), v.astype(f32),
      jnp.exp(g.astype(f32)).swapaxes(1, 2) if channel
      else lanes(jnp.exp(g)), lanes(beta))
    return o, states


def gated_delta_decode(states, rows, q, k, v, g, beta, *,
                       interpret: bool = False):
    """One token for each of B rows over the slots' states [N, nv, dk, dv]
    float32, row b's at ``states[rows[b]]`` (several idle rows may name
    the sink row 0, whose content is then undefined): q, k [B, nk, dk]
    (q scaled), v [B, nv, dv], beta [B, nv], g [B, nv] or, a decay a key
    channel, [B, nv, dk]. Returns (o [B, nv, dv] float32, the states with
    the rows' updated)."""
    if interpret or _on_tpu():
        return _decode_call(states, rows, q, k, v, g, beta,
                            interpret=interpret)
    one = jax.vmap(lambda s, *x: gated_delta_scan(
        *(a[None] for a in x), s))
    o, new = one(states[rows], q, k, v, g, beta)
    return o[:, 0], states.at[rows].set(new)
