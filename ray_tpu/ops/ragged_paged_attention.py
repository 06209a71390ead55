"""Ragged paged attention for TPU (Pallas): ONE kernel family for every
paged-KV attention dispatch — prefill chunks, speculative-verify windows,
and decode (the q_len=1 degenerate case).

Reproduces the design of "Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU" (PAPERS.md): each row of a dispatch
is a variable-length query window `[start, start + q_len)` attending over
that row's paged prefix PLUS itself, with the window's own K/V already
scattered into the pages (the engine writes K/V before attention on every
path, so the kernel never needs a separate in-window concat). HBM traffic
is proportional to each row's TRUE length, not the pool capacity:

* grid ``(row, q_tile, kv_pages)`` with the block table scalar-prefetched
  so the K/V page BlockSpec index maps select each row's physical pages;
* the query window is TILED over the middle grid axis (`_q_tile`): the
  q/out blocks and the f32 accumulators are per query row, so a tile is
  an independent page sweep, and what one grid step keeps in VMEM is
  bounded by ``tile * heads * head_dim`` instead of by the engine's
  chunk_size (a whole 128-token window of 32 heads x 128 is refused by
  the v5e compiler: over the 16 MiB scoped-VMEM limit);
* the index map CLAMPS the logical page to the tile's last live page, so
  grid steps at/beyond the live page count re-request the block already
  resident and the pipeline elides the fetch — pages a row doesn't own,
  and pages wholly in a tile's causal future, are neither read nor
  computed (`pl.when` skips the body);
* a streaming-softmax accumulator in VMEM scratch carries across the
  page sweep (TPU grids iterate the last dimension fastest, so scratch
  persists across one tile's sweep — same contract as
  `ops/paged_attention._paged_decode_kernel`);
* causal masking INSIDE the query window: key position ``k_pos`` is
  attended by query position ``q_pos = start + i`` iff ``k_pos <= q_pos``
  — which covers the prefix (always attended) and the window (causal)
  with one predicate;
* GQA by the static per-kv-head loop proven in the decode kernel: each
  group of ``groups`` query heads runs a [Q*G, page] MXU tile against its
  kv head's [page, D] block — no jnp.repeat materialization anywhere.

Row layout convention (everything else follows from it): with Q the
query TILE, the flattened score/accumulator row index is
``h_kv * (Q * G) + q * G + g`` — per-kv-head blocks, query-major within
a block — because per-kv-head q slices ``q[:, h*G:(h+1)*G, :]`` reshape
contiguously to [Q*G, D].

The pure-jnp oracle (`ragged_paged_reference`) uses the same
grouped-einsum GQA form and is the CPU fallback's numerical contract;
the kernel runs under ``interpret=True`` in tier-1 so parity is asserted
without a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# one masking constant for the whole paged family: the q_len=1
# equivalence baseline (ops/paged_attention) must mask identically
from .paged_attention import NEG_INF


def _ragged_kernel(bt_ref, start_ref, qlen_ref,       # scalar prefetch
                   q_ref, k_ref, v_ref,               # blocks
                   o_ref,                             # output
                   acc_ref, m_ref, l_ref,             # VMEM scratch
                   *, scale: float, page_size: int, num_kv_heads: int,
                   groups: int, q_tile: int, max_pages: int):
    from jax.experimental import pallas as pl

    r = pl.program_id(0)
    t = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    start = start_ref[r]
    q_len = qlen_ref[r]
    kv_len = start + q_len                 # positions < kv_len are live
    tile_start = start + t * q_tile        # position of the tile's query 0
    n_pages = _tile_pages(start, q_len, t, q_tile, page_size)

    @pl.when(p < n_pages)
    def _compute():
        qg = q_tile * groups
        q = q_ref[...]                                    # [Q, H, D]
        rows = []
        for h in range(num_kv_heads):
            q_sub = q[:, h * groups:(h + 1) * groups, :].reshape(qg, -1)
            k_sub = k_ref[:, h, :]                        # [page, D]
            rows.append(jax.lax.dot_general(
                q_sub, k_sub, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale)
        s = jnp.concatenate(rows, axis=0)                 # [KVH*Q*G, page]
        n_rows = num_kv_heads * qg
        # row index -> query index (row layout: h*(Q*G) + q*G + g)
        q_idx = (jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, page_size), 0) // groups) % q_tile
        q_pos = tile_start + q_idx
        k_pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, page_size), 1)
        # one predicate covers prefix (k_pos < start <= q_pos) and the
        # causal window; k_pos < kv_len additionally hides stale K/V in
        # the tail page for PAD queries whose q_pos exceeds the row
        keep = (k_pos <= q_pos) & (k_pos < kv_len)
        s = jnp.where(keep, s, NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_cur = jnp.max(s, axis=-1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        pexp = jnp.exp(s - m_new)
        pexp = jnp.where(keep, pexp, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_prev * alpha + jnp.sum(pexp, axis=-1)[:, None]
        m_ref[:] = m_new
        pvs = []
        for h in range(num_kv_heads):
            p_sub = pexp[h * qg:(h + 1) * qg, :]          # [Q*G, page]
            v_sub = v_ref[:, h, :]                        # [page, D]
            pvs.append(jax.lax.dot_general(
                p_sub.astype(v_sub.dtype), v_sub, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))      # [Q*G, D]
        pv = jnp.concatenate(pvs, axis=0)                 # [KVH*Q*G, D]
        acc_ref[:] = acc_ref[:] * alpha + pv

    @pl.when(p == max_pages - 1)
    def _finalize():
        qg = q_tile * groups
        l = jnp.maximum(l_ref[:], 1e-30)                  # noqa: E741
        o = acc_ref[:] / l                                # [KVH*Q*G, D]
        for h in range(num_kv_heads):
            blk = o[h * qg:(h + 1) * qg, :].reshape(
                q_tile, groups, -1)
            o_ref[:, h * groups:(h + 1) * groups, :] = blk.astype(
                o_ref.dtype)


# Most query elements (tile * heads * head_dim) one grid step may hold:
# the q and out blocks (double-buffered) and the f32 accumulators all
# scale with it. 64 x 32 x 128 is the widest tile the v5e compiler
# accepts inside its 16 MiB scoped-VMEM limit at pages of 16, 64 and 128
# (tests/test_chip_compile.py holds that); the next power of two is
# refused.
_Q_TILE_ELEMS = 64 * 32 * 128


def _q_tile(q_window: int, heads: int, head_dim: int) -> int:
    """Query rows per grid step: the whole window when it fits the VMEM
    budget (decode, verify windows, small models), else the largest
    multiple of 8 that does."""
    fit = max(8, _Q_TILE_ELEMS // (heads * head_dim) // 8 * 8)
    return q_window if q_window <= fit else fit


def _tile_pages(start, q_len, t, q_tile: int, page_size: int):
    """Live KV pages of query tile ``t`` of one row: those holding keys
    below min(kv_len, end of the tile) — later keys are in the causal
    future of every query of the tile — and none for a tile wholly past
    q_len (padding). Shared by the kernel body and the K/V index map so
    the fetch clamp and the compute skip can never disagree."""
    live = jnp.minimum(start + q_len, start + (t + 1) * q_tile)
    return jnp.where(t * q_tile < q_len,
                     (live + page_size - 1) // page_size, 0)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, starts,
                           q_lens, *, scale: float | None = None,
                           interpret: bool = False):
    """q [R, Q, H, D]; k_pages/v_pages [P, page, KVH, D];
    block_tables [R, max_pages] int32 (physical page per logical page);
    starts [R] int32 (position of each row's first query token);
    q_lens [R] int32 (true query tokens this row, <= Q; 0 = padding row).

    Row r's queries sit at positions ``starts[r] + i`` and attend every
    key position ``<= starts[r] + i`` (paged prefix + causal window); the
    window's OWN K/V must already be scattered into the pages. Query
    positions ``i >= q_lens[r]`` produce garbage outputs the caller
    discards (their compute is bounded by the row's live pages). Returns
    [R, Q, H, D].
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, qw, h, d = q.shape
    _, page_size, kvh, _ = k_pages.shape
    groups = h // kvh
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5
    tq = _q_tile(qw, h, d)
    n_tiles = -(-qw // tq)
    if n_tiles * tq != qw:
        # pad queries sit past q_len: garbage by contract, sliced off below
        q = jnp.pad(q, ((0, 0), (0, n_tiles * tq - qw), (0, 0), (0, 0)))

    kernel = functools.partial(
        _ragged_kernel, scale=scale, page_size=page_size,
        num_kv_heads=kvh, groups=groups, q_tile=tq, max_pages=max_pages)

    def _q_index(ri, t, p, bt, start, qlen):
        return (ri, t, 0, 0)

    def _kv_index(ri, t, p, bt, start, qlen):
        # clamp to the tile's last live page: grid steps beyond the live
        # count re-request the resident block (fetch elided), so HBM
        # traffic tracks true length even when the table tail is stale
        n = _tile_pages(start[ri], qlen[ri], t, tq, page_size)
        return (bt[ri, jnp.minimum(p, jnp.maximum(n - 1, 0))], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(r, n_tiles, max_pages),
        in_specs=[
            pl.BlockSpec((None, tq, h, d), _q_index),
            pl.BlockSpec((None, page_size, kvh, d), _kv_index),
            pl.BlockSpec((None, page_size, kvh, d), _kv_index),
        ],
        out_specs=pl.BlockSpec((None, tq, h, d), _q_index),
        scratch_shapes=[
            pltpu.VMEM((kvh * tq * groups, d), jnp.float32),
            pltpu.VMEM((kvh * tq * groups, 1), jnp.float32),
            pltpu.VMEM((kvh * tq * groups, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(block_tables, starts, q_lens, q, k_pages, v_pages)
    return out[:, :qw] if n_tiles * tq != qw else out


def ragged_decode_attention(q, k_pages, v_pages, block_table, lengths,
                            *, scale: float | None = None,
                            interpret: bool = False):
    """Decode as the q_len=1 degenerate case. Same contract as
    ``ops.paged_attention.paged_decode_attention``: q [B, H, D],
    lengths [B] = tokens in cache INCLUDING the current step's (attend
    positions < length). Returns [B, H, D]."""
    lengths = lengths.astype(jnp.int32)
    out = ragged_paged_attention(
        q[:, None], k_pages, v_pages, block_table,
        starts=jnp.maximum(lengths - 1, 0),
        q_lens=jnp.minimum(lengths, 1),     # length 0 rows = padding
        scale=scale, interpret=interpret)
    return out[:, 0]


def ragged_paged_reference(q, k_pages, v_pages, block_tables, starts,
                           q_lens, scale: float | None = None):
    """Numerical oracle (jnp gather, grouped-GQA einsum — no repeat).
    Same contract as the kernel; masks exactly the kernel's live-key
    predicate, so outputs match at every query position i < q_lens[r]."""
    r, qw, h, d = q.shape
    _, page_size, kvh, _ = k_pages.shape
    groups = h // kvh
    max_pages = block_tables.shape[1]
    klen = max_pages * page_size
    if scale is None:
        scale = d ** -0.5
    k = k_pages[block_tables].reshape(r, klen, kvh, d)
    v = v_pages[block_tables].reshape(r, klen, kvh, d)
    qg = q.reshape(r, qw, kvh, groups, d).astype(jnp.float32)
    s = jnp.einsum("rqhgd,rkhd->rhgqk", qg,
                   k.astype(jnp.float32)) * scale
    k_pos = jnp.arange(klen)
    q_pos = starts[:, None] + jnp.arange(qw)[None, :]
    kv_len = starts + q_lens
    keep = (k_pos[None, None, :] <= q_pos[:, :, None]) & \
        (k_pos[None, None, :] < kv_len[:, None, None])    # [R, Q, K]
    s = jnp.where(keep[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("rhgqk,rkhd->rqhgd", w, v.astype(jnp.float32))
    return out.reshape(r, qw, h, d).astype(q.dtype)
