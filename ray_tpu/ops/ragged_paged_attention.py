"""Ragged paged attention for TPU (Pallas): ONE kernel family for every
paged-KV attention dispatch — prefill chunks, speculative-verify windows,
and decode (the q_len=1 degenerate case).

Reproduces the design of "Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU" (PAPERS.md): each row of a dispatch
is a variable-length query window `[start, start + q_len)` attending over
that row's paged prefix PLUS itself, with the window's own K/V already
scattered into the pages (the engine writes K/V before attention on every
path, so the kernel never needs a separate in-window concat). HBM traffic
is proportional to each row's TRUE length (rounded up to a block), not
the pool capacity:

* grid ``(row, q_tile, kv_block)``. One grid step of the page sweep
  covers a BLOCK of consecutive logical pages, never narrower than 128
  keys (8 pages of 16: a vreg's lanes, the MXU's width — at pages of 16
  a step of one page used 16 of 128 lanes and paid a grid step per 16
  keys: PERF.md §6, PR 25). The per-kv-head body takes the widest block
  its VMEM budget holds (`window_step`: 512 keys under the latent
  prefill tile, 1,024 under the latent decode tile and the GQA prefill
  tiles; never wider than the table needs): ONE ``[rows, block_keys]``
  score tile, one streaming-softmax update and one rescale of the f32
  accumulator for all of a block's keys, where at 128 keys the rescale
  of a ``[1024, 512]`` accumulator — four times the score tile — and the
  softmax's bookkeeping held the latent prefill step at a third of the
  MXU's peak (4.3 us a 128 keys against 1.5; 2,985 us a call where
  6,696: PERF.md §6, PR 32). The all-heads body below is bound by the
  bytes it fetches, and a step costs 0.3-0.5 us beside them whatever it
  carries: it takes a block as wide as a step's bytes pay for — up to 2
  MiB of K and V a step, within a sixteenth of the table (128 keys at
  2,048 lanes of kv heads, 256 at 1,024 under a 256-page table, 1,024 at
  512 under one of 1,024 pages; us a call by width and shape at
  `_ALL_HEADS_STEP_BYTES`: 2,645 -> 1,500 at Qwen3-Next's decode shape,
  280 -> 220 at doc-QA's, PERF.md §6, PR 48). The width comes from
  static shapes alone;
* a pool is ``[P, page, KVH * D]``: one token's kv heads side by side
  on the last axis, head ``h`` in lanes ``[h * D, (h + 1) * D)``. The
  pools stay in HBM (``memory_space=ANY``) and the kernel gathers a
  block's pages itself: the block table is scalar-prefetched, each page
  is one `make_async_copy` (contiguous on both sides) into a
  double-buffered ``[2, N * page, KVH * D]`` VMEM scratch, and a live
  step starts the NEXT block's copies before it waits for its own. (N
  pipelined BlockSpec operands per pool were measured first: their
  bookkeeping costs 1.3 us on every grid step, live or dead, against
  0.07 us for a dead step here.) Every copy started is waited for inside
  the same sweep. In that buffer a kv head's ``[N * page, D]`` keys are
  a static, lane-aligned slice — whole tiles — where a ``[.., KVH, D]``
  buffer would hold one head's keys as one sublane out of each key's
  tile (a strided load a key, twice a head: PERF.md §6, PR 30);
* the query window is TILED over the middle grid axis (`_q_tile`): the
  q/out blocks and the f32 accumulators are per query row, so a tile is
  an independent sweep, and what one grid step keeps in VMEM is bounded
  by ``tile * heads * head_dim`` instead of by the engine's chunk_size;
* a page's logical index is CLAMPED to the tile's last live page
  (`_tile_pages`, the one source for the fetch clamp and the compute
  skip): pages a row doesn't own — the table's stale tail, the sink
  page — and pages wholly in a tile's causal future are neither read
  nor computed (`pl.when` skips a dead block's body: nothing is copied
  and a dead step costs its launch alone). In a partly live block the
  clamp repeats the last live page, at key positions the mask hides —
  and the all-heads body, whose blocks are wide where a key is narrow,
  copies the 128-key groups that hold a live page alone and zeroes the
  values of the rest (`_live_groups`: an idle decode row attends one key
  of the sink, thirty-odd such rows a call);
* a streaming-softmax accumulator in VMEM scratch carries across the
  sweep (TPU grids iterate the last dimension fastest, so scratch
  persists across one tile's sweep);
* causal masking INSIDE the query window: key position ``k_pos`` (from
  the LOGICAL page index) is attended by query position ``q_pos = start
  + i`` iff ``k_pos <= q_pos`` and ``k_pos < kv_len`` — which covers the
  prefix (always attended), the window (causal), a partly live block and
  its clamped duplicates with one predicate. In the per-kv-head body a
  block every key of which every query of the tile attends
  (`_prefix_blocks`: all but the last one or two of a long sweep) takes
  a body WITHOUT the predicate and its two selects — the same bits,
  3-4% of a call (PR 32);
* GQA without any jnp.repeat, in one of two forms chosen by the static
  shape ``Q * G`` (Q the query TILE, G query heads a kv head) alone:

  - ``Q * G > 8`` (prefill and verify tiles; the latent form at every
    window): a static per-kv-head loop.
    Each group of G query heads runs a [Q*G, N*page] MXU tile against
    its kv head's [N*page, D] block, and the softmax state is kept per
    kv head (``[KVH, Q*G, ...]``), so only one head's scores are live at
    a time. A head's score/accumulator row index is ``q * G + g`` —
    query-major — because per-kv-head q slices ``q[:, h*G:(h+1)*G, :]``
    reshape contiguously to [Q*G, D];
  - ``Q * G <= 8`` (decode: 4 rows a kv head at 32/8 heads, 1 at 16/16;
    verify windows of 2 at groups 4, of up to 8 without groups):
    per head the MXU would take a fresh [N*page, D] K and V block as its
    stationary operand for a handful of rows, 2 x KVH times a step. All
    heads go through it together instead: the queries become one
    block-diagonal ``[Q*H, KVH*D]`` operand (row ``q * H + head`` keeps
    its own kv head's D lanes, zeros elsewhere), scores are ONE
    ``[Q*H, KVH*D] x [N*page, KVH*D]^T`` product, PV is ONE ``[Q*H,
    N*page] x [N*page, KVH*D]`` product of which each row keeps its own
    head's D lanes. The extra terms are exact zeros (scores) or are
    dropped by a select (PV): the same mathematics. Measured a live
    step, us (PR 30): 1.48 -> 0.93 at 32/8 heads, 2.59 -> 1.47 at 16/16;
    a VPU form (broadcast-multiply, lane reduce) gave 2.49 and 1.65.
    Verify windows under the line: 1.50 -> 0.98 (window 2, 32/8), 2.60 ->
    1.49 / 1.55 / 1.70 (windows 2 / 5 / 8, 16/16). Forced above it the
    one product still leads at ``Q * H`` = 256 rows and trails at 512
    (PERF.md §6): the line is on the safe side of the crossover.

The pure-jnp oracle (`ragged_paged_reference`) uses the same
grouped-einsum GQA form and is the CPU fallback's numerical contract;
the kernel runs under ``interpret=True`` in tier-1 so parity is asserted
without a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# one masking constant for the whole paged family: the kernel and both
# jnp oracles below must mask identically
NEG_INF = -1e30


# A kv head's score tile of at most this many rows (one f32 sublane
# tile) leaves the MXU all but empty: such tiles — decode, the smallest
# verify windows — take all heads through it in one product. Measured
# on both sides (module docstring): faster or level at every shape at
# or under it.
_ALL_HEADS_ROWS = 8


def _all_heads(q_tile: int, groups: int) -> bool:
    return q_tile * groups <= _ALL_HEADS_ROWS


def _softmax_step(s, keep, m_ref, l_ref, i):
    """Fold one block's scores ``s`` [rows, keys] into the streaming
    softmax state of slot ``i``; returns the block's probabilities and
    the factor the accumulator of slot ``i`` is rescaled by. ``keep``
    None is a block every key of which every row attends: both selects
    are then the identity and are left out (the same bits)."""
    if keep is not None:
        s = jnp.where(keep, s, NEG_INF)
    m_prev, l_prev = m_ref[i], l_ref[i]                   # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
    pexp = jnp.exp(s - m_new)
    if keep is not None:
        pexp = jnp.where(keep, pexp, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[i] = l_prev * alpha + jnp.sum(pexp, axis=-1)[:, None]
    m_ref[i] = m_new
    return pexp, alpha


def _ragged_kernel(bt_ref, start_ref, qlen_ref,       # scalar prefetch
                   q_ref, *refs,
                   scale: float, page_size: int, num_kv_heads: int,
                   groups: int, q_tile: int, block_pages: int,
                   v_width: int | None, window: int | None = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if v_width is None:
        # pools in HBM; output block; block buffers, DMA; softmax state
        (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
         acc_ref, m_ref, l_ref) = refs
    else:
        # the latent form: ONE pool and one block buffer, whose first
        # ``v_width`` lanes are the values — each page is copied once
        k_hbm, o_ref, k_buf, sems, acc_ref, m_ref, l_ref = refs
        v_hbm = v_buf = None

    r = pl.program_id(0)
    t = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    start = start_ref[r]
    q_len = qlen_ref[r]
    kv_len = start + q_len                 # positions < kv_len are live
    tile_start = start + t * q_tile        # position of the tile's query 0
    n_pages = _tile_pages(start, q_len, t, q_tile, page_size)
    block_keys = block_pages * page_size
    if window is None:
        # the sweep starts at the table's first page: grid step = block
        sweep_first, b = 0, step
        # live blocks of this sweep; never more than the grid has steps,
        # so no copy is started that no step waits for
        n_blocks = jnp.minimum((n_pages + block_pages - 1) // block_pages,
                               pl.num_programs(2))
        last_page = jnp.maximum(
            jnp.minimum(n_pages, bt_ref.shape[1]) - 1, 0)
    else:
        # the window form: the sweep starts at the block that holds the
        # first key the tile's first query attends, grid step 0 is that
        # block, and the table is a ring (logical page p in column
        # p % width), so no column bounds a logical index
        sweep_first = _window_first_page(tile_start, window, page_size)
        b0 = sweep_first // block_pages
        b = step + b0
        n_blocks = b0 + jnp.clip(
            (n_pages + block_pages - 1) // block_pages - b0, 0,
            pl.num_programs(2))
        last_page = jnp.maximum(n_pages - 1, sweep_first)
    heads = num_kv_heads * groups
    d = q_ref.shape[-1]
    d_v = d if v_width is None else v_width
    all_heads = v_width is None and _all_heads(q_tile, groups)

    # pages of the narrowest (128-key) block: what one iteration of the
    # copy loops below unrolls
    lane_pages = min(block_pages, _lane_block_keys(page_size) // page_size)
    # the all-heads body copies a partly live block's live groups alone
    trim = all_heads and window is None and block_pages > lane_pages

    def _copies(block, slot, first_page=0):
        """The page copies (one a pool a page) that fill ``lane_pages``
        pages from ``first_page`` on of buffer ``slot`` with logical
        pages [block * block_pages + first_page, ...) of row r, clamped
        to the tile's last live page (and, in the window form, to its
        first): a page the row does not own is never read (the table's
        tail is stale, or the poisoned sink, or a page behind the window
        that was handed back), and the duplicates in a partly live block
        sit at key positions the predicate below masks."""
        out = []
        for j in range(lane_pages):
            page = first_page + j
            logical = jnp.minimum(block * block_pages + page, last_page)
            if window is not None:
                logical = jnp.maximum(logical, sweep_first) \
                    % bt_ref.shape[1]
            phys = bt_ref[r, logical]
            rows = pl.ds(page * page_size, page_size)
            out.append(pltpu.make_async_copy(
                k_hbm.at[phys], k_buf.at[slot, rows], sems.at[0, slot]))
            if v_hbm is not None:
                out.append(pltpu.make_async_copy(
                    v_hbm.at[phys], v_buf.at[slot, rows], sems.at[1, slot]))
        return out

    def _each_copy(block, slot, act):
        """``act`` on every page copy of a block. A block wider than 128
        keys loops over its 128-key groups: unrolled, 64 pages' copies —
        started, prefetched and waited for — tripled the time to trace
        and lower the kernel (1.06 s where 0.34 at doc-QA's prefill
        shape), which every program of the warm-up ladder pays on every
        start (PERF.md §6, PR 32)."""
        def group(g, carry):
            for c in _copies(block, slot, g * lane_pages):
                act(c)
            return carry
        if block_pages == lane_pages:
            group(0, None)
        else:
            jax.lax.fori_loop(0, _live_groups(block), group, None)

    def _live_groups(block):
        """128-key groups of a live block that `_each_copy` copies: all of
        them, but in the all-heads body only those that hold a live page —
        there a row of one key (an idle decode row attends the sink's
        first) would fetch a whole block of duplicates, 2 MiB where 256
        KiB, once a row a call. What the copies leave out of V is zeroed
        (`_zero_dead_values`): probabilities of 0 never meet VMEM that
        nothing wrote; K's dead groups yield scores the predicate
        replaces."""
        if not trim:
            return block_pages // lane_pages
        return jnp.clip(
            (n_pages - block * block_pages + lane_pages - 1) // lane_pages,
            1, block_pages // lane_pages)

    def _zero_dead_values(block, slot):
        """Zero the V rows of the groups `_each_copy` left out of
        ``block``: none in a wholly live block."""
        lane_keys = lane_pages * page_size

        def group(g, carry):
            at = pl.ds(pl.multiple_of(g * lane_keys, lane_keys), lane_keys)
            v_buf[slot, at, :] = jnp.zeros(
                (lane_keys, v_buf.shape[-1]), v_buf.dtype)
            return carry
        jax.lax.fori_loop(_live_groups(block), block_pages // lane_pages,
                          group, None)

    def _keep(rows, rows_per_query):
        """Live-key predicate [rows, block_keys], row = query-major.
        Key positions come from the LOGICAL page index: one predicate
        covers the prefix (k_pos < start <= q_pos), the causal window
        and a partly live block; k_pos < kv_len hides stale K/V of the
        tail page from PAD queries whose q_pos exceeds the row."""
        q_pos = tile_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_keys), 0) // rows_per_query
        k_pos = b * block_keys + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_keys), 1)
        keep = (k_pos <= q_pos) & (k_pos < kv_len)
        if window is not None:
            keep &= q_pos - k_pos < window
        return keep

    @pl.when(b < n_blocks)
    def _compute():
        slot = step % 2

        @pl.when(step == 0)
        def _first():
            _each_copy(b, 0, lambda c: c.start())

        @pl.when(b + 1 < n_blocks)
        def _prefetch():
            _each_copy(b + 1, 1 - slot, lambda c: c.start())

        _each_copy(b, slot, lambda c: c.wait())
        if trim:
            _zero_dead_values(b, slot)
        if all_heads:
            q = q_ref[...]                                # [Q, H, D]
            # every head in one product: row q * H + head of the
            # block-diagonal operand keeps its own kv head's D lanes
            rows = q_tile * heads
            row_head = jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0) % heads // groups
            lane_head = jax.lax.broadcasted_iota(
                jnp.int32, (1, num_kv_heads * d), 1) // d
            wide = jnp.concatenate(
                [q.reshape(rows, d)] * num_kv_heads, axis=1)
            s = jax.lax.dot_general(
                jnp.where(row_head == lane_head, wide, jnp.zeros_like(wide)),
                k_buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            pexp, alpha = _softmax_step(s, _keep(rows, heads),
                                        m_ref, l_ref, 0)
            v_blk = v_buf[slot]                           # [keys, KVH*D]
            pv = jax.lax.dot_general(
                pexp.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [Q*H, KVH*D]
            acc = acc_ref[0] * alpha
            for h in range(num_kv_heads):
                acc += jnp.where(row_head == h,
                                 pv[:, h * d:(h + 1) * d], 0.0)
            acc_ref[0] = acc
            return
        # per kv head: [Q*G, block_keys] scores live at a time, not
        # [KVH*Q*G, block_keys] (2 x 1 MiB of f32 at the prefill tile)
        qg = q_tile * groups

        def _heads(keep):
            for h in range(num_kv_heads):
                lanes = slice(h * d, (h + 1) * d)
                q_sub = q_ref[:, h * groups:(h + 1) * groups, :].reshape(
                    qg, d)
                s = jax.lax.dot_general(
                    q_sub, k_buf[slot, :, lanes], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                pexp, alpha = _softmax_step(s, keep, m_ref, l_ref, h)
                v_blk = (k_buf[slot, :, :d_v] if v_buf is None
                         else v_buf[slot, :, lanes])      # [block_keys, Dv]
                pv = jax.lax.dot_general(
                    pexp.astype(v_blk.dtype), v_blk,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [Q*G, Dv]
                acc_ref[h] = acc_ref[h] * alpha + pv

        # a block every key of which every query of the tile attends
        # needs no predicate — all but the last block or two of a long
        # sweep. Each body reads q from its ref itself: a value loaded
        # above the branch is copied whole before it (1.3 MB at the
        # latent tile: measured slower than never skipping)
        if window is not None:
            # a window's sweep is a handful of blocks whose first and last
            # are masked: one body, half the kernel to compile in each of
            # the ladder's prefill programs (1.7 -> 1.1 s, sandbox, PR 40)
            _heads(_keep(qg, groups))
            return
        in_prefix = b < _prefix_blocks(start, q_len, t, q_tile, block_keys)

        @pl.when(in_prefix)
        def _unmasked():
            _heads(None)

        @pl.when(jnp.logical_not(in_prefix))
        def _masked():
            _heads(_keep(qg, groups))

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        if all_heads:
            o = acc_ref[0] / jnp.maximum(l_ref[0], 1e-30)  # [Q*H, D]
            o_ref[...] = o.reshape(q_tile, heads, d).astype(o_ref.dtype)
            return
        for h in range(num_kv_heads):
            o = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)  # [Q*G, Dv]
            o_ref[:, h * groups:(h + 1) * groups, :] = o.reshape(
                q_tile, groups, d_v).astype(o_ref.dtype)


# Most query elements (tile * heads * head_dim) one grid step may hold:
# the q and out blocks (double-buffered) and the f32 accumulators all
# scale with it. A compile-fit constant, not a tuned one: at 64 x 32 x
# 128 the v5e compiler accepts every shape tests/test_chip_compile.py
# holds (pages of 16 and 128, tables of 4 to 256 pages, 32/8 and the
# tp=4 shard's 8/2 heads) inside its 16 MiB scoped-VMEM limit. Since the
# softmax runs per kv head (PR 25) it also accepts 128 and 256 rows at
# these widths (sandbox compiles, PR 25); widening is left to a PR that
# measures it.
_Q_TILE_ELEMS = 64 * 32 * 128


# The latent form's query tile (rows x heads x lanes one grid step may
# hold): all heads share the one key block, so a tile's rows x heads are
# the rows of ONE product, and a wider tile sweeps a row's pages fewer
# times. Measured at 32 heads x 640 lanes, four 128-row chunks over
# 12,288 cached tokens, us a call: at 128 keys a step tiles of 8 / 16 /
# 32 rows 8,037 / 7,003 / 6,431 (PERF.md §6, PR 31); at 512 keys a step
# 32 rows 2,985, 64 rows 2,788 (PR 32). 64 rows were measured and left:
# 6.6% of the kernel is ~1.5% of the cell's tokens per second, under what
# a run resolves, for twice the compile time, executable and VMEM (30 MiB).
_LATENT_TILE_ELEMS = 32 * 32 * 640


def _q_tile(q_window: int, heads: int, head_dim: int,
            latent: bool = False) -> int:
    """Query rows per grid step: the whole window when it fits the form's
    element budget (decode, verify windows, small models), else the
    largest multiple of 8 that does."""
    elems = _LATENT_TILE_ELEMS if latent else _Q_TILE_ELEMS
    fit = max(8, elems // (heads * head_dim) // 8 * 8)
    return q_window if q_window <= fit else fit


def _tile_pages(start, q_len, t, q_tile: int, page_size: int, xp=jnp):
    """Live KV pages of query tile ``t`` of one row: those holding keys
    below min(kv_len, end of the tile) — later keys are in the causal
    future of every query of the tile — and none for a tile wholly past
    q_len (padding). The one source for the page copies' clamp and for
    the compute skip (in blocks: ceil(pages / block_pages)), so the two
    can never disagree — and, with ``xp=numpy``, for the engine's count
    of the steps a dispatch swept (`live_key_steps`)."""
    live = xp.minimum(start + q_len, start + (t + 1) * q_tile)
    return xp.where(t * q_tile < q_len,
                    (live + page_size - 1) // page_size, 0)


def _window_first_page(tile_start, window: int, page_size: int):
    """First logical page a sweep of the window form reads: the one that
    holds key ``tile_start - window + 1``, the oldest the tile's first
    query attends. Whoever owns the row's pages keeps every page from
    this one on (llm/paged_engine.py hands back the ones before it)."""
    return jnp.maximum(tile_start - window + 1, 0) // page_size


def window_sweep_steps(q_tile: int, window: int, block_keys: int) -> int:
    """Grid steps a window-form sweep needs at most: the blocks that keys
    ``[first query - window + 1, last query]`` of one tile can touch."""
    return (window + q_tile - 2) // block_keys + 2


def window_table_pages(window: int, page_size: int, write_tokens: int) -> int:
    """Width of a window layer's block table, a RING (logical page ``p``
    in column ``p % width``): the pages of one window behind the oldest
    query of a dispatch, of the ``write_tokens`` the dispatch may write
    past it, and one more for the two ends falling inside pages. A column
    is then reused only by a page a whole window behind every query."""
    return -(-(window + write_tokens) // page_size) + 1


def _prefix_blocks(start, q_len, t, q_tile: int, block_keys: int, xp=jnp):
    """Leading blocks of tile ``t``'s sweep that need no predicate: every
    key of such a block lies at or below the tile's first query and below
    kv_len, so every row of the tile attends all of it, it holds no
    clamped duplicate, and `_keep` would be all true there."""
    return xp.minimum(start + t * q_tile + 1, start + q_len) // block_keys


def live_key_steps(starts, q_lens, q_window: int, table_pages: int, *,
                   q_tile: int, block_keys: int, page_size: int):
    """(live grid steps, those of them that carry the predicate) of ONE
    call's sweeps over rows ``[starts[r], starts[r] + q_lens[r])`` — what
    either body computes (the all-heads body carries the predicate on
    every step, whatever the second count says), counted on the host with
    the kernel's own `_tile_pages` and `_prefix_blocks`. A live step
    scores a whole block: steps x the block's pages are the pages a call
    sweeps (the engine's ``decode_swept_pages``)."""
    starts = np.asarray(starts, np.int64)[:, None]
    q_lens = np.asarray(q_lens, np.int64)[:, None]
    t = np.arange(-(-q_window // q_tile))[None, :]
    block_pages = max(1, block_keys // page_size)
    blocks = np.minimum(
        -(-_tile_pages(starts, q_lens, t, q_tile, page_size, np)
          // block_pages), -(-table_pages // block_pages))
    plain = np.minimum(
        _prefix_blocks(starts, q_lens, t, q_tile, block_keys, np), blocks)
    return int(blocks.sum()), int((blocks - plain).sum())


# The per-kv-head body's key block is the widest, doubling from 128 keys,
# that (1) fits `_VMEM_BUDGET` by `_step_vmem_bytes` — what the call states
# to the compiler as its ``vmem_limit_bytes``, whose default scope is 16
# MiB of a v5e core's 128 MiB; (2) keeps one kv head's score tile within
# `_SCORE_TILE_ELEMS` — at 512K scores a step is ~1.2 GFLOP at the latent
# widths and its fixed costs are spent: wider only rounds every sweep up
# further; (3) is at most `_MAX_BLOCK_KEYS`. All three by measurement,
# kernel alone, us a call (PERF.md §6, PR 32): the latent prefill tile (32
# rows x 32 heads, a 12,288-token cache: compute-bound) 6,696 at 128 keys,
# 3,340 / 3,092 / 3,332 at 256 / 512 / 1,024; the latent decode tile 1,958
# -> 845 / 724 / 722 at 512 / 1,024 / 2,048; the doc-QA prefill tile (256
# rows a kv head, a third of its time the pages' bytes: more copies in
# flight pay) 237 -> 123 / 101 / 131 at 512 / 1,024 / 2,048. Scopes of 20
# to 100 MiB ran the same widths alike.
_VMEM_BUDGET = 32 * 2 ** 20
_SCORE_TILE_ELEMS = 512 * 1024
_MAX_BLOCK_KEYS = 1024

# The all-heads body's step is bound by the K and V bytes it fetches, and a
# step costs 0.3-0.5 us beside them whatever it fetches (the grid step, the
# copies' start and wait, the block-diagonal operand, one rescale, the
# predicate), a dead one 0.07: its key block doubles from 128 keys to the
# widest that fetches at most `_ALL_HEADS_STEP_BYTES` a step and is at most
# a sixteenth of the table — a row's sweep waits for its first block with
# nothing to overlap it and rounds its last one up, a block and a half
# whatever the row holds, so the blocks of a short table stay narrow (a
# step's bytes alone cannot tell OLMoE's rows under 128 pages, best at 1
# MiB a step, from the two 512-lane shapes under 1,024, best at 2 MiB).
# Inside the compiler's own VMEM scope (no ``vmem_limit_bytes`` is stated
# for this body: two slots a pool of a 2 MiB step are 4 MiB). Measured,
# kernel alone, one v5e, us a decode call at 128 / 256 / 512 / 1,024 keys a
# step (PERF.md §6, PR 48), by heads (lanes of K), live rows x context and
# the table's pages:
#   32/8 x 128 (1,024)   8 of 32 x 2-4k, 256     280 / 220 / 210 / 231
#                        12 of 32 x 2-4k, 256    359 / 289 / 289 / 315
#                        8 of 32 x 4-8k, 512     471 / 349 / 325 / 334
#   16/2 x 256 (512)     32 of 64 x 8-16k, 1,024 2,645 / 2,074 / 1,679 / 1,500
#                        32 of 64 x 2-6k, 1,024  1,282 / 913 / 705 / 612
#   32/4 x 128 (512)     24 of 32 x 8-15k, 1,024 1,920 / 1,493 / 1,220 / 1,110
#                        24 of 32 x 4-8k, 512    1,010 / 787 / 658 / 608
#   16/16 x 128 (2,048)  48 of 64 x 0.2-1.7k, 128  661 / 681 / 764 / 961
#                        64 of 64 x 1-2k, 128    1,214 / 1,270 / 1,402 / 1,681
#   8/2 x 128 (256: the tp=4 shard) 8 of 32 x 2-4k, 256  217 / 171 / 146 / 141
# Building the block-diagonal operand once a row into scratch was measured
# beside each of these and is within 3% either way: it is built a step.
# As the engine runs a call, its idle rows attend one key of the sink: with
# those rows in it and a partly live block's copies trimmed to its live
# 128-key groups (`_live_groups`), doc-QA's first line reads 322 / 266 / 263
# / 289 (untrimmed 324 / 285 / 325 / 436), Qwen3-Next's 2,690 / 2,181 /
# 1,755 / 1,564 (2,701 / 2,146 / 1,779 / 1,685), Mellum's 1,938 / 1,548 /
# 1,255 / 1,128, OLMoE's 696 / 694 / 727: a row's first fetch, ~1.9 us
# that nothing overlaps, live row or idle, is what is left.
_ALL_HEADS_STEP_BYTES = 2 * 2 ** 20
_ALL_HEADS_TABLE_BLOCKS = 16
_DEFAULT_VMEM_SCOPE = 16 * 2 ** 20


def _lane_block_keys(page_size: int) -> int:
    """Keys of the narrowest block: as many pages as make the key axis 128
    wide (a vreg's lanes, the MXU's width), one where a page already is."""
    return max(1, 128 // page_size) * page_size


def _step_vmem_bytes(q_tile: int, heads: int, kv_heads: int, d: int,
                     d_v: int, pools: int, block_keys: int,
                     itemsize: int, all_heads: bool = False) -> int:
    """VMEM one grid step holds at a block of ``block_keys``: the q and
    out blocks (double-buffered by the pipeline), the f32 accumulators and
    softmax state (m and l lie over 128 lanes each), two slots a pool of
    the block buffer, the score tile as f32 scores, f32 probabilities and
    their cast, and the PV product. The per-kv-head body keeps a slot of
    state a kv head, scores one kv head at a time and holds one more copy
    of a block where a kv head is a lane slice of it, not all of it; the
    all-heads body keeps one slot for all ``q_tile * heads`` rows, and its
    PV product and its block-diagonal query operand are as wide as a
    block's lanes. Within 2 MiB under to 6 over what the v5e compiler asks
    for at the cells' shapes (sandbox compiles, PR 32)."""
    slots, lanes = (1, kv_heads * d) if all_heads else (kv_heads, d_v)
    rows = q_tile * heads // slots
    blocks = 2 * q_tile * heads * (d + d_v) * itemsize
    state = slots * rows * (d_v + 2 * 128) * 4
    buffers = (pools * (2 + (slots > 1)) * block_keys * kv_heads * d
               * itemsize)
    scores = rows * block_keys * (4 + 4 + itemsize) + rows * lanes * 4
    wide = all_heads * rows * lanes * itemsize
    return blocks + state + buffers + scores + wide


def window_step(q_window: int, heads: int, kv_heads: int, d: int, *,
                page_size: int, table_pages: int, itemsize: int,
                v_width: int | None = None,
                window: int | None = None) -> dict:
    """{'q_tile', 'block_keys'} of the call the family makes at these
    static shapes (``v_width`` set: the latent form, one kv head as wide
    as q) — for the wrappers below, and for whoever counts the steps such
    a call sweeps or the pages it copies (`live_key_steps`; a model
    module's ``attn_step``). Both bodies double the block from 128 keys,
    never past `_MAX_BLOCK_KEYS`, the VMEM the body holds
    (`_step_vmem_bytes`) or a block none of whose halves covers the whole
    table: a 512-key block over a 16-page table would copy and score what
    no row holds. The per-kv-head body takes the widest such block that
    keeps its score tile within `_SCORE_TILE_ELEMS`; the all-heads body
    stops where a step's bytes pay for it (`_ALL_HEADS_STEP_BYTES`, within
    a sixteenth of the table): 128 keys at OLMoE's 2,048 lanes, 256 at
    doc-QA's 1,024 under its 256-page table, 1,024 at Mellum's and
    Qwen3-Next's 512 under theirs of 1,024 pages. The window form's sweep
    starts and ends inside blocks, so a block is at most a quarter of the
    window: wider ones score more masked keys than they save steps — and
    under the all-heads body (a sliding layer's decode: nine steps, the
    first and last partly masked) it keeps the 128-key step, not measured
    at another."""
    latent = v_width is not None
    q_tile = _q_tile(q_window, heads, d, latent)
    keys = _lane_block_keys(page_size)
    all_heads = not latent and _all_heads(q_tile, heads // kv_heads)
    if all_heads and window is not None:
        return dict(q_tile=q_tile, block_keys=keys)
    d_v, pools = (v_width, 1) if latent else (d, 2)

    def pays(keys: int) -> bool:
        """Is a block of ``keys`` worth its step, for all the body
        knows?"""
        if all_heads:
            return (pools * kv_heads * d * itemsize * keys
                    <= _ALL_HEADS_STEP_BYTES
                    and _ALL_HEADS_TABLE_BLOCKS * keys
                    <= table_pages * page_size)
        return q_tile * heads // kv_heads * keys <= _SCORE_TILE_ELEMS

    while (2 * keys <= _MAX_BLOCK_KEYS
           and (window is None or 2 * keys <= max(window // 4, keys))
           and keys < table_pages * page_size
           and pays(2 * keys)
           and _step_vmem_bytes(q_tile, heads, kv_heads, d, d_v, pools,
                                2 * keys, itemsize, all_heads)
           <= (_DEFAULT_VMEM_SCOPE if all_heads else _VMEM_BUDGET)):
        keys *= 2
    return dict(q_tile=q_tile, block_keys=keys)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, starts,
                           q_lens, *, scale: float | None = None,
                           interpret: bool = False,
                           window: int | None = None):
    """q [R, Q, H, D]; k_pages/v_pages [P, page, KVH * D] (head ``h`` in
    lanes ``[h * D, (h + 1) * D)`` of the last axis);
    block_tables [R, max_pages] int32 (physical page per logical page);
    starts [R] int32 (position of each row's first query token);
    q_lens [R] int32 (true query tokens this row, <= Q; 0 = padding row).

    Row r's queries sit at positions ``starts[r] + i`` and attend every
    key position ``<= starts[r] + i`` (paged prefix + causal window); the
    window's OWN K/V must already be scattered into the pages. Query
    positions ``i >= q_lens[r]`` produce garbage outputs the caller
    discards (their compute is bounded by the row's live pages). Returns
    [R, Q, H, D].

    ``window`` (the window form, a sliding-window layer): a query also
    attends only keys less than ``window`` positions behind it, the sweep
    starts at the page of the oldest such key (`_window_first_page`), and
    ``block_tables`` is a ring at least `window_table_pages` wide: logical
    page ``p`` sits in column ``p % width``, and pages before the sweep's
    first are never read, so their columns may hold anything.
    """
    _, qw, h, d = q.shape
    _, page_size, kv_lanes = k_pages.shape
    step = window_step(
        qw, h, kv_lanes // d, d, page_size=page_size,
        table_pages=block_tables.shape[1],
        itemsize=k_pages.dtype.itemsize, window=window)
    if window is not None and block_tables.shape[1] < window_table_pages(
            window, page_size, qw):
        raise ValueError(
            f"a window of {window} keys under {qw} queries needs a ring of "
            f"{window_table_pages(window, page_size, qw)} pages of "
            f"{page_size} or more; the table has {block_tables.shape[1]}")
    return _ragged_call(
        q, k_pages, v_pages, block_tables, starts, q_lens,
        scale=float(d ** -0.5 if scale is None else scale),
        interpret=interpret, window=window, **step)


# Jitted so that a program traces and lowers the kernel body once per
# shape, not once per layer: models/llama.py unrolls its layers in
# Python, and 20 lowerings of this body are 3.7-5.6 s of every program's
# set-up where one shared function is 0.1-0.2 s (sandbox, PR 25).
@functools.partial(jax.jit, static_argnames=("scale", "q_tile", "block_keys",
                                             "interpret", "v_width",
                                             "window"))
def _ragged_call(q, k_pages, v_pages, block_tables, starts, q_lens, *,
                 scale: float, q_tile: int, block_keys: int,
                 interpret: bool, v_width: int | None = None,
                 window: int | None = None):
    """``v_pages`` None is the latent form (ragged_latent_attention): one
    pool, one shared kv head as wide as q, values its first ``v_width``
    lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, qw, h, d = q.shape
    _, page_size, kv_lanes = k_pages.shape
    latent = v_pages is None
    d_v = v_width if latent else d
    kvh = kv_lanes // d
    groups = h // kvh
    nb = max(1, block_keys // page_size)
    n_tiles = -(-qw // q_tile)
    padded = n_tiles * q_tile
    if padded != qw:
        # pad queries sit past q_len: garbage by contract, sliced off below
        q = jnp.pad(q, ((0, 0), (0, padded - qw), (0, 0), (0, 0)))

    kernel = functools.partial(
        _ragged_kernel, scale=scale, page_size=page_size,
        num_kv_heads=kvh, groups=groups, q_tile=q_tile, block_pages=nb,
        v_width=v_width if latent else None, window=window)

    def _q_index(ri, t, b, bt, start, qlen):
        return (ri, t, 0, 0)

    pool_in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    block_buf = pltpu.VMEM((2, nb * page_size, kv_lanes), k_pages.dtype)
    pools = (k_pages,) if latent else (k_pages, v_pages)
    # softmax state: a slot a kv head, or one slot for all heads
    all_heads = not latent and _all_heads(q_tile, groups)
    slots, rows = (1, q_tile * h) if all_heads else (kvh, q_tile * groups)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # the window form sweeps one window's blocks, however wide its
        # ring is
        grid=(r, n_tiles, -(-block_tables.shape[1] // nb) if window is None
              else window_sweep_steps(q_tile, window, nb * page_size)),
        in_specs=[pl.BlockSpec((None, q_tile, h, d), _q_index)]
        + [pool_in_hbm] * len(pools),
        out_specs=pl.BlockSpec((None, q_tile, h, d_v), _q_index),
        scratch_shapes=[block_buf] * len(pools) + [  # two slots a pool
            pltpu.SemaphoreType.DMA((len(pools), 2)),   # [pool, slot]
            pltpu.VMEM((slots, rows, d_v), jnp.float32),
            pltpu.VMEM((slots, rows, 1), jnp.float32),
            pltpu.VMEM((slots, rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, padded, h, d_v), q.dtype),
        interpret=interpret,
        # the per-kv-head body's blocks are sized against this scope, the
        # all-heads body's against the compiler's own (window_step)
        compiler_params=None if all_heads else pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_BUDGET),
        # the trace reduction tells the family by this prefix and its two
        # shapes by the output's window (benchmarks/reduce/)
        name="ragged_paged_attention" + (
            "_latent" if latent else "" if window is None else "_window"),
    )(block_tables, starts, q_lens, q, *pools)
    return out[:, :qw] if padded != qw else out


def ragged_latent_attention(q, pages, block_tables, starts, q_lens, *,
                            v_width: int, scale: float,
                            interpret: bool = False):
    """The family's latent (MLA, absorbed) form: ONE pool
    ``pages [P, page, W]`` holds, per token, one shared kv head whose KEY
    is all ``W`` lanes and whose VALUE is the key's first ``v_width``
    lanes (the compressed latent; the rope key rides behind it).
    q [R, Q, H, W] — every query head attends that one head — and the
    result is [R, Q, H, v_width]. Rows, windows, the page sweep, the
    clamp, the dead-step skip and the softmax step are
    ragged_paged_attention's own (same contract for block_tables, starts,
    q_lens); each page is copied into VMEM once and serves both products.
    ``scale`` is the model's (the uncompressed head size's, not W's)."""
    _, qw, h, d = q.shape
    return _ragged_call(
        q, pages, None, block_tables, starts, q_lens, scale=float(scale),
        interpret=interpret, v_width=int(v_width), **window_step(
            qw, h, 1, d, page_size=pages.shape[1],
            table_pages=block_tables.shape[1],
            itemsize=pages.dtype.itemsize, v_width=int(v_width)))


def ragged_decode_attention(q, k_pages, v_pages, block_table, lengths,
                            *, scale: float | None = None,
                            interpret: bool = False,
                            window: int | None = None):
    """Decode as the q_len=1 degenerate case: q [B, H, D], k_pages /
    v_pages [num_pages, page_size, KVH * D], block_table [B, max_pages],
    lengths [B] = tokens in cache INCLUDING the current step's (attend
    positions < length). Returns [B, H, D]."""
    lengths = lengths.astype(jnp.int32)
    out = ragged_paged_attention(
        q[:, None], k_pages, v_pages, block_table,
        starts=jnp.maximum(lengths - 1, 0),
        q_lens=jnp.minimum(lengths, 1),     # length 0 rows = padding
        scale=scale, interpret=interpret, window=window)
    return out[:, 0]


def _ring_key_positions(kv_len, table_pages: int, page_size: int):
    """[R, table_pages * page] key position each lane of a gathered RING
    table holds (logical page ``p`` in column ``p % table_pages``): the
    newest page congruent to the column at or below the row's last live
    one; negative where no page has reached the column yet."""
    last = (jnp.maximum(kv_len, 1) - 1) // page_size          # [R]
    col = jnp.arange(table_pages)[None, :]
    logical = last[:, None] - (last[:, None] - col) % table_pages
    return (logical[:, :, None] * page_size
            + jnp.arange(page_size)[None, None, :]).reshape(
                kv_len.shape[0], -1)


def ragged_paged_reference(q, k_pages, v_pages, block_tables, starts,
                           q_lens, scale: float | None = None,
                           window: int | None = None):
    """Numerical oracle (jnp gather, grouped-GQA einsum — no repeat).
    Same contract as the kernel; masks exactly the kernel's live-key
    predicate, so outputs match at every query position i < q_lens[r]."""
    r, qw, h, d = q.shape
    _, page_size, kv_lanes = k_pages.shape
    kvh = kv_lanes // d
    groups = h // kvh
    max_pages = block_tables.shape[1]
    klen = max_pages * page_size
    if scale is None:
        scale = d ** -0.5
    k = k_pages[block_tables].reshape(r, klen, kvh, d)
    v = v_pages[block_tables].reshape(r, klen, kvh, -1)   # [.., Dv]
    qg = q.reshape(r, qw, kvh, groups, d).astype(jnp.float32)
    s = jnp.einsum("rqhgd,rkhd->rhgqk", qg,
                   k.astype(jnp.float32)) * scale
    q_pos = starts[:, None] + jnp.arange(qw)[None, :]
    kv_len = starts + q_lens
    k_pos = (jnp.arange(klen)[None] if window is None else
             _ring_key_positions(kv_len, max_pages, page_size))[:, None, :]
    keep = (k_pos <= q_pos[:, :, None]) & \
        (k_pos < kv_len[:, None, None])                   # [R, Q, K]
    if window is not None:
        keep &= (k_pos >= 0) & (q_pos[:, :, None] - k_pos < window)
    s = jnp.where(keep[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("rhgqk,rkhd->rqhgd", w, v.astype(jnp.float32))
    return out.reshape(r, qw, h, -1).astype(q.dtype)


def ragged_latent_reference(q, pages, block_tables, starts, q_lens, *,
                            v_width: int, scale: float):
    """ragged_latent_attention's oracle and CPU fallback: the window
    oracle over one kv head whose values are the keys' first ``v_width``
    lanes."""
    return ragged_paged_reference(q, pages, pages[..., :v_width],
                                  block_tables, starts, q_lens, scale)


def paged_decode_reference(q, k_pages, v_pages, block_table, lengths,
                           scale: float | None = None,
                           window: int | None = None):
    """Numerical oracle of the decode shape (jnp gather) and
    llama.decode_paged's fallback off the TPU. Same contract as
    ragged_decode_attention.

    GQA runs as a grouped einsum against the ungathered-head K/V
    (q reshaped [B, KVH, G, D]) — the head axes line up by construction
    (query head h attends kv head h // G), so no O(groups) jnp.repeat
    materialization of the gathered cache is ever built."""
    b, h, d = q.shape
    _, page_size, kv_lanes = k_pages.shape
    kvh = kv_lanes // d
    groups = h // kvh
    max_pages = block_table.shape[1]
    if scale is None:
        scale = d ** -0.5
    # gather each sequence's pages, split the lanes -> [B, keys, KVH, D]
    k = k_pages[block_table].reshape(b, max_pages * page_size, kvh, d)
    v = v_pages[block_table].reshape(b, max_pages * page_size, kvh, d)
    qg = q.reshape(b, kvh, groups, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg,
                   k.astype(jnp.float32)) * scale
    if window is None:
        pos = jnp.arange(max_pages * page_size)[None, :]
        keep = pos < lengths[:, None]
    else:
        pos = _ring_key_positions(lengths, max_pages, page_size)
        keep = (pos >= 0) & (pos < lengths[:, None]) & \
            (lengths[:, None] - 1 - pos < window)
    s = jnp.where(keep[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgk,bkhd->bhgd", w,
                      v.astype(jnp.float32)).reshape(b, h, d).astype(q.dtype)
