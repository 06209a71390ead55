"""Flash attention for TPU: Pallas forward kernel + memory-efficient VJP.

The reference framework has no attention kernels (attention lives in vLLM /
torch, which it only orchestrates — SURVEY.md §2.4); on TPU the kernel is
ours. Design:

* Forward: a Pallas kernel tiled (block_q × block_k) over the MXU, with the
  standard streaming-softmax accumulator in VMEM scratch carried across the
  k-block grid dimension (TPU grids iterate sequentially, last dim fastest,
  so scratch persists across the k sweep of one q block). Emits the
  log-sum-exp residual for the backward pass and for ring-attention
  composition (parallel.ring).
* Backward: two Pallas kernels (a dq sweep and a dkv sweep) with f32 VMEM
  accumulators, GQA gathered via BlockSpec index maps (no repeat). A jnp
  blockwise-recompute fallback (chunked `lax.scan`, O(S) memory) covers
  non-TPU backends.
* CPU / debugging: `mha_reference` (the numerical oracle) is used when not
  on TPU; the Pallas path also runs under `interpret=True` in tests.

Layout convention: [batch, seq, heads, head_dim] (models/ convention), with
grouped-query attention supported via num_kv_heads <= num_heads.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Reference (numerical oracle; CPU path)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal: bool = False,
                  scale: Optional[float] = None,
                  segment_ids=None) -> jax.Array:
    """Plain softmax attention. q [B,Sq,H,D], k/v [B,Sk,KVH,D]; KVH may
    divide H (GQA). Returns [B,Sq,H,D]."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    groups = q.shape[2] // k.shape[2]
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    if segment_ids is not None:
        q_seg, kv_seg = segment_ids
        seg = q_seg[:, :, None] == kv_seg[:, None, :]
        s = jnp.where(seg[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref,          # blocks
                o_ref, lse_ref,               # outputs
                acc_ref, m_ref, l_ref,        # VMEM scratch (carried over k)
                *, causal: bool, scale: float, block_q: int, block_k: int,
                num_k_blocks: int, kv_valid: int = 0):
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # kv_valid > 0: sequences were padded to the block grid; padded k
    # columns must not contribute (static mask — kv_valid is a trace-time
    # constant)
    pad_mask = kv_valid > 0  # static: pad columns exist in SOME block

    def _compute():
        q = q_ref[:, :]                                        # [BQ, D]
        k = k_ref[:, :]                                        # [BK, D]
        v = v_ref[:, :]                                        # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [BQ, BK]
        keep = None
        if causal or pad_mask:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = (q_pos >= k_pos) if causal else None
            if pad_mask:
                inb = k_pos < kv_valid
                keep = inb if keep is None else (keep & inb)
            s = jnp.where(keep, s, NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_cur = jnp.max(s, axis=-1)[:, None]                   # [BQ, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                 # [BQ, BK]
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                        # [BQ, 1]
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1)[:, None]
        m_ref[:] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [BQ, D]
        acc_ref[:] = acc_ref[:] * alpha + pv

    if causal:
        # Skip fully-masked tiles: block contributes iff any q_pos >= k_pos,
        # i.e. the block's last q row sees the block's first k column.
        @pl.when((iq + 1) * block_q - 1 >= ik * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)                       # noqa: E741
        o_ref[:, :] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[:] = m_ref[:] + jnp.log(l)                     # [BQ, 1]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad_to_blocks(q, k, v, block_q: int, block_k: int):
    """Zero-pad seq dims to the kernel's block grid (q rows to 8-aligned
    q blocks, k columns to 128-aligned k blocks — the TPU tile shapes the
    s = q @ k.T [BQ, BK] intermediate needs). Padded k columns are masked
    in the kernels via kv_valid; padded q rows are sliced off after."""
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    block_q = min(block_q, _round_up(sq, 8))
    block_k = min(block_k, _round_up(sk, 128))
    sq_pad = _round_up(sq, block_q)
    sk_pad = _round_up(sk, block_k)
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0), (0, 0)))
    if sk_pad != sk:
        pad = ((0, 0), (0, sk_pad - sk), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    return q, k, v, block_q, block_k, sq_pad, sk_pad


def _flash_fwd(q, k, v, causal: bool, scale: float,
               block_q: int, block_k: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    groups = h // kvh
    q, k, v, block_q, block_k, sq_pad, sk_pad = _pad_to_blocks(
        q, k, v, block_q, block_k)
    nq, nk = sq_pad // block_q, sk_pad // block_k

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, num_k_blocks=nk,
        kv_valid=sk if sk_pad != sk else 0)

    # Kernel layout is [B, H, S, D] with batch/head block dims squeezed
    # (None), so every ref is 2-D and the (8, 128)-tiling constraint falls
    # on (seq_block, head_dim) where it belongs.
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    grid = (b, h, nq, nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, iq, ik: (bi, hi // groups, ik, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, iq, ik: (bi, hi // groups, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
            # trailing unit dim keeps the (8, 128)-tiling rule satisfied
            # (last block dim == array dim); squeezed on return
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qt, kt, vt)
    out = jnp.swapaxes(out, 1, 2)
    lse = lse[..., 0]
    if sq_pad != sq:
        out = out[:, :sq]
        lse = lse[:, :, :sq]
    return out, lse


# ---------------------------------------------------------------------------
# Pallas backward kernels (dq sweep + dkv sweep)
# ---------------------------------------------------------------------------
#
# Standard flash-attention backward split into two MXU-friendly passes:
#   dq kernel : grid (B, H, nq, nk) — k-sweep innermost, dq accumulator in
#               VMEM scratch carried across the k blocks of one q block.
#   dkv kernel: grid (B, H, nk, nq) — q-sweep innermost, dk/dv accumulators
#               carried across the q blocks of one k block.
# GQA: k/v blocks are gathered per q-head via the BlockSpec index map
# (hi // groups) — no materialized repeat. dk/dv come out per q-head
# [B, Sk, H, D] and are group-summed to [B, Sk, KVH, D] by XLA (cheap,
# fused elementwise reduction).
# delta = rowsum(dO · O) is precomputed outside (bandwidth-bound, fuses).


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc_ref,
                   *, causal: bool, scale: float, block_q: int, block_k: int,
                   num_k_blocks: int, kv_valid: int = 0):
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    pad_mask = kv_valid > 0  # static: pad columns exist in SOME block

    def _compute():
        q = q_ref[:, :]                                        # [BQ, D]
        k = k_ref[:, :]                                        # [BK, D]
        v = v_ref[:, :]                                        # [BK, D]
        do = do_ref[:, :]                                      # [BQ, D]
        lse = lse_ref[:, :]                                    # [BQ, 1]
        delta = delta_ref[:, :]                                # [BQ, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [BQ, BK]
        if causal or pad_mask:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = (q_pos >= k_pos) if causal else None
            if pad_mask:
                inb = k_pos < kv_valid
                keep = inb if keep is None else (keep & inb)
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - lse)                                   # [BQ, BK]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [BQ, BK]
        ds = p * (dp - delta) * scale
        dq_acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [BQ, D]

    if causal:
        @pl.when((iq + 1) * block_q - 1 >= ik * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        dq_ref[:, :] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, causal: bool, scale: float, block_q: int, block_k: int,
                    num_q_blocks: int, kv_valid: int = 0):
    from jax.experimental import pallas as pl

    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    pad_mask = kv_valid > 0  # static: pad columns exist in SOME block

    def _compute():
        q = q_ref[:, :]                                        # [BQ, D]
        k = k_ref[:, :]                                        # [BK, D]
        v = v_ref[:, :]                                        # [BK, D]
        do = do_ref[:, :]                                      # [BQ, D]
        lse = lse_ref[:, :]                                    # [BQ, 1]
        delta = delta_ref[:, :]                                # [BQ, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [BQ, BK]
        if causal or pad_mask:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = (q_pos >= k_pos) if causal else None
            if pad_mask:
                inb = k_pos < kv_valid
                keep = inb if keep is None else (keep & inb)
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - lse)                                   # [BQ, BK]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [BQ, BK]
        ds = (p * (dp - delta) * scale).astype(q.dtype)        # [BQ, BK]
        # dk += ds^T @ q ; dv += p^T @ dO   (contract over the q dim)
        dk_acc_ref[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [BK, D]
        dv_acc_ref[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [BK, D]

    if causal:
        @pl.when((iq + 1) * block_q - 1 >= ik * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(iq == num_q_blocks - 1)
    def _finalize():
        dk_ref[:, :] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[:, :] = dv_acc_ref[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, *, causal: bool, scale: float,
               block_q: int, block_k: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    groups = h // kvh
    q, k, v, block_q, block_k, sq_pad, sk_pad = _pad_to_blocks(
        q, k, v, block_q, block_k)
    kv_valid = sk if sk_pad != sk else 0
    if sq_pad != sq:
        # padded q rows: zero grads; lse pad value is irrelevant (their
        # p rows multiply a zero dO) but must be finite
        pad_rows = ((0, 0), (0, sq_pad - sq), (0, 0), (0, 0))
        out = jnp.pad(out, pad_rows)
        g = jnp.pad(g, pad_rows)
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, sq_pad - sq)))
    nq, nk = sq_pad // block_q, sk_pad // block_k

    qt = jnp.swapaxes(q, 1, 2)                                 # [B,H,Sq,D]
    kt = jnp.swapaxes(k, 1, 2)                                 # [B,KVH,Sk,D]
    vt = jnp.swapaxes(v, 1, 2)
    gt = jnp.swapaxes(g, 1, 2)                                 # [B,H,Sq,D]
    delta = jnp.sum(gt.astype(jnp.float32)
                    * jnp.swapaxes(out, 1, 2).astype(jnp.float32),
                    axis=-1, keepdims=True)                    # [B,H,Sq,1]
    lse4 = lse[..., None]                                      # [B,H,Sq,1]

    q_spec = pl.BlockSpec((None, None, block_q, d),
                          lambda bi, hi, iq, ik: (bi, hi, iq, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, d),
                           lambda bi, hi, iq, ik: (bi, hi // groups, ik, 0))
    row_spec = pl.BlockSpec((None, None, block_q, 1),
                            lambda bi, hi, iq, ik: (bi, hi, iq, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          kv_valid=kv_valid),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qt, kt, vt, gt, lse4, delta)[0]

    # dkv sweep: q innermost. Note the index maps take (bi, hi, ik, iq).
    q_spec2 = pl.BlockSpec((None, None, block_q, d),
                           lambda bi, hi, ik, iq: (bi, hi, iq, 0))
    kv_spec2 = pl.BlockSpec((None, None, block_k, d),
                            lambda bi, hi, ik, iq: (bi, hi // groups, ik, 0))
    row_spec2 = pl.BlockSpec((None, None, block_q, 1),
                             lambda bi, hi, ik, iq: (bi, hi, iq, 0))
    dkv_out_spec = pl.BlockSpec((None, None, block_k, d),
                                lambda bi, hi, ik, iq: (bi, hi, ik, 0))

    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          kv_valid=kv_valid),
        grid=(b, h, nk, nq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[dkv_out_spec, dkv_out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk_pad, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qt, kt, vt, gt, lse4, delta)

    dq = jnp.swapaxes(dq, 1, 2)                                # [B,Sq,H,D]
    dk_h = jnp.swapaxes(dk_h, 1, 2)                            # [B,Sk,H,D]
    dv_h = jnp.swapaxes(dv_h, 1, 2)
    if sq_pad != sq:
        dq = dq[:, :sq]
    if sk_pad != sk:
        dk_h = dk_h[:, :sk]
        dv_h = dv_h[:, :sk]
    if groups > 1:
        dk = dk_h.reshape(b, sk, kvh, groups, d).sum(axis=3)
        dv = dv_h.reshape(b, sk, kvh, groups, d).sum(axis=3)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Memory-efficient backward (blockwise recompute, jnp — CPU fallback)
# ---------------------------------------------------------------------------

def _bwd_blockwise(res, g, *, causal, scale, block_k):
    """Recompute attention k-block by k-block; O(Sq·block_k) live memory."""
    q, k, v, out, lse = res
    groups = q.shape[2] // k.shape[2]
    kr = jnp.repeat(k, groups, axis=2) if groups > 1 else k
    vr = jnp.repeat(v, groups, axis=2) if groups > 1 else v

    b, sq, h, d = q.shape
    sk = kr.shape[1]
    nk = max(1, sk // block_k)
    bk = sk // nk

    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    # delta_i = sum_d(dO_i * O_i) — the standard flash-bwd residual
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)     # [B,Sq,H]
    q_pos = jnp.arange(sq)

    kb = jnp.moveaxis(kr.astype(jnp.float32).reshape(b, nk, bk, h, d), 1, 0)
    vb = jnp.moveaxis(vr.astype(jnp.float32).reshape(b, nk, bk, h, d), 1, 0)

    def step(dq_acc, blk):
        k_blk, v_blk, ik = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = ik * bk + jnp.arange(bk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        # p = exp(s - lse): exact softmax probabilities via saved lse
        p = jnp.exp(s - lse[..., None])                        # [B,H,Sq,BK]
        dp = jnp.einsum("bqhd,bkhd->bhqk", gf, v_blk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.moveaxis(delta, -1, 1)[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, k_blk,
                                     preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf,
                            preferred_element_type=jnp.float32)
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p, gf,
                            preferred_element_type=jnp.float32)
        return dq_acc, (dk_blk, dv_blk)

    dq, (dk_b, dv_b) = jax.lax.scan(
        step, jnp.zeros(q.shape, jnp.float32),
        (kb, vb, jnp.arange(nk)))
    dk = jnp.moveaxis(dk_b, 0, 1).reshape(b, sk, h, d)
    dv = jnp.moveaxis(dv_b, 0, 1).reshape(b, sk, h, d)
    if groups > 1:
        dk = dk.reshape(b, sk, k.shape[2], groups, d).sum(axis=3)
        dv = dv.reshape(b, sk, k.shape[2], groups, d).sum(axis=3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False):
    """Fused attention. q [B,Sq,H,D]; k/v [B,Sk,KVH,D] (GQA when KVH<H).

    Pallas kernel on TPU (or interpret=True); jnp reference elsewhere.
    """
    out, _ = _fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


@functools.cache
def _on_tpu() -> bool:
    """Cached platform probe shared by every kernel-vs-reference dispatch
    (flash fwd/bwd, paged prefill/verify/decode). A backend that fails to
    initialise raises here: answering "not a TPU" would run the jnp
    reference on whatever is left and hide the device."""
    return jax.devices()[0].platform == "tpu"


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret or _on_tpu():
        return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    out = mha_reference(q, k, v, causal, scale)
    # lse for the backward: recomputed cheaply at reference sizes
    groups = q.shape[2] // k.shape[2]
    kr = jnp.repeat(k, groups, axis=2) if groups > 1 else k
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], kr.shape[1]), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)                         # [B,H,Sq]
    return out, lse


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    # Tag the residuals so a `save_attn` remat policy (models/llama.py)
    # keeps them across the layer checkpoint: the backward then reads the
    # saved out/lse instead of replaying the whole attention forward —
    # the standard large-model policy (save softmax stats, recompute the
    # cheap projections).
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret or _on_tpu():
        return _flash_bwd(q, k, v, out, lse, g, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return _bwd_blockwise(res, g, causal=causal, scale=scale, block_k=block_k)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
