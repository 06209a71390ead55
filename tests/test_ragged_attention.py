"""Ragged paged attention: kernel-vs-oracle parity (interpret mode on
CPU — tier-1 exercises the REAL Pallas kernel, not just the fallback),
decode-as-q_len=1 equivalence with the original decode kernel, sink-page
safety, and the paged-engine end-to-end contracts: kernel-on vs
plain-JAX fallback within fp accumulation tolerance, block-table page
bucketing changing nothing but the gather width, and the bucketed
warmup ladder keeping the no-mid-burst-compiles contract."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.models import llama
from ray_tpu.ops.ragged_paged_attention import (
    paged_decode_reference, ragged_decode_attention, ragged_paged_attention,
    ragged_paged_reference,
)


def _pools(rng, P, page, kvh, d):
    """Pools as the engine stores them, [P, page, KVH * D] (head h in
    lanes [h * D, (h + 1) * D)), from per-head test data [P, page, KVH,
    D]: the one place these tests know the storage layout."""
    k = jnp.asarray(rng.randn(P, page, kvh, d), jnp.float32)
    v = jnp.asarray(rng.randn(P, page, kvh, d), jnp.float32)
    return k.reshape(P, page, kvh * d), v.reshape(P, page, kvh * d)


def _assert_rows_close(got, ref, q_lens, atol=2e-5):
    """Compare only the live query positions; pad rows/positions are
    contractually garbage."""
    for r in range(got.shape[0]):
        n = int(q_lens[r])
        if n:
            np.testing.assert_allclose(
                np.asarray(got)[r, :n], np.asarray(ref)[r, :n], atol=atol,
                err_msg=f"row {r}")


@pytest.mark.parametrize("groups,page", [(1, 8), (2, 8), (4, 8), (2, 16)])
def test_kernel_matches_oracle_ragged_rows(groups, page):
    """Parity sweep over GQA ratios {1,2,4} and both page sizes (the
    full cross product adds interpreter wall without new code paths —
    page size is orthogonal to the GQA loop, so one 16-page case
    suffices) with genuinely ragged rows: a from-zero prefill window, a
    mid-sequence verify window whose start is NOT page-aligned, a
    tail-partial page, and an empty padding row."""
    rng = np.random.RandomState(0)
    kvh, d, P, maxp = 2, 32, 24, 6
    h = kvh * groups
    q = jnp.asarray(rng.randn(4, 8, h, d), jnp.float32)
    kp, vp = _pools(rng, P, page, kvh, d)
    bt = jnp.asarray(rng.randint(1, P, (4, maxp)), jnp.int32)
    starts = jnp.asarray([0, 13, 2 * page + 3, 0], jnp.int32)
    q_lens = jnp.asarray([8, 5, 3, 0], jnp.int32)
    ref = ragged_paged_reference(q, kp, vp, bt, starts, q_lens)
    got = ragged_paged_attention(q, kp, vp, bt, starts, q_lens,
                                 interpret=True)
    _assert_rows_close(got, ref, q_lens)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("q_window", [32, 20])
def test_kernel_tiles_the_query_window(monkeypatch, q_window):
    """A window wider than the VMEM budget is swept tile by tile (what
    lets the 8B-width chunk compile for the chip — test_chip_compile.py):
    shrink the budget so a tiny window needs several tiles, one of them
    padded (20 = 2.5 tiles of 8), with rows that end mid-tile, start
    off a page boundary, and leave whole tiles as padding."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    rng = np.random.RandomState(3)
    page, kvh, groups, d, P, maxp = 8, 2, 2, 32, 24, 8
    h = kvh * groups
    monkeypatch.setattr(rpa, "_Q_TILE_ELEMS", 8 * h * d)
    assert rpa._q_tile(q_window, h, d) == 8
    q = jnp.asarray(rng.randn(4, q_window, h, d), jnp.float32)
    kp, vp = _pools(rng, P, page, kvh, d)
    bt = jnp.asarray(rng.randint(1, P, (4, maxp)), jnp.int32)
    starts = jnp.asarray([0, 13, 2 * page + 3, 0], jnp.int32)
    q_lens = jnp.asarray([q_window, 11, 3, 0], jnp.int32)
    ref = ragged_paged_reference(q, kp, vp, bt, starts, q_lens)
    got = ragged_paged_attention(q, kp, vp, bt, starts, q_lens,
                                 interpret=True)
    assert got.shape == q.shape
    _assert_rows_close(got, ref, q_lens)
    assert np.isfinite(np.asarray(got)).all()


def test_kernel_skips_pages_beyond_live_count():
    """Sink-page-0 safety: block-table entries at/beyond a row's live
    page count point at a POISONED page; `pl.when` + the clamped index
    map must never let it contribute (the engine zeroes those entries —
    they alias the sink page every idle write lands in)."""
    rng = np.random.RandomState(1)
    page, kvh, d, P, maxp = 8, 2, 32, 16, 8
    q = jnp.asarray(rng.randn(2, 4, 4, d), jnp.float32)
    kp, vp = _pools(rng, P, page, kvh, d)
    kp = kp.at[0].set(1e9)
    vp = vp.at[0].set(1e9)
    starts = jnp.asarray([3, 9], jnp.int32)
    q_lens = jnp.asarray([4, 2], jnp.int32)
    bt = rng.randint(1, P, (2, maxp)).astype(np.int32)
    live = -(-(np.asarray(starts) + np.asarray(q_lens)) // page)
    for r in range(2):
        bt[r, live[r]:] = 0            # beyond-live -> poisoned sink
    bt = jnp.asarray(bt)
    ref = ragged_paged_reference(q, kp, vp, bt, starts, q_lens)
    got = ragged_paged_attention(q, kp, vp, bt, starts, q_lens,
                                 interpret=True)
    _assert_rows_close(got, ref, q_lens)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e3   # poison never attended


def _poisoned_case(rng, rows, q_window, h, kvh, d, page, maxp, starts,
                   q_lens, P=40):
    """Random q and pools, a random table whose entries at/beyond each
    row's live page count point at poisoned page 0 (what the engine's
    zeroed table tail aliases)."""
    q = jnp.asarray(rng.randn(rows, q_window, h, d), jnp.float32)
    kp, vp = _pools(rng, P, page, kvh, d)
    kp, vp = kp.at[0].set(1e9), vp.at[0].set(1e9)
    bt = rng.randint(1, P, (rows, maxp)).astype(np.int32)
    for r in range(rows):
        bt[r, -(-(starts[r] + q_lens[r]) // page):] = 0
    return (q, kp, vp, jnp.asarray(bt), jnp.asarray(starts, jnp.int32),
            jnp.asarray(q_lens, jnp.int32))


# What the 128-key block sweep adds (N = max(1, 128 // page) logical
# pages a grid step). Each case: page, max_pages, starts, q_lens, with a
# query window of 8 over 2 KV heads x 2 groups (the tp=4 shard's KV head
# count).
_BLOCK_CASES = {
    # N = 8: a row of 19 live pages (its third block holds 3), one that
    # ends on a block's edge (128 keys), a q_len = 0 row BETWEEN live
    # rows, and two shorter than one block
    "page16_rows_end_inside_a_block": (
        16, 24, [291, 120, 0, 0, 37], [8, 8, 0, 5, 3]),
    # max_pages = 11 is not a multiple of N = 8: the second block's last
    # five operands clamp inside the table
    "page16_table_not_a_multiple_of_the_block": (
        16, 11, [160, 0, 100], [8, 0, 7]),
    # the bucket floor: a 4-wide table under one 8-page block
    "page16_table_narrower_than_one_block": (
        16, 4, [50, 0, 3], [8, 0, 4]),
    # N = 16, and a table of 20 pages = 1.25 blocks
    "page8_sixteen_pages_a_block": (
        8, 20, [130, 0, 0, 99], [8, 6, 0, 2]),
    # N = 1: today's program, one page a step
    "page128_one_page_a_block": (
        128, 3, [250, 0, 128], [8, 8, 1]),
}


@pytest.mark.parametrize("case", sorted(_BLOCK_CASES))
def test_block_sweep_matches_oracle(case):
    page, maxp, starts, q_lens = _BLOCK_CASES[case]
    args = _poisoned_case(np.random.RandomState(11), len(starts), 8, 4, 2,
                          32, page, maxp, starts, q_lens)
    ref = ragged_paged_reference(*args)
    got = ragged_paged_attention(*args, interpret=True)
    _assert_rows_close(got, ref, q_lens)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e3   # poison never attended


@pytest.mark.parametrize("page,maxp", [(16, 24), (16, 11), (16, 4),
                                       (8, 20), (128, 2)])
def test_block_sweep_decode_matches_oracle(page, maxp):
    """Decode (query window 1) over the same table shapes: lengths that
    end inside a block, on a block's edge, inside the first block, and
    padding rows (length 0) between live ones."""
    cap = maxp * page
    lengths = np.asarray([cap, 0, min(cap, 128), 0, 1, cap - page - 3, 17])
    rng = np.random.RandomState(12)
    q, kp, vp, bt, _, _ = _poisoned_case(
        rng, len(lengths), 1, 8, 2, 32, page, maxp,
        np.zeros_like(lengths), lengths)
    lengths = jnp.asarray(lengths, jnp.int32)
    ref = paged_decode_reference(q[:, 0], kp, vp, bt, lengths)
    got = ragged_decode_attention(q[:, 0], kp, vp, bt, lengths,
                                  interpret=True)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref)[live],
                               atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e3


@pytest.mark.parametrize("rows,page,maxp", [(64, 16, 16), (5, 128, 2)])
def test_decode_of_sixteen_kv_heads_without_groups(rows, page, maxp):
    """OLMoE's form at the decode shape: 16 query heads on 16 kv heads
    (groups = 1, one query row a kv head), 64 rows whose lengths end
    inside a block, on a block's edge and on a page's edge, idle rows
    between them, the poisoned sink behind every table's tail."""
    cap = maxp * page
    lengths = np.random.RandomState(14).randint(1, cap + 1, rows)
    lengths[:5] = [cap, 0, 128, page, 0]
    q, kp, vp, bt, _, _ = _poisoned_case(
        np.random.RandomState(15), rows, 1, 16, 16, 32, page, maxp,
        np.zeros_like(lengths), lengths, P=96)
    lengths = jnp.asarray(lengths, jnp.int32)
    ref = paged_decode_reference(q[:, 0], kp, vp, bt, lengths)
    got = ragged_decode_attention(q[:, 0], kp, vp, bt, lengths,
                                  interpret=True)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref)[live],
                               atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e3


# Both sides of the kernel's one static branch, `q_tile * groups`
# against `_ALL_HEADS_ROWS` = 8: at or under it every head goes through
# one block-diagonal product, over it each kv head has its own. Each
# case: query window, query heads, kv heads.
_HEAD_FORM_CASES = {
    "decode_groups4_all_heads": (1, 8, 2),
    "window2_groups4_all_heads_at_the_threshold": (2, 8, 2),
    "window3_groups4_per_head_over_it": (3, 8, 2),
    "window8_groups1_all_heads_at_the_threshold": (8, 4, 4),
    "window9_groups1_per_head_over_it": (9, 4, 4),
    "window5_groups1_sixteen_heads_all_heads": (5, 16, 16),
}


@pytest.mark.parametrize("case", sorted(_HEAD_FORM_CASES))
def test_both_head_forms_match_oracle(case):
    from ray_tpu.ops import ragged_paged_attention as rpa
    qw, h, kvh = _HEAD_FORM_CASES[case]
    assert rpa._all_heads(rpa._q_tile(qw, h, 32), h // kvh) == \
        ("all_heads" in case)
    # windows that start off a page's edge, end inside a block, a
    # q_len = 0 row between live ones, one shorter than its window
    starts, q_lens = [291, 0, 120, 37], [qw, 0, qw, max(1, qw - 1)]
    args = _poisoned_case(np.random.RandomState(16), 4, qw, h, kvh, 32, 16,
                          24, starts, q_lens)
    ref = ragged_paged_reference(*args)
    got = ragged_paged_attention(*args, interpret=True)
    _assert_rows_close(got, ref, q_lens)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e3   # poison never attended


@pytest.mark.parametrize("page", [8, 16])
def test_block_sweep_two_tile_window(monkeypatch, page):
    """A 16-query window swept as two tiles of 8 over blocks of 128 keys:
    the first tile's causal end falls inside a block the second tile
    needs whole, one row's queries end mid-tile, one row is padding."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    h, kvh, d = 4, 2, 32
    monkeypatch.setattr(rpa, "_Q_TILE_ELEMS", 8 * h * d)
    assert rpa._q_tile(16, h, d) == 8
    starts, q_lens = [250, 0, 117, 0], [16, 0, 11, 16]
    args = _poisoned_case(np.random.RandomState(13), 4, 16, h, kvh, d,
                          page, 272 // page, starts, q_lens)
    ref = ragged_paged_reference(*args)
    got = ragged_paged_attention(*args, interpret=True)
    _assert_rows_close(got, ref, q_lens)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e3


def test_decode_is_qlen1_of_ragged_kernel():
    """Decode equivalence: the ragged kernel at q_len=1 must match the
    jnp decode oracle on the same contract (lengths INCLUDE the current
    step's token)."""
    rng = np.random.RandomState(2)
    page, kvh, d, P, maxp = 16, 4, 64, 12, 4
    q = jnp.asarray(rng.randn(3, 8, d), jnp.float32)
    kp, vp = _pools(rng, P, page, kvh, d)
    bt = jnp.asarray(rng.randint(0, P, (3, maxp)), jnp.int32)
    lengths = jnp.asarray([5, 33, 64], jnp.int32)
    ref = paged_decode_reference(q, kp, vp, bt, lengths)
    new = ragged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(new), np.asarray(ref), atol=2e-5)


def test_prefill_and_verify_kernel_vs_fallback():
    """models/llama.py dispatch parity: prefill_paged_chunk (incl. a
    ragged tail chunk) and verify_paged_rows produce matching logits and
    IDENTICAL page writes whether attention runs in the ragged kernel
    (interpret) or the plain-jnp fallback."""
    cfg = llama.llama_tiny(vocab_size=64, n_heads=4, n_kv_heads=2, dim=32,
                           n_layers=2, mlp_dim=64, max_seq_len=128)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    page, maxp, P = 8, 6, 12
    caches = llama.init_paged_cache(cfg, P, page)
    rng = np.random.RandomState(1)
    bt = np.zeros((maxp,), np.int32)
    bt[:4] = [1, 2, 3, 4]
    btj = jnp.asarray(bt)

    chunk0 = jnp.asarray(rng.randint(1, 60, (1, 16)), jnp.int32)
    lg_fb, c_fb, _ = llama.prefill_paged_chunk(
        params, chunk0, caches, btj, jnp.int32(0), cfg, page_size=page)
    lg_k, c_k, _ = llama.prefill_paged_chunk(
        params, chunk0, caches, btj, jnp.int32(0), cfg, page_size=page,
        interpret=True)
    np.testing.assert_allclose(np.asarray(lg_fb), np.asarray(lg_k),
                               rtol=2e-5, atol=2e-5)
    # layer 0 K/V is computed BEFORE any attention so its pages match
    # bitwise; deeper layers inherit the attention impl's fp differences
    np.testing.assert_array_equal(np.asarray(c_fb[0]["k"]),
                                  np.asarray(c_k[0]["k"]))
    for a, b in zip(c_fb, c_k):
        np.testing.assert_allclose(np.asarray(a["k"]), np.asarray(b["k"]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(a["v"]), np.asarray(b["v"]),
                                   rtol=2e-5, atol=2e-5)

    # ragged tail: 11 of 16 tokens real; pad-page writes route to sink
    chunk1 = jnp.asarray(rng.randint(1, 60, (1, 16)), jnp.int32)
    lg_fb2, c_fb2, _ = llama.prefill_paged_chunk(
        params, chunk1, c_fb, btj, jnp.int32(16), cfg, page_size=page,
        true_chunk_len=jnp.int32(11))
    lg_k2, c_k2, _ = llama.prefill_paged_chunk(
        params, chunk1, c_k, btj, jnp.int32(16), cfg, page_size=page,
        true_chunk_len=jnp.int32(11), interpret=True)
    np.testing.assert_allclose(np.asarray(lg_fb2)[:11],
                               np.asarray(lg_k2)[:11],
                               rtol=2e-5, atol=2e-5)

    # verify window: starts mid-page, two rows
    toks = jnp.asarray(rng.randint(1, 60, (2, 4)), jnp.int32)
    bt2 = np.zeros((2, maxp), np.int32)
    bt2[0, :4] = [1, 2, 3, 4]
    bt2[1, :2] = [5, 6]
    starts = jnp.asarray([27, 5], jnp.int32)
    lv_fb, _, _ = llama.verify_paged_rows(
        params, toks, c_fb2, jnp.asarray(bt2), starts, cfg, page_size=page)
    lv_k, _, _ = llama.verify_paged_rows(
        params, toks, c_k2, jnp.asarray(bt2), starts, cfg, page_size=page,
        interpret=True)
    np.testing.assert_allclose(np.asarray(lv_fb), np.asarray(lv_k),
                               rtol=2e-5, atol=2e-5)


TINY = llama.llama_tiny(vocab_size=258, max_seq_len=512)


def _mk_engine(**kw):
    d = dict(model=TINY, max_batch_size=2, page_size=8, num_pages=256,
             max_pages_per_seq=40, chunk_size=16, decode_window=1,
             page_buckets="on")
    d.update(kw)
    return PagedInferenceEngine(PagedEngineConfig(**d), rng_seed=0)


def test_engine_kernel_vs_fallback_end_to_end():
    """Kernel-on (interpret) and plain-JAX-fallback engines agree on
    greedy tokens AND chosen-token logprobs within fp32-accumulation
    tolerance across chunked prefill + windowed decode."""
    mk = lambda interp: PagedInferenceEngine(PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=128,
                               n_layers=2, dim=32, n_heads=4, n_kv_heads=2,
                               mlp_dim=64),
        max_batch_size=2, page_size=8, num_pages=64, max_pages_per_seq=8,
        chunk_size=16, decode_window=1), rng_seed=0, interpret=interp)
    kern, fall = mk(True), mk(False)
    kern.params = fall.params
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, 250, (n,))) for n in (5, 21)]
    sp = SamplingParams(max_tokens=4, logprobs=True)
    a = kern.generate(prompts, sp)
    b = fall.generate(prompts, sp)
    for x, y in zip(a, b):
        assert x["token_ids"] == y["token_ids"]
        np.testing.assert_allclose(x["logprobs"], y["logprobs"],
                                   rtol=1e-4, atol=1e-5)


def test_engine_page_bucketing_changes_nothing_but_width():
    """Bucketed vs forced-off engines: identical tokens and logprobs,
    and the bucketed one actually dispatched at narrower block tables
    than the full width. ("auto" engages only at max_pages_per_seq >=
    48 — the production default of 64 qualifies — so this 40-page
    config opts in with "on".)"""
    on, off = _mk_engine(), _mk_engine(page_buckets="off")
    assert on._bucketing and not off._bucketing
    assert not _mk_engine(page_buckets="auto")._bucketing   # 40 < 48
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, 250, (n,))) for n in (9, 27)]
    # greedy/no-logprobs: logprob parity across dispatch paths is
    # test_engine_kernel_vs_fallback_end_to_end's job — asking for it
    # here would double every family's compiled-program count
    sp = SamplingParams(max_tokens=4)
    a = on.generate(prompts, sp)
    b = off.generate(prompts, sp)
    for x, y in zip(a, b):
        assert x["token_ids"] == y["token_ids"]
    widths_on = {k[2] for k in on._decode_win_fns} | \
        {k[2] for k in on._prefill_rows_fns}
    assert widths_on and max(widths_on) < 40, widths_on
    assert {k[2] for k in off._decode_win_fns} == {40}
    # absolute correctness at a bucketed width: greedy == full forward
    ids = list(prompts[1])
    want = []
    for _ in range(4):
        logits = llama.apply(on.params, np.asarray([ids], np.int32), TINY)
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        want.append(nxt)
        ids.append(nxt)
    assert a[1]["token_ids"] == want


@pytest.mark.slow  # ~20s: ladder warmup compiles prefill+decode x 4 buckets
def test_bucketed_warmup_covers_every_bucket_program():
    """With bucketing engaged, warmup() compiles the whole page-bucket
    ladder, and a burst spanning several buckets triggers ZERO new
    program keys (the no-mid-burst-compiles contract of
    test_warmup_covers_every_burst_program, extended to buckets)."""
    cfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=256),
        max_batch_size=2, page_size=8, num_pages=256,
        max_pages_per_seq=32, chunk_size=16, prefill_rows=1,
        decode_window=1, page_buckets="on")
    eng = PagedInferenceEngine(cfg, rng_seed=0)
    assert eng._bucketing
    assert eng._page_bucket_ladder() == [4, 8, 16, 32]
    eng.warmup()
    families = (eng._prefill_rows_fns, eng._decode_win_fns)
    warmed = tuple(set(d) for d in families)
    assert {k[2] for k in eng._prefill_rows_fns} == {4, 8, 16, 32}
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(1, 250, (n,))) for n in (5, 120)]
    out = eng.generate(prompts, SamplingParams(max_tokens=40))
    assert all(r["token_ids"] for r in out)
    for d, before in zip(families, warmed):
        assert set(d) == before, (set(d) - before, "compiled mid-burst")


@pytest.mark.slow  # subprocess bench smoke, ~60s
def test_bench_kernels_quick_smoke():
    """bench_kernels --quick must complete and report sane values: the
    bucketed fallback dispatch never slower than 2x the full-width one
    (it does strictly less gather work; 2x guards only against
    collapse, not noise), all wall numbers positive."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "bench_kernels.py"), "--quick"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, p.stdout + p.stderr
    rows = [json.loads(line) for line in p.stdout.splitlines()
            if line.startswith("{")]
    by_name = {r["metric"]: r for r in rows}
    for family in ("prefill", "verify", "decode"):
        full = by_name[f"kernel_{family}_full_ms"]["value"]
        bucket = by_name[f"kernel_{family}_bucket_ms"]["value"]
        assert full > 0 and bucket > 0
        assert bucket < 2 * full, (family, full, bucket)
    assert by_name["kernel_prefill_ttft_ratio"]["value"] > 0


def test_spec_verify_dispatches_bucketed():
    """The bucketed speculative-verify path actually DISPATCHES: a
    solo self-similar greedy prompt drives _spec_step through sliced
    block tables (the verify W arithmetic covers start..start+s1-1
    writes), reproducing exact greedy output and warming only ladder
    widths."""
    model = llama.llama_tiny(vocab_size=258, max_seq_len=256)
    mk = lambda buckets: PagedInferenceEngine(PagedEngineConfig(
        model=model, max_batch_size=2, page_size=8, num_pages=96,
        max_pages_per_seq=24, chunk_size=16, decode_window=4,
        spec_tokens=8, page_buckets=buckets), rng_seed=0)
    on, off = mk("on"), mk("off")
    on.params = off.params
    prompt = [7, 8, 9] * 5
    sp = SamplingParams(max_tokens=48)
    a = off.generate([prompt], sp)[0]
    b = on.generate([prompt], sp)[0]
    assert a["token_ids"] == b["token_ids"]
    assert on.stats["spec_dispatches"] > 0, on.stats
    widths = {k[2] for k in on._verify_fns}
    assert widths and widths <= set(on._page_bucket_ladder()), widths
    assert max(widths) < 24, widths       # verify ran on SLICED tables


# ---- the latent (MLA) form: one pool, values a lane slice of the keys ----

@pytest.mark.parametrize("case", ["decode", "prefill", "verify",
                                  "clamped_tail", "tiled_window"])
def test_latent_form_matches_its_oracle(case):
    """ragged_latent_attention under interpret=True against
    ragged_latent_reference: ONE pool [P, page, W] whose rows are the keys
    and whose first v_width lanes are the values, every query head on that
    one kv head. Decode (window 1, ragged lengths, an idle row), a prefill
    chunk with a padding row, verify windows, a table whose tail is stale
    (the clamp must never read it: the sink page is poisoned), and a
    window wider than the latent tile."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    rng = np.random.RandomState(7)
    pool_pages, page, lanes, v_width, heads = 48, 8, 128, 96, 4
    pages = rng.randn(pool_pages, page, lanes).astype(np.float32)
    pages[0] = np.nan                              # the sink is never read
    starts, q_lens, window, table = {
        "decode": ([37, 0, 120, 9], [1, 1, 1, 0], 1, 16),
        "prefill": ([16, 0, 48], [16, 11, 0], 16, 8),
        "verify": ([21, 60], [3, 3], 3, 8),
        "clamped_tail": ([8, 40], [8, 8], 8, 16),
        "tiled_window": ([32, 0], [24, 24], 24, 8),
    }[case]
    rows = len(starts)
    perm = rng.permutation(np.arange(1, pool_pages))
    bt = np.zeros((rows, table), np.int32)         # tail: the poisoned sink
    at = 0
    for r in range(rows):
        live = -(-(starts[r] + q_lens[r]) // page)
        bt[r, :live] = perm[at:at + live]
        at += live
    q = jnp.asarray(rng.randn(rows, window, heads, lanes), jnp.float32)
    args = (q, jnp.asarray(pages), jnp.asarray(bt),
            jnp.asarray(starts, jnp.int32), jnp.asarray(q_lens, jnp.int32))
    kw = dict(v_width=v_width, scale=0.11)
    if case == "tiled_window":                     # three 8-row tiles
        got = rpa._ragged_call(args[0], args[1], None, *args[2:], q_tile=8,
                               block_keys=128, interpret=True, **kw)
    else:
        got = rpa.ragged_latent_attention(*args, interpret=True, **kw)
    assert got.shape == (rows, window, heads, v_width)
    # the oracle gathers the whole table: give it a finite sink
    want = rpa.ragged_latent_reference(
        q, jnp.asarray(pages).at[0].set(0.0), *args[2:], **kw)
    live = np.arange(window)[None, :] < np.asarray(q_lens)[:, None]
    assert live.any() and np.isfinite(np.asarray(got)[live]).all()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=1e-5)


# ---- the per-kv-head body: wide key blocks, blocks without a predicate ----

# block widths (keys a grid step) the derivation can return
_WIDTHS = (128, 256, 512, 1024)


def _per_head_case(form, rng, table, starts, q_lens, window, page=8,
                   heads=(4, 2, 32)):
    """(args, kwargs of _ragged_call, oracle) of one call in the latent
    form (4 heads on one 128-lane kv head, values its first 96 lanes) or
    the GQA form at ``heads`` = (query heads, kv heads, head size): 2 kv
    heads x 2 query heads of 32 unless given. Every row's live pages are
    its own; the table's tail is the poisoned sink."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    rows = len(starts)
    live = [-(-(s + n) // page) for s, n in zip(starts, q_lens)]
    pool_pages = sum(live) + 1
    perm = rng.permutation(np.arange(1, pool_pages))
    bt = np.zeros((rows, table), np.int32)
    at = 0
    for r in range(rows):
        bt[r, :live[r]] = perm[at:at + live[r]]
        at += live[r]
    tail = (jnp.asarray(bt), jnp.asarray(starts, jnp.int32),
            jnp.asarray(q_lens, jnp.int32))
    if form == "latent":
        pages = rng.randn(pool_pages, page, 128).astype(np.float32)
        pages[0] = np.nan                          # the sink is never read
        q = jnp.asarray(rng.randn(rows, window, 4, 128), jnp.float32)
        kw = dict(v_width=96, scale=0.11)
        args = (q, jnp.asarray(pages), None) + tail
        want = rpa.ragged_latent_reference(
            q, jnp.asarray(pages).at[0].set(0.0), *tail, **kw)
    else:
        h, kvh, d = heads
        kp, vp = _pools(rng, pool_pages, page, kvh, d)
        kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
        q = jnp.asarray(rng.randn(rows, window, h, d), jnp.float32)
        kw = dict(scale=d ** -0.5)
        args = (q, kp, vp) + tail
        want = ragged_paged_reference(q, kp.at[0].set(0.0),
                                      vp.at[0].set(0.0), *tail)
    return args, kw, want


@pytest.mark.parametrize("block_keys", _WIDTHS)
@pytest.mark.parametrize("form", ["latent", "gqa"])
def test_per_head_body_at_every_block_width(form, block_keys):
    """One call, a 24-query window in 16-row tiles (not a multiple: the
    second tile is half padding) over pages of 8, rows chosen so that the
    sweep meets: blocks wholly in the prefix (row 0: ~1,200 cached
    tokens), the diagonal block from position 0 (row 1), a partly live
    tail block with clamped duplicates behind an unaligned start and a
    dead second tile (row 2), a kv_len that ends exactly with a block so
    that the last live query's whole sweep is unmasked, and one that ends
    inside an otherwise-prefix block (rows 3, 4), a pad row (row 5)."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    starts = [1200, 0, 1013, 1007, 1031, 0]
    q_lens = [24, 24, 11, 17, 20, 0]
    args, kw, want = _per_head_case(form, np.random.RandomState(11), 160,
                                    starts, q_lens, 24)
    assert not rpa._all_heads(16, 2)
    steps, masked = rpa.live_key_steps(
        starts, q_lens, 24, 160, q_tile=16, block_keys=block_keys,
        page_size=8)
    assert 0 < masked < steps               # both bodies ran
    got = rpa._ragged_call(*args, q_tile=16, block_keys=block_keys,
                           interpret=True, **kw)
    live = np.arange(24)[None, :] < np.asarray(q_lens)[:, None]
    assert np.isfinite(np.asarray(got)[live]).all()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("block_keys", _WIDTHS)
@pytest.mark.parametrize("form", ["latent", "gqa"])
def test_per_head_body_under_a_table_narrower_than_a_block(form, block_keys):
    """A 6-page table (48 keys) under blocks of 128 to 1,024: one grid step
    whose copies are clamped to the row's last live page."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    starts, q_lens = [20, 0, 0], [16, 9, 0]
    args, kw, want = _per_head_case(form, np.random.RandomState(12), 6,
                                    starts, q_lens, 16)
    got = rpa._ragged_call(*args, q_tile=16, block_keys=block_keys,
                           interpret=True, **kw)
    live = np.arange(16)[None, :] < np.asarray(q_lens)[:, None]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=1e-5)


# ---- the all-heads body's derived key block (PR 48; the body itself at every
# width: tests/test_ragged_all_heads.py) ----

# the decode shape (query window 1) of the five serving configurations:
# window_step's arguments and the key block it derives
_DECODE_STEPS = {
    # 8 x 128 lanes under doc-QA's 256-page bucket: 1 MiB of K and V a step
    "docqa": (dict(heads=32, kv_heads=8, d=128, table_pages=256), 256),
    # 16 x 128: a 128-key step already carries 1 MiB, the table is short
    "olmoe": (dict(heads=16, kv_heads=16, d=128, table_pages=128), 128),
    # 4 x 128 and 2 x 256 lanes under 1,024 pages: 1 MiB at the widest
    "mellum_full": (dict(heads=32, kv_heads=4, d=128, table_pages=1024),
                    1024),
    "qwen3next": (dict(heads=16, kv_heads=2, d=256, table_pages=1024), 1024),
    # the window form keeps its 128-key step under the all-heads body
    "mellum_window": (dict(heads=32, kv_heads=4, d=128, table_pages=97,
                           window=1024), 128),
    # the latent form is the per-kv-head body's: PR 32's width
    "kanana": (dict(heads=32, kv_heads=1, d=640, v_width=512,
                    table_pages=1024), 1024),
}


@pytest.mark.parametrize("name", sorted(_DECODE_STEPS))
def test_window_step_at_the_serving_decode_shapes(name):
    from ray_tpu.ops import ragged_paged_attention as rpa
    shape, keys = _DECODE_STEPS[name]
    assert rpa.window_step(1, **shape, page_size=16, itemsize=2) == {
        "q_tile": 1, "block_keys": keys}


@pytest.mark.parametrize("table_pages,keys", [
    (4, 128), (64, 128), (128, 128), (256, 256), (512, 512), (1024, 512)])
def test_all_heads_block_follows_the_table_and_the_steps_bytes(table_pages,
                                                              keys):
    """Doc-QA's heads over its ladder of table buckets: a block is at most
    a sixteenth of the table (a row's sweep pays a block and a half
    whatever it holds) and stops doubling at 2 MiB of K and V a step —
    static shapes alone, the same for any model with these heads."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    step = rpa.window_step(1, 32, 8, 128, page_size=16,
                           table_pages=table_pages, itemsize=2)
    assert step == {"q_tile": 1, "block_keys": keys}
    assert 2 * 8 * 128 * 2 * keys <= rpa._ALL_HEADS_STEP_BYTES


def test_unmasked_and_masked_steps_agree_bit_for_bit():
    """Where every key of a block is live for every row of the tile the
    predicate is all true and both selects are the identity: the softmax
    step without them (``keep`` None) returns the same bits as the one
    with them, operation by operation (eagerly: nothing is fused)."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    rng = np.random.RandomState(13)
    s = jnp.asarray(rng.randn(32, 256) * 3, jnp.float32)
    m = jnp.asarray(rng.randn(32, 1), jnp.float32)
    l = jnp.asarray(rng.rand(32, 1) + 1, jnp.float32)
    (m0, l0), (m1, l1) = ([m], [l]), ([m], [l])    # the refs of two sweeps
    plain = rpa._softmax_step(s, None, m0, l0, 0)
    kept = rpa._softmax_step(s, jnp.ones(s.shape, bool), m1, l1, 0)
    assert not np.array_equal(np.asarray(m0[0]), np.asarray(m))
    for a, b in zip(plain + (m0[0], l0[0]), kept + (m1[0], l1[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("form", ["latent", "gqa"])
def test_a_sweep_forced_through_the_masked_body_gives_the_same(monkeypatch,
                                                               form):
    """The whole kernel with its prefix blocks forced through the body
    with the predicate (`_prefix_blocks` -> 0) against the shipped one.
    Under the interpreter the two bodies are two XLA:CPU programs whose
    fused reductions sum in another order, so this holds to 1e-6 and the
    bits are held by the step's test above."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    starts, q_lens = [640, 300], [16, 13]
    args, kw, _ = _per_head_case(form, np.random.RandomState(13), 96,
                                 starts, q_lens, 16)
    steps, masked = rpa.live_key_steps(starts, q_lens, 16, 96, q_tile=16,
                                       block_keys=128, page_size=8)
    assert (steps, masked) == (6 + 3, 2)
    call = functools.partial(rpa._ragged_call, *args, q_tile=16,
                             block_keys=128, interpret=True, **kw)
    fast = np.asarray(call())
    monkeypatch.setattr(rpa, "_prefix_blocks", lambda *a, **k: 0)
    jax.clear_caches()                  # _ragged_call is jitted
    try:
        slow = np.asarray(call())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    live = np.arange(16)[None, :] < np.asarray(q_lens)[:, None]
    assert np.isfinite(fast[live]).all()
    np.testing.assert_allclose(fast[live], slow[live], atol=1e-6, rtol=0)
