"""Layers of two kinds in models/llama.py and the paged engine (Mellum2's
architecture at a small size, seeded float32 weights): sliding layers that
see `sliding_window` keys beside full layers that see all, each kind with
its RoPE, its page pool and its block table.

Everything is compared with benchmarks/reference/mellum_decoder.py, which
shares no code with the program. Tolerances: both sides compute in float32
and differ in the order of their sums alone (measured 3e-6 on logits of
about unit size), so 1e-4 is thirty times the noise and a hundredth of
what the smallest fault moves — the controls at the end (a sliding layer
without its window, a full layer without YaRN's factor) move logits by
more than 1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.models.mellum import Builder
from benchmarks.reference.mellum_decoder import MellumDecoder
from ray_tpu.llm.engine import SamplingParams
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.models import llama

TOL = 1e-4
WINDOW, PAGE, CHUNK = 16, 8, 16
MODEL = dict(
    hidden_size=48, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    vocab_size=128, max_position_embeddings=512, rms_norm_eps=1e-6,
    sliding_window=WINDOW, torch_dtype="float32", attention_bias=False,
    tie_word_embeddings=False, hidden_act="silu",
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": None},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}})


@pytest.fixture(scope="module")
def built():
    b = Builder(MODEL, use_flash=False, remat=False)
    return b.cfg, b.init_params(3), MellumDecoder(MODEL)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], n).astype(np.int32)


def _engine(cfg, params, **kw):
    kw = dict(dict(max_batch_size=3, page_size=PAGE, num_pages=96,
                   num_window_pages=40, max_pages_per_seq=32,
                   chunk_size=CHUNK, prefill_rows=2, decode_window=4,
                   page_buckets="on"), **kw)
    return PagedInferenceEngine(PagedEngineConfig(model=cfg, **kw), params)


def _gaps(ref, params, prompt, served):
    """Reference logit of the best token less that of the served one, at
    each served position (teacher-forced): 0 where they agree."""
    seq = np.asarray(list(prompt) + list(served), np.int32)
    rows = np.asarray(ref.logits(params, jnp.asarray(seq)))[
        len(prompt) - 1:len(seq) - 1]
    return rows.max(-1) - rows[np.arange(len(served)), served]


# -- (a) the training forward ------------------------------------------------

def test_apply_matches_the_reference(built):
    cfg, params, ref = built
    toks = _tokens(7 * WINDOW + 5)          # several windows deep
    got = np.asarray(llama.apply(params, jnp.asarray(toks)[None], cfg))[0]
    want = np.asarray(ref.logits(params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


# -- (b) chunked prefill and decode through the two-kind cache ---------------

def test_paged_forwards_match_the_reference_at_every_position(built):
    """Two sequences prefilled chunk by chunk in turn, then decoded
    together, over a window pool too small to hold both without the pages
    one hands back going to the other: logits at EVERY position against
    the reference's full forward."""
    cfg, params, ref = built
    ring = llama.window_ring_pages(cfg, PAGE, CHUNK)
    lens = {0: 6 * CHUNK + 3, 1: 5 * CHUNK}
    seqs = {r: _tokens(n + 6, seed=10 + r) for r, n in lens.items()}
    n_full = 2 + sum(-(-(n + 6) // PAGE) for n in lens.values())
    caches = llama.init_paged_cache(cfg, n_full, PAGE, 2 * ring + 1)
    full = np.zeros((2, 16), np.int32)
    rings = np.zeros((2, ring), np.int32)
    free_full = list(range(1, n_full))
    free_win = list(range(1, 2 * ring + 1))
    held = {0: {}, 1: {}}                   # logical page -> window page
    reused = set()
    was_held: set = set()

    def grow(r, upto):
        for p in range(-(-upto // PAGE)):
            if full[r, p] == 0:
                full[r, p] = free_full.pop()
        for p in range(max(held[r], default=-1) + 1, -(-upto // PAGE)):
            pid = free_win.pop()
            if pid in was_held:
                reused.add(pid)
            held[r][p] = rings[r, p % ring] = pid

    def hand_back(r, next_pos):
        for p in [p for p in held[r]
                  if (p + 1) * PAGE <= next_pos - WINDOW + 1]:
            was_held.add(held[r][p])
            free_win.insert(0, held[r].pop(p))

    got = {0: [], 1: []}
    pos = {0: 0, 1: 0}
    while any(pos[r] < lens[r] for r in pos):
        for r in (0, 1):
            if pos[r] >= lens[r]:
                continue
            n = min(CHUNK, lens[r] - pos[r])
            grow(r, pos[r] + n)
            chunk = np.zeros((1, CHUNK), np.int32)
            chunk[0, :n] = seqs[r][pos[r]:pos[r] + n]
            logits, caches, _ = llama.prefill_paged_chunk(
                params, jnp.asarray(chunk), caches,
                (jnp.asarray(full[r]), jnp.asarray(rings[r])),
                jnp.int32(pos[r]), cfg, page_size=PAGE,
                true_chunk_len=jnp.int32(n))
            got[r].append(np.asarray(logits)[:n])
            pos[r] += n
            hand_back(r, pos[r])
    for step in range(6):
        lengths = np.asarray([lens[0] + step, lens[1] + step], np.int32)
        for r in (0, 1):
            grow(r, int(lengths[r]) + 1)
        toks = np.asarray([[seqs[0][lengths[0]]], [seqs[1][lengths[1]]]])
        logits, caches, _ = llama.decode_paged(
            params, jnp.asarray(toks), caches,
            (jnp.asarray(full), jnp.asarray(rings)), jnp.asarray(lengths),
            cfg, page_size=PAGE)
        for r in (0, 1):
            got[r].append(np.asarray(logits)[r][None])
            hand_back(r, int(lengths[r]) + 1)
    assert reused, "no window page went from one sequence to the other"
    for r in (0, 1):
        want = np.asarray(ref.logits(params, jnp.asarray(seqs[r])))
        np.testing.assert_allclose(np.concatenate(got[r]), want,
                                   atol=TOL, rtol=0)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas_interpret"])
def test_verify_rows_over_the_pair_of_tables(built, interpret):
    """The speculative-verify forward several windows deep: S1 tokens a
    row fed at once over (full table, ring), logits at every fed position
    against the reference."""
    cfg, params, ref = built
    ring = llama.window_ring_pages(cfg, PAGE, CHUNK)
    n, s1 = 5 * CHUNK, 5
    seq = _tokens(n + s1, seed=21)
    pages = -(-(n + s1) // PAGE)
    caches = llama.init_paged_cache(cfg, pages + 1, PAGE, pages + 1)
    full = np.arange(1, pages + 1, dtype=np.int32)
    rings = np.zeros((ring,), np.int32)
    for pos in range(0, n, CHUNK):
        for p in range(pos // PAGE, (pos + CHUNK) // PAGE):
            rings[p % ring] = p + 1     # a newer page takes the column
        _, caches, _ = llama.prefill_paged_chunk(
            params, jnp.asarray(seq[None, pos:pos + CHUNK]), caches,
            (jnp.asarray(full), jnp.asarray(rings)), jnp.int32(pos), cfg,
            page_size=PAGE, interpret=interpret)
    for p in range(n // PAGE, pages):
        rings[p % ring] = p + 1
    logits, _, _ = llama.verify_paged_rows(
        params, jnp.asarray(seq[None, n:n + s1]), caches,
        (jnp.asarray(full[None]), jnp.asarray(rings[None])),
        jnp.asarray([n], jnp.int32), cfg, page_size=PAGE,
        interpret=interpret)
    want = np.asarray(ref.logits(params, jnp.asarray(seq)))[n:n + s1]
    np.testing.assert_allclose(np.asarray(logits)[0], want, atol=TOL, rtol=0)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas_interpret"])
def test_engine_hands_window_pages_back_and_serves_the_reference(
        built, interpret):
    cfg, params, ref = built
    eng = _engine(cfg, params)
    eng._interpret = interpret
    doc = _tokens(150).tolist()
    prompts = [doc, _tokens(77, seed=1).tolist(), doc[:140] + [5, 6, 7]]
    outs = eng.generate(prompts, SamplingParams(max_tokens=24))
    for prompt, out in zip(prompts, outs):
        gap = _gaps(ref, params, prompt, out["token_ids"])
        assert gap.max() <= TOL, gap
    st = eng.pool_stats()
    # three sequences of up to 22 pages each went through a 40-page pool:
    # only because pages behind the window went back while they ran
    assert st["window_pages_claimed"] > eng.cfg.num_window_pages
    assert st["window_pages_returned"] == st["window_pages_claimed"]
    assert st["full_pages_returned"] == st["full_pages_claimed"]
    assert all(kind.space.live() == 0 for kind in eng.cache.kinds)
    # a live sequence never held more than a ring and a dispatch
    assert st["window_pool_live_pages"] <= st["decode_dispatches"] * 3 * (
        eng.cache.window.ring + 2 * CHUNK // PAGE)
    assert st["decode_live_wpages"] <= st["decode_table_wpages"]


def test_short_requests_never_hand_a_page_back(built):
    cfg, params, _ = built
    eng = _engine(cfg, params, enable_prefix_caching=False)
    eng.generate([_tokens(WINDOW - 6).tolist()],
                 SamplingParams(max_tokens=4))
    st = eng.stats
    # claimed once, returned at the release alone: the same pages the
    # full pool gave and took
    assert st["window_pages_claimed"] == st["full_pages_claimed"] == 2
    assert st["window_evictions"] == 0


# -- (c) the prefix cache through the window layers' tail ---------------------

def test_second_ask_hits_through_the_window_tail(built):
    cfg, params, ref = built
    doc = _tokens(9 * CHUNK).tolist()
    ask = [doc + [1, 2, 3], doc + [4, 5, 6, 7]]
    eng = _engine(cfg, params)
    first = eng.generate([ask[0]], SamplingParams(max_tokens=8))[0]
    saved0 = eng.stats["prefix_tokens_saved"]
    warm = eng.generate([ask[1]], SamplingParams(max_tokens=8))[0]
    assert eng.stats["prefix_tokens_saved"] - saved0 == len(doc)
    assert eng.stats["prefix_tail_cut"] == eng.stats["prefix_tail_lost"] == 0
    cold = _engine(cfg, params).generate(
        [ask[1]], SamplingParams(max_tokens=8))[0]
    assert warm["token_ids"] == cold["token_ids"]
    for prompt, out in ((ask[0], first), (ask[1], warm)):
        assert _gaps(ref, params, prompt, out["token_ids"]).max() <= TOL


@pytest.mark.parametrize("evict,counter,saved", [
    # the window pages of the document's last chunk are gone: the hit
    # falls back to the longest prefix that still has its tail
    ("last_chunk", "prefix_tail_cut", 5 * CHUNK),
    # every window page is gone: no prefix is a hit
    ("all", "prefix_tail_lost", 0),
])
def test_a_lost_window_tail_shortens_the_hit_never_the_answer(
        built, evict, counter, saved):
    cfg, params, ref = built
    doc = _tokens(9 * CHUNK).tolist()
    eng = _engine(cfg, params)
    # a short answer: the first ask ends inside the window of the
    # document's end, so every page it published is still held at release
    eng.generate([doc[:6 * CHUNK] + [1, 2]], SamplingParams(max_tokens=2))
    pool = eng.cache.window.space
    hashes = eng.cache.hash_chain(doc[:6 * CHUNK])
    gone = hashes[5 * CHUNK // PAGE:] if evict == "last_chunk" else hashes
    for h in gone:
        pid = pool.hash_to_page.get(h)
        if pid is not None:
            pool.unpark(pid)
            pool.forget(pid)
            pool.free.append(pid)
    ask = doc[:6 * CHUNK] + [9, 8, 7]
    before = eng.stats["prefix_tokens_saved"]
    out = eng.generate([ask], SamplingParams(max_tokens=8))[0]
    assert eng.stats[counter] == 1
    assert eng.stats["prefix_tokens_saved"] - before == saved
    assert _gaps(ref, params, ask, out["token_ids"]).max() <= TOL


def test_handed_back_body_pages_go_before_the_tail(built):
    """A long prompt's early pages park at the LRU's cold end: under
    pressure the pool reclaims them and keeps the pages a follow-up on the
    same document needs."""
    cfg, params, _ = built
    eng = _engine(cfg, params, max_batch_size=1, num_window_pages=20)
    doc = _tokens(12 * CHUNK).tolist()
    eng.generate([doc + [1, 2, 3]], SamplingParams(max_tokens=4))
    assert eng.stats["window_evictions"] > 0
    before = eng.stats["prefix_tokens_saved"]
    eng.generate([doc + [4, 5, 6]], SamplingParams(max_tokens=4))
    assert eng.stats["prefix_tokens_saved"] - before == len(doc)


# -- refusals ----------------------------------------------------------------

def test_what_a_two_kind_cache_cannot_do_is_refused_by_name(built):
    cfg, params, _ = built
    eng = _engine(cfg, params)
    for call in (lambda: eng.prefill_export([1, 2, 3], SamplingParams()),
                 lambda: eng.export_prefix([b"x"]),
                 lambda: eng.import_prefix({"page_size": PAGE})):
        with pytest.raises(NotImplementedError, match="two-kind"):
            call()
    with pytest.raises(ValueError, match="kv_spill"):
        _engine(cfg, params, kv_spill=True)
    with pytest.raises(ValueError, match="num_window_pages"):
        _engine(cfg, params, num_window_pages=8)
    with pytest.raises(ValueError, match="tp"):
        llama.check_mesh(cfg, {"tp": 2})
    with pytest.raises(ValueError, match="sliding-window"):
        PagedInferenceEngine(PagedEngineConfig(
            model=llama.llama_tiny(), num_window_pages=8))


# -- (e) YaRN by hand ----------------------------------------------------------

def test_yarn_frequencies_and_factor_worked_by_hand():
    """head_dim 128, theta 5e5, factor 16, original context 8192 (the
    published values). A frequency theta^(-2k/128) turns
    8192 / (2 pi theta^(2k/128)) times over the original context: 32 turns
    at k = 128 ln(8192 / (64 pi)) / (2 ln 5e5) = 18.08, one turn at
    k = 128 ln(8192 / (2 pi)) / (2 ln 5e5) = 34.98. So k <= 18 keep their
    frequency, k >= 35 are divided by 16, and k between mix the two by
    (k - 18) / 17. The factor on cos and sin is 0.1 ln 16 + 1."""
    y = llama.Yarn(factor=16.0, original_max_position=8192, beta_fast=32.0,
                   beta_slow=1.0)
    inv, factor = llama.yarn_inv_freq(y, 5e5, 128)
    plain = [5e5 ** (-2 * k / 128) for k in range(64)]
    assert factor == pytest.approx(1.2772588722239782, abs=1e-12)
    assert factor == pytest.approx(0.1 * math.log(16) + 1)
    for k in (0, 7, 18):
        assert float(inv[k]) == pytest.approx(plain[k], rel=1e-6)
    for k in (35, 50, 63):
        assert float(inv[k]) == pytest.approx(plain[k] / 16, rel=1e-6)
    # k = 26: ramp 8 / 17; theta^(-52/128) = 4.8395e-3 by hand
    assert plain[26] == pytest.approx(4.8395e-3, rel=1e-4)
    assert float(inv[26]) == pytest.approx(
        plain[26] * (8 / 17 / 16 + 9 / 17), rel=1e-6)
    # the given factor is used as given
    given = llama.Yarn(16.0, 8192, 32.0, 1.0, attention_factor=1.25)
    assert llama.yarn_inv_freq(given, 5e5, 128)[1] == 1.25
    # the reference computes the same table on its own
    ref = MellumDecoder({"head_dim": 128, "rope_parameters": {"full": {
        "rope_type": "yarn", "rope_theta": 5e5, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}}})
    ref_inv, ref_factor = ref.inv_freq("full")
    np.testing.assert_allclose(np.asarray(inv), ref_inv, rtol=1e-6)
    assert ref_factor == factor
    # sliding layers rotate by the plain frequencies
    cfg = llama.LlamaConfig(
        dim=2304, n_heads=32, n_kv_heads=4, head_dim=128, n_layers=2,
        layer_types=("sliding", "full"), sliding_window=1024, rope_yarn=y,
        rope_theta=5e5)
    pos = jnp.asarray([[3, 20000]])
    cos_s, _ = llama.rope_freqs(cfg, pos, sliding=True)
    cos_f, _ = llama.rope_freqs(cfg, pos)
    np.testing.assert_allclose(
        cos_s[0, 1], np.cos(20000 * np.asarray(plain)), atol=2e-3)
    np.testing.assert_allclose(
        cos_f[0, 1, 40], factor * math.cos(20000 * plain[40] / 16),
        rtol=1e-4)


# -- (f) a head size that is not hidden / heads --------------------------------

def test_head_dim_128_under_hidden_2304_counts_and_builds():
    """Mellum2's published widths, counted by hand: q and o are
    2304 x 4096 (32 heads of 128, where 2304 / 32 = 72), k and v
    2304 x 512; an expert 3 x 2304 x 896; a router 2304 x 64."""
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    experts = 64 * 3 * 2304 * 896
    layer = attn + experts + 2304 * 64 + 2 * 2304
    assert (attn, experts) == (21_233_664, 396_361_728)
    whole = 2 * 98304 * 2304 + 2304 + 28 * layer
    cfg = llama.LlamaConfig(
        vocab_size=98304, dim=2304, n_layers=28, n_heads=32, n_kv_heads=4,
        head_dim=128, mlp_dim=896, moe_experts=64, moe_top_k=8)
    assert cfg.head_dim == 128
    assert cfg.num_params() == whole == 12_149_915_904
    # left out, a head is dim // n_heads as it always was
    assert llama.LlamaConfig().head_dim == 128
    assert llama.llama_tiny().head_dim == 16
    shapes = jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0),
                           llama.LlamaConfig(**{
                               **cfg.__dict__, "n_layers": 1})))
    assert shapes["layers"]["wq"].shape == (1, 2304, 4096)
    assert shapes["layers"]["wo"].shape == (1, 4096, 2304)
    assert shapes["layers"]["wk"].shape == (1, 2304, 512)
    assert shapes["layers"]["w_gate"].shape == (1, 64, 2304, 896)


def test_the_builder_owns_the_new_keys_and_refuses_what_the_block_lacks():
    from benchmarks.models.llama_dense import Builder as Dense
    b = Builder(MODEL)
    assert b.cfg.layer_types == ("sliding",) * 3 + ("full",)
    assert (b.cfg.sliding_window, b.cfg.head_dim) == (WINDOW, 16)
    assert b.cfg.rope_yarn.factor == 4.0 and b.cfg.moe_renormalize
    with pytest.raises(ValueError):
        Dense(MODEL)                    # the dense builder still refuses
    for key, bad in (("attention_bias", True),
                     ("tie_word_embeddings", True),
                     ("mlp_layer_types", ["dense"] * 4)):
        with pytest.raises(ValueError, match=key):
            Builder({**MODEL, key: bad})


# -- controls: what the reference check must catch -----------------------------

@pytest.mark.parametrize("control", ["window", "yarn_factor"])
def test_a_reference_without_the_mechanism_fails_the_check(built, control):
    """A reference that computes the sliding layers without their window,
    or the full layers without YaRN's factor, is far from the program: by
    more than the benchmark's margin of 0.1 on the logits, and (the
    window) on the served tokens' gap, which is what the benchmark's
    check compares."""
    cfg, params, _ = built
    prompt = _tokens(9 * CHUNK).tolist()
    broken = MellumDecoder(MODEL, **{control: False})
    got = np.asarray(llama.apply(params, jnp.asarray(prompt)[None], cfg))[0]
    want = np.asarray(broken.logits(params, jnp.asarray(prompt)))
    assert np.abs(got - want).max() > 0.5
    if control == "window":
        out = _engine(cfg, params).generate(
            [prompt], SamplingParams(max_tokens=8))[0]
        assert _gaps(broken, params, prompt, out["token_ids"]).max() > 0.1
