"""Paged-KV engine tests: paged-vs-full-forward greedy consistency, page
accounting, chunked prefill, TTFT wiring (reference parity: the vLLM
engine correctness surface the reference orchestrates,
llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:180)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.models import llama, mla_moe

# the engine's two model modules: k and v pools a layer, and ONE latent
# pool a layer (models/mla_moe.py)
TINY_MODELS = {
    "llama": lambda **kw: llama.llama_tiny(vocab_size=258, **kw),
    "latent": lambda **kw: mla_moe.mla_moe_tiny(vocab_size=258, **kw),
}


@pytest.fixture(scope="module", params=list(TINY_MODELS))
def engine(request):
    return _tiny_engine(request.param)


# and a model with sliding-window layers beside full ones (two page
# pools, the window layers' table a ring), for the tests of step()'s order
RUN_AHEAD_MODELS = dict(
    TINY_MODELS,
    window=lambda **kw: llama.llama_tiny(
        vocab_size=258, layer_types=("sliding", "full"), sliding_window=16,
        **kw))


def _tiny_engine(kind, **over):
    # prefill_rows is set: these tests count the rows of a 4-row dispatch
    # (the tiny routed model's derived budget is another, tested below)
    kw = dict(model=RUN_AHEAD_MODELS[kind](max_seq_len=128), max_batch_size=4,
              page_size=8, num_pages=64, max_pages_per_seq=16, chunk_size=16,
              prefill_rows=4)
    if kind == "window":
        kw["num_window_pages"] = 64
    kw.update(over)
    return PagedInferenceEngine(PagedEngineConfig(**kw), rng_seed=0)


def test_paged_greedy_matches_full_forward(engine):
    tok = engine.tokenizer
    prompt_ids = tok.encode("hello world")
    out = engine.generate([prompt_ids], SamplingParams(max_tokens=8))[0]

    ids = list(prompt_ids)
    want = []
    for _ in range(8):
        logits = engine.model.apply(
            engine.params, np.asarray([ids], np.int32), engine.cfg.model)
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        want.append(nxt)
        ids.append(nxt)
        if nxt == tok.eos_id:
            break
    assert out["token_ids"] == want
    assert out["ttft_s"] is not None and out["ttft_s"] > 0


def test_chunked_prefill_long_prompt(engine):
    """Prompt spanning several chunks must match the full forward."""
    tok = engine.tokenizer
    prompt_ids = tok.encode("a" * 50)  # > 2 chunks of 16
    out = engine.generate([prompt_ids], SamplingParams(max_tokens=4))[0]
    ids = list(prompt_ids)
    want = []
    for _ in range(4):
        logits = engine.model.apply(
            engine.params, np.asarray([ids], np.int32), engine.cfg.model)
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        want.append(nxt)
        ids.append(nxt)
        if nxt == tok.eos_id:
            break
    assert out["token_ids"] == want


def test_paged_continuous_batching_and_page_recycling(engine):
    prompts = [f"request number {i}" for i in range(9)]  # > 4 slots
    outs = engine.generate(prompts, SamplingParams(max_tokens=6))
    assert len(outs) == 9
    stats = engine.pool_stats()
    # all pages back in the allocatable pool (page 0 stays reserved);
    # with prefix caching on, retired pages park in the cached LRU
    assert (stats["free_pages"] + stats["cached_pages"]
            == engine.cfg.num_pages - 1)
    assert stats["active"] == stats["pending"] == stats["prefilling"] == 0


def test_paged_outputs_independent_of_cosched(engine):
    """Greedy output of a prompt must not depend on what else is running
    (no cross-slot KV corruption through the shared page pool)."""
    tok = engine.tokenizer
    probe = tok.encode("the quick brown fox")
    alone = engine.generate([probe], SamplingParams(max_tokens=6))[0]
    crowd = [tok.encode(f"noise {i} {'x' * (5 + 7 * i)}") for i in range(3)]
    together = engine.generate([probe] + crowd,
                               SamplingParams(max_tokens=6))[0]
    assert together["token_ids"] == alone["token_ids"]


def test_admission_waits_for_pool_capacity():
    cfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=128),
        max_batch_size=4, page_size=8, num_pages=12,  # tiny pool
        max_pages_per_seq=8, chunk_size=8)
    eng = PagedInferenceEngine(cfg, rng_seed=0)
    tok = eng.tokenizer
    prompts = [tok.encode("z" * 30) for _ in range(4)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=4))
    assert len(outs) == 4
    assert all(len(o["token_ids"]) >= 1 for o in outs)
    st = eng.pool_stats()
    assert st["free_pages"] + st["cached_pages"] == cfg.num_pages - 1


def _greedy_reference(params, cfg, prompt_ids, n):
    ids = list(prompt_ids)
    want = []
    for _ in range(n):
        logits = llama.apply(params, np.asarray([ids], np.int32), cfg)
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        want.append(nxt)
        ids.append(nxt)
    return want


def test_slot_reuse_does_not_corrupt_pages():
    """Regression: a recycled slot's stale block-table row must not leak
    writes into pages now owned by another (or the same) sequence."""
    cfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=128),
        max_batch_size=1, page_size=8, num_pages=32,
        max_pages_per_seq=8, chunk_size=16)
    eng = PagedInferenceEngine(cfg, rng_seed=0)
    long_p = list(np.arange(1, 41) % 250 + 1)    # 40 tokens (6 pages)
    short_p = list(np.arange(1, 21) % 250 + 1)   # 20 tokens (3 pages)
    eng.generate([long_p], SamplingParams(max_tokens=4))
    got = eng.generate([short_p], SamplingParams(max_tokens=4))[0]
    want = _greedy_reference(eng.params, cfg.model, short_p, 4)
    assert got["token_ids"] == want


def test_final_chunk_beyond_block_table_is_safe():
    """Regression: when the final chunk's page span crosses the end of the
    block table (max_pages_per_seq not a chunk multiple), writes must not
    be shifted onto earlier pages."""
    cfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=128),
        max_batch_size=1, page_size=8, num_pages=32,
        max_pages_per_seq=6, chunk_size=32)
    eng = PagedInferenceEngine(cfg, rng_seed=0)
    prompt = list(np.arange(1, 41) % 250 + 1)    # 40 tokens, pages [4..8)
    got = eng.generate([prompt], SamplingParams(max_tokens=4))[0]
    want = _greedy_reference(eng.params, cfg.model, prompt, 4)
    assert got["token_ids"] == want


def test_propose_draft_prompt_lookup():
    P = PagedInferenceEngine._propose_draft
    ctx = np.asarray([5, 6, 7, 8, 5, 6], np.int32)
    assert P(ctx, 2, 2) == [7, 8]          # tail (5,6) matched at pos 0
    assert P(ctx, 2, 1) == [7]
    assert P(np.asarray([1, 2, 3], np.int32), 2, 4) == []   # no match
    # most RECENT earlier occurrence wins
    ctx2 = np.asarray([1, 2, 9, 1, 2, 4, 1, 2], np.int32)
    assert P(ctx2, 2, 1) == [4]
    assert P(np.asarray([7], np.int32), 2, 4) == []         # too short


@pytest.mark.slow  # 18s parity re-proof; spec decode stays covered by the repetitive-text win + prefix-cache composition tests
def test_spec_decode_exact_greedy_parity():
    """Speculation must reproduce exact greedy output, token for token,
    while emitting more than one token per dispatch once the generation
    self-repeats (tiny random models loop quickly under greedy)."""
    model = llama.llama_tiny(vocab_size=258, max_seq_len=256)
    mk = lambda spec: PagedInferenceEngine(PagedEngineConfig(
        model=model, max_batch_size=2, page_size=8, num_pages=96,
        max_pages_per_seq=24, chunk_size=16, decode_window=4,
        spec_tokens=12 if spec else 0), rng_seed=0)
    base, spec = mk(False), mk(True)
    spec.params = base.params  # identical weights

    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(1, 250, (11,))),
               [7, 8, 9] * 5]               # self-similar prompt
    sp = SamplingParams(max_tokens=40)
    a = base.generate(prompts, sp)
    b = spec.generate(prompts, sp)
    for x, y in zip(a, b):
        assert x["token_ids"] == y["token_ids"]


def test_spec_decode_beats_window_on_repetitive_text():
    """Solo self-repeating generation (tiny greedy models loop fast):
    the verify path must finish in fewer dispatches than the windowed
    engine, with the EMA controller keeping speculation on."""
    model = llama.llama_tiny(vocab_size=258, max_seq_len=256)
    mk = lambda spec: PagedInferenceEngine(PagedEngineConfig(
        model=model, max_batch_size=2, page_size=8, num_pages=96,
        max_pages_per_seq=24, chunk_size=16, decode_window=4,
        spec_tokens=12 if spec else 0), rng_seed=0)
    base, spec = mk(False), mk(True)
    spec.params = base.params

    prompt = [7, 8, 9] * 5                  # self-similar seed
    sp = SamplingParams(max_tokens=64)
    a = base.generate([prompt], sp)[0]
    b = spec.generate([prompt], sp)[0]
    assert a["token_ids"] == b["token_ids"]
    assert spec.stats["spec_accepted"] > 0, spec.stats
    spent = spec.stats["decode_dispatches"] + spec.stats["spec_dispatches"]
    assert spent < base.stats["decode_dispatches"], (
        spec.stats, base.stats)


def test_warmup_covers_every_burst_program():
    """After warmup(), a mixed burst (several prompt lengths, partial
    final prefill pack, window-1 and full-window decodes, spec verify)
    must trigger ZERO new jit entries: a mid-burst compile lands in some
    request's TTFT (seconds per program on a v5e — chip_smoke.py prints
    the warm-up's compile time), so the row-bucketing + warmup contract
    is exactly 'no compiles after deploy' (reference analog: vLLM's deploy-time graph capture,
    vllm_engine.py:180)."""
    rng = np.random.RandomState(3)
    cfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=256),
        max_batch_size=4, page_size=8, num_pages=128,
        max_pages_per_seq=16, chunk_size=16, prefill_rows=3,
        decode_window=4, spec_tokens=6)
    eng = PagedInferenceEngine(cfg, rng_seed=0)
    eng.warmup()
    families = (eng._prefill_rows_fns, eng._decode_win_fns,
                eng._verify_fns)
    warmed = tuple(set(d) for d in families)
    # odd prompt lengths force a partial final prefill pack; the
    # self-similar prompt triggers the spec verify path solo
    prompts = [list(rng.randint(1, 250, (n,))) for n in (5, 17, 33)]
    prompts.append([7, 8, 9] * 6)
    out = eng.generate(prompts, SamplingParams(max_tokens=24))
    assert all(r["token_ids"] for r in out)
    # spec verify only fires when EVERY active slot carries a draft — run
    # the self-similar prompt solo so the verify family gets exercised
    out2 = eng.generate([[7, 8, 9] * 6], SamplingParams(max_tokens=24))
    assert out2[0]["token_ids"]
    assert eng.stats["spec_dispatches"] > 0, eng.stats
    for d, before in zip(families, warmed):
        assert set(d) == before, (set(d) - before, "compiled mid-burst")


def test_logprobs_reported_and_consistent():
    """Chosen-token logprobs ride every program family (prefill first
    token, windowed decode, spec verify) and are the model-natural
    log_softmax values: re-running the same greedy generation twice
    yields identical tokens AND logprobs, all finite and <= 0."""
    model = llama.llama_tiny(vocab_size=258, max_seq_len=256)
    mk = lambda spec: PagedInferenceEngine(PagedEngineConfig(
        model=model, max_batch_size=2, page_size=8, num_pages=96,
        max_pages_per_seq=24, chunk_size=16, decode_window=4,
        spec_tokens=8 if spec else 0), rng_seed=0)
    base, spec = mk(False), mk(True)
    spec.params = base.params

    prompt = [7, 8, 9] * 5
    sp = SamplingParams(max_tokens=24, logprobs=1)
    a = base.generate([prompt], sp)[0]
    b = spec.generate([prompt], sp)[0]
    assert a["token_ids"] == b["token_ids"]
    assert len(a["logprobs"]) == len(a["token_ids"])
    assert all(np.isfinite(v) and v <= 0.0 for v in a["logprobs"])
    # windowed vs spec paths agree on the values (same forward math)
    np.testing.assert_allclose(a["logprobs"], b["logprobs"],
                               rtol=2e-3, atol=2e-3)
    # logprobs=0 (default) omits them from the result
    c = base.generate([prompt], SamplingParams(max_tokens=4))[0]
    assert c["logprobs"] is None


# -- prefill_paged_rows: one batched forward for the rows of a dispatch ----
#
# The rows of a prefill dispatch go through each layer together
# (models/llama.py _prefill_rows). Each case feeds the same rows ONE AT A
# TIME, in order, through prefill_paged_chunk — the one-row case of the
# same loop, each row seeing only what earlier rows wrote — and asks for
# the same last-token logits and the same pages in every layer's pool.
# float32 on the CPU: the two differ in summation order only.

_PAGE, _CHUNK, _POOL, _WIDTH = 8, 16, 40, 10
_ROWS_TOL = dict(rtol=2e-5, atol=2e-5)


def _rows_cfg(**kw):
    d = dict(vocab_size=64, n_heads=4, n_kv_heads=2, dim=32, n_layers=2,
             mlp_dim=48, max_seq_len=128)
    d.update(kw)
    return llama.llama_tiny(**d)


def _bt(*pages):
    row = np.zeros((_WIDTH,), np.int32)
    row[:len(pages)] = pages
    return row


def _rows_case(name):
    """(cfg, params, caches, rows, kwargs): rows are (tokens [C], block
    table row, start_pos, true_len) in dispatch order; caches hold
    whatever prefix the rows attend over."""
    rng = np.random.RandomState(3)

    def toks(n=_CHUNK):
        out = np.zeros((_CHUNK,), np.int32)
        out[:n] = rng.randint(1, 60, (n,))
        return out

    cfg = _rows_cfg(moe_experts=4, moe_top_k=2) if name == "moe" \
        else _rows_cfg()
    params = llama.init(jax.random.PRNGKey(0), cfg)
    caches = llama.init_paged_cache(cfg, _POOL, _PAGE)
    kwargs = {}
    a, b = _bt(1, 2, 3, 4, 5, 6, 7, 8), _bt(9, 10, 11, 12)
    if name in ("one_sequence", "interpret"):
        # four consecutive chunks of one sequence in one dispatch
        rows = [(toks(), a, 16 * i, 16) for i in range(4)]
        kwargs["interpret"] = name == "interpret"
    elif name in ("mixed_starts", "moe", "lora"):
        # a prefix of two sequences is in the pool already; the dispatch
        # mixes their next chunks with two new sequences
        for i in range(2):
            _, caches, _ = llama.prefill_paged_chunk(
                params, toks()[None], caches, jnp.asarray(a),
                jnp.int32(16 * i), cfg, page_size=_PAGE)
        _, caches, _ = llama.prefill_paged_chunk(
            params, toks()[None], caches, jnp.asarray(b), jnp.int32(0),
            cfg, page_size=_PAGE)
        rows = [(toks(), a, 32, 16), (toks(13), b, 16, 13),
                (toks(), _bt(20, 21), 0, 16), (toks(5), _bt(30), 0, 5)]
    elif name == "ragged_and_pad":
        # a ragged last chunk (3 tokens: its second page is a pad page
        # and goes to the sink) and two pad rows
        short = _bt(1, 2, 3)
        rows = [(toks(), short, 0, 16), (toks(3), short, 16, 3),
                (toks(0), _bt(), 0, 0), (toks(0), _bt(), 0, 0)]
    else:
        raise AssertionError(name)
    if name == "lora":
        from ray_tpu.llm import lora
        from ray_tpu.llm.multilora.slots import AdapterSlotTable
        table = AdapterSlotTable(cfg, max_adapters=3, max_rank=4)
        table.load(1, lora.random_adapter(
            jax.random.PRNGKey(7), cfg, rank=4, alpha=64.0,
            targets=("wq", "wv", "lm_head")))
        table.load(2, lora.random_adapter(
            jax.random.PRNGKey(9), cfg, rank=2, alpha=32.0,
            targets=("wq", "wk", "wv", "wo")))
        kwargs.update(lora=table.tree, slots=jnp.asarray([0, 1, 2, 0]))
    return cfg, params, caches, rows, kwargs


def _batched(cfg, params, caches, rows, **kwargs):
    chunks, bts, sps, tls = (jnp.asarray(np.stack(col)) for col in zip(
        *[(t, bt, np.int32(sp), np.int32(tl)) for t, bt, sp, tl in rows]))
    return llama.prefill_paged_rows(params, chunks, caches, bts, sps, tls,
                                    cfg, page_size=_PAGE, **kwargs)


@pytest.mark.parametrize("case", ["one_sequence", "mixed_starts",
                                  "ragged_and_pad", "moe", "lora",
                                  "interpret"])
def test_prefill_rows_batched_matches_rows_one_at_a_time(case):
    cfg, params, caches0, rows, kwargs = _rows_case(case)
    last, caches, load = _batched(cfg, params, caches0, rows, **kwargs)

    slots = kwargs.get("slots")
    one, want_load, want_last = caches0, 0, {}
    for i, (tok, bt, sp, tl) in enumerate(rows):
        if tl == 0:
            continue
        lg, one, ld = llama.prefill_paged_chunk(
            params, jnp.asarray(tok)[None], one, jnp.asarray(bt),
            jnp.int32(sp), cfg, page_size=_PAGE,
            true_chunk_len=jnp.int32(tl), lora=kwargs.get("lora"),
            slot=None if slots is None else slots[i])
        want_last[i] = np.asarray(lg[tl - 1])
        want_load = want_load + (0 if ld is None else np.asarray(ld))
    for i, want in want_last.items():
        np.testing.assert_allclose(np.asarray(last[i]), want, **_ROWS_TOL,
                                   err_msg=f"row {i}")
    # every page but the sink, in every layer (a pad row or a pad page
    # that wrote anywhere else shows here)
    for got, ref in zip(caches, one):
        for n in "kv":
            np.testing.assert_allclose(np.asarray(got[n][1:]),
                                       np.asarray(ref[n][1:]), **_ROWS_TOL)
    if case == "ragged_and_pad":
        touched = [1, 2, 3]
        for got in caches:
            untouched = np.delete(np.asarray(got["k"]), [0] + touched, 0)
            assert not untouched.any()
            assert np.asarray(got["k"])[touched].any()
    if case == "moe":
        # pad tokens are routed too: every row counts its whole chunk
        assert int(load.sum()) == (len(rows) * _CHUNK * cfg.moe_top_k
                                   * cfg.n_layers)
        np.testing.assert_array_equal(np.asarray(load), want_load)
    else:
        assert load is None
    if case == "lora":
        # slot 0 adds an exact +0.0: base rows are bit-identical to the
        # program without the table, whatever their neighbours carry
        plain, plain_caches, _ = _batched(cfg, params, caches0, rows)
        for i in (0, 3):
            np.testing.assert_array_equal(np.asarray(last[i]),
                                          np.asarray(plain[i]))
        for i in (1, 2):
            assert np.abs(np.asarray(last[i] - plain[i])).max() > 1e-3
        np.testing.assert_array_equal(          # row 0's pages, layer 1
            np.asarray(caches[1]["v"][5:7]),
            np.asarray(plain_caches[1]["v"][5:7]))


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_prefill_rows_is_one_forward_not_a_loop_over_rows():
    """Counts, does not time: at R = 4 the traced program holds no scan
    and no while, and each layer's w_gate meets ONE dot_general, whose
    left side has R x C rows."""
    cfg = _rows_cfg()
    params = llama.init(jax.random.PRNGKey(0), cfg)
    caches = llama.init_paged_cache(cfg, _POOL, _PAGE)
    r = 4
    closed = jax.make_jaxpr(
        lambda p, c: llama.prefill_paged_rows(
            p, jnp.zeros((r, _CHUNK), jnp.int32), c,
            jnp.zeros((r, _WIDTH), jnp.int32), jnp.zeros((r,), jnp.int32),
            jnp.zeros((r,), jnp.int32), cfg, page_size=_PAGE))(
        params, caches)
    names = {e.primitive.name for e in _eqns(closed.jaxpr)}
    assert not names & {"scan", "while"}, names

    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path((params, caches))[0]]
    w_gate = closed.jaxpr.invars[paths.index("[0]['layers']['w_gate']")]
    # follow the stack through its per-layer slices to the matmuls
    frontier, dots = [w_gate], []
    while frontier:
        var = frontier.pop()
        for e in closed.jaxpr.eqns:
            if var not in e.invars:
                continue
            if e.primitive.name == "dot_general":
                dots.append(e)
            else:
                assert e.primitive.name in ("slice", "squeeze"), e
                frontier.extend(e.outvars)
    assert len(dots) == cfg.n_layers
    for e in dots:
        lhs, rhs = (v.aval.shape for v in e.invars)
        assert rhs == (cfg.dim, cfg.mlp_dim)
        assert lhs == (r, _CHUNK, cfg.dim)        # R x C rows, one matmul


@pytest.mark.parametrize("kind", list(TINY_MODELS))
@pytest.mark.parametrize("path", ["spill_tier", "pd_transfer"])
def test_pages_that_leave_the_pool_come_back_in_its_layout(path, kind):
    """The paths that carry pages out of the pools and back index them by
    the leading axis alone, so a change of the page's layout breaks them
    in silence: a prefix demoted to the spill tier and promoted back, and
    a prefill exported by `_export_kv_locked` and imported by `_import_fn`
    on another engine, continue with exactly the tokens of an engine whose
    pages never left. Two kv heads of 8 lanes each: a page is [page,
    KVH * D] and a head order lost on the way would change the tokens.
    The latent model's layer is ONE pool [page, latent_lanes]: the paths
    carry whatever pools a layer has, by name."""
    if kind == "latent":
        model = TINY_MODELS[kind](max_seq_len=256)
        pools = {"ckv": (8, model.latent_lanes)}
    else:
        model = llama.llama_tiny(vocab_size=258, max_seq_len=256, dim=32,
                                 n_layers=2, n_heads=4, n_kv_heads=2,
                                 mlp_dim=64)
        pools = dict.fromkeys(
            "kv", (8, model.n_kv_heads * model.head_dim))
    cfg = dict(model=model, max_batch_size=4, page_size=8, num_pages=32,
               max_pages_per_seq=16, chunk_size=16,
               enable_prefix_caching=True)
    sp = SamplingParams(max_tokens=10, temperature=0.0)
    rng = np.random.RandomState(21)
    shared = list(rng.randint(1, 250, (64,)))
    ask = shared + list(rng.randint(1, 250, (13,)))

    def run(eng, ids, params=sp):
        req = eng.submit(ids, params)
        while not req.done:
            eng.step()
        return list(req.out_ids)

    stay = PagedInferenceEngine(PagedEngineConfig(**cfg), rng_seed=0)
    assert {n: a.shape[1:] for n, a in stay.caches[0].items()} == pools
    assert stay.page_nbytes == model.n_layers * sum(
        int(np.prod(p)) * 4 for p in pools.values())
    want = run(stay, ask)

    if path == "spill_tier":
        eng = PagedInferenceEngine(
            PagedEngineConfig(kv_spill=True, **cfg), rng_seed=0)
        run(eng, shared + [7], SamplingParams(max_tokens=2))
        hashes = eng.hash_prompt(shared)
        for i in range(8):                  # push the prefix off the LRU
            run(eng, list(np.random.RandomState(900 + i).randint(
                1, 250, (96,))), SamplingParams(max_tokens=2))
        assert eng.cached_prefix_len(hashes) == 0
        assert eng.spill.covered_run(hashes) == len(hashes)
        got = run(eng, ask)                 # admission promotes it back
        assert eng.stats["spill_promotions"] >= len(hashes)
    else:
        pre = PagedInferenceEngine(PagedEngineConfig(**cfg), rng_seed=0)
        eng = PagedInferenceEngine(PagedEngineConfig(**cfg), rng_seed=0)
        payload = pre.prefill_export(ask, sp)
        assert {n: a.shape[1:]
                for n, a in payload["pages"][0].items()} == pools
        req = eng.import_prefill(payload, sp)
        eng.run_until_done([req])
        got = eng._result(req)["token_ids"]
    assert got == want


# -- launches run ahead of readbacks (step()) -------------------------------

def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 250, (n,))]


@pytest.fixture(scope="module", params=list(TINY_MODELS))
def solo(request):
    """(model kind, an engine that is given one prompt at a time, with no
    prefix cache: what every mixed run below has to reproduce)."""
    return request.param, _tiny_engine(request.param,
                                       enable_prefix_caching=False)


@pytest.mark.parametrize("case", ["late_long_prompts", "prefix_burst",
                                  "small_pool"])
def test_run_ahead_tokens_equal_one_at_a_time(solo, case):
    """A prefill launched beside the last decode, a decode launched beside
    that prefill, a prefill beside a prefill: whatever was outstanding
    when a dispatch went out, every request's greedy tokens are those of
    the same prompt run alone."""
    kind, alone = solo
    sp = sp_first = SamplingParams(max_tokens=9)
    if case == "late_long_prompts":
        eng = _tiny_engine(kind)
        sp_first = SamplingParams(max_tokens=24)    # still decoding then
        first = [_ids(11, 1), _ids(20, 2)]
        later = [_ids(100, 3), _ids(70, 4), _ids(90, 5)]
    elif case == "prefix_burst":
        eng = _tiny_engine(kind)
        head = _ids(80, 6)
        first, later = [head + _ids(3 + i, 7 + i) for i in range(4)], []
    else:
        # 16 allocatable pages: a 50-token prompt and its answer take 8,
        # so two requests wait in _pending for pages a retirement frees
        eng = _tiny_engine(kind, num_pages=17, max_pages_per_seq=8,
                           enable_prefix_caching=False)
        first, later = [_ids(50, 20 + i) for i in range(4)], []
    eng.params = alone.params
    reqs = [eng.submit(p, sp_first) for p in first]
    if later:
        while not eng._active:              # the first prompts decode
            eng.step()
        reqs += [eng.submit(p, sp) for p in later]
    waited = False
    while not all(r.done for r in reqs):
        eng.step()
        waited |= bool(eng._pending and eng._free_slots)
        assert len(eng._inflight) <= 1      # one between two step() calls
    eng._drain()
    assert waited or case != "small_pool"   # for pages, with slots free
    want = [alone.generate([p], r.params)[0]["token_ids"]
            for p, r in zip(first + later, reqs)]
    assert [r.out_ids for r in reqs] == want
    st = eng.stats
    assert 0 < st["dispatches_overlapped"] <= \
        st["prefill_dispatches"] + st["decode_dispatches"]
    if case == "prefix_burst":
        # the followers mapped the leader's pages in and did not compute
        # them again beside it: 80 tokens = 5 chunks each
        assert st["prefix_tokens_saved"] == 3 * 80
    assert not eng._inflight and not eng.has_work()
    assert st["tokens_out"] == sum(r.params.max_tokens for r in reqs)


def test_decode_after_decode_launches_behind_the_one_before(engine):
    """With nothing prefilling every decode after the first is launched
    beside the one before it, unbooked: its rows start from the tokens
    that one left on the device, and the readback runs one dispatch
    behind. Behind a full window goes one step, behind that step the
    next full window."""
    reqs = [engine.submit(_ids(9 + i, 30 + i), SamplingParams(max_tokens=30))
            for i in range(3)]
    while engine._prefilling or engine._pending or not engine._active:
        engine.step()
    engine._drain()                 # the first window went out in that step
    assert [len(r.out_ids) for r in reqs] == [9] * 3
    before = dict(engine.stats)
    windows = _windows(engine)
    while not all(r.done for r in reqs):
        engine.step()
        assert len(engine._inflight) <= 1   # one between two step() calls
        assert all(d.family == "decode" for d in engine._inflight)
    del engine._launched                    # the spy
    assert not engine._inflight             # the last step launched nothing
    d = {k: engine.stats[k] - before[k] for k in before}
    assert d["prefill_dispatches"] == 0
    # 21 tokens to come: 8 + 1 + 8 + 1 + 3 of a last window, every
    # dispatch but the first behind an outstanding one, all rows fed there
    assert windows == [8, 1, 8, 1, 8] and d["decode_steps"] == sum(windows)
    assert d["decode_dispatches"] == 5
    assert d["dispatches_overlapped"] == d["decode_dispatches"] - 1
    assert d["decode_rows_fed_on_device"] == 4 * 3
    # max_tokens ends a request where the host can foresee it: no row ran
    # for a request that was done
    assert d["decode_dead_rows"] == 0
    assert d["tokens_out"] == 3 * 21 and [len(r.out_ids) for r in reqs] == \
        [30] * 3


def _windows(eng) -> list:
    """Spy on ``eng``'s launches: the list that each decode launch's
    window is appended to (``del eng._launched`` takes the spy off)."""
    ws, launched = [], eng._launched

    def spy(family, outs, **host):
        if family == "decode":
            ws.append(host["w"])
        return launched(family, outs, **host)
    eng._launched = spy
    return ws


def _one_at_a_time(eng, prompts, params, windows=()):
    """Every dispatch read back and booked before the next is launched:
    each decode starts from the host's tokens. What a decode launched
    behind an unbooked one has to reproduce. ``windows``: the window of
    each decode dispatch in turn, where the run has to make the same
    dispatches as another (a sampled draw is keyed by the launch)."""
    reqs = [eng.submit(p, sp) for p, sp in zip(prompts, params)]
    full, done = eng.cfg.decode_window, _windows(eng)
    while not all(r.done for r in reqs):
        if len(done) < len(windows):
            eng.cfg.decode_window = windows[len(done)]
        eng.step()
        eng._drain()
    eng.cfg.decode_window = full
    del eng._launched
    assert eng.stats["decode_rows_fed_on_device"] == 0
    assert not windows or done == list(windows)
    return [list(r.out_ids) for r in reqs]


@pytest.fixture(scope="module", params=list(RUN_AHEAD_MODELS))
def lone(request):
    """(model kind, an engine with no prefix cache that runs one prompt
    at a time and books every dispatch before the next)."""
    kind = request.param
    return kind, _tiny_engine(kind, enable_prefix_caching=False)


@pytest.mark.parametrize("case", [
    "max_tokens_on_a_window_edge", "max_tokens_inside_a_window",
    "stop_token_inside_a_window", "pool_dry_mid_window", "sampled",
    "import_between_two_launches"])
def test_decode_behind_a_decode_serves_the_tokens_of_one_at_a_time(lone,
                                                                   case):
    """A decode launched before the last one is booked: whatever ends a
    row of the unbooked dispatch, each request's tokens are those of the
    same prompt run alone with every dispatch booked before the next."""
    kind, alone = lone
    sp = SamplingParams
    prompts = [_ids(21, 70), _ids(13, 71)]
    over, fed_rows = {}, True
    if case == "max_tokens_on_a_window_edge":
        # the first token is a prefill's; two and three whole windows
        params = [sp(max_tokens=17), sp(max_tokens=25)]
    elif case == "max_tokens_inside_a_window":
        params = [sp(max_tokens=12), sp(max_tokens=22)]
    elif case == "stop_token_inside_a_window":
        full = _one_at_a_time(alone, prompts[:1], [sp(max_tokens=40)])[0]
        # a token first seen inside the second decode window (tokens
        # 9..16), not at its edge: the third window is launched before
        # the stop is on the host
        k = next(k for k in range(9, 16) if full[k] not in full[:k])
        params = [sp(max_tokens=40, stop_token_ids=(full[k],)),
                  sp(max_tokens=40)]
        # two slots: the third request waits for the stopped one's
        prompts = prompts + [_ids(30, 72)]
        params = params + [sp(max_tokens=9)]
        over = dict(max_batch_size=2)
    elif case == "pool_dry_mid_window":
        # 16 allocatable pages of 8 tokens; the requests hold 6 and 3
        # when they start to decode and would need 10 and 8: the second
        # is cut short mid-window, and the first ends on its pages
        prompts = [_ids(40, 79), _ids(20, 74)]
        params = [sp(max_tokens=40), sp(max_tokens=40)]
        over = dict(num_pages=17, max_pages_per_seq=12,
                    enable_prefix_caching=False)
    elif case == "sampled":
        params = [sp(max_tokens=20, temperature=0.8, top_k=20, seed=7),
                  sp(max_tokens=27, temperature=1.1, seed=8)]
    else:
        params = [sp(max_tokens=30), sp(max_tokens=14)]
    eng = _tiny_engine(kind, **over)
    eng.params = alone.params
    if case == "sampled":
        # a draw is keyed by the launch counter and the row: the same
        # dispatches in the same order, in an engine of the same seed
        windows = _windows(eng)
        reqs = [eng.submit(p, q) for p, q in zip(prompts, params)]
        eng.run_until_done(reqs)
        ref = _tiny_engine(kind, **over)
        ref.params = alone.params
        want = _one_at_a_time(ref, prompts, params, windows)
    elif case == "import_between_two_launches":
        want = [alone.generate([p], q)[0]["token_ids"]
                for p, q in zip(prompts, params)]
        first = eng.submit(prompts[0], params[0])
        while len(first.out_ids) < 2:
            eng.step()
        (out,) = eng._inflight              # a decode, unbooked
        if kind == "window":
            # no payload carries two kinds of page: the prompt comes in
            # by the queue, and its prefill's booking gives its first token
            second = eng.submit(prompts[1], params[1])
        else:
            second = eng.import_prefill(
                alone.prefill_export(prompts[1], params[1]), params[1])
            eng.step()  # launches behind it: one row fed there, one here
            assert eng._inflight[-1].host["reqs"] == {
                first.slot: first, second.slot: second}
            assert eng._inflight[-1].host["fed_rows"] == 1
        reqs = [first, second]
        eng.run_until_done(reqs)
    else:
        want = [_one_at_a_time(alone, [p], [q])[0]
                for p, q in zip(prompts, params)]
        reqs = [eng.submit(p, q) for p, q in zip(prompts, params)]
        eng.run_until_done(reqs)
    got = [list(r.out_ids) for r in reqs]
    st = eng.stats
    assert st["decode_rows_fed_on_device"] > 0
    assert not eng._inflight and not eng.has_work()
    if case == "pool_dry_mid_window":
        # cut short where its pages ended, never wrong before that
        assert all(g == w[:len(g)] for g, w in zip(got, want))
        assert [len(g) for g in got] == [40, 28]
        assert st["decode_dead_rows"] == 0      # foreseen at the launch
    else:
        assert got == want
    if case == "stop_token_inside_a_window":
        assert got[0][-1] == params[0].stop_token_ids[0]
        assert len(got[0]) == k + 1 < 16
        # the window launched behind the one that held the stop ran a
        # row for a request its booking found done: none of it is kept
        assert st["decode_dead_rows"] > 0
        assert st["tokens_out"] == sum(map(len, got))
    elif case != "sampled":
        # what ends a request there the host foresees (a sampled token
        # may be the end of sequence)
        assert st["decode_dead_rows"] == 0
    # every page and slot is back
    pool = eng.pool_stats()
    assert pool["free_pages"] + pool["cached_pages"] == eng.cfg.num_pages - 1
    assert len(eng._free_slots) == eng.cfg.max_batch_size


def test_blocking_calls_leave_nothing_outstanding(engine):
    """run_until_done returns with every dispatch read back, also when the
    requests it waited for are done and others are not; has_work() is
    true while a dispatch is outstanding."""
    sp = SamplingParams
    short = engine.submit(_ids(12, 40), sp(max_tokens=2))
    long = engine.submit(_ids(90, 41), sp(max_tokens=30))
    engine.step()
    assert engine._inflight and engine.has_work()
    engine.run_until_done([short])
    assert short.done and not long.done
    assert not engine._inflight and engine.has_work()
    n = len(long.out_ids)
    engine.step()                       # launches; books nothing new
    assert len(engine._inflight) == 1 and len(long.out_ids) == n
    assert engine.generate([_ids(7, 42)], sp(max_tokens=3))[0]["token_ids"]
    engine.run_until_done([long])
    assert not engine._inflight and not engine.has_work()
    assert len(long.out_ids) == 30


@pytest.mark.parametrize("another_waits", [True, False])
def test_a_finished_prompt_joins_decode_one_dispatch_later(engine,
                                                           another_waits):
    """The decode dispatch launched beside the prefill that ends a prompt
    cannot carry that prompt: its second token comes from the decode
    dispatch after the next one to be read back. When no other prompt
    waits, the decode (a full window) follows that prefill's booking and
    carries it."""
    sp = SamplingParams
    old = engine.submit(_ids(10, 50), sp(max_tokens=60))
    while not old.out_ids:
        engine.step()
    new = engine.submit(_ids(30, 51), sp(max_tokens=4))     # two rows
    other = engine.submit(_ids(100, 52), sp(max_tokens=2)) \
        if another_waits else None      # two more rows, and five to go
    while not new.out_ids:
        engine.step()
    # booked so far: every decode but the one left outstanding
    first_at = engine.stats["decode_dispatches"]
    (out,) = engine._inflight
    assert out.family == "decode"
    assert (new in out.host["reqs"].values()) == (not another_waits)
    assert out.host["w"] == (1 if another_waits else engine.cfg.decode_window)
    while len(new.out_ids) < 2:
        engine.step()
    assert engine.stats["decode_dispatches"] == first_at + 1 + another_waits
    engine.run_until_done([r for r in (old, new, other) if r])


def test_import_between_steps_waits_for_the_outstanding_decode(solo):
    """What a decode replica does under its step lock (pd_disagg.py
    `start`): a prefill imported while a decode dispatch is outstanding
    is no row of that dispatch, and decodes from the next launch on."""
    kind, pre = solo
    sp = SamplingParams(max_tokens=12)
    eng = _tiny_engine(kind)
    eng.params = pre.params
    a, b = _ids(30, 60), _ids(45, 61)
    first = eng.submit(a, sp)
    while len(first.out_ids) < 2:
        eng.step()
    (out,) = eng._inflight
    assert out.family == "decode"
    second = eng.import_prefill(pre.prefill_export(b, sp), sp)
    assert second.slot not in out.host["reqs"]
    eng.step()                  # books it: only the first request's tokens
    assert len(second.out_ids) == 1
    eng.run_until_done([first, second])
    want = [pre.generate([p], sp)[0]["token_ids"] for p in (a, b)]
    assert [first.out_ids, second.out_ids] == want
    assert not pre._inflight and not pre.has_work()     # prefill_export's


# -- the prefill row budget: derived from the model's routing and the pools --

def _window_need(rows, *, batch=4, window=16, page=8, chunk=16):
    """Window pages `batch` sequences need at a budget of ``rows``
    (_init_window_pool's sum)."""
    from ray_tpu.ops.ragged_paged_attention import window_table_pages
    write = rows * chunk
    return batch * window_table_pages(window, page, write) \
        + 2 * -(-write // page) + 1


assert _window_need(8) < _window_need(16)


def _routed_window(**kw):
    # 32 experts top-2 at chunk 16: the mean group reaches no tile before
    # the cap, so the routing alone derives 16 rows
    return llama.llama_tiny(
        vocab_size=258, moe_experts=32, moe_top_k=2, mlp_dim=16,
        layer_types=("sliding", "full"), sliding_window=16, **kw)


ROW_BUDGETS = {
    # case: (model, engine overrides, budget, ladder) — None: refused
    "dense": (lambda: llama.llama_tiny(), dict(chunk_size=128), 4, [1, 2, 4]),
    "64_experts_top8": (
        lambda: llama.llama_tiny(moe_experts=64, moe_top_k=8, mlp_dim=16),
        dict(chunk_size=128), 8, [1, 4, 8]),
    "128_experts_top6": (
        lambda: mla_moe.mla_moe_tiny(moe_experts=128, moe_top_k=6, mlp_dim=8),
        dict(chunk_size=128), 16, [1, 4, 16]),
    "every_layer_dense": (
        lambda: mla_moe.mla_moe_tiny(n_layers=1, n_dense_layers=1),
        dict(chunk_size=128), 4, [1, 2, 4]),
    "window_pool_holds_8_not_16": (
        _routed_window, dict(num_window_pages=_window_need(8)), 8, [1, 4, 8]),
    "window_pool_holds_16": (
        _routed_window, dict(num_window_pages=_window_need(16)), 16,
        [1, 4, 16]),
    "set_by_the_user": (
        lambda: mla_moe.mla_moe_tiny(), dict(prefill_rows=2), 2, [1, 2]),
    "set_by_the_user_routed_8": (
        lambda: mla_moe.mla_moe_tiny(), dict(prefill_rows=8), 8, [1, 4, 8]),
    "set_by_the_user_dense_8": (
        lambda: llama.llama_tiny(), dict(prefill_rows=8), 8, [1, 2, 4, 8]),
    "set_by_the_user_over_the_pool": (
        _routed_window,
        dict(prefill_rows=16, num_window_pages=_window_need(8)), None, None),
}


@pytest.mark.parametrize("case", list(ROW_BUDGETS))
def test_prefill_row_budget(case):
    """Unset, the budget follows the model's routing (4 dense; rows until
    an expert's mean group fills the grouped kernel's largest tile, at
    most 16) and gives way to the window pool; set, it is kept, and
    refused where the window pool cannot hold its ring."""
    model, over, budget, ladder = ROW_BUDGETS[case]
    kw = dict(model=model(), max_batch_size=4, page_size=8, num_pages=64,
              max_pages_per_seq=16, chunk_size=16)
    kw.update(over)
    if budget is None:
        with pytest.raises(ValueError, match="num_window_pages"):
            PagedInferenceEngine(PagedEngineConfig(**kw))
        return
    eng = PagedInferenceEngine(PagedEngineConfig(**kw))
    assert eng.prefill_rows == budget
    assert eng._prefill_row_ladder() == ladder
    summary = eng.profile_summary()
    assert summary["prefill_rows"] == budget
    assert summary["prefill_row_ladder"] == ladder
    assert eng.cfg.prefill_rows == over.get("prefill_rows")    # untouched


def test_derived_budget_gives_the_tokens_of_four_rows_and_compiles_nothing(
        capsys):
    """A routed model at its derived budget (16 rows here): greedy tokens
    of prompts of 1-20 chunks equal those at prefill_rows=4; warm-up holds
    at most three prefill programs a page bucket, says so in its one log
    line, and a run that packs every row count from 1 to the budget then
    compiles nothing."""
    kw = dict(model=mla_moe.mla_moe_tiny(vocab_size=258, max_seq_len=256),
              max_batch_size=4, page_size=8, num_pages=160,
              max_pages_per_seq=24, chunk_size=8, page_buckets="on",
              enable_prefix_caching=False)
    four = PagedInferenceEngine(PagedEngineConfig(prefill_rows=4, **kw))
    eng = PagedInferenceEngine(PagedEngineConfig(**kw), four.params)
    assert eng.prefill_rows == 16 and four.prefill_rows == 4
    eng.warmup()
    assert "prefill rows [1, 4, 16] (derived)" in capsys.readouterr().err
    assert eng.warm_programs == eng.profiler.compiles > 0
    for bucket in eng._page_bucket_ladder():
        rows = sorted(k[0] for k in eng._prefill_rows_fns if k[2] == bucket)
        assert rows == [1, 4, 16]
    # lengths of 1 to 20 chunks, whole and ragged, queued together
    sp = SamplingParams(max_tokens=5)
    prompts = [_ids(n, 70 + n) for n in (8, 13, 40, 160, 3, 96, 121, 64, 27)]
    got = eng.generate(prompts, sp)
    want = four.generate(prompts, sp)
    assert [g["token_ids"] for g in got] == [w["token_ids"] for w in want]
    assert eng.stats["prefill_tokens"] == four.stats["prefill_tokens"]
    assert eng.stats["prefill_dispatches"] < four.stats["prefill_dispatches"]
    # every row count once: a prompt of r whole chunks alone is one dispatch
    before = eng.stats["prefill_dispatches"]
    for r in range(1, eng.prefill_rows + 1):
        eng.generate([_ids(8 * r, 100 + r)], SamplingParams(max_tokens=2))
    assert eng.stats["prefill_dispatches"] == before + eng.prefill_rows
    assert eng.profiler.compiles == eng.warm_programs
    assert eng.profile_summary()["in_window_compiles"] == 0
