"""Paged-KV engine tests: kernel numerics, paged-vs-full-forward greedy
consistency, page accounting, chunked prefill, TTFT wiring (reference
parity: the vLLM engine correctness surface the reference orchestrates,
llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:180)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.models import llama


def test_paged_kernel_matches_reference():
    from ray_tpu.ops.paged_attention import (
        paged_decode_attention, paged_decode_reference,
    )
    rng = np.random.RandomState(0)
    B, H, KVH, D, page, P, maxp = 3, 8, 4, 64, 16, 12, 4
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    k_pages = jnp.asarray(rng.randn(P, page, KVH, D), jnp.float32)
    v_pages = jnp.asarray(rng.randn(P, page, KVH, D), jnp.float32)
    bt = jnp.asarray(rng.randint(0, P, (B, maxp)), jnp.int32)
    lengths = jnp.asarray([5, 33, 64], jnp.int32)
    ref = paged_decode_reference(q, k_pages, v_pages, bt, lengths)
    got = paged_decode_attention(q, k_pages, v_pages, bt, lengths,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


@pytest.fixture(scope="module")
def engine():
    cfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=128),
        max_batch_size=4, page_size=8, num_pages=64,
        max_pages_per_seq=16, chunk_size=16)
    return PagedInferenceEngine(cfg, rng_seed=0)


def test_paged_greedy_matches_full_forward(engine):
    tok = engine.tokenizer
    prompt_ids = tok.encode("hello world")
    out = engine.generate([prompt_ids], SamplingParams(max_tokens=8))[0]

    ids = list(prompt_ids)
    want = []
    for _ in range(8):
        logits = llama.apply(engine.params, np.asarray([ids], np.int32),
                             engine.cfg.model)
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
        logits = llama.apply(engine.params, np.asarray([ids], np.int32),
                             engine.cfg.model)
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
