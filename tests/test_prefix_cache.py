"""Automatic prefix caching for the paged KV engine: refcounted pages,
content-hash reuse, COW isolation, LRU eviction under pressure, PD-disagg
import dedupe, and prefix-affinity routing (paged_engine.py
enable_prefix_caching; reference role: vLLM's block-hash automatic prefix
caching on a paged layout)."""
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.models import llama, mla_moe

TINY = llama.llama_tiny(vocab_size=258, max_seq_len=640)
LATENT = mla_moe.mla_moe_tiny(vocab_size=258, max_seq_len=640)


def _cfg(on=True, **kw):
    defaults = dict(model=TINY, max_batch_size=4, page_size=8, num_pages=128,
                    max_pages_per_seq=16, chunk_size=16,
                    enable_prefix_caching=on)
    defaults.update(kw)
    return PagedEngineConfig(**defaults)


def _prompt(n, seed=0):
    return list(np.random.RandomState(seed).randint(1, 250, (n,)))


@pytest.mark.parametrize("model", [TINY, LATENT], ids=["llama", "latent"])
def test_shared_system_prompt_zero_recompute(model):
    """Acceptance: 16 requests sharing a 512-token system prompt — the
    second and later requests perform ZERO prefill for the whole cached
    region (everything up to the last chunk, which must recompute so the
    first token samples from real logits), and greedy outputs are
    bit-identical with caching on vs off."""
    chunk, page, n_req = 64, 16, 16
    mk = lambda on: PagedInferenceEngine(PagedEngineConfig(
        model=model, max_batch_size=n_req, page_size=page, num_pages=600,
        max_pages_per_seq=40, chunk_size=chunk,
        enable_prefix_caching=on), rng_seed=0)
    system = _prompt(512, seed=1)
    prompts = [list(system) for _ in range(n_req)]
    sp = SamplingParams(max_tokens=8)

    on, off = mk(True), mk(False)
    off.params = on.params
    got = on.generate(prompts, sp)
    want = off.generate(prompts, sp)
    assert [o["token_ids"] for o in got] == [w["token_ids"] for w in want]

    st = on.pool_stats()
    # reusable region per request: chunk-aligned, short of the prompt by
    # one chunk = 448 of 512 tokens; all 15 followers skip exactly that
    saved_per_req = ((512 - 1) // chunk) * chunk
    assert saved_per_req == 448
    assert st["prefix_tokens_saved"] == (n_req - 1) * saved_per_req, st
    assert st["prefix_hits"] == (n_req - 1) * saved_per_req // page
    # dispatch budget: the cached run prefills one full prompt + one tail
    # chunk per follower; the uncached run prefills every prompt from zero
    assert st["prefill_dispatches"] < off.pool_stats()["prefill_dispatches"]
    assert off.pool_stats()["prefix_tokens_saved"] == 0


@pytest.mark.slow  # 6s; warm-prefix reuse stays proven by shared-system-prompt + multi-turn tests (tier-1)
def test_warm_cache_across_sequential_requests():
    """A retired request's pages serve the next request's admission-time
    longest-prefix match (the multi-turn / repeated-system-prompt path)."""
    eng = PagedInferenceEngine(_cfg(), rng_seed=0)
    ref = PagedInferenceEngine(_cfg(on=False), rng_seed=0)
    ref.params = eng.params
    base = _prompt(48, seed=2)
    sp = SamplingParams(max_tokens=6)
    for i in range(3):
        p = base + [10 + i]
        a = eng.generate([p], sp)[0]
        b = ref.generate([p], sp)[0]
        assert a["token_ids"] == b["token_ids"]
    st = eng.pool_stats()
    # followers 2 and 3 each reuse the 48-token shared head (6 pages)
    assert st["prefix_tokens_saved"] == 2 * 48, st
    assert st["prefix_hit_rate"] > 0
    assert st["cached_pages"] > 0
    assert st["free_pages"] + st["cached_pages"] == eng.cfg.num_pages - 1


def test_cow_divergence_mid_page():
    """Two requests diverging in the middle of a page/chunk must not see
    each other's KV: the diverging page's content hash differs, so the
    second request writes a private copy (copy-on-write at page
    granularity) while still sharing the pages before the split."""
    eng = PagedInferenceEngine(_cfg(), rng_seed=0)
    ref = PagedInferenceEngine(_cfg(on=False), rng_seed=0)
    ref.params = eng.params
    a = _prompt(50, seed=3)
    b = list(a)
    b[44] = (b[44] + 1) % 250 + 1       # diverge mid-page (page 5 of 8)
    sp = SamplingParams(max_tokens=6)
    out_a = eng.generate([a], sp)[0]
    out_b = eng.generate([b], sp)[0]    # shares chunks before the split
    assert eng.pool_stats()["prefix_tokens_saved"] > 0
    assert out_a["token_ids"] == ref.generate([a], sp)[0]["token_ids"]
    assert out_b["token_ids"] == ref.generate([b], sp)[0]["token_ids"]
    # re-running A afterwards must be unaffected by B's divergence
    assert out_a["token_ids"] == eng.generate([a], sp)[0]["token_ids"]


def test_eviction_under_pressure_never_touches_live_pages():
    """Allocation under a tight pool evicts only unreferenced LRU pages:
    every page of an in-flight request keeps refcount >= 1 and never sits
    in the eviction pool, while cached pages recycle freely."""
    cfg = _cfg(num_pages=40, max_batch_size=2, max_pages_per_seq=8)
    eng = PagedInferenceEngine(cfg, rng_seed=0)
    ref = PagedInferenceEngine(_cfg(on=False, num_pages=40, max_batch_size=2,
                                    max_pages_per_seq=8), rng_seed=0)
    ref.params = eng.params
    sp = SamplingParams(max_tokens=6)
    space = eng.cache.full.space
    for seed in range(6):               # distinct prompts fill + churn LRU
        p = _prompt(40, seed=10 + seed)
        reqs = [eng.submit(p, sp), eng.submit(_prompt(40, seed=50 + seed),
                                              sp)]
        while not all(r.done for r in reqs):
            eng.step()
            for req in (*eng._prefilling, *eng._active.values()):
                for pid in req.pages:
                    assert space.refs[pid] >= 1
                    assert pid not in space.lru
        got = eng._result(reqs[0])
        want = ref.generate([p], sp)[0]
        assert got["token_ids"] == want["token_ids"]
    st = eng.pool_stats()
    assert st["prefix_evictions"] > 0, st
    # pool accounting intact after churn
    assert st["free_pages"] + st["cached_pages"] == cfg.num_pages - 1
    assert not np.any(space.refs < 0)
    for h, pid in space.hash_to_page.items():
        assert space.page_to_hash[pid] == h
    for pid in space.lru:
        assert space.refs[pid] == 0 and pid in space.page_to_hash


def test_pd_import_dedupes_cached_pages():
    """Exported payloads carry page hashes; a decode replica importing a
    prefix it already holds maps the existing pages instead of
    re-scattering them, and both sequences decode correctly while
    sharing."""
    cfg = _cfg()
    sp = SamplingParams(max_tokens=8)
    prompt = _prompt(37, seed=4)
    single = PagedInferenceEngine(cfg, rng_seed=0)
    expected = single.generate([prompt], sp)[0]

    pre = PagedInferenceEngine(cfg, rng_seed=0)
    dec = PagedInferenceEngine(cfg, rng_seed=0)
    payload = pre.prefill_export(prompt, sp)
    assert len(payload["page_hashes"]) == 37 // cfg.page_size

    r1 = dec.import_prefill(payload, sp)
    assert dec.pool_stats()["prefix_hits"] == 0    # cold import
    r2 = dec.import_prefill(pre.prefill_export(prompt, sp), sp)
    st = dec.pool_stats()
    assert st["prefix_hits"] == 37 // cfg.page_size, st
    # the full prefix pages are literally shared between the two imports
    n_full = 37 // cfg.page_size
    assert r1.pages[:n_full] == r2.pages[:n_full]
    assert r1.pages[n_full:] != r2.pages[n_full:]  # private tails
    dec.run_until_done([r1, r2])
    assert dec._result(r1)["token_ids"] == expected["token_ids"]
    assert dec._result(r2)["token_ids"] == expected["token_ids"]
    # the prefill replica reuses its own cache across exports too
    assert pre.pool_stats()["prefix_tokens_saved"] > 0


def test_multi_turn_reuses_generated_pages():
    """Pages holding GENERATED tokens are published at retirement, so a
    follow-up whose prompt embeds the previous completion (multi-turn
    chat) reuses them. KV exists for all but the last generated token —
    the reusable region extends into the first turn's output."""
    eng = PagedInferenceEngine(_cfg(chunk_size=8), rng_seed=0)
    ref = PagedInferenceEngine(_cfg(on=False, chunk_size=8), rng_seed=0)
    ref.params = eng.params
    turn1 = _prompt(32, seed=5)
    out1 = eng.generate([turn1], SamplingParams(max_tokens=16))[0]
    turn2 = turn1 + out1["token_ids"] + _prompt(8, seed=6)
    saved0 = eng.pool_stats()["prefix_tokens_saved"]
    a = eng.generate([turn2], SamplingParams(max_tokens=6))[0]
    saved = eng.pool_stats()["prefix_tokens_saved"] - saved0
    assert saved > len(turn1), saved   # reuse reaches into generated text
    b = ref.generate([turn2], SamplingParams(max_tokens=6))[0]
    assert a["token_ids"] == b["token_ids"]


@pytest.mark.slow  # 8s composition re-proof; spec decode and prefix cache each stay covered separately
def test_spec_decode_composes_with_prefix_cache():
    """Speculative decoding on a warm prefix cache still reproduces exact
    greedy output."""
    mk = lambda spec, on: PagedInferenceEngine(
        _cfg(on=on, max_batch_size=2, num_pages=96, max_pages_per_seq=24,
             decode_window=4, spec_tokens=12 if spec else 0), rng_seed=0)
    base, spec = mk(False, False), mk(True, True)
    spec.params = base.params
    prompt = [7, 8, 9] * 11             # 33 tokens: spans chunks + pages
    sp = SamplingParams(max_tokens=40)
    want = base.generate([prompt], sp)[0]
    cold = spec.generate([prompt], sp)[0]
    warm = spec.generate([prompt], sp)[0]
    assert want["token_ids"] == cold["token_ids"] == warm["token_ids"]
    assert spec.stats["spec_accepted"] > 0
    assert spec.pool_stats()["prefix_tokens_saved"] > 0


def test_disabled_flag_restores_legacy_accounting():
    eng = PagedInferenceEngine(_cfg(on=False), rng_seed=0)
    eng.generate([_prompt(40, seed=7)], SamplingParams(max_tokens=4))
    st = eng.pool_stats()
    assert st["cached_pages"] == 0
    assert st["free_pages"] == eng.cfg.num_pages - 1
    assert st["prefix_hits"] == st["prefix_misses"] == 0
    assert st["prefix_tokens_saved"] == st["prefix_evictions"] == 0
    assert st["prefix_hit_rate"] == 0.0


class TestPrefixAffinityRouting:
    """serve/handle.py: LLM-style requests rendezvous-hash onto a stable
    replica (warm prefix cache) and yield to least-loaded under skew."""

    @staticmethod
    def _handle(n):
        from types import SimpleNamespace

        from ray_tpu.serve.handle import DeploymentHandle, _ReplicaSet
        h = DeploymentHandle("d", "a", controller=None)
        replicas = [SimpleNamespace(
            _actor_id=SimpleNamespace(hex=lambda i=i: f"replica-{i:02d}"))
            for i in range(n)]
        return h, _ReplicaSet(0, replicas)

    def test_affinity_key_extraction(self):
        from ray_tpu.serve.handle import DeploymentHandle
        key = DeploymentHandle._affinity_key
        assert key(({"prompt": "sys. hello"},), {}) == "tok:sys. hello"
        assert key(({"prompt": [1, 2, 3]},), {}) == "tok:1,2,3"
        # explicit session beats prompt-derived keys
        assert key(({"prompt": "x", "session_id": "s1"},), {}) == "sid:s1"
        assert key(({"prompt": "x"},), {"session_id": "s2"}) == "sid:s2"
        # non-LLM calls keep pure load balancing
        assert key(("just a string",), {}) is None
        assert key((), {}) is None
        assert key(({"other": 1},), {}) is None

    def test_same_prefix_same_replica(self):
        h, replicas = self._handle(4)
        picks = {h._pick(replicas, "tok:shared-system-prompt")
                 for _ in range(8)}
        assert len(picks) == 1
        # a different prefix may land elsewhere, deterministically
        other = {h._pick(replicas, "tok:another-prompt") for _ in range(8)}
        assert len(other) == 1

    def test_affinity_yields_to_least_loaded(self):
        from ray_tpu.serve.handle import _AFFINITY_SLACK
        h, replicas = self._handle(4)
        pref = h._pick(replicas, "tok:hot-prefix")
        replicas.inflight[pref] = _AFFINITY_SLACK + 1
        idle = h._pick(replicas, "tok:hot-prefix")
        assert idle != pref
        assert replicas.inflight[idle] == 0
