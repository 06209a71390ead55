"""Prefill/decode disaggregation (reference:
llm/_internal/serve/deployments/prefill_decode_disagg/prefill_decode_disagg.py
:64 PDProxyServer, :160 build_app)."""
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.models import llama, mla_moe

MODELS = {"llama": llama.llama_tiny, "latent": mla_moe.mla_moe_tiny}


def _cfg(kind="llama"):
    model = MODELS[kind](vocab_size=258, max_seq_len=256)
    return PagedEngineConfig(
        model=model, max_batch_size=4, page_size=8, num_pages=128,
        max_pages_per_seq=16, chunk_size=16)


GREEDY = SamplingParams(max_tokens=12, temperature=0.0)


def _prompt(n, seed=0):
    return list(np.random.RandomState(seed).randint(1, 257, (n,)))


class TestEngineExportImport:
    @pytest.mark.parametrize("kind", list(MODELS))
    def test_pd_matches_single_engine_greedy(self, kind):
        """Disaggregated prefill->transfer->decode must produce EXACTLY the
        tokens a single engine produces under greedy sampling — the KV
        pages carry the full prefill state, whatever pools a layer's
        cache is made of (k and v, or one latent pool)."""
        cfg = _cfg(kind)
        prompt = _prompt(37)  # crosses several chunks and pages

        single = PagedInferenceEngine(cfg, rng_seed=0)
        expected = single.generate([prompt], GREEDY)[0]

        pre = PagedInferenceEngine(cfg, rng_seed=0)
        dec = PagedInferenceEngine(cfg, rng_seed=0)
        payload = pre.prefill_export(prompt, GREEDY)
        assert payload["first_token"] == expected["token_ids"][0]
        assert set(payload["pages"][0]) == set(pre.caches[0])
        # prefill replica released everything: reusable immediately
        # (prefix caching parks retired pages in the cached LRU)
        st = pre.pool_stats()
        assert st["active"] == 0
        assert st["free_pages"] + st["cached_pages"] == cfg.num_pages - 1

        req = dec.import_prefill(payload, GREEDY)
        dec.run_until_done([req])
        out = dec._result(req)
        assert out["token_ids"] == expected["token_ids"], (
            out["token_ids"], expected["token_ids"])

    def test_import_rejects_page_size_mismatch(self):
        cfg = _cfg()
        pre = PagedInferenceEngine(cfg, rng_seed=0)
        payload = pre.prefill_export(_prompt(10), GREEDY)
        payload["page_size"] = 4
        dec = PagedInferenceEngine(cfg, rng_seed=0)
        with pytest.raises(ValueError, match="page_size"):
            dec.import_prefill(payload, GREEDY)

    def test_decode_replica_serves_many_sequentially(self):
        """A decode engine recycles slots/pages across imported prefills."""
        cfg = _cfg()
        pre = PagedInferenceEngine(cfg, rng_seed=0)
        dec = PagedInferenceEngine(cfg, rng_seed=0)
        for seed in range(3):
            payload = pre.prefill_export(_prompt(21, seed), GREEDY)
            req = dec.import_prefill(payload, GREEDY)
            dec.run_until_done([req])
            assert dec._result(req)["token_ids"]
        st = dec.pool_stats()
        assert st["active"] == 0
        assert st["free_pages"] + st["cached_pages"] == cfg.num_pages - 1


class TestPDProxy:
    @pytest.mark.slow  # tier-1 budget: proxy wiring is covered by
    # the PD handoff tests; this full cluster e2e costs ~24s
    def test_proxy_end_to_end(self, ray_start_regular):
        ray = ray_start_regular
        from ray_tpu.llm.pd_disagg import build_pd_proxy

        cfg = _cfg()
        proxy = build_pd_proxy(n_prefill=1, n_decode=1, engine_cfg=cfg)
        prompt = _prompt(29)

        single = PagedInferenceEngine(cfg, rng_seed=0)
        expected = single.generate([prompt], GREEDY)[0]

        out = ray.get(proxy.generate.remote(prompt, GREEDY), timeout=300)
        assert out["token_ids"] == expected["token_ids"]
        stats = ray.get(proxy.proxy_stats.remote(), timeout=60)
        assert stats["requests"] == 1


def _quiesce(store, budget=10.0) -> int:
    """Stable store-object baseline (test_data_streaming.py idiom)."""
    import gc
    import time
    deadline = time.time() + budget
    last, stable_since = store.num_objects(), time.time()
    while time.time() < deadline:
        gc.collect()
        n = store.num_objects()
        if n != last:
            last, stable_since = n, time.time()
        elif time.time() - stable_since > 1.0:
            break
        time.sleep(0.1)
    return last


def _settle(store, base, budget=10.0):
    """Leaked-object count: 0 once the store is back AT (or below — the
    baseline may itself hold a transient about to be collected) the
    pre-channel count; positive residue means the teardown leaked."""
    import gc
    import time
    deadline = time.time() + budget
    while time.time() < deadline:
        gc.collect()
        if store.num_objects() <= base:
            return 0
        time.sleep(0.2)
    return store.num_objects() - base


class TestSealedChannelHandoff:
    """KV payloads cross prefill->decode over a dag/channel.py ring:
    ZERO control dispatches per payload (the wiring calls amortize to ~0
    over the stream), token-identical to the actor-call handoff, and a
    closed channel leaves nothing in the object store."""

    def test_replica_channel_matches_single_engine(self, ray_start_regular):
        ray = ray_start_regular
        from ray_tpu.llm.pd_disagg import DecodeReplica, PrefillReplica

        cfg = _cfg()
        prompts = [_prompt(29, seed=s) for s in range(3)]
        single = PagedInferenceEngine(cfg, rng_seed=0)
        expected = [single.generate([p], GREEDY)[0] for p in prompts]

        pre = ray.remote(PrefillReplica).remote(cfg)
        dec = ray.remote(DecodeReplica).remote(cfg)
        spec = ray.get(dec.open_kv_channel.remote(4, None), timeout=300)
        assert spec, "no shared store: sealed channel cannot engage"
        assert ray.get(pre.connect_kv_channel.remote(spec), timeout=60)
        assert ray.get(pre.has_kv_channel.remote(), timeout=60)

        # the handoff itself: payloads seal into shm, the decode-side
        # drain thread imports them — no per-payload control dispatch
        for i, p in enumerate(prompts):
            ray.get(pre.prefill_chan.remote(p, f"c{i}", GREEDY),
                    timeout=300)
        outs = [ray.get(dec.wait_cid.remote(f"c{i}"), timeout=300)
                for i in range(len(prompts))]
        for out, want in zip(outs, expected):
            assert out["token_ids"] == want["token_ids"]
        ray.get(pre.close_kv_channel.remote(), timeout=60)

    def test_channel_teardown_drains_store(self, ray_start_regular):
        """Open -> stream -> close must sweep every ring slot and ack:
        the sentinel retires the drain thread, which sweeps the ring, so
        the store returns to its baseline object count."""
        import time
        ray = ray_start_regular
        from ray_tpu.core.api import _runtime
        from ray_tpu.llm.pd_disagg import DecodeReplica, PrefillReplica

        cfg = _cfg()
        pre = ray.remote(PrefillReplica).remote(cfg)
        dec = ray.remote(DecodeReplica).remote(cfg)
        # replicas up (and their warmup allocations settled) BEFORE the
        # baseline snapshot
        ray.get([pre.check_health.remote(), dec.check_health.remote()],
                timeout=300)
        store = _runtime().store
        base = _quiesce(store)

        spec = ray.get(dec.open_kv_channel.remote(4, None), timeout=60)
        assert spec
        assert ray.get(pre.connect_kv_channel.remote(spec), timeout=60)
        ray.get(pre.prefill_chan.remote(_prompt(29), "c0", GREEDY),
                timeout=300)
        out = ray.get(dec.wait_cid.remote("c0"), timeout=300)
        assert out["token_ids"]
        ray.get(pre.close_kv_channel.remote(), timeout=60)
        assert _settle(store, base) == 0

    @pytest.mark.slow  # tier-1 budget: two full proxies, ~40s; the
    # replica-level test above covers the handoff fast
    def test_proxy_chan_vs_actor_equivalence(self, ray_start_regular):
        """The PDProxy A/B the bench measures: identical tokens across
        handoff transports, and the channel arm's per-payload control
        dispatches (wiring amortized over the stream) stay <= 0.1."""
        ray = ray_start_regular
        from ray_tpu.llm.pd_disagg import build_pd_proxy

        cfg = _cfg()
        n_requests = 20
        prompts = [_prompt(16 + (i % 3) * 8, seed=i)
                   for i in range(n_requests)]

        def run_arm(use_channels):
            proxy = build_pd_proxy(n_prefill=1, n_decode=1,
                                   engine_cfg=cfg,
                                   use_channels=use_channels)
            outs = ray.get([proxy.generate.remote(p, GREEDY)
                            for p in prompts], timeout=600)
            st = ray.get(proxy.proxy_stats.remote(), timeout=60)
            if use_channels:
                assert st["channels"], "channel wiring did not engage"
                ray.get(proxy.shutdown_channels.remote(), timeout=60)
            return [o["token_ids"] for o in outs]

        assert run_arm(False) == run_arm(True)
        # wiring = open_kv_channel + connect_kv_channel per pair; every
        # payload after that crosses in shm with zero dispatches
        assert 2.0 / n_requests <= 0.1
