"""Serving benchmark: p50 TTFT + decode throughput of the paged engine.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}.
North-star (BASELINE.json config 4): p50 TTFT < 200 ms with continuous
batching — vs_baseline = 0.2 / p50_s (>= 1.0 passes).

Workload: a burst of requests with mixed prompt lengths arrives at once
(worst case for TTFT: every prompt queues behind running decodes); chunked
prefill bounds how long any decode step stalls.

``--metrics``: after the run, print a second JSON line with
``serve.metrics_summary()`` (histogram-derived p50/p95/p99 TTFT,
inter-token, queue wait, KV utilization, token/request counters) — the
telemetry the engines recorded via ray_tpu.util.metrics during the burst.

``--shared-prefix``: run the prefix-cache scenario instead — a burst of
requests sharing one long system prompt with varied tails, caching on vs
off; reports hit rate, prompt tokens saved, and the TTFT delta the cache
buys (paged_engine.py enable_prefix_caching).

``--long-tail``: session-replay scenario for the cache heat plane —
Zipf-distributed sessions whose combined prefix working set is a
multiple of the page pool, so hot sessions stay cached while the long
tail churns through eviction. Emits a warm-TTFT + hit-rate line and a
per-chain heat-histogram line (fold both with ``bench_trend
--history``), counter-verified: per-chain totals == engine aggregates
== flushed ``rtpu_llm_prefix_cache_*`` counters. This is ROADMAP item
4's success-metric harness.

``--long-tail --tiered``: adds an A/B arm replaying the bit-identical
request stream with ``kv_spill`` on (host tier budget 10x the device
pool): evicted prefixes demote to the host spill tier and promote
back on revisit instead of re-prefilling cold. Asserts the two arms'
greedy outputs match token-for-token and counter-verifies the tiered
arm against ``rtpu_llm_prefix_spill_*`` and
``metrics_summary()["cache"]["spill"]``; emits a third JSON line with
the tiered hit rate (vs_baseline = tiered / untiered hit rate).

``--mesh-tp N``: tensor-parallel serving A/B — the same paged engine
single-chip vs sharded over a tp=N NamedSharding mesh
(PagedEngineConfig.mesh). Asserts greedy outputs are token-identical
across arms and that steady-state decode does ZERO involuntary
reshards (the engine's mesh_reshard_bytes counter stays 0: every
committed buffer still carries its pinned sharding after each
dispatch); reports tokens/s + TTFT for both arms and the accounted
host<->device transfer bytes (token ids in, tokens/logits out — the
only bytes that should move). On CPU the mesh is virtual
(forced-host-platform devices), so the ratio measures overhead, not
speedup.

``--pd-chan``: prefill/decode disaggregation handoff A/B — the PDProxy
actor-call handoff (one control dispatch carrying the payload ref per
request) vs the sealed-channel ring (PR 10's RingWriter; KV payloads
seal into shm, the decode replica's drain thread imports them, credit
backpressure throttles prefill admission). Asserts token-identical
outputs across arms and reports handoff control dispatches per KV
payload: the channel arm pays only the per-pair wiring calls,
amortized to ~0 over the request stream.

``--trace out.json``: flight-record the measured section (core/flight.py)
and print a wait/dispatch breakdown JSON line next to the numbers; the
trace file opens in Perfetto/chrome://tracing.
"""
import json
import sys
import time

import jax
import numpy as np

from bench import device_info, require_accelerator


def _emit(result: dict, **dumps_kw) -> None:
    """Print one result line; every line names the device it ran on."""
    device = device_info()
    if device["platform"] == "cpu":
        # counts (hits, dispatches, bytes, tokens) hold on any backend;
        # a time or a rate taken here is not a device measurement
        result = {**result, "note": "CPU run: times and rates are not "
                                    "device metrics"}
    print(json.dumps({**result, "device": device}, **dumps_kw))


def main():
    if "--mesh-tp" in sys.argv:
        # first: it may ask the CPU for virtual devices, which has to
        # happen before the backend starts
        return _mesh_tp(int(sys.argv[sys.argv.index("--mesh-tp") + 1]))
    require_accelerator()
    if "--shared-prefix" in sys.argv:
        return _shared_prefix()
    if "--long-tail" in sys.argv:
        return _long_tail()
    if "--decode-plan" in sys.argv:
        return _decode_plan()
    if "--soak" in sys.argv:
        return _soak()
    if "--multi-tenant" in sys.argv:
        return _multi_tenant()
    if "--pd-chan" in sys.argv:
        return _pd_chan()
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.paged_engine import (
        PagedEngineConfig, PagedInferenceEngine,
    )
    from ray_tpu.models import llama

    on_tpu = device_info()["platform"] == "tpu"
    if on_tpu:
        model = llama.LlamaConfig(
            vocab_size=32000, dim=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, mlp_dim=4096, max_seq_len=2048,
            dtype=jax.numpy.bfloat16, remat=False, use_flash=False)
        # 32 slots: the whole burst admits at once (page pool holds
        # 65k tokens, the burst peaks at ~10k: 7680 prompt + 2048
        # decode); prefill_rows=8 packs the burst's ~45 chunks into ~6
        # dispatches
        cfg = PagedEngineConfig(
            model=model, max_batch_size=32, page_size=64, num_pages=1024,
            max_pages_per_seq=32, chunk_size=256, prefill_rows=8)
        n_requests, max_tokens = 32, 64
        prompt_lens = [64, 128, 256, 512]
    else:  # JAX_PLATFORMS=cpu given: counts only, no device number
        model = llama.llama_tiny(vocab_size=258, max_seq_len=256)
        cfg = PagedEngineConfig(
            model=model, max_batch_size=4, page_size=8, num_pages=128,
            max_pages_per_seq=16, chunk_size=16)
        n_requests, max_tokens = 6, 8
        prompt_lens = [16, 32]

    eng = PagedInferenceEngine(cfg, rng_seed=0)
    rng = np.random.RandomState(0)

    # deploy-time warmup (vLLM-style): compile every program family the
    # burst will dispatch, so no request's TTFT includes an XLA compile
    # (what a mid-burst compile costs on a v5e: not measured)
    warm_s = eng.warmup()
    warm = eng.generate(
        [list(rng.randint(1, model.vocab_size, (prompt_lens[0],)))],
        SamplingParams(max_tokens=4))
    assert warm[0]["token_ids"]

    prompts = [list(rng.randint(1, model.vocab_size,
                                (prompt_lens[i % len(prompt_lens)],)))
               for i in range(n_requests)]
    sp = SamplingParams(max_tokens=max_tokens)

    trace_t0 = time.monotonic_ns()
    t0 = time.perf_counter()
    reqs = [eng.submit(p, sp) for p in prompts]
    while not all(r.done for r in reqs):
        eng.step()
    wall = time.perf_counter() - t0

    ttfts = sorted(r.first_token_t - r.submit_t for r in reqs)
    p50 = ttfts[len(ttfts) // 2]
    p99 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]
    gen_tokens = sum(len(r.out_ids) for r in reqs)
    _emit({
        "metric": "serve_ttft_p50",
        "value": round(p50, 4),
        "unit": (f"s (p99={p99:.3f}s, {gen_tokens / wall:.0f} gen tok/s, "
                 f"{n_requests} reqs burst, warmup={warm_s:.1f}s, "
                 f"{jax.devices()[0].platform})"),
        "vs_baseline": round(0.2 / max(p50, 1e-9), 4),
    })

    if "--metrics" in sys.argv:
        from ray_tpu.serve.metrics import metrics_summary
        _emit({"metric": "serve_metrics_summary",
                          "value": metrics_summary()}, default=str)

    from bench import flight_report, trace_arg
    flight_report(trace_arg(sys.argv), trace_t0)

    _pd_interference(model, cfg, rng, max_tokens, prompt_lens, on_tpu)


def _shared_prefix():
    """Prefix-cache scenario: one shared system prompt + per-request
    tails (the dominant production traffic shape — system prompts,
    few-shot templates, multi-turn histories). Runs the identical burst
    with ``enable_prefix_caching`` on and off and prints ONE JSON line:
    TTFT p50 with caching on, the off-run p50, the cache hit rate and
    prompt tokens not recomputed. vs_baseline = p50_off / p50_on
    (>= 1.0 means caching pays for itself)."""
    import dataclasses

    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.paged_engine import (
        PagedEngineConfig, PagedInferenceEngine,
    )
    from ray_tpu.models import llama

    on_tpu = device_info()["platform"] == "tpu"
    if on_tpu:
        model = llama.LlamaConfig(
            vocab_size=32000, dim=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, mlp_dim=4096, max_seq_len=2048,
            dtype=jax.numpy.bfloat16, remat=False, use_flash=False)
        cfg = PagedEngineConfig(
            model=model, max_batch_size=16, page_size=64, num_pages=1024,
            max_pages_per_seq=32, chunk_size=256, prefill_rows=8)
        n_requests, max_tokens, sys_len, tail_len = 16, 32, 1024, 64
    else:  # JAX_PLATFORMS=cpu given: counts only, no device number
        model = llama.llama_tiny(vocab_size=258, max_seq_len=640)
        cfg = PagedEngineConfig(
            model=model, max_batch_size=8, page_size=16, num_pages=512,
            max_pages_per_seq=24, chunk_size=64)
        n_requests, max_tokens, sys_len, tail_len = 8, 8, 256, 16

    rng = np.random.RandomState(0)
    system = list(rng.randint(1, model.vocab_size, (sys_len,)))
    prompts = [system + list(rng.randint(1, model.vocab_size, (tail_len,)))
               for _ in range(n_requests)]
    sp = SamplingParams(max_tokens=max_tokens)

    def run(enable):
        eng = PagedInferenceEngine(
            dataclasses.replace(cfg, enable_prefix_caching=enable),
            rng_seed=0)
        eng.warmup()
        # warm the cache the way production traffic does: one request
        # with the shared system prompt has already been served
        eng.generate([system + [1] * 4], SamplingParams(max_tokens=2))
        t0 = time.perf_counter()
        reqs = [eng.submit(p, sp) for p in prompts]
        while not all(r.done for r in reqs):
            eng.step()
        wall = time.perf_counter() - t0
        ttfts = sorted(r.first_token_t - r.submit_t for r in reqs)
        outs = [list(r.out_ids) for r in reqs]
        return ttfts[len(ttfts) // 2], wall, eng.pool_stats(), outs

    trace_t0 = time.monotonic_ns()
    p50_on, wall_on, st, outs_on = run(True)
    p50_off, wall_off, _, outs_off = run(False)
    assert outs_on == outs_off, "prefix caching changed greedy outputs"
    from bench import flight_report, trace_arg
    flight_report(trace_arg(sys.argv), trace_t0)
    _emit({
        "metric": "serve_prefix_cache_ttft_p50",
        "value": round(p50_on, 4),
        "unit": (f"s (off={p50_off:.4f}s, hit_rate="
                 f"{st['prefix_hit_rate']:.3f}, tokens_saved="
                 f"{st['prefix_tokens_saved']}, wall {wall_on:.2f}s vs "
                 f"{wall_off:.2f}s off, {n_requests} reqs x {sys_len}-tok "
                 f"shared prefix, {jax.devices()[0].platform})"),
        "vs_baseline": round(p50_off / max(p50_on, 1e-9), 4),
    })


def _long_tail():
    """Cache heat plane scenario: N sessions, request popularity drawn
    Zipf(alpha) so a few sessions dominate while a long tail barely
    repeats; every session's prefix is distinct and the combined
    working set is a multiple of the page pool, forcing the cache to
    keep the hot head resident and churn the tail through eviction.
    Reports the hit rate and warm-vs-cold TTFT (vs_baseline =
    cold_p50 / warm_p50 — what cache residency buys a revisited
    session), plus a per-chain heat histogram. Before printing, the
    per-chain table is counter-verified against the engine aggregates
    AND the flushed rtpu_llm_prefix_cache_* metric store — one page
    event, one attribution, no drift."""
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm import telemetry
    from ray_tpu.llm.paged_engine import (
        PagedEngineConfig, PagedInferenceEngine,
    )
    from ray_tpu.models import llama
    from ray_tpu.util.metrics import collect_store

    on_tpu = device_info()["platform"] == "tpu"
    if on_tpu:
        model = llama.LlamaConfig(
            vocab_size=32000, dim=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, mlp_dim=4096, max_seq_len=2048,
            dtype=jax.numpy.bfloat16, remat=False, use_flash=False)
        cfg = PagedEngineConfig(
            model=model, max_batch_size=16, page_size=64, num_pages=512,
            max_pages_per_seq=16, chunk_size=256, prefill_rows=8)
        n_sessions, n_requests = 96, 400
        prefix_len, tail_len, max_tokens = 512, 64, 8
    else:  # JAX_PLATFORMS=cpu given: counts only, the shape is
        model = llama.llama_tiny(vocab_size=258, max_seq_len=256)
        cfg = PagedEngineConfig(
            model=model, max_batch_size=4, page_size=8, num_pages=192,
            max_pages_per_seq=16, chunk_size=32)
        n_sessions, n_requests = 72, 300
        prefix_len, tail_len, max_tokens = 64, 8, 4
    alpha = 1.1
    tiered = "--tiered" in sys.argv
    # working set: every session's prefix pages + a decode page; the
    # pool holds a fraction of it, so residency is earned by heat
    pages_per_prefix = prefix_len // cfg.page_size
    working_set = n_sessions * pages_per_prefix

    trace_t0 = time.monotonic_ns()

    def _run_arm(acfg, spill_budget_pages=None):
        # fresh rng per arm, same seed: every arm replays a
        # bit-identical session/order/tail stream
        rng = np.random.RandomState(0)
        sessions = [list(rng.randint(1, model.vocab_size,
                                     (prefix_len,)))
                    for _ in range(n_sessions)]
        # Zipf-ranked popularity over the session ids
        weights = 1.0 / np.arange(1, n_sessions + 1) ** alpha
        weights /= weights.sum()
        order = rng.choice(n_sessions, size=n_requests, p=weights)
        arm = PagedInferenceEngine(acfg, rng_seed=0)
        if spill_budget_pages is not None:
            arm.spill.max_bytes = (spill_budget_pages
                                   * arm.spill.page_nbytes)
        arm.warmup()
        sp = SamplingParams(max_tokens=max_tokens, temperature=0.0)
        seen: set = set()
        warm, cold, outs = [], [], []
        t0 = time.perf_counter()
        for sid in order:
            ids = sessions[sid] + list(
                rng.randint(1, model.vocab_size, (tail_len,)))
            r = arm.submit(ids, sp)
            while not r.done:
                arm.step()
            ttft = r.first_token_t - r.submit_t
            (warm if sid in seen else cold).append(ttft)
            seen.add(sid)
            outs.append(tuple(r.out_ids))
        arm_wall = time.perf_counter() - t0
        # force a final telemetry publish (chain gauges rate-limited)
        arm._chain_ship_t = 0.0
        telemetry.on_step(arm)
        return arm, warm, cold, outs, arm_wall

    eng, warm_ttfts, cold_ttfts, outs_u, wall = _run_arm(cfg)

    # -- counter verification: table == engine.stats == metric store -- #
    st, totals = eng.stats, eng.chains.totals()
    for tk, sk in (("hits", "prefix_hits"), ("misses", "prefix_misses"),
                   ("evictions", "prefix_evictions"),
                   ("tokens_saved", "prefix_tokens_saved")):
        assert totals[tk] == st[sk], \
            f"chain-table drift: {tk}={totals[tk]} vs {sk}={st[sk]}"
    store = collect_store()

    def _shipped(name):
        rec = store.get(name)
        return sum(rec["series"].values()) if rec else 0.0
    for name, sk in (
            ("rtpu_llm_prefix_cache_hits_total", "prefix_hits"),
            ("rtpu_llm_prefix_cache_misses_total", "prefix_misses"),
            ("rtpu_llm_prefix_cache_evictions_total",
             "prefix_evictions"),
            ("rtpu_llm_prefix_cache_tokens_saved_total",
             "prefix_tokens_saved")):
        assert int(_shipped(name)) == st[sk], \
            f"metric-store drift: {name}={_shipped(name)} vs {st[sk]}"

    acct = eng.prefix_accounting()
    warm_p50 = sorted(warm_ttfts)[len(warm_ttfts) // 2]
    cold_p50 = sorted(cold_ttfts)[len(cold_ttfts) // 2]
    _emit({
        "metric": "serve_longtail_warm_ttft_p50",
        "value": round(warm_p50, 4),
        "unit": (f"s (cold={cold_p50:.4f}s, hit_rate="
                 f"{acct['hit_rate']:.3f}, tokens_saved="
                 f"{acct['tokens_saved']}, evictions="
                 f"{acct['evictions']}, {n_requests} reqs over "
                 f"{n_sessions} zipf({alpha}) sessions, working set "
                 f"{working_set}p vs pool {cfg.num_pages}p, "
                 f"wall {wall:.1f}s, {jax.devices()[0].platform})"),
        "vs_baseline": round(cold_p50 / max(warm_p50, 1e-9), 4),
    })
    # heat histogram: how concentrated cache value is across chains —
    # the shape tiering will exploit (spill the cold right half)
    rows = eng.chains.top(n_sessions)
    hist = {"buckets": [0, 1, 4, 16, 64, 256],
            "chains": [0] * 6, "hits": [0] * 6}
    for row in rows:
        b = sum(1 for lo in hist["buckets"][1:] if row["hits"] >= lo)
        hist["chains"][b] += 1
        hist["hits"][b] += row["hits"]
    _emit({
        "metric": "serve_longtail_heat_histogram",
        "value": hist,
        "unit": (f"chains/hits per hit-count bucket; tracked="
                 f"{eng.chains.stats()['tracked']}, overflow_assign="
                 f"{eng.chains.stats()['overflow_assignments']}, "
                 f"table_max_bytes={eng.chains.stats()['max_bytes']}"),
        "vs_baseline": None,
    })

    if tiered:
        # A/B arm: same engine config + kv_spill on, host budget 10x
        # the device pool — evicted prefixes demote to the host tier
        # instead of dying, and a revisit promotes them back
        # (bit-identical pages) instead of re-prefilling cold.
        import dataclasses
        from ray_tpu.serve.metrics import metrics_summary
        budget_pages = 10 * cfg.num_pages
        teng, t_warm, t_cold, outs_t, t_wall = _run_arm(
            dataclasses.replace(cfg, kv_spill=True),
            spill_budget_pages=budget_pages)
        # promoted pages must be bit-identical to a cold prefill:
        # greedy outputs of the two arms match token-for-token
        assert outs_t == outs_u, \
            "tiered arm outputs diverged from untiered arm"
        # counter-verify the tiered arm: chain-table sums == engine
        # aggregates == live tier residence == shipped
        # rtpu_llm_prefix_spill_* store == metrics_summary() fold
        # (the untiered arm ships zero spill events, so the store's
        # spill rows are the tiered arm's alone)
        ts, ttot = teng.stats, teng.chains.totals()
        tacct = teng.prefix_accounting()
        assert ttot["spilled_pages"] == teng.spill.resident_pages()
        assert ttot["promotions"] == ts["spill_promotions"]
        assert tacct["spill_resident_pages"] == \
            teng.spill.resident_pages()
        assert tacct["spill_demotions"] == ts["spill_demotions"]
        store2 = collect_store()

        def _shipped2(name):
            rec = store2.get(name)
            return sum(rec["series"].values()) if rec else 0.0
        for name, sk in (
                ("rtpu_llm_prefix_spill_demotions_total",
                 "spill_demotions"),
                ("rtpu_llm_prefix_spill_promotions_total",
                 "spill_promotions"),
                ("rtpu_llm_prefix_spill_expired_total",
                 "spill_expired"),
                ("rtpu_llm_prefix_spill_pages_total", "spill_pages"),
                ("rtpu_llm_prefix_spill_bytes_total", "spill_bytes")):
            assert int(_shipped2(name)) == ts[sk], \
                f"spill metric drift: {name}={_shipped2(name)} " \
                f"vs {sk}={ts[sk]}"
        fold = metrics_summary()["cache"]["spill"]
        assert fold["demotions"] == ts["spill_demotions"]
        assert fold["promotions"] == ts["spill_promotions"]
        assert ts["spill_promotions"] > 0, \
            "tiered arm never promoted — scenario broken"
        t_warm_p50 = sorted(t_warm)[len(t_warm) // 2]
        t_cold_p50 = sorted(t_cold)[len(t_cold) // 2]
        hit_gain = tacct["hit_rate"] / max(acct["hit_rate"], 1e-9)
        _emit({
            "metric": "serve_longtail_tiered_hit_rate",
            "value": round(tacct["hit_rate"], 4),
            "unit": (f"hit rate with kv_spill on vs "
                     f"{acct['hit_rate']:.3f} untiered (spill budget "
                     f"{budget_pages}p = 10x pool, demotions="
                     f"{ts['spill_demotions']}, promotions="
                     f"{ts['spill_promotions']}, expired="
                     f"{ts['spill_expired']}, tokens_saved="
                     f"{tacct['tokens_saved']} vs "
                     f"{acct['tokens_saved']}, warm p50 "
                     f"{t_warm_p50:.4f}s vs {warm_p50:.4f}s, cold p50 "
                     f"{t_cold_p50:.4f}s vs {cold_p50:.4f}s, outputs "
                     f"bit-identical, wall {t_wall:.1f}s vs "
                     f"{wall:.1f}s, {jax.devices()[0].platform})"),
            "vs_baseline": round(hit_gain, 4),
        })

    from bench import flight_report, trace_arg
    flight_report(trace_arg(sys.argv), trace_t0)


def _decode_plan():
    """Static decode plan scenario: stream completions through a REAL
    serve deployment (handle -> replica -> engine) with the sealed-ring
    channel transport on vs the per-chunk stream_next poll transport,
    and report CONTROL-PLANE dispatches per streamed item — a count, not
    a time, so it is machine-independent. The plan's whole point is
    ~0 dispatches/token in steady state (one setup call per request);
    the poll transport pays roughly one actor call per chunk batch.
    Outputs are asserted identical across transports. CPU-only: device
    speed is irrelevant to dispatch economy, so no accelerator probe."""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.config import cfg as rcfg
    from ray_tpu.llm.paged_engine import PagedEngineConfig
    from ray_tpu.llm.serving import LLMConfig, build_llm_deployment
    from ray_tpu.models import llama

    rcfg.override(worker_prestart=2)
    ray_tpu.init(num_cpus=2, object_store_memory=512 << 20)
    ecfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=256),
        max_batch_size=4, page_size=8, num_pages=128,
        max_pages_per_seq=16, chunk_size=16)
    app = build_llm_deployment(
        LLMConfig(model_id="tiny", engine=ecfg, warmup=False))
    h = serve.run(app, name="decode-plan")
    hs = h.options(method_name="completions_stream", stream=True)
    prompts = ["the quick brown fox", "jumps over", "a lazy dog today",
               "serving tokens fast"]

    def run_mode(plan: bool):
        rcfg.override(serve_static_decode_plan=plan)
        outs = []
        for p in prompts:
            gen = hs.remote({"prompt": p, "max_tokens": 16,
                             "temperature": 0.0})
            outs.append("".join(c["choices"][0]["text"] for c in gen))
        return outs

    trace_t0 = time.monotonic_ns()
    outs_on = run_mode(True)
    outs_off = run_mode(False)
    assert outs_on == outs_off, \
        "static decode plan changed streamed outputs"

    from ray_tpu.serve.metrics import metrics_summary
    st = metrics_summary().get("stream", {})
    chan, poll = st.get("chan", {}), st.get("poll", {})
    chan_rate = chan.get("dispatches_per_item")
    poll_rate = poll.get("dispatches_per_item")
    _emit({
        "metric": "serve_stream_dispatches_per_token",
        "value": None if chan_rate is None else round(chan_rate, 4),
        "unit": (f"control dispatches per streamed item, static plan "
                 f"(poll transport={None if poll_rate is None else round(poll_rate, 4)}; "
                 f"chan {chan.get('dispatches', 0):.0f} disp/"
                 f"{chan.get('items', 0):.0f} items, poll "
                 f"{poll.get('dispatches', 0):.0f}/"
                 f"{poll.get('items', 0):.0f}; outputs identical)"),
        # >= 1 means the static plan beats polling; 'amortized zero'
        # shows up as a large ratio (setup-only vs per-chunk calls)
        "vs_baseline": (None if not chan_rate or poll_rate is None
                        else round(poll_rate / chan_rate, 3)),
    })
    from bench import flight_report, trace_arg
    flight_report(trace_arg(sys.argv), trace_t0)
    serve.shutdown()
    ray_tpu.shutdown()


def _mesh_tp(tp: int):
    """Tensor-parallel serving A/B (see module docstring --mesh-tp)."""
    import os
    if os.environ.get("JAX_PLATFORMS") == "cpu" and \
            "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # the CPU was asked for by name: give it virtual devices, before
        # the backend initialises
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={max(8, tp)}").strip()
    require_accelerator()
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.paged_engine import (
        PagedEngineConfig, PagedInferenceEngine,
    )
    from ray_tpu.models import llama

    if len(jax.devices()) < tp:
        _emit({
            "metric": "serve_mesh_tp_decode_tokens_per_s", "value": None,
            "unit": f"tok/s (need {tp} devices, have {len(jax.devices())})",
            "vs_baseline": None})
        raise SystemExit(3)

    model = llama.llama_tiny(vocab_size=258, max_seq_len=256)
    base = dict(model=model, max_batch_size=4, page_size=8, num_pages=128,
                max_pages_per_seq=16, chunk_size=16)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 258, (n,))) for n in (16, 32, 24, 16)]
    sp = SamplingParams(max_tokens=24, temperature=0.0)

    def run_arm(mesh):
        eng = PagedInferenceEngine(
            PagedEngineConfig(mesh=mesh, **base), rng_seed=0)
        eng.warmup(families=("prefill", "decode"))
        t0 = time.perf_counter()
        outs = eng.generate(prompts, sp)
        wall = time.perf_counter() - t0
        toks = sum(len(o["token_ids"]) for o in outs)
        ttfts = sorted(o["ttft_s"] for o in outs)
        return (outs, toks / wall, ttfts[len(ttfts) // 2],
                dict(eng.stats))

    trace_t0 = time.monotonic_ns()
    outs1, tps1, ttft1, st1 = run_arm(None)
    outsN, tpsN, ttftN, stN = run_arm({"tp": tp})
    assert [o["token_ids"] for o in outs1] == \
        [o["token_ids"] for o in outsN], "mesh changed greedy outputs"
    assert stN["mesh_reshard_bytes"] == 0, \
        f"involuntary reshards: {stN['mesh_reshard_bytes']} bytes"
    assert st1["mesh_dispatches"] == 0  # off-mesh arm counts nothing
    _emit({
        "metric": "serve_mesh_tp_decode_tokens_per_s",
        "value": round(tpsN, 1),
        "unit": (f"tok/s on tp={tp} NamedSharding mesh (single-chip "
                 f"{tps1:.1f} tok/s; ttft p50 {ttftN:.4f}s vs "
                 f"{ttft1:.4f}s; outputs token-identical; "
                 f"{stN['mesh_dispatches']} dispatches moved "
                 f"{stN['mesh_input_bytes']}B in / "
                 f"{stN['mesh_output_bytes']}B out, reshard_bytes=0; "
                 f"{jax.devices()[0].platform} virtual mesh)"),
        "vs_baseline": round(tpsN / max(tps1, 1e-9), 3),
    })
    from bench import flight_report, trace_arg
    flight_report(trace_arg(sys.argv), trace_t0)


def _pd_chan():
    """Sealed-channel PD handoff A/B (see module docstring --pd-chan)."""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import ray_tpu
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.paged_engine import (
        PagedEngineConfig, PagedInferenceEngine,
    )
    from ray_tpu.llm.pd_disagg import build_pd_proxy
    from ray_tpu.models import llama

    ray_tpu.init(num_cpus=2, object_store_memory=512 << 20)
    model = llama.llama_tiny(vocab_size=258, max_seq_len=256)
    cfg = PagedEngineConfig(
        model=model, max_batch_size=4, page_size=8, num_pages=128,
        max_pages_per_seq=16, chunk_size=16)
    rng = np.random.RandomState(0)
    n_requests = 30
    prompts = [list(rng.randint(1, 258, (16 + (i % 3) * 8,)))
               for i in range(n_requests)]
    sp = SamplingParams(max_tokens=12, temperature=0.0)

    def run_arm(use_channels):
        proxy = build_pd_proxy(n_prefill=1, n_decode=1, engine_cfg=cfg,
                               use_channels=use_channels)
        t0 = time.perf_counter()
        outs = ray_tpu.get(
            [proxy.generate.remote(p, sp) for p in prompts], timeout=600)
        wall = time.perf_counter() - t0
        st = ray_tpu.get(proxy.proxy_stats.remote(), timeout=60)
        if use_channels:
            assert st["channels"], "sealed-channel wiring did not engage"
            ray_tpu.get(proxy.shutdown_channels.remote(), timeout=60)
        return outs, wall, st

    trace_t0 = time.monotonic_ns()
    outs_actor, wall_actor, _ = run_arm(False)
    outs_chan, wall_chan, _ = run_arm(True)
    assert [o["token_ids"] for o in outs_actor] == \
        [o["token_ids"] for o in outs_chan], \
        "channel handoff changed outputs"
    # handoff control dispatches per KV payload: the actor arm pays one
    # decode-side call carrying the payload ref per request; the channel
    # arm pays only the wiring (open_kv_channel + connect_kv_channel per
    # prefill->decode pair), amortized across the stream — the payloads
    # themselves cross in shm with zero dispatches.
    actor_rate = 1.0
    chan_rate = 2.0 / n_requests
    assert chan_rate <= 0.1, chan_rate
    _emit({
        "metric": "serve_pd_chan_dispatches_per_handoff",
        "value": round(chan_rate, 4),
        "unit": (f"control dispatches per KV payload, sealed-channel arm "
                 f"(actor-call arm={actor_rate}; {n_requests} reqs, "
                 f"outputs token-identical; wall {wall_chan:.1f}s vs "
                 f"{wall_actor:.1f}s actor, cpu)"),
        "vs_baseline": round(actor_rate / chan_rate, 1),
    })
    from bench import flight_report, trace_arg
    flight_report(trace_arg(sys.argv), trace_t0)
    ray_tpu.shutdown()


def _soak():
    """Front-door soak (serve/frontdoor/): a REAL serve deployment —
    2 LLM replicas behind 2 controller-managed proxies with SLO-aware
    admission — slammed with thousands of concurrent HTTP connections.
    CPU-only by design: the gates under test (zero 500s, sheds are
    429-with-Retry-After ONLY, bounded p99 for admitted traffic,
    cross-replica prefix-directory hits bit-identical to cold prefill)
    are data-plane properties, not device speed. Prints the headline
    JSON line (vs_baseline = 1.0 iff every gate holds) plus an
    admission-counter line and a ``serve_soak_slo_verdict`` line — the
    shipped serve SLOs evaluated against the soak's own TSDB capture
    (the burn engine must flag the deliberate shed storm and clear the
    zero-500s error ratio); ``bench_trend --history`` folds all three.

    Flags: ``--connections N`` (default 2500), ``--quick`` (400)."""
    import asyncio
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.config import cfg as rcfg
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.paged_engine import (PagedEngineConfig,
                                          PagedInferenceEngine)
    from ray_tpu.llm.serving import LLMConfig, build_llm_deployment
    from ray_tpu.models import llama

    conns = 400 if "--quick" in sys.argv else 2500
    if "--connections" in sys.argv:
        conns = int(sys.argv[sys.argv.index("--connections") + 1])

    # fast TSDB tick so the soak's own capture carries enough points
    # for the SLO burn windows (fast-short = 20 ticks = 10 s here)
    rcfg.override(worker_prestart=2, tsdb_scrape_s=0.5)
    ray_tpu.init(num_cpus=2, object_store_memory=512 << 20)
    ecfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=256),
        max_batch_size=8, page_size=8, num_pages=256,
        max_pages_per_seq=24, chunk_size=16)
    app = build_llm_deployment(
        LLMConfig(model_id="tiny", engine=ecfg, num_replicas=2,
                  max_ongoing_requests=16, warmup=False))
    serve.run(app, name="default", http_port=18511, num_proxies=2)

    ports = sorted(p["port"] for p in serve.status()["proxies"])
    assert len(ports) >= 2, "soak requires >= 2 proxies"

    system = ("You are a helpful, precise assistant. Use short answers "
              "and cite nothing. ") * 2
    rng = np.random.RandomState(0)
    fixed_prompt = system + "What is 2+2?"

    # prime: a small warm wave serves the shared system prefix on one
    # replica and lets it publish to the prefix directory (production
    # steady state) — the storm's spillover traffic on the OTHER
    # replica then admission-matches via cross-replica import
    import json as _json
    import urllib.request
    for _ in range(2):
        req = urllib.request.Request(
            f"http://127.0.0.1:{ports[0]}/default", method="POST",
            data=_json.dumps({"prompt": fixed_prompt, "max_tokens": 4,
                              "temperature": 0.0}).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=120).read()
    time.sleep(1.0)     # > cfg.serve_prefix_publish_s

    trace_t0 = time.monotonic_ns()

    async def run_load():
        import aiohttp
        out = []
        sem = asyncio.Semaphore(conns)          # all in flight at once

        async def one(session, i):
            port = ports[i % len(ports)]
            prompt = (fixed_prompt if i % 7 == 0 else
                      system + f"Question {rng.randint(1e6)}?")
            t0 = time.perf_counter()
            try:
                async with sem, session.post(
                        f"http://127.0.0.1:{port}/default",
                        json={"prompt": prompt, "max_tokens": 4,
                              "temperature": 0.0},
                        timeout=aiohttp.ClientTimeout(total=120)) as r:
                    body = await r.json()
                    out.append((r.status, time.perf_counter() - t0,
                                r.headers.get("Retry-After"),
                                body if i % 7 == 0 else None))
            except Exception as e:  # noqa: BLE001 — a gate failure
                out.append(("exc:" + type(e).__name__,
                            time.perf_counter() - t0, None, None))

        connector = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=connector) as s:
            await asyncio.gather(*(one(s, i) for i in range(conns)))
        return out

    t0 = time.perf_counter()
    results = asyncio.new_event_loop().run_until_complete(run_load())
    wall = time.perf_counter() - t0

    statuses = [r[0] for r in results]
    n200 = statuses.count(200)
    n429 = statuses.count(429)
    n_other = len(statuses) - n200 - n429
    bare_500s = sum(1 for s in statuses if s == 500)
    shed_clean = all(ra is not None for s, _t, ra, _b in results
                     if s == 429)
    admitted_lat = sorted(t for s, t, _ra, _b in results if s == 200)
    p99 = admitted_lat[int(len(admitted_lat) * 0.99)] if admitted_lat \
        else None
    p50 = admitted_lat[len(admitted_lat) // 2] if admitted_lat else None

    # cross-replica prefix directory: counter-verified hits, and the
    # served text for the fixed prompt is BIT-IDENTICAL to a cold
    # local prefill (same config, same seed, greedy)
    time.sleep(3.0)     # worker metric flush cadence
    ms = serve.metrics_summary()
    pd = ms.get("prefix_directory") or {}
    dir_hits = pd.get("hits", 0)
    served_texts = {b["choices"][0]["text"] for s, _t, _ra, b in results
                    if s == 200 and b}
    cold = PagedInferenceEngine(ecfg, rng_seed=0)
    cold_out = cold.generate([cold.tokenizer.encode(fixed_prompt)],
                             SamplingParams(max_tokens=4))[0]
    bit_identical = served_texts == {cold_out["text"]} if served_texts \
        else False

    gates = {
        "zero_500s": bare_500s == 0 and n_other == 0,
        "sheds_are_429_with_retry_after": shed_clean,
        "admitted_p99_bounded": p99 is not None and p99 < 60.0,
        "prefix_directory_hits": dir_hits > 0,
        "bit_identical_to_cold_prefill": bit_identical,
    }
    _emit({
        "metric": "serve_soak_admitted_p99",
        "value": None if p99 is None else round(p99, 4),
        "unit": (f"s e2e over {conns} concurrent conns x 2 proxies "
                 f"(p50={None if p50 is None else round(p50, 4)}s, "
                 f"{n200} ok / {n429} shed / {n_other} other in "
                 f"{wall:.1f}s, dir_hits={dir_hits:.0f}, "
                 f"imported_pages="
                 f"{pd.get('imported_pages', 0):.0f}, "
                 f"gates={gates})"),
        "vs_baseline": 1.0 if all(gates.values()) else 0.0,
    })
    _emit({"metric": "serve_soak_admission",
                      "value": ms.get("admission"),
                      "unit": "admitted/shed counters + queue waits"},
                     default=str)

    # SLO verdict against the soak's OWN TSDB capture: the burn engine
    # must DETECT the deliberate shed storm (shed_ratio burning) while
    # correctly reporting the zero-500s run healthy (error_ratio ok) —
    # a counter-verified exercise of the whole obs pipeline under real
    # overload. Folded round-over-round by bench_trend --history.
    from ray_tpu import state as state_mod
    from ray_tpu.core import runtime as rt_mod
    rt = rt_mod.get_runtime_if_exists()
    if rt is not None and getattr(rt, "obs", None) is not None:
        rt.obs.scrape_once()    # fold the final post-load counters
    slo = state_mod.slo_report()
    rows = {r["slo"]: r for r in slo.get("slos", [])}
    shed_row = rows.get("shed_ratio", {})
    err_row = rows.get("error_ratio", {})
    slo_gates = {
        "all_shipped_slos_evaluated": len(rows) >= 4,
        "shed_storm_detected": (shed_row.get("state") != "ok"
                                or (shed_row.get("burn_fast")
                                    or [0.0])[0] > 1.0),
        "error_ratio_ok": err_row.get("state", "ok") == "ok",
    }
    _emit({
        "metric": "serve_soak_slo_verdict",
        "value": round((shed_row.get("burn_fast") or [0.0])[0], 3),
        "unit": (f"shed_ratio fast-short burn rate (states="
                 f"{slo.get('states')}, "
                 f"tsdb {slo.get('tsdb', {}).get('series', 0)} series/"
                 f"{slo.get('tsdb', {}).get('ticks', 0)} ticks, "
                 f"slo_gates={slo_gates})"),
        "vs_baseline": 1.0 if all(slo_gates.values()) else 0.0,
    })
    from bench import flight_report, trace_arg
    flight_report(trace_arg(sys.argv), trace_t0)
    serve.shutdown()
    ray_tpu.shutdown()
    raise SystemExit(0 if all(gates.values()) else 1)


def _multi_tenant():
    """Multi-tenant LoRA scenario (llm/multilora + tenant front door),
    CPU-only by design — every gate is a COUNT or a status-code
    property, not a device speed:

    1. **dispatch economy**: the same burst over ONE shared paged base
       model costs the same device dispatches per token whether its
       rows are 1 tenant or N tenants (counter-verified via the
       engine's rtpu_llm_*-backed stats, like --decode-plan) — the
       slot table multiplexes adapters into shared programs, never
       extra dispatches. Each tenant's greedy output is asserted
       bit-identical to its merged-engine reference while we're at it.
    2. **fairness under overload**: a REAL serve deployment behind an
       admission-gated proxy; a heavy tenant floods it while a light
       tenant trickles. Gates: the heavy tenant sheds tenant_quota
       429s (all with Retry-After), the light tenant's requests ALL
       admit with bounded latency, zero bare 500s, and the per-tenant
       split is counter-verified in metrics_summary()["tenants"].

    Prints ONE JSON line; vs_baseline = 1.0 iff every gate holds.
    """
    import asyncio
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from ray_tpu.llm import SamplingParams, lora
    from ray_tpu.llm.paged_engine import (PagedEngineConfig,
                                          PagedInferenceEngine)
    from ray_tpu.models import llama

    model = llama.llama_tiny(vocab_size=258, max_seq_len=256)
    ecfg = dict(max_batch_size=8, page_size=8, num_pages=256,
                max_pages_per_seq=24, chunk_size=16)
    n_tenants = 4
    base = None   # engine seeds its own params from rng_seed

    # -- part 1: dispatches/token flat in tenant count -------------------
    rng = np.random.RandomState(0)
    adapters = [lora.random_adapter(
        jax.random.PRNGKey(10 + i), model, rank=4, alpha=32.0,
        targets=("wq", "wv", "lm_head")) for i in range(n_tenants)]
    prompts = [list(rng.randint(1, 250, (24 if i % 2 else 40,)))
               for i in range(12)]
    sp = SamplingParams(max_tokens=16)

    def run_tenants(k: int):
        eng = PagedInferenceEngine(PagedEngineConfig(
            model=model, max_adapters=n_tenants + 1, lora_rank=4,
            **ecfg), params=base, rng_seed=0)
        # pin every request to exactly max_tokens (instance-level EOS
        # shadow): the dispatch comparison needs IDENTICAL output
        # shapes across the two runs — with live EOS, different
        # tenants stop at different steps and the tail's thinner
        # decode windows shift dispatches/token for reasons that have
        # nothing to do with multiplexing
        eng.tokenizer.eos_id = None
        for i in range(n_tenants):
            eng.load_adapter_slot(i + 1, adapters[i])
        reqs = []
        for i, p in enumerate(prompts):
            s = (i % k) + 1
            reqs.append(eng.submit(p, sp, adapter_slot=s,
                                   prefix_salt=bytes([s])))
        while not all(r.done for r in reqs):
            eng.step()
        st = eng.stats
        disp = (st["prefill_dispatches"] + st["decode_dispatches"]
                + st["spec_dispatches"])
        return disp / max(st["tokens_out"], 1), reqs, eng

    dpt_1, _, _ = run_tenants(1)
    dpt_n, reqs_n, eng_n = run_tenants(n_tenants)
    ratio = dpt_n / max(dpt_1, 1e-9)

    # per-tenant greedy parity against the merged oracle
    merged_ok = True
    for t in range(n_tenants):
        ref_eng = PagedInferenceEngine(
            PagedEngineConfig(model=model, **ecfg),
            params=lora.merge(PagedInferenceEngine(
                PagedEngineConfig(model=model, **ecfg),
                rng_seed=0).params, adapters[t]), rng_seed=0)
        ref_eng.tokenizer.eos_id = None   # same full-length contract
        idx = t   # first request of tenant t+1 in the round-robin
        ref = ref_eng.submit(prompts[idx], sp)
        while not ref.done:
            ref_eng.step()
        merged_ok &= (reqs_n[idx].out_ids == ref.out_ids)

    # -- part 2: fairness split under overload ---------------------------
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.config import cfg as rcfg
    from ray_tpu.llm.serving import LLMConfig, build_llm_deployment

    rcfg.override(worker_prestart=2)
    ray_tpu.init(num_cpus=2, object_store_memory=512 << 20)
    app = build_llm_deployment(LLMConfig(
        model_id="tiny",
        engine=PagedEngineConfig(model=model, **ecfg),
        num_replicas=1, max_ongoing_requests=8, warmup=False))
    serve.run(app, name="default", http_port=18521, num_proxies=1)
    port = serve.status()["proxies"][0]["port"]

    trace_t0 = time.monotonic_ns()
    heavy_n, light_n = 60, 8
    results = {"heavy": [], "light": []}

    async def run_load():
        import aiohttp

        async def one(session, tenant, i):
            t0 = time.perf_counter()
            try:
                async with session.post(
                        f"http://127.0.0.1:{port}/default",
                        json={"prompt": f"q {tenant} {i}",
                              "max_tokens": 4, "tenant": tenant},
                        timeout=aiohttp.ClientTimeout(total=120)) as r:
                    await r.read()
                    results[tenant].append(
                        (r.status, time.perf_counter() - t0,
                         r.headers.get("Retry-After")))
            except Exception as e:  # noqa: BLE001 — a gate failure
                results[tenant].append(
                    ("exc:" + type(e).__name__,
                     time.perf_counter() - t0, None))

        async def light_trickle(session):
            for i in range(light_n):
                await one(session, "light", i)
                await asyncio.sleep(0.05)

        connector = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=connector) as s:
            await asyncio.gather(
                light_trickle(s),
                *(one(s, "heavy", i) for i in range(heavy_n)))

    t0 = time.perf_counter()
    asyncio.new_event_loop().run_until_complete(run_load())
    wall = time.perf_counter() - t0

    time.sleep(3.0)     # worker metric flush cadence
    ms = serve.metrics_summary()
    tstats = ms.get("tenants", {})
    h_status = [r[0] for r in results["heavy"]]
    l_status = [r[0] for r in results["light"]]
    l_lat = sorted(t for s, t, _ra in results["light"] if s == 200)
    l_p99 = l_lat[int(len(l_lat) * 0.99)] if l_lat else None
    shed_clean = all(ra is not None for s, _t, ra in results["heavy"]
                     if s == 429)
    bare_500s = h_status.count(500) + l_status.count(500)
    gates = {
        "dispatches_flat_in_tenants": abs(ratio - 1.0) < 0.05,
        "tenant_outputs_match_merged": merged_ok,
        "heavy_tenant_shed_429": h_status.count(429) > 0 and shed_clean,
        "light_tenant_all_admitted": (l_status.count(200) == light_n
                                      and l_status.count(429) == 0),
        "light_p99_bounded": l_p99 is not None and l_p99 < 30.0,
        "zero_500s": bare_500s == 0,
        "tenant_split_counter_verified": (
            tstats.get("heavy", {}).get("shed", 0) > 0
            and tstats.get("light", {}).get("shed", 1) == 0
            and tstats.get("light", {}).get("admitted", 0) >= light_n),
    }
    _emit({
        "metric": "serve_multi_tenant_light_p99",
        "value": None if l_p99 is None else round(l_p99, 4),
        "unit": (f"s light-tenant e2e under a {heavy_n}-conn heavy "
                 f"flood ({n_tenants} tenants x 1 base model; "
                 f"dispatches/token {dpt_n:.4f} vs {dpt_1:.4f} "
                 f"single-tenant = {ratio:.3f}x; heavy "
                 f"{h_status.count(200)} ok / {h_status.count(429)} "
                 f"shed, light {l_status.count(200)}/{light_n} ok in "
                 f"{wall:.1f}s; tenants={tstats}; gates={gates})"),
        "vs_baseline": 1.0 if all(gates.values()) else 0.0,
    })
    from bench import flight_report, trace_arg
    flight_report(trace_arg(sys.argv), trace_t0)
    serve.shutdown()
    ray_tpu.shutdown()
    raise SystemExit(0 if all(gates.values()) else 1)


def _pd_interference(model, cfg, rng, max_tokens, prompt_lens, on_tpu):
    """Decode-stall comparison: max inter-token gap of an ACTIVE decode
    while a long prompt prefills — colocated single engine vs
    disaggregated decode replica (llm/pd_disagg.py; reference:
    prefill_decode_disagg.py:64). Disaggregation exists precisely to keep
    long prefills from stalling running decodes. NOTE: with one chip both
    PD replicas still share the device, so the PD number here bounds
    interference from above; separate-chip deployments only improve it."""
    import time

    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.paged_engine import PagedInferenceEngine

    long_len = prompt_lens[-1] * 2
    short = list(rng.randint(1, model.vocab_size, (prompt_lens[0],)))
    long_p = list(rng.randint(1, model.vocab_size, (long_len,)))
    sp = SamplingParams(max_tokens=max_tokens, temperature=0.0)

    def max_gap(engine, req, inject):
        """Step until req done; inject() once after 2 tokens; return the
        max wall gap between consecutive generated tokens."""
        gaps, last, seen, injected = [], None, 0, False
        while not req.done:
            engine.step()
            now = time.perf_counter()
            if len(req.out_ids) > seen:
                if last is not None:
                    gaps.append(now - last)
                last, seen = now, len(req.out_ids)
                if seen >= 2 and not injected:
                    inject()
                    injected = True
        return max(gaps) if gaps else 0.0

    # colocated: one engine does both phases
    colo = PagedInferenceEngine(cfg, rng_seed=0)
    colo.generate([short], SamplingParams(max_tokens=2))  # warm compiles
    req = colo.submit(short, sp)
    colo_gap = max_gap(colo, req, lambda: colo.submit(long_p, sp))

    # disaggregated: decode replica never sees prefill work
    pre = PagedInferenceEngine(cfg, rng_seed=0)
    dec = PagedInferenceEngine(cfg, rng_seed=0)
    pre.generate([short], SamplingParams(max_tokens=2))
    payload = pre.prefill_export(short, sp)
    dreq = dec.import_prefill(payload, sp)
    import threading
    background = threading.Thread(
        target=lambda: pre.prefill_export(long_p, sp), daemon=True)
    pd_gap = max_gap(dec, dreq, background.start)
    background.join(timeout=120)

    _emit({
        "metric": "serve_pd_decode_stall",
        "value": round(pd_gap, 4),
        "unit": (f"s max inter-token gap under long-prefill injection "
                 f"(colocated={colo_gap:.4f}s, "
                 f"{jax.devices()[0].platform})"),
        # the PD decode replica should stall less than the colocated engine
        "vs_baseline": round(colo_gap / max(pd_gap, 1e-9), 4),
    })


if __name__ == "__main__":
    main()
