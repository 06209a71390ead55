"""Serving telemetry: engine/serve metrics + request-scoped traces
(reference: serve/_private metrics feeding the metrics agent, vLLM's
Stats/StatLogger loop)."""
import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import metrics as um


@pytest.fixture
def fresh_registry():
    um._reset_registry()
    yield
    um._reset_registry()


@pytest.fixture(scope="module")
def engine():
    from ray_tpu.llm.paged_engine import (PagedEngineConfig,
                                          PagedInferenceEngine)
    from ray_tpu.models import llama
    cfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=128),
        max_batch_size=4, page_size=8, num_pages=64,
        max_pages_per_seq=16, chunk_size=16)
    return PagedInferenceEngine(cfg, rng_seed=0)


def _drive(engine, n_requests=3, max_tokens=4):
    from ray_tpu.llm import SamplingParams
    tok = engine.tokenizer
    reqs = [engine.submit(tok.encode("hello world " * (i + 1)),
                          SamplingParams(max_tokens=max_tokens))
            for i in range(n_requests)]
    while not all(r.done for r in reqs):
        engine.step()
    return reqs


def test_engine_metrics_and_summary(fresh_registry, engine):
    from ray_tpu.serve import metrics_summary
    _drive(engine)
    summary = metrics_summary()
    for key in ("ttft", "queue_wait", "inter_token"):
        stats = summary[key]
        assert stats["count"] >= 3 or key == "inter_token"
        for q in ("p50", "p95", "p99"):
            assert stats[q] is not None and 0.0 <= stats[q] < 60.0
    assert summary["requests"]["llm"] >= 3
    assert summary["requests"]["llm_tokens"] >= 3
    assert "paged" in summary["kv_utilization"]

    text = "\n".join(um.prometheus_lines(um.local_store()))
    assert "rtpu_llm_ttft_seconds_bucket" in text
    assert "rtpu_llm_kv_utilization" in text
    assert 'rtpu_llm_dispatches_total{engine="paged",family="prefill"}' \
        in text
    assert 'rtpu_llm_requests_total{engine="paged",finish=' in text


def test_loop_phase_seconds_and_live_slots_reach_metrics(fresh_registry,
                                                         engine):
    """The two /metrics families of the engine loop: one counter of
    seconds with a ``phase`` label for every ns_* key, and the live
    slots summed over decode dispatches."""
    from ray_tpu.llm.paged_engine import PHASES
    engine._telem_shipped = None    # ship the whole history once more
    _drive(engine)
    text = "\n".join(um.prometheus_lines(um.local_store()))
    for key in PHASES:
        line = (f'rtpu_llm_loop_seconds_total{{engine="paged",'
                f'phase="{key[3:]}"}}')
        # the loop phases belong to LLMServer: a bare engine has none
        assert (line in text) == ("loop" not in key), key
    shipped = float(next(
        ln for ln in text.splitlines() if ln.startswith(
            'rtpu_llm_loop_seconds_total{engine="paged",'
            'phase="decode_device"}')).split()[-1])
    assert shipped == pytest.approx(
        engine.stats["ns_decode_device"] * 1e-9, rel=1e-6)
    assert 'rtpu_llm_decode_live_slots_total{engine="paged"}' in text


def _shipped(text: str, series: str) -> float:
    return float(next(ln for ln in text.splitlines()
                      if ln.startswith(series)).split()[-1])


def test_launch_and_stream_counters_reach_metrics(fresh_registry, engine):
    """The families an operator reads S12 by: the launches' seconds by
    family beside the phases' seconds, and the streams' chunks, lag and
    CPU (counted by serving's stream pump; set by hand here, a bare
    engine has no stream)."""
    engine._telem_shipped = None
    engine.stats.update(stream_chunks=engine.stats["stream_chunks"] + 5,
                        stream_lag_ns=engine.stats["stream_lag_ns"] + 10**9,
                        stream_cpu_ns=engine.stats["stream_cpu_ns"] + 10**7,
                        stream_passes=engine.stats["stream_passes"] + 3,
                        stream_deferred=engine.stats["stream_deferred"] + 2)
    _drive(engine)
    text = "\n".join(um.prometheus_lines(um.local_store()))
    for family in ("prefill", "decode"):
        series = (f'rtpu_llm_launch_seconds_total{{engine="paged",'
                  f'family="{family}"}}')
        assert _shipped(text, series) == pytest.approx(
            engine.stats[f"launch_ns_{family}"] * 1e-9, rel=1e-6)
        # a launch is part of the family's device phase
        assert _shipped(text, series) <= _shipped(
            text, f'rtpu_llm_loop_seconds_total{{engine="paged",'
                  f'phase="{family}_device"}}')
    for name, key, scale in (
            ("rtpu_llm_stream_chunks_total", "stream_chunks", 1.0),
            ("rtpu_llm_stream_passes_total", "stream_passes", 1.0),
            ("rtpu_llm_stream_deferred_total", "stream_deferred", 1.0),
            ("rtpu_llm_stream_lag_seconds_total", "stream_lag_ns", 1e-9),
            ("rtpu_llm_stream_cpu_seconds_total", "stream_cpu_ns", 1e-9)):
        assert _shipped(text, name + '{engine="paged"}') == pytest.approx(
            engine.stats[key] * scale, rel=1e-6)
    # no launch is shipped as a phase by a prefix's accident
    assert 'phase="ns_' not in text and 'phase="launch' not in text


def test_engine_request_span_parents_to_submitter(fresh_registry, engine):
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.core.config import cfg
    from ray_tpu.util import tracing

    class _StubRT:
        def __init__(self):
            self.spans = []

        def record_trace_span(self, rec):
            self.spans.append(rec)

    stub = _StubRT()
    # save/restore instead of asserting None: an earlier test module
    # leaking a runtime must not fail THIS test (order independence)
    prev_rt = rt_mod.get_runtime_if_exists()
    cfg.override(tracing_enabled=True)
    rt_mod.set_runtime(stub)
    try:
        from ray_tpu.serve.context import (reset_request_context,
                                           set_request_context)
        token = set_request_context(request_id="req-abc")
        try:
            with tracing.span("serve.replica"):
                reqs = _drive(engine, n_requests=1)
        finally:
            reset_request_context(token)
        while not all(r.done for r in reqs):
            engine.step()
    finally:
        rt_mod.set_runtime(prev_rt)
        cfg.reset("tracing_enabled")

    by_name = {s["name"]: s for s in stub.spans}
    replica = by_name["serve.replica"]
    # select OUR request's span explicitly: a leftover request from an
    # earlier test sharing the module-scoped engine may retire here too
    llm = next(s for s in stub.spans if s["name"] == "llm.request"
               and s.get("request_id") == "req-abc")
    # one stitched tree: same trace id, engine span under the replica span
    assert llm["trace_id"] == replica["trace_id"]
    assert llm["parent_id"] == replica["span_id"]
    assert llm["request_id"] == "req-abc"
    assert llm["dur_s"] >= 0.0


@pytest.mark.parametrize("traced", [True, False])
def test_engine_request_span_has_its_three_children(fresh_registry, engine,
                                                    traced):
    """With tracing on, one request yields llm.request and under it
    exactly llm.queue, llm.prefill and llm.decode: end to end in time,
    on the request's trace and request id, with the token counts as
    arguments. With tracing off the engine emits no span at all."""
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.core.config import cfg
    from ray_tpu.serve.context import (reset_request_context,
                                       set_request_context)

    class _StubRT:
        def __init__(self):
            self.spans = []

        def record_trace_span(self, rec):
            self.spans.append(rec)

    stub = _StubRT()
    prev_rt = rt_mod.get_runtime_if_exists()
    cfg.override(tracing_enabled=traced)
    rt_mod.set_runtime(stub)
    token = set_request_context(request_id="req-kids")
    try:
        (req,) = _drive(engine, n_requests=1, max_tokens=5)
    finally:
        reset_request_context(token)
        rt_mod.set_runtime(prev_rt)
        cfg.reset("tracing_enabled")
    mine = [s for s in stub.spans if s.get("request_id") == "req-kids"]
    if not traced:
        assert req.trace_ctx is None and stub.spans == []
        return
    (root,) = [s for s in mine if s["name"] == "llm.request"]
    kids = [s for s in mine if s.get("parent_id") == root["span_id"]]
    assert [k["name"] for k in kids] == \
        ["llm.queue", "llm.prefill", "llm.decode"]
    assert len(mine) == 4
    assert {k["trace_id"] for k in kids} == {root["trace_id"]}
    assert len({k["span_id"] for k in mine}) == 4
    # contiguous: each starts where the one before ended, and together
    # they cover the request's span
    at = root["start_s"]
    for k in kids:
        assert k["start_s"] == pytest.approx(at, abs=1e-6)
        assert k["dur_s"] >= 0.0
        at = k["start_s"] + k["dur_s"]
    assert at == pytest.approx(root["start_s"] + root["dur_s"], abs=1e-6)
    assert kids[1]["dur_s"] > 0.0 and kids[2]["dur_s"] > 0.0
    for k in kids:
        assert k["args"] == {"prompt_tokens": len(req.prompt_ids),
                             "prefix_tokens_saved": req.prefix_tokens_saved,
                             "out_tokens": 5}


def test_proxy_root_span_ignores_ambient_context(fresh_registry):
    from ray_tpu.core.config import cfg
    from ray_tpu.util import tracing
    cfg.override(tracing_enabled=True)
    try:
        with tracing.span("server.boot") as boot:
            with tracing.span("serve.proxy", root=True) as req_span:
                pass
        assert req_span["trace_id"] != boot["trace_id"]
        assert req_span["parent_id"] is None
    finally:
        cfg.reset("tracing_enabled")


@pytest.fixture
def ray(ray_start_regular):
    import ray_tpu.serve as serve
    yield ray_start_regular
    serve.shutdown()


def test_serve_request_path_metrics_end_to_end(ray):
    from ray_tpu import serve, state

    @serve.deployment
    def echo(payload):
        return {"got": payload["v"]}

    serve.run(echo.bind(), name="default", http_port=18125)
    time.sleep(0.5)
    req = urllib.request.Request(
        "http://127.0.0.1:18125/", data=json.dumps({"v": 7}).encode(),
        headers={"Content-Type": "application/json"})
    deadline = time.monotonic() + 15
    while True:
        try:
            with urllib.request.urlopen(req, timeout=5) as resp:
                assert json.loads(resp.read()) == {"got": 7}
            break
        except Exception:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.3)

    # proxy/replica/controller series flush to the head on a ~2s cadence
    want = ("rtpu_serve_proxy_requests_total",
            "rtpu_serve_request_latency_seconds_bucket",
            "rtpu_serve_handle_requests_total",
            "rtpu_serve_replica_requests_total",
            "rtpu_serve_replica_latency_seconds_bucket",
            "rtpu_serve_queue_depth",
            "rtpu_serve_replicas")
    deadline = time.monotonic() + 20
    while True:
        text = state._prometheus_text()
        missing = [w for w in want if w not in text]
        if not missing:
            break
        if time.monotonic() > deadline:
            raise AssertionError(f"series never reached /metrics: {missing}")
        time.sleep(0.3)
    assert 'rtpu_serve_proxy_requests_total{route="/default",' \
           'method="POST",status="200"}' in text

    summary = serve.metrics_summary()
    assert summary["requests"]["proxy"] >= 1
    assert summary["requests"]["replica"] >= 1
    assert summary["requests"]["errors"] == 0
    e2e = summary["e2e_latency"]
    for q in ("p50", "p95", "p99"):
        assert e2e[q] is not None and 0.0 <= e2e[q] < 60.0

    # dashboard surfacing: GET /api/serve_metrics returns the summary
    from ray_tpu import dashboard
    port = dashboard.start_dashboard(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/serve_metrics",
                timeout=10) as r:
            body = json.loads(r.read())
        assert body["requests"]["proxy"] >= 1
    finally:
        dashboard.stop_dashboard()


def test_batch_metrics(ray):
    from ray_tpu import serve

    @serve.deployment
    class Batcher:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.02)
        async def __call__(self, xs: list) -> list:
            return [x * 2 for x in xs]

    handle = serve.run(Batcher.bind(), name="batched")
    responses = [handle.remote(i) for i in range(8)]
    assert sorted(r.result(30.0) for r in responses) == \
        sorted(i * 2 for i in range(8))

    from ray_tpu import state
    deadline = time.monotonic() + 20
    while True:
        text = state._prometheus_text()
        if "rtpu_serve_batch_size_bucket" in text and \
                "rtpu_serve_batch_wait_seconds_bucket" in text:
            break
        if time.monotonic() > deadline:
            raise AssertionError("batch histograms never reached /metrics")
        time.sleep(0.3)
