"""The front path's clock (serve/metrics.py, PR 53): a proxied request keeps
its id, its arrival stamp and its trace through both handle hops — proxy ->
``OpenAIRouter`` -> the model deployment, the second made by the router's
drain thread —, every stage of the way is observed once a request where the
work happens, a chunk's stages reach the series once a stream, and nothing
is ever a difference of two hosts' clocks."""
import json
import threading
import time
import types
import urllib.request

import pytest

from ray_tpu.util import metrics as um

PORT = 18653
APP = "front"
MODEL = "tiny"
ROUTER, LLM = "openai-router", f"llm:{MODEL}"
# every per-request stage and the deployment(s) it is tagged with
STAGES = {"intake": (ROUTER,), "open": (ROUTER, LLM), "to_submit": (LLM,),
          "first_chunk": (LLM,), "first_hop": (ROUTER, LLM),
          "first_relay": (ROUTER,), "first_write": (ROUTER,)}


# --------------------------------------------------------------------- #
# no cluster: what a stamp of another host's, or none, records
# --------------------------------------------------------------------- #

@pytest.fixture
def fresh_registry():
    um._reset_registry()
    yield
    um._reset_registry()


def _front(stage, deployment):
    from ray_tpu.serve import metrics_summary
    return metrics_summary()["requests"].get("front", {}).get(
        stage, {}).get(deployment)


def _submit_under(**context):
    """``telemetry.on_submit`` as ``engine.submit`` calls it, 5 ms after
    the arrival stamp the context carries."""
    from ray_tpu.llm import telemetry
    from ray_tpu.serve.context import (reset_request_context,
                                       set_request_context)
    req = types.SimpleNamespace(submit_t=time.perf_counter())
    if "ingress_ns" in context:
        context["ingress_ns"] = int(req.submit_t * 1e9) - 5_000_000
    token = set_request_context(app_name="a", deployment="d", **context)
    try:
        telemetry.on_submit(types.SimpleNamespace(), req)
    finally:
        reset_request_context(token)
    return req


def test_to_submit_is_this_hosts_stamp_or_nothing(fresh_registry):
    from ray_tpu.serve.context import host_name
    req = _submit_under(request_id="r1", ingress_ns=1,
                        ingress_host=host_name())
    assert req.request_id == "r1"
    got = _front("to_submit", "d")
    assert got["count"] == 1 and got["mean"] == pytest.approx(0.005, abs=1e-5)
    # another host's clock: the id still rides, no stage is recorded
    req = _submit_under(request_id="r2", ingress_ns=1,
                        ingress_host="some-other-host")
    assert req.request_id == "r2"
    # a call outside a proxied request (a driver's handle sends 0)
    _submit_under(request_id="r3")
    assert _front("to_submit", "d")["count"] == 1


def test_local_ingress_reads_a_handles_context_dict():
    from ray_tpu.serve.context import host_name, local_ingress_ns
    assert local_ingress_ns() == 0
    assert local_ingress_ns({}) == 0
    assert local_ingress_ns({"ingress_ns": 7,
                             "ingress_host": host_name()}) == 7
    assert local_ingress_ns({"ingress_ns": 7, "ingress_host": "x"}) == 0


def test_proc_label_is_cached_and_forgotten_in_a_forked_child(monkeypatch):
    """``_proc()`` runs every engine step: after the first it makes no
    system call, and a forked child derives its own."""
    import os

    from ray_tpu.llm import telemetry
    label = telemetry._proc()
    assert label.endswith(f":{os.getpid()}")
    monkeypatch.setattr(os, "getpid", lambda: 1 / 0)
    assert telemetry._proc() == label
    monkeypatch.undo()
    telemetry._forget_proc()        # what os.register_at_fork runs
    monkeypatch.setattr(os, "getpid", lambda: 4242)
    assert telemetry._proc().endswith(":4242")
    monkeypatch.undo()
    telemetry._forget_proc()
    assert telemetry._proc() == label


def test_summary_folds_stages_by_deployment(fresh_registry):
    from ray_tpu.serve import metrics as sm
    for dep, ms in ((ROUTER, (2, 4)), (LLM, (10,))):
        for v in ms:
            sm.observe_stage("open", v * 1_000_000, APP, dep)
    sm.add_chunks("hop", 6_000_000, 3, APP, LLM)
    sm.add_chunks("hop", 0, 0, APP, ROUTER)        # nothing to add
    sm.proxy_loop_lag().observe(0.002, tags={"proxy": "proxy-0"})
    req = sm.metrics_summary()["requests"]
    assert req["front"]["open"][ROUTER]["count"] == 2
    assert req["front"]["open"][ROUTER]["mean"] == pytest.approx(0.003)
    assert req["front"]["open"][LLM]["mean"] == pytest.approx(0.010)
    assert req["chunks"] == {"hop": {LLM: {
        "count": 3.0, "mean": pytest.approx(0.002)}}}
    assert req["loop_lag"]["count"] == 1
    assert set(req["loop_lag"]) == {"count", "mean", "p99"}


# --------------------------------------------------------------------- #
# a cluster: the OpenAI path with a real (tiny) engine behind it
# --------------------------------------------------------------------- #

def _app():
    """``build_openai_app``'s shape, with a model deployment that tells
    what its replica saw. Built in a function: the replicas unpickle the
    classes by value."""
    from ray_tpu import serve
    from ray_tpu.llm.openai_api import OpenAIRouter
    from ray_tpu.llm.paged_engine import PagedEngineConfig
    from ray_tpu.llm.serving import LLMConfig, LLMServer
    from ray_tpu.models import llama

    class Seen(LLMServer):
        def __init__(self):
            super().__init__(LLMConfig(
                model_id=MODEL, warmup=False,
                engine=PagedEngineConfig(
                    model=llama.llama_tiny(vocab_size=258, max_seq_len=128),
                    max_batch_size=8, page_size=8, num_pages=128,
                    max_pages_per_seq=16, chunk_size=16)))
            self.saw = []

        def completions_stream(self, request):
            from ray_tpu.serve.context import get_request_context
            stream = super().completions_stream(request)
            ctx, req = get_request_context(), stream._req
            self.saw.append({
                "prompt": request["prompt"], "request_id": req.request_id,
                "ingress_ns": ctx.ingress_ns,
                "ingress_host": ctx.ingress_host,
                "trace": getattr(req, "trace_ctx", None)})
            return stream

        def seen(self):
            return self.saw

        def boom(self, n):
            for i in range(int(n)):
                yield i
            raise ValueError("boom")

    llm = serve.deployment(Seen, name=LLM, max_ongoing_requests=32)
    router = serve.deployment(OpenAIRouter, name=ROUTER,
                              max_ongoing_requests=32)
    return router.bind([MODEL], llm.bind())


@pytest.fixture(scope="module")
def front():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.config import cfg
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cfg.override(tracing_enabled=True)
    ray_tpu.init(num_cpus=2, object_store_memory=256 << 20)
    try:
        serve.run(_app(), name=APP, http_port=PORT)
        from ray_tpu.serve.api import CONTROLLER_NAME
        from ray_tpu.serve.handle import DeploymentHandle
        llm = DeploymentHandle(LLM, APP, ray_tpu.get_actor(CONTROLLER_NAME))
        # the replica's first request compiles the tiny model's programs
        llm.options(method_name="completions").remote(
            {"prompt": "warm", "max_tokens": 4}).result(timeout_s=300)
        yield llm
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        cfg.reset("tracing_enabled")


def _stream(prompt, max_tokens=6, read=None):
    """One streamed completion over HTTP; the SSE payloads. ``read``: stop
    after that many and hang up."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{PORT}/{APP}/v1/completions", method="POST",
        data=json.dumps({"model": MODEL, "prompt": prompt, "stream": True,
                         "max_tokens": max_tokens}).encode(),
        headers={"Content-Type": "application/json"})
    out = []
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 200
        for raw in r:
            if raw.startswith(b"data: {"):
                out.append(json.loads(raw[6:]))
                if read is not None and len(out) >= read:
                    break
    return out


def _requests():
    """``metrics_summary()["requests"]`` once every process has flushed."""
    from ray_tpu.serve import metrics_summary
    time.sleep(2.6)
    return metrics_summary()["requests"]


def _count(group, stage, dep):
    return (group.get(stage, {}).get(dep) or {"count": 0})["count"]


def _spans(want, timeout=20.0):
    """Trace spans of the timeline, once ``want(spans)`` is true."""
    import ray_tpu
    deadline = time.monotonic() + timeout
    while True:
        spans = [{"name": e["name"], **e["args"]}
                 for e in ray_tpu.timeline() if e.get("cat") == "trace"]
        if want(spans) or time.monotonic() > deadline:
            return spans
        time.sleep(0.2)


def test_the_model_replica_sees_the_proxys_request(front):
    """Through both hops: the id the proxy minted, the stamp its clock took
    and the trace it opened reach ``engine.submit`` in the model replica.
    On the parent the router's drain thread called the model deployment
    from an empty context: the id arrived as "" and the trace ended at
    the router."""
    from ray_tpu.serve.context import host_name
    t0 = time.perf_counter_ns()
    chunks = _stream("identity", max_tokens=5)
    t1 = time.perf_counter_ns()
    assert chunks and chunks[-1]["choices"][0]["finish_reason"]
    (saw,) = [s for s in front.options(method_name="seen").remote().result(
        timeout_s=60) if s["prompt"] == "identity"]
    assert saw["request_id"] and saw["ingress_host"] == host_name()
    assert t0 < saw["ingress_ns"] < t1

    def whole(spans):
        return any(s["name"] == "llm.request"
                   and s.get("request_id") == saw["request_id"]
                   for s in spans) and any(
            s["name"] == "serve.proxy"
            and s.get("request_id") == saw["request_id"] for s in spans)
    spans = _spans(whole)
    proxy = next(s for s in spans if s["name"] == "serve.proxy"
                 and s.get("request_id") == saw["request_id"])
    llm = next(s for s in spans if s["name"] == "llm.request"
               and s.get("request_id") == saw["request_id"])
    # one trace, and llm.request hangs under the proxy's span: through
    # the model replica's task and the router's
    assert llm["trace_id"] == proxy["trace_id"] == saw["trace"][0]
    by_id = {s["span_id"]: s for s in spans}
    chain, at = [], llm
    while at.get("parent_id") in by_id:
        at = by_id[at["parent_id"]]
        chain.append(at["name"])
    assert chain[-1] == "serve.proxy" and len(chain) == 3, chain
    assert all("handle_request_streaming" in name for name in chain[:2])
    # the proxy's span carries its own two stages
    assert proxy["intake_ms"] >= 0 and proxy["first_write_ms"] >= 0


def test_every_stage_counts_every_proxied_request_once(front):
    n = 6
    before = _requests()
    stats0 = front.options(method_name="engine_stats").remote().result(
        timeout_s=60)
    threads = [threading.Thread(target=_stream, args=(f"count {i}", 8))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    after = _requests()
    stats1 = front.options(method_name="engine_stats").remote().result(
        timeout_s=60)
    for stage, deps in STAGES.items():
        for dep in deps:
            got = _count(after["front"], stage, dep) - _count(
                before.get("front", {}), stage, dep)
            assert got == n, (stage, dep, got)
            assert after["front"][stage][dep]["mean"] >= 0
    # every item of both rings and every chunk the proxy wrote, added as
    # the streams settled
    chunks0, chunks1 = before.get("chunks", {}), after["chunks"]
    written = stats1["stream_chunks"] - stats0["stream_chunks"]
    assert written >= n
    for stage, dep, least in (("hop", LLM, written), ("hop", ROUTER, n),
                              ("relay", ROUTER, n), ("write", ROUTER, n)):
        assert _count(chunks1, stage, dep) - _count(
            chunks0, stage, dep) >= least, (stage, dep)
    # the pump's time inside its ring writes
    assert stats1["stream_write_ns"] > stats0["stream_write_ns"]
    assert after["loop_lag"]["count"] > before.get(
        "loop_lag", {"count": 0})["count"]


def test_a_drivers_stream_records_items_and_no_stage(front):
    """A handle called outside a proxied request sends no stamp: its
    stream's items are counted, once, and no front stage is."""
    from ray_tpu.serve import metrics_summary
    before = _requests()
    items0 = metrics_summary().get("stream", {}).get("chan", {}).get(
        "items", 0)
    gen = front.options(method_name="completions_stream",
                        stream=True).remote(
        {"prompt": "driver", "max_tokens": 6})
    got = list(gen)
    assert got[-1]["choices"][0]["finish_reason"]
    (saw,) = [s for s in front.options(method_name="seen").remote().result(
        timeout_s=60) if s["prompt"] == "driver"]
    assert saw["ingress_ns"] == 0 and saw["request_id"] == ""
    after = _requests()
    assert after.get("front") == before.get("front")
    assert after.get("chunks") == before.get("chunks")
    assert metrics_summary()["stream"]["chan"]["items"] - items0 == len(got)


# --------------------------------------------------------------------- #
# a ring in this process: what a stream adds to the series, and when
# --------------------------------------------------------------------- #

def _ring(dep, messages, staged=True):
    """A handle's ring generator over a ring written here, as a replica's
    drain thread or a ``_RingSink`` writes it."""
    import os

    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.dag.channel import RingWriter
    from ray_tpu.serve.handle import ChannelResponseGenerator
    chan = {"base": os.urandom(16), "stop": os.urandom(16), "ring": 16}
    writer = RingWriter(rt_mod.get_runtime_if_exists().store, chan["base"],
                        ObjectID(chan["stop"]), chan["ring"])
    for msg in messages:
        writer.write(msg)
    settled = []
    gen = ChannelResponseGenerator(None, chan, lambda: settled.append(1),
                                   {"app": "unit", "deployment": dep},
                                   staged)
    return gen, settled


def _series(dep):
    """(stream items, hop events, hop seconds, first hops) of a tag."""
    from ray_tpu.serve.metrics import _by_labels
    store = um.collect_store()

    def total(name, **labels):
        rec = _by_labels(store.get(name), tuple(labels)).get(
            tuple(labels.values()))
        return sum(rec["series"].values()) if rec else 0.0
    first = _by_labels(store.get("rtpu_serve_front_stage_seconds"),
                       ("deployment", "stage", "le")).get(
        (dep, "first_hop", "+Inf"))
    return (total("rtpu_serve_stream_items_total", deployment=dep),
            total("rtpu_serve_chunk_events_total", deployment=dep,
                  stage="hop"),
            total("rtpu_serve_chunk_seconds_total", deployment=dep,
                  stage="hop"),
            sum(first["series"].values()) if first else 0.0)


def _stamped(kind, item):
    return (kind, item, time.perf_counter_ns() - 2_000_000)


def test_a_finished_stream_adds_its_totals_once_at_the_end(front):
    gen, settled = _ring("finished", [_stamped("i", k) for k in range(5)]
                         + [_stamped("e", None)])
    assert [next(gen) for _ in range(5)] == list(range(5))
    # five chunks in: one first_hop observed, nothing else added yet
    assert _series("finished") == (0.0, 0.0, 0.0, 1.0)
    assert gen.take_ns > 0
    with pytest.raises(StopIteration):
        next(gen)
    items, hops, seconds, first = _series("finished")
    assert (items, hops, first, settled) == (5.0, 5.0, 1.0, [1])
    assert 5 * 0.002 <= seconds < 5 * 0.002 + 1.0
    gen.cancel()
    del gen
    assert _series("finished")[:2] == (5.0, 5.0)


def test_a_cancelled_and_a_failed_stream_add_what_they_read(front):
    gen, settled = _ring("cancelled", [_stamped("i", k) for k in range(4)])
    assert [next(gen), next(gen)] == [0, 1]
    assert _series("cancelled")[:2] == (0.0, 0.0)
    gen.cancel()
    assert _series("cancelled")[:2] == (2.0, 2.0) and settled == [1]
    gen, settled = _ring("failed", [_stamped("i", 0), _stamped("i", 1),
                                    _stamped("x", ValueError("boom"))])
    with pytest.raises(ValueError, match="boom"):
        list(gen)
    assert _series("failed")[:2] == (2.0, 2.0) and settled == [1]


def test_a_dropped_stream_leaves_its_totals_to_the_next(front):
    """A finalizer may run inside any lock: it adds nothing itself."""
    from ray_tpu.serve import handle
    gen, settled = _ring("dropped", [_stamped("i", k) for k in range(3)])
    assert [next(gen) for _ in range(3)] == [0, 1, 2]
    del gen
    assert settled == [1] and _series("dropped")[:2] == (0.0, 0.0)
    handle._add_totals()            # as the next stream to open or settle
    assert _series("dropped")[:2] == (3.0, 3.0)


def test_a_message_without_a_stamp_is_read_without_a_lag(front):
    """The parent's writers sent ``(kind, item)``: counted as an item,
    never taken for a lag."""
    gen, _ = _ring("unstamped", [("i", "a"), ("i", "b"), ("e", None)])
    assert list(gen) == ["a", "b"]
    assert _series("unstamped") == (2.0, 0.0, 0.0, 0.0)
    # and a stamped stream outside a proxied request
    gen, _ = _ring("unstaged", [_stamped("i", "a"), _stamped("e", None)],
                   staged=False)
    assert list(gen) == ["a"]
    assert _series("unstaged") == (1.0, 0.0, 0.0, 0.0)


def test_a_polled_stream_adds_its_items_once(front):
    """The fallback transport (no shared ring): the items of every
    ``stream_next`` reply, added as the stream settles — a failed one's
    too."""
    from ray_tpu.core.config import cfg
    from ray_tpu.serve import metrics_summary
    from ray_tpu.serve.handle import DeploymentResponseGenerator

    def polled():
        return metrics_summary().get("stream", {}).get("poll", {}).get(
            "items", 0.0)
    cfg.override(serve_static_decode_plan=False)
    try:
        base = polled()
        gen = front.options(method_name="completions_stream",
                            stream=True).remote(
            {"prompt": "polled", "max_tokens": 6})
        assert isinstance(gen, DeploymentResponseGenerator)
        first = next(gen)
        assert polled() == base and gen.take_ns > 0
        got = [first] + list(gen)
        assert polled() - base == len(got)
        gen = front.options(method_name="boom", stream=True).remote(3)
        with pytest.raises(Exception, match="boom"):
            list(gen)
        gen.cancel()
        assert polled() - base == len(got) + 3
    finally:
        cfg.reset("serve_static_decode_plan")
