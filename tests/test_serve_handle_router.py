"""One router a deployment a process (serve/handle.py ``_Router``): every
handle on a deployment — ``options()``, ``handle.method``, a pickled copy
— is a view of it, so a handle made per request (``llm/openai_api.py``
makes one for every completion) costs no controller round trip and no
long-poll listener thread. Counted, never timed: threads by name, fetches
by the ``rtpu_serve_handle_refreshes_total`` series, in-flight requests by
the router's own counts."""
import gc
import pickle
import threading
import time

import pytest

N = 50


@pytest.fixture(scope="module")
def cluster():
    import ray_tpu
    from ray_tpu import serve
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=256 << 20)
    try:
        yield ray_tpu
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _gated(name):
    """Built in a function: the replica unpickles the class by value. A
    name a test: a listener is told by its deployment's, and an earlier
    test's may still sit out its last poll."""
    import os

    from ray_tpu import serve

    class Gated:
        def __init__(self):
            self._gate = threading.Event()

        def __call__(self, x=None):
            return x

        def echo(self, x=None):
            return x

        def pid(self):
            return os.getpid()

        def count(self, n):
            yield from range(int(n))

        def wait_gate(self):
            return self._gate.wait(60)

        def open_gate(self):
            self._gate.set()

    return serve.deployment(Gated, name=name, max_ongoing_requests=16)


def _listeners(deployment):
    return [t for t in threading.enumerate()
            if t.name == f"serve-lp-{deployment}"]


def _series(name, app, deployment):
    """{why or proc: value} of one deployment's rows in the merged store
    (the driver's own deltas are shipped by the read)."""
    from ray_tpu.util import metrics as um
    rec = um.collect_store().get(name) or {"series": {}}
    out = {}
    for key, value in rec["series"].items():
        tags = dict(key)
        if (tags["app"], tags["deployment"]) == (app, deployment):
            label = tags.get("why") or tags.get("proc")
            out[label] = out.get(label, 0.0) + value
    return out


def _until(cond, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


def _views(h, i):
    """The three ways a caller gets another handle on h's deployment."""
    return (h.options(method_name="echo", stream=False), h.echo,
            pickle.loads(pickle.dumps(h)).options(method_name="echo"))[i % 3]


def test_views_share_one_listener_and_one_cold_fetch(cluster):
    from ray_tpu import serve
    h = serve.run(_gated("g-views").bind(), name="views")
    views = [_views(h, i) for i in range(N)]
    assert all(v._router is h._router for v in views)
    assert [v.remote(i).result(timeout_s=60)
            for i, v in enumerate(views)] == list(range(N))
    assert len(_listeners("g-views")) == 1
    assert _series("rtpu_serve_handle_refreshes_total", "views",
                   "g-views") == {"cold": 1.0}
    assert list(_series("rtpu_serve_handle_routers", "views",
                        "g-views").values()) == [1.0]


def test_a_view_keeps_only_what_selects_a_call(cluster):
    from ray_tpu import serve
    h = serve.run(_gated("g-selects").bind(), name="selects")
    stream = h.options(method_name="count", stream=True)
    assert list(stream.remote(4)) == [0, 1, 2, 3]
    assert h.remote("x").result(timeout_s=60) == "x"        # h is unchanged
    pinned = h.options(replica_index=5, multiplexed_model_id="m")
    assert (pinned._replica_index, pinned._model_id, pinned._method) == (
        5, "m", "__call__")
    assert pinned.echo._replica_index == 5
    assert pinned._router is stream._router is h._router
    assert h.num_replicas() == 1
    assert len(_listeners("g-selects")) == 1


def test_views_in_a_replica_share_the_replicas_router(cluster):
    """A handle pickled into another deployment's replica N times over is
    N views of that process's one router."""
    from ray_tpu import serve

    class Caller:
        def __init__(self, child):
            self._blob = pickle.dumps(child)

        def __call__(self, n):
            hs = [pickle.loads(self._blob).options(method_name="pid")
                  for _ in range(int(n))]
            pids = {h.remote().result(timeout_s=60) for h in hs}
            return {"pids": len(pids),
                    "routers": len({id(h._router) for h in hs}),
                    "listeners": sum(t.name == "serve-lp-g-nested"
                                     for t in threading.enumerate())}

    caller = serve.deployment(Caller, name="caller")
    h = serve.run(caller.bind(_gated("g-nested").bind()), name="nested")
    assert h.remote(N).result(timeout_s=120) == {
        "pids": 1, "routers": 1, "listeners": 1}
    assert _until(lambda: _series(
        "rtpu_serve_handle_refreshes_total", "nested", "g-nested")
        == {"cold": 1.0}, 15)


def test_streams_through_the_openai_router_add_no_thread(cluster):
    """The program's OpenAIRouter makes a handle for every request: N
    streamed completions over a pushing deployment leave the router
    replica with the threads it had after the first."""
    from ray_tpu import serve
    from tools.front_path import MODEL, build_app
    h = serve.run(build_app(sessions=8, chunk_s=0.002, chunks=3),
                  name="front")
    sse = h.options(method_name="v1_completions", stream=True)
    body = {"model": MODEL, "stream": True, "prompt": [1, 2, 3]}

    def ask():
        lines = list(sse.remote(body))
        assert len(lines) == 4 and lines[-1] == "data: [DONE]\n\n"

    def settled():
        """Thread names of the router replica once its streams' drain
        threads have ended."""
        names = []

        def quiet():
            names[:] = h.threads.remote().result(timeout_s=60)
            return not any(n.startswith("serve-stream") for n in names)
        assert _until(quiet, 20), names
        return names

    ask()
    first = settled()
    for _ in range(N):
        ask()
    after = settled()
    assert [n for n in after if n.startswith("serve-lp-")] == [
        f"serve-lp-llm:{MODEL}"]
    assert len(after) <= len(first) + 1, (first, after)
    assert _until(lambda: _series(
        "rtpu_serve_handle_refreshes_total", "front", f"llm:{MODEL}")
        .get("cold") == 1.0, 15)
    assert list(_series("rtpu_serve_handle_routers", "front",
                        f"llm:{MODEL}").values()) == [1.0]


def test_a_scale_change_reaches_views_made_before_and_after(cluster):
    import ray_tpu
    from ray_tpu import serve
    h = serve.run(_gated("g-scaled").bind(), name="scaled")
    before = h.options(method_name="pid")
    assert isinstance(before.remote().result(timeout_s=60), int)
    assert len(h._router.rs.replicas) == 1
    ray_tpu.get(h._ctrl.set_target.remote("scaled", "g-scaled", 2),
                timeout=60)
    # pushed by the one listener, not fetched by a request
    assert _until(lambda: len(h._router.rs.replicas) == 2, 60)
    after = h.options(method_name="pid")
    assert after._router.rs is before._router.rs
    pids = {v.remote().result(timeout_s=60)
            for v in (before, after) for _ in range(20)}
    assert len(pids) == 2
    assert len(_listeners("g-scaled")) == 1


def test_inflight_of_two_views_is_one_count(cluster):
    from ray_tpu import serve
    h = serve.run(_gated("g-counted").bind(), name="counted")
    a = h.options(method_name="wait_gate")
    b = pickle.loads(pickle.dumps(h)).wait_gate
    ra, rb = a.remote(), b.remote()
    rs = h._router.rs
    assert rs.inflight == [2]
    h.open_gate.remote().result(timeout_s=60)
    assert rs.inflight == [2]               # open_gate settled, they did not
    assert ra.result(timeout_s=60) and rb.result(timeout_s=60)
    assert rs.inflight == [0] and h._router.rs is rs


def test_threads_that_meet_at_a_cold_router_fetch_once(cluster):
    """Many threads route through one object: the first requests of a
    process arrive together, as a router replica's do."""
    from ray_tpu import serve
    h = serve.run(_gated("g-threads").bind(), name="threads")
    h = pickle.loads(pickle.dumps(h))       # serve.run warmed nothing
    assert h._router.rs.version == -1
    start, got = threading.Barrier(16), []

    def call(i):
        start.wait()
        got.append(h.options(method_name="echo").remote(i).result(
            timeout_s=60))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(got) == list(range(16))
    assert len(_listeners("g-threads")) == 1
    assert h._router.rs.inflight == [0]
    assert _series("rtpu_serve_handle_refreshes_total", "threads",
                   "g-threads") == {"cold": 1.0}


def test_a_dropped_response_and_a_finished_stream_count_down(cluster):
    from ray_tpu import serve
    h = serve.run(_gated("g-dropped").bind(), name="dropped")
    h.echo.remote(1)                         # never asked for its result
    gen = h.options(method_name="count", stream=True).remote(3)
    assert h._router.rs.inflight == [1]      # the dropped one is gone
    assert list(gen) == [0, 1, 2]
    assert h._router.rs.inflight == [0]
    cancelled = h.options(method_name="count", stream=True).remote(1000)
    assert next(cancelled) == 0 and h._router.rs.inflight == [1]
    cancelled.cancel()
    assert h._router.rs.inflight == [0]


def test_a_dropped_last_handle_ends_the_listener_within_one_poll(cluster):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import handle as hmod
    h = serve.run(_gated("g-lastone").bind(), name="lastone")
    assert h.echo.remote(1).result(timeout_s=60) == 1
    ctrl, router = h._ctrl, h._router
    mine = [t for t in _listeners("g-lastone") if t.is_alive()]
    key = next(k for k, r in hmod._routers.items() if r is router)
    del h, router
    gc.collect()
    assert key not in hmod._routers
    # the parked poll returns at the next change (or after its 30 s)
    ray_tpu.get(ctrl.set_target.remote("lastone", "g-lastone", 2), timeout=60)
    assert _until(lambda: sum(t.is_alive() for t in mine) == len(mine) - 1)


def test_shutdown_ends_listeners_and_the_next_run_starts_cold(cluster):
    from ray_tpu import serve
    h = serve.run(_gated("g-again").bind(), name="again")
    old_pid = h.pid.remote().result(timeout_s=60)
    old_router = h._router
    assert _listeners("g-again")
    serve.shutdown()
    assert _until(lambda: not _listeners("g-again"), 60)
    h2 = serve.run(_gated("g-again").bind(), name="again")
    assert h2._router is not old_router
    assert h2._router.rs.version == -1 and not h2._router.rs.replicas
    assert h2.pid.remote().result(timeout_s=60) != old_pid
    assert len(_listeners("g-again")) == 1
