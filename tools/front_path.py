"""The serving front path alone, on a CPU: client -> HTTP proxy ->
``OpenAIRouter`` -> a deployment that PUSHES chunks — no engine, no model.

What a request costs in front of the engine (PERF.md §3, layer "HTTP front
and router") is the proxy's, the router's and the handles' work, and none
of it needs a chip. This runs the program's own proxy and
``llm/openai_api.py`` ``OpenAIRouter`` over a fake LLM deployment whose
``completions_stream`` returns an object with ``attach(sink)`` (as
``llm/serving.py``'s ``TokenStream``) and whose ONE pump thread pushes a
chunk every ``--chunk-s`` seconds a stream. S closed-loop sessions send
prompts of P ids and read ``--chunks`` chunks an answer. Every process
stamps with ``time.perf_counter()`` (CLOCK_MONOTONIC: one clock a
machine), so the lags are differences of stamps taken in two processes:

- way in: the client's send -> the fake deployment's ``completions_stream``
- first chunk / later chunks: the pump's ``put`` -> the client's read

and beside them the router replica's thread count, the handles' series
(``rtpu_serve_handle_routers``, ``rtpu_serve_handle_refreshes_total``) and
``front_stages_ms``: the program's own split of the same way, from
``metrics_summary()["requests"]`` (serve/metrics.py, "the front path's
clock"; PR 53). Which stage is which thread:

- ``intake``: the proxy's event loop, up to the default-executor thread
  that runs ``call()``; ``loop_lag`` is that loop's lateness
- ``open``: that thread (tagged ``openai-router``), then the router
  replica's ``serve-stream-chan-<sid>`` drain thread at ``_sse``'s first
  ``next`` (tagged ``llm:fake``): the actor round trip that opens a stream
- ``to_submit``: the fake deployment's executor thread, where the engine's
  ``submit`` would stamp (``llm/telemetry.py`` ``on_submit`` there)
- ``first_chunk``: the fake's pump thread, a first chunk falling due ->
  its ring write's stamp (``LLMServer._pump`` there, from the first
  token's booking); between the two a real engine's TTFT would lie
- ``first_hop`` / ``hop``: ring 1 (``llm:fake``), written by the fake's
  pump and read by the router's drain thread; ring 2 (``openai-router``),
  written by that drain thread and read by a ``rtpu-proxy-stream`` thread
- ``first_relay`` / ``relay``: the router's drain thread, ring 1's read ->
  ring 2's write returned
- ``first_write`` / ``write``: the proxy's stream thread's read -> the
  event loop's ``stream.write`` returned

    python -m tools.front_path --sessions 64 --prompt-ids 12000 --chunk-s 0.2
    python -m tools.front_path --sessions 12 --prompt-ids 3000 --chunk-s 0.1

prints one JSON line. 40 s by default; nothing here is a device number.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import statistics
import time

APP = "llm"
MODEL = "fake"


def build_app(sessions: int, chunk_s: float, chunks: int):
    """The application: the program's router in front of the pushing
    fake. Classes are made here so replicas unpickle them by value."""
    import queue
    import threading

    from ray_tpu import serve
    from ray_tpu.llm.openai_api import OpenAIRouter

    class QueueSink:
        """The sink of a stream that is pulled (``stream_next``, where
        replica and caller share no store)."""

        def __init__(self):
            self.items = queue.SimpleQueue()

        def put(self, chunk):
            self.items.put(chunk)
            return True

        def end(self):
            self.items.put(None)
            return True

        def closed(self):
            return False

    class Pushed:
        """One open answer; the server's pump feeds whatever sink the
        serve replica attaches (serve/controller.py ``_RingSink``)."""

        def __init__(self, server, t_in, front=None):
            self.server, self.t_in, self.front = server, t_in, front
            self.sink, self.sent, self.due = None, 0, t_in + chunk_s

        def attach(self, sink):
            self.sink = sink
            with self.server.lock:
                self.server.open.append(self)

        def __iter__(self):
            return self

        def __next__(self):
            if self.sink is None:
                self.attach(QueueSink())
            chunk = self.sink.items.get()
            if chunk is None:
                raise StopIteration
            return chunk

    class FakeLLM:
        def __init__(self):
            self.lock = threading.Lock()
            self.open: list = []
            threading.Thread(target=self._pump, daemon=True,
                             name="fake-llm-pump").start()

        def completions_stream(self, body):
            t_in = time.perf_counter()
            # what llm/telemetry.py on_submit records for a real engine
            from ray_tpu.serve.context import (get_request_context,
                                               local_ingress_ns)
            from ray_tpu.serve.metrics import observe_stage
            ctx, ingress_ns = get_request_context(), local_ingress_ns()
            if not ingress_ns:
                return Pushed(self, t_in)
            front = (ctx.app_name, ctx.deployment)
            observe_stage("to_submit", int(t_in * 1e9) - ingress_ns, *front)
            return Pushed(self, t_in, front)

        def _pump(self):
            while True:
                time.sleep(0.002)
                with self.lock:
                    streams = list(self.open)
                over = [s for s in streams if self._feed(s)]
                if over:
                    with self.lock:
                        self.open = [s for s in self.open if s not in over]

        @staticmethod
        def _feed(s) -> bool:
            """True once the stream is over. A sink without credit keeps
            the chunk for the next pass; none of its calls blocks."""
            if s.sink.closed():
                return True
            if s.sent == chunks:
                return s.sink.end()
            if time.perf_counter() < s.due:
                return False
            last = s.sent == chunks - 1
            chunk = {"model": MODEL, "t_in": s.t_in,
                     "t_put": time.perf_counter(),
                     "choices": [{"index": 0, "text": "x", "finish_reason":
                                  "length" if last else None}]}
            if s.sink.put(chunk):
                if s.sent == 0 and s.front:
                    # llm/serving.py's pump: the first token's booking
                    # (here: the chunk fell due) -> the ring write's stamp
                    from ray_tpu.serve.metrics import observe_stage
                    wrote = getattr(s.sink, "wrote_ns", 0) or int(
                        time.perf_counter() * 1e9)
                    observe_stage("first_chunk", wrote - int(s.due * 1e9),
                                  *s.front)
                s.sent += 1
                s.due += chunk_s
            return False

    class Router(OpenAIRouter):
        def threads(self, _body=None) -> list:
            return sorted(t.name for t in threading.enumerate())

    fake = serve.deployment(FakeLLM, name=f"llm:{MODEL}",
                            max_ongoing_requests=2 * sessions)
    router = serve.deployment(Router, name="openai-router",
                              max_ongoing_requests=2 * sessions)
    return router.bind([MODEL], fake.bind())


async def _session(http, url: str, body: bytes, until: float, out: dict):
    while time.perf_counter() < until:
        sent = time.perf_counter()
        first = True
        async with http.post(url, data=body, headers={
                "Content-Type": "application/json"}) as resp:
            if resp.status != 200:
                out["errors"] += 1
                await resp.read()
                continue
            async for raw in resp.content:
                if not raw.startswith(b"data: {"):
                    continue
                now = time.perf_counter()
                chunk = json.loads(raw[6:])
                if first:
                    out["way_in"].append(chunk["t_in"] - sent)
                    out["first_chunk"].append(now - chunk["t_put"])
                    first = False
                else:
                    out["later_chunks"].append(now - chunk["t_put"])
        out["requests"] += 1


async def _drive(port: int, sessions: int, prompt_ids: int, seconds: float):
    import aiohttp
    url = f"http://127.0.0.1:{port}/{APP}/v1/completions"
    body = json.dumps({"model": MODEL, "stream": True, "max_tokens": 1,
                       "prompt": [i % 50000 for i in range(prompt_ids)]
                       }).encode()
    out = {"way_in": [], "first_chunk": [], "later_chunks": [],
           "requests": 0, "errors": 0}
    async with aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=seconds + 120)) as http:
        until = time.perf_counter() + seconds
        await asyncio.gather(*(_session(http, url, body, until, out)
                               for _ in range(sessions)))
    return out


def _ms(values: list) -> dict:
    if len(values) < 2:
        return {"n": len(values)}
    qs = statistics.quantiles(values, n=10)
    return {"n": len(values), "mean": round(statistics.fmean(values) * 1e3, 1),
            "p50": round(statistics.median(values) * 1e3, 1),
            "p90": round(qs[8] * 1e3, 1)}


def run(sessions: int, prompt_ids: int, chunk_s: float, chunks: int,
        seconds: float, port: int) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.metrics import metrics_summary
    ray_tpu.init(num_cpus=4, object_store_memory=512 << 20)
    try:
        h = serve.run(build_app(sessions, chunk_s, chunks), name=APP,
                      http_port=port)
        threads = h.options(method_name="threads")
        before = threads.remote().result(timeout_s=120)
        t0 = time.perf_counter()
        out = asyncio.run(_drive(port, sessions, prompt_ids, seconds))
        took = time.perf_counter() - t0
        after = threads.remote().result(timeout_s=120)
        time.sleep(2.5)     # one flush tick of the workers' metrics
        summary = metrics_summary()
        handles = summary.get("handles")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    n_chunks = len(out["first_chunk"]) + len(out["later_chunks"])
    return {
        "sessions": sessions, "prompt_ids": prompt_ids, "chunk_s": chunk_s,
        "chunks_an_answer": chunks, "seconds": round(took, 1),
        "requests_per_s": round(out["requests"] / took, 2),
        "chunks_per_s": round(n_chunks / took, 1), "errors": out["errors"],
        "way_in_ms": _ms(out["way_in"]),
        "first_chunk_ms": _ms(out["first_chunk"]),
        "later_chunks_ms": _ms(out["later_chunks"]),
        "router_threads": {"before": len(before), "after": len(after),
                           "serve_lp_after": sum(
                               t.startswith("serve-lp-") for t in after)},
        "handles": handles,
        "front_stages_ms": stage_table(summary["requests"]),
    }


def stage_table(requests: dict) -> dict:
    """``metrics_summary()["requests"]``'s front-path groups in ms:
    {stage: {deployment: {n, mean, p95}}} of the per-request stages,
    {stage: {deployment: {n, mean}}} under ``"chunks"`` for every item,
    and the proxy's ``loop_lag``."""
    def ms(stats, keys):
        return {"n": int(stats["count"]), **{
            k: round(stats[k] * 1e3, 3) for k in keys
            if stats.get(k) is not None}}
    out = {stage: {dep: ms(st, ("mean", "p95")) for dep, st in deps.items()}
           for stage, deps in requests.get("front", {}).items()}
    out["chunks"] = {
        stage: {dep: ms(st, ("mean",)) for dep, st in deps.items()}
        for stage, deps in requests.get("chunks", {}).items()}
    if "loop_lag" in requests:
        out["loop_lag"] = ms(requests["loop_lag"], ("mean", "p99"))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sessions", type=int, default=64)
    ap.add_argument("--prompt-ids", type=int, default=12000)
    ap.add_argument("--chunk-s", type=float, default=0.2)
    ap.add_argument("--chunks", type=int, default=40)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--port", type=int, default=18649)
    a = ap.parse_args()
    print(json.dumps(run(a.sessions, a.prompt_ids, a.chunk_s, a.chunks,
                         a.seconds, a.port)))


if __name__ == "__main__":
    main()
