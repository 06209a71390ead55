"""A streaming deployment through a real replica, both ways of serving
it (serve/controller.py ``_start_stream_channel``): a return value that
offers ``attach(sink)`` — the LLM server's ``TokenStream`` — is PUSHED
into its ring by the deployment's own thread and has no thread of its
own in the replica; a plain generator keeps its drain thread; both also
work over the ``stream_next`` fallback; a cancelled consumer leaves
nothing in the store; and a request refused at submit reaches an HTTP
client of the stream with the status the unary call gives it."""
import gc
import json
import time
import urllib.error
import urllib.request

import pytest

PORT = 18571


def _deployment():
    """Built in a function: the replica unpickles the classes by value."""
    import threading

    from ray_tpu import serve
    from ray_tpu.llm.paged_engine import PagedEngineConfig
    from ray_tpu.llm.serving import LLMConfig, LLMServer
    from ray_tpu.models import llama

    class OneChar:
        eos_id = bos_id = None

        def encode(self, text, add_bos=False):
            return [ord(c) - 0x4E00 for c in text]

        def decode(self, ids):
            return "".join(chr(0x4E00 + int(i)) for i in ids)

    class NoSlots:
        """The adapter table of a server that knows no adapter and has
        every slot in flight."""

        def resolve(self, lora_id, steplock, pin=False):
            if lora_id == "full":
                raise RuntimeError(
                    "overloaded: every adapter slot is in flight")
            raise KeyError(lora_id)

    class Served(LLMServer):
        def __init__(self):
            super().__init__(LLMConfig(
                model_id="tiny", warmup=False,
                engine=PagedEngineConfig(
                    model=llama.llama_tiny(vocab_size=258, max_seq_len=128),
                    tokenizer=OneChar(), max_batch_size=4, page_size=8,
                    num_pages=64, max_pages_per_seq=16, chunk_size=16)))
            self._multilora = NoSlots()

        def plain(self, n):
            """A generator, as any user deployment's: pulled."""
            for i in range(int(n)):
                yield {"i": i, "thread": threading.current_thread().name}

        def threads(self):
            return sorted(t.name for t in threading.enumerate())

    return serve.deployment(Served, name="served", max_ongoing_requests=32)


@pytest.fixture(scope="module")
def served():
    import ray_tpu
    from ray_tpu import serve
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, object_store_memory=256 << 20)
    try:
        h = serve.run(_deployment().bind(), name="pushed", http_port=PORT)
        # the replica's first request compiles the tiny model's programs
        h.options(method_name="completions").remote(
            _request(0)).result(timeout_s=300)
        yield h
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _request(i, max_tokens=12, **more):
    return {"prompt": list(range(1 + i, 25 + i)), "max_tokens": max_tokens,
            **more}


def _text(chunks):
    return "".join(c["choices"][0]["text"] for c in chunks)


def _threads(h):
    return h.options(method_name="threads").remote().result(timeout_s=60)


def _post(path, payload, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{PORT}/pushed/{path}", method="POST",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def test_a_pushed_stream_has_no_thread_in_the_replica(served):
    """Eight token streams open at once over the ring: the replica runs
    its one pump and no ``serve-stream-chan-*`` thread, and each client
    reads the text and ``finish_reason`` the unary call gives."""
    from ray_tpu.serve.handle import ChannelResponseGenerator
    unary = served.options(method_name="completions")
    want = [unary.remote(_request(i, 40)).result(timeout_s=120)["choices"][0]
            for i in range(8)]
    hs = served.options(method_name="completions_stream", stream=True)
    gens = [hs.remote(_request(i, 40)) for i in range(8)]
    assert all(isinstance(g, ChannelResponseGenerator) for g in gens)
    firsts = [next(g) for g in gens]
    names = _threads(served)
    assert names.count("llm-stream-pump") == 1
    assert not [n for n in names if n.startswith("serve-stream-chan-")]
    for g, first, ref in zip(gens, firsts, want):
        chunks = [first] + list(g)
        assert _text(chunks) == ref["text"] and len(ref["text"]) == 40
        assert chunks[-1]["choices"][0]["finish_reason"] == \
            ref["finish_reason"] == "length"
        assert not any(c["choices"][0]["finish_reason"] for c in chunks[:-1])


def test_a_plain_generator_keeps_its_drain_thread(served):
    from ray_tpu.serve.handle import ChannelResponseGenerator
    gen = served.options(method_name="plain", stream=True).remote(5)
    assert isinstance(gen, ChannelResponseGenerator)
    items = list(gen)
    assert [it["i"] for it in items] == list(range(5))
    assert all(it["thread"].startswith("serve-stream-chan-") for it in items)


@pytest.mark.parametrize("method, arg", [
    ("completions_stream", None), ("plain", 5)])
def test_both_kinds_stream_over_the_poll_transport(served, method, arg):
    """With the static decode plan off the handle pulls ``stream_next``
    batches: a ``TokenStream`` is iterated there like any generator."""
    from ray_tpu.core.config import cfg
    from ray_tpu.serve.handle import DeploymentResponseGenerator
    hs = served.options(method_name=method, stream=True)
    cfg.override(serve_static_decode_plan=False)
    try:
        gen = hs.remote(_request(3) if arg is None else arg)
        assert isinstance(gen, DeploymentResponseGenerator)
        items = list(gen)
    finally:
        cfg.reset("serve_static_decode_plan")
    if arg is None:
        ref = served.options(method_name="completions").remote(
            _request(3)).result(timeout_s=120)["choices"][0]
        assert _text(items) == ref["text"]
        assert items[-1]["choices"][0]["finish_reason"] == "length"
    else:
        assert [it["i"] for it in items] == list(range(arg))
        assert not any(it["thread"].startswith("serve-stream-chan-")
                       for it in items)


def _objects(store, at_most=None, budget=20.0):
    """The store's object count once it has stood still for half a
    second (frees are asynchronous), or as soon as it is ``at_most``."""
    deadline = time.monotonic() + budget
    last, since = store.num_objects(), time.monotonic()
    while time.monotonic() < deadline:
        gc.collect()
        n = store.num_objects()
        if at_most is not None and n <= at_most:
            return n
        if n != last:
            last, since = n, time.monotonic()
        elif at_most is None and time.monotonic() - since > 0.5:
            return n
        time.sleep(0.05)
    return store.num_objects()


@pytest.mark.parametrize("plan", [True, False], ids=["ring", "poll"])
def test_a_cancelled_consumer_leaves_the_store_at_its_baseline(served, plan):
    """Cancelled after two chunks of a hundred tokens: the ring's slots,
    acks and stop flag are swept by the pump's next write (or the
    replica's generator dropped, over the poll transport) and the
    replica counts no request as ongoing."""
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.core.config import cfg
    store = rt_mod.get_runtime_if_exists().store
    hs = served.options(method_name="completions_stream", stream=True)
    list(hs.remote(_request(5)))
    base = _objects(store)
    cfg.override(serve_static_decode_plan=plan)
    try:
        gen = hs.remote(_request(6, 100))
        assert next(gen)["choices"][0]["text"]
        assert next(gen)["choices"][0]["text"]
        gen.cancel()
        with pytest.raises(StopIteration):
            next(gen)
    finally:
        cfg.reset("serve_static_decode_plan")
    assert _objects(store, at_most=base) <= base
    # and the next stream is served as the first was
    assert len(_text(list(hs.remote(_request(7))))) == 12


@pytest.mark.parametrize("lora, status", [("nobody", 500), ("full", 503)])
def test_a_request_refused_at_submit_has_the_unary_calls_status(
        served, lora, status):
    """``_submit`` runs in the call that makes the stream: an unknown
    adapter (ValueError) and a full slot table (RuntimeError
    "overloaded: ...", retryable) reach an HTTP client of the STREAM
    with the status line the unary call gives them — before this the
    stream had answered 200 and broken off."""
    body = {"prompt": [1, 2, 3], "max_tokens": 4, "lora": lora}
    unary = _post("completions", body)
    stream = _post("completions_stream?stream=1", body)
    assert unary[0] == stream[0] == status
    if status == 503:
        assert json.loads(stream[1])["error"] == "overloaded"
        assert stream[2].get("Retry-After") == "1"
    ok = _post("completions_stream?stream=1", {"prompt": [1, 2, 3],
                                               "max_tokens": 4})
    assert ok[0] == 200 and b"finish_reason" in ok[1]
