"""The replica's stream pump (llm/serving.py LLMServer._pump): one thread
carries every open stream's tokens to its sink — the text and the
``finish_reason`` are what ``completions()`` gives, a sink without
credit delays itself alone and loses nothing, a closed one is dropped,
the loop's death reaches every stream, and no thread belongs to a
stream."""
import threading
import time

import pytest

from benchmarks.tokenizer import OneCharTokenizer
from ray_tpu.llm.paged_engine import PagedEngineConfig
from ray_tpu.llm.serving import LLMConfig, LLMServer, TokenStream
from ray_tpu.models import llama


class Sink:
    """An in-memory sink of the push protocol, which refuses its first
    ``refuse`` puts."""

    def __init__(self, refuse=0):
        self.items, self.error, self.ended = [], None, False
        self.refuse, self.refused = refuse, 0
        self.shut = False
        self.over = threading.Event()

    def put(self, item):
        if self.refuse > 0:
            self.refuse -= 1
            self.refused += 1
            return False
        self.items.append(item)
        return True

    def end(self):
        self.ended = True
        self.over.set()
        return True

    def fail(self, exc):
        self.error = exc
        self.over.set()
        return True

    def closed(self):
        return self.shut

    @property
    def texts(self):
        return [it["choices"][0]["text"] for it in self.items]


def _server(**over):
    kw = dict(model=llama.llama_tiny(vocab_size=258, max_seq_len=128),
              # every id is one character and none ends an answer: a
              # delta of text is a delta of tokens (the default
              # ByteTokenizer decodes a random model's bytes to
              # replacement characters that a later byte rewrites)
              tokenizer=OneCharTokenizer(258),
              max_batch_size=4, page_size=8, num_pages=64,
              max_pages_per_seq=16, chunk_size=16)
    kw.update(over)
    return LLMServer(LLMConfig(model_id="tiny-pump", warmup=False,
                               engine=PagedEngineConfig(**kw)))


@pytest.fixture(scope="module")
def srv():
    server = _server()
    # compile what the tests below dispatch, outside their clocks
    server.completions({"prompt": list(range(1, 25)), "max_tokens": 12})
    yield server
    server._stop = True
    server._wake.set()


def _request(i, max_tokens=12):
    return {"prompt": list(range(1 + i, 25 + i)), "max_tokens": max_tokens}


def _stream_deltas(server, before):
    st = server.engine_stats()
    return {k: st[k] - before[k] for k in st if k.startswith("stream_")}


def _wait(sinks, timeout=120):
    deadline = time.monotonic() + timeout
    for sink in sinks:
        assert sink.over.wait(max(0.0, deadline - time.monotonic()))


def test_thirty_two_streams_get_what_completions_gives(srv):
    """32 concurrent streams over four slots, each pushed to a sink:
    concatenated, a sink's chunks are the text ``completions()`` gives
    for the same greedy prompt, the last one carries its
    ``finish_reason`` and no other does, and the pump's counters are
    what the sinks got."""
    want = [srv.completions(_request(i))["choices"][0] for i in range(32)]
    before = srv.engine_stats()
    sinks = [Sink() for _ in range(32)]
    for i, sink in enumerate(sinks):
        srv.completions_stream(_request(i)).attach(sink)
    _wait(sinks)
    got = _stream_deltas(srv, before)
    for sink, ref in zip(sinks, want):
        assert sink.ended and sink.error is None
        assert "".join(sink.texts) == ref["text"] and len(ref["text"]) == 12
        reasons = [it["choices"][0]["finish_reason"] for it in sink.items]
        assert reasons[-1] == ref["finish_reason"] == "length"
        assert not any(reasons[:-1])
        assert all(it["model"] == "tiny-pump" for it in sink.items)
    assert got["stream_chunks"] == sum(
        sum(map(bool, sink.texts)) for sink in sinks)
    assert got["stream_first_chunks"] == 32
    assert 0 < got["stream_first_lag_ns"] <= got["stream_lag_ns"]
    assert 0 < got["stream_passes"] <= got["stream_chunks"]
    assert got["stream_deferred"] == 0


def test_a_sink_without_credit_gets_one_longer_delta(srv):
    """Two streams of one prompt, decoded side by side; one sink refuses
    its first three puts. It loses nothing: its first chunk is the text
    the other got in several, the refusals are ``stream_deferred``, and
    the other stream got every chunk in its own time."""
    before = srv.engine_stats()
    slow, quick = Sink(refuse=3), Sink()
    req = _request(0, max_tokens=40)
    srv.completions_stream(req).attach(slow)
    srv.completions_stream(req).attach(quick)
    _wait([slow, quick])
    got = _stream_deltas(srv, before)
    assert slow.refused == 3 and got["stream_deferred"] == 3
    assert "".join(slow.texts) == "".join(quick.texts)
    assert len(slow.items) < len(quick.items)
    held = next(n for n in range(1, len(quick.items) + 1)
                if "".join(quick.texts[:n]) == slow.texts[0])
    assert held >= 2
    assert got["stream_chunks"] == sum(
        sum(map(bool, s.texts)) for s in (slow, quick))


def test_a_closed_sink_is_dropped_and_the_others_finish(srv):
    gone, stays = Sink(), Sink()
    srv.completions_stream(_request(1, max_tokens=40)).attach(gone)
    srv.completions_stream(_request(2, max_tokens=40)).attach(stays)
    gone.shut = True
    _wait([stays])
    assert stays.ended and "".join(stays.texts)
    # dropped: neither ended nor failed, and nothing after the flag
    # but what a pass already under way had put
    assert not gone.ended and gone.error is None
    assert len(gone.items) <= 1


def test_the_iterator_is_the_pull_side_of_the_same_pump(srv):
    """Iterated, a stream is its own sink: ``next()`` gives the chunks,
    StopIteration follows the closing one for good, and a stream that
    has a sink takes no second."""
    ref = srv.completions(_request(3))["choices"][0]
    stream = srv.completions_stream(_request(3))
    assert isinstance(stream, TokenStream) and iter(stream) is stream
    chunks = list(stream)
    assert "".join(c["choices"][0]["text"] for c in chunks) == ref["text"]
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    with pytest.raises(StopIteration):
        next(stream)
    with pytest.raises(RuntimeError, match="already has a sink"):
        stream.attach(Sink())
    # closed early, it ends for its reader and the pump lets it go
    early = srv.completions_stream(_request(4, max_tokens=40))
    assert next(early)["choices"][0]["text"]
    early.close()
    with pytest.raises(StopIteration):
        next(early)


def test_a_refused_request_is_refused_by_the_call_itself(srv):
    """``_submit`` runs in the call that makes the stream, not at the
    first ``next()``: a request the server cannot take raises there,
    typed as ``completions()`` types it, and leaves no stream behind."""
    before = srv.engine_stats()
    for call in (srv.completions_stream, srv.completions):
        with pytest.raises(ValueError, match="no adapter slot table"):
            call({"prompt": [1, 2, 3], "lora": "nobody"})
    srv._multilora = _Overloaded()
    try:
        with pytest.raises(RuntimeError, match="^overloaded: "):
            srv.completions_stream({"prompt": [1, 2, 3], "lora": "full"})
    finally:
        srv._multilora = None
    assert _stream_deltas(srv, before)["stream_chunks"] == 0


class _Overloaded:
    def resolve(self, lora_id, steplock, pin=False):
        raise RuntimeError("overloaded: every adapter slot is in flight")


def test_no_thread_belongs_to_a_stream():
    """24 streams open at once: the process runs the threads it ran
    with none — the loop and ONE pump."""
    server = _server()
    try:
        server.completions(_request(0, max_tokens=4))
        idle = threading.active_count()
        sinks = [Sink() for _ in range(24)]
        for i, sink in enumerate(sinks):
            server.completions_stream(_request(i, 40)).attach(sink)
        while not any(sink.items for sink in sinks):
            time.sleep(0.005)
        assert not all(sink.over.is_set() for sink in sinks)
        assert threading.active_count() == idle
        pumps = [t for t in threading.enumerate()
                 if t.name == "llm-stream-pump" and t.is_alive()]
        assert server._pump_thread in pumps
        _wait(sinks)
        assert all(sink.ended for sink in sinks)
    finally:
        server._stop = True
        server._wake.set()


def test_the_loops_death_fails_every_open_stream():
    """The engine loop dies with streams open: each gets the error
    through its sink or its iterator, as ``completions()`` raises it."""
    server = _server()
    server.completions(_request(0, max_tokens=4))
    step = server.engine.step

    def dies_mid_answer():
        if any(len(r.out_ids) >= 16
               for r in server.engine._active.values()):
            raise MemoryError("the device is gone")
        step()

    server.engine.step = dies_mid_answer
    pushed = [Sink() for _ in range(6)]
    for i, sink in enumerate(pushed):
        server.completions_stream(_request(i, 100)).attach(sink)
    pulled = server.completions_stream(_request(7, 100))
    _wait(pushed, timeout=30)
    # four had a slot and some of their answer, two waited for one
    assert sum(bool(sink.items) for sink in pushed) == 4
    for sink in pushed:
        assert not sink.ended and len("".join(sink.texts)) < 100
        assert isinstance(sink.error, RuntimeError)
        assert "engine loop died" in str(sink.error)
        assert isinstance(sink.error.__cause__, MemoryError)
    with pytest.raises(RuntimeError, match="engine loop died"):
        for _ in pulled:
            pass
    with pytest.raises(RuntimeError, match="engine loop died"):
        server.check_health()
    server._stop = True
