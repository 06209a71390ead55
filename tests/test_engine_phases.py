"""The serving loop's phase times and dispatch counters
(llm/paged_engine.py PHASES / stats, util/profiling.phase): the phases
partition the stepping thread's wall time (its CPU time is read from
outside it), a launch is told from a readback's wait, the counters count what
the dispatch decided, the programs carry their family's name, under a
profiler session the phases are spans on the trace's host plane, and the
stream pump counts a token's way from its booking to its stream's sink
(llm/serving.py LLMServer._pump; the pump's own tests are
tests/test_stream_pump.py)."""
import re
import sys
import threading
import time

import jax
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.llm.paged_engine import (LAUNCHES, PHASES, STREAM_COUNTERS,
                                      PagedEngineConfig,
                                      PagedInferenceEngine)
from ray_tpu.models import llama, mla_moe

ENGINE_PHASES = [k for k in PHASES if "loop" not in k]


def _cfg(**over):
    kw = dict(model=llama.llama_tiny(vocab_size=258, max_seq_len=128),
              max_batch_size=4, page_size=8, num_pages=64,
              max_pages_per_seq=16, chunk_size=16)
    kw.update(over)
    return PagedEngineConfig(**kw)


@pytest.fixture(scope="module", params=["llama", "latent"])
def engine(request):
    over = {} if request.param == "llama" else dict(
        model=mla_moe.mla_moe_tiny(vocab_size=258, max_seq_len=128))
    eng = PagedInferenceEngine(_cfg(**over), rng_seed=0)
    # compile what the tests below dispatch, outside their clocks
    eng.generate([list(range(1, 40)), list(range(3, 20))],
                 SamplingParams(max_tokens=10))
    return eng


def _deltas(eng, before):
    return {k: v - before[k] for k, v in eng.stats.items()
            if not k.startswith("max_")}


def test_phase_adds_time_keeps_the_longest_and_never_swallows():
    from ray_tpu.util.profiling import phase
    stats = {}
    for pause in (0.002, 0.02, 0.001):
        with phase(stats, "ns_x", "rtpu.test.x"):
            time.sleep(pause)
    assert 23e6 <= stats["ns_x"] < 200e6
    assert 20e6 <= stats["max_ns_x"] < stats["ns_x"]
    with pytest.raises(KeyError):
        with phase(stats, "ns_x", "rtpu.test.x"):
            raise KeyError("through")
    assert set(stats) == {"ns_x", "max_ns_x"}


def test_phase_counts_an_occurrence_under_a_second_heading():
    """``also`` grows by what the phase's own key grows by, occurrence
    for occurrence, and has no maximum of its own."""
    from ray_tpu.util.profiling import phase
    stats = {"launch_ns_t": 5}
    for _ in range(3):
        with phase(stats, "ns_t_device", "rtpu.test.launch", "launch_ns_t"):
            time.sleep(0.001)
    with phase(stats, "ns_t_device", "rtpu.test.wait"):
        time.sleep(0.001)
    assert 3e6 <= stats["launch_ns_t"] - 5 < stats["ns_t_device"]
    assert set(stats) == {"ns_t_device", "max_ns_t_device", "launch_ns_t"}


@pytest.fixture(scope="module", params=["llama", "latent"])
def deep_engine(request):
    """An engine whose step takes tens of milliseconds on a CPU, as it
    does on a chip: eight layers at four (two) times the tiny width. At
    the tiny models' 2.5 ms a step the interpreter's own ~50 us between
    one phase's exit and the next one's entry are 2% of it."""
    if request.param == "llama":
        model = llama.llama_tiny(vocab_size=258, max_seq_len=128, dim=256,
                                 n_layers=8, mlp_dim=1024)
    else:
        model = mla_moe.mla_moe_tiny(vocab_size=258, max_seq_len=128, dim=128,
                                     n_layers=8, dense_mlp_dim=512,
                                     mlp_dim=128)
    eng = PagedInferenceEngine(_cfg(model=model), rng_seed=0)
    eng.generate([list(range(1, 40)), list(range(3, 20))],
                 SamplingParams(max_tokens=10))
    return eng


def test_engine_phases_partition_the_step(deep_engine):
    """Every nanosecond of step() falls in one of the eight engine
    phases, whichever dispatch a launch or a readback belongs to: their
    deltas sum to the wall time around the step() calls."""
    engine = deep_engine
    assert set(ENGINE_PHASES) <= set(engine.stats)
    assert all("max_" + k in engine.stats for k in PHASES)
    before = dict(engine.stats)
    reqs = [engine.submit(list(range(5 + i, 45 + 3 * i)),
                          SamplingParams(max_tokens=20)) for i in range(3)]
    wall = 0
    while engine.has_work():        # to the last readback
        t0 = time.perf_counter_ns()
        engine.step()
        wall += time.perf_counter_ns() - t0
    assert all(r.done for r in reqs)
    d = _deltas(engine, before)
    # launches behind unbooked dispatches are in it: prefills beside a
    # decode, and decodes behind the decode before them
    assert d["dispatches_overlapped"] > 0
    assert d["decode_rows_fed_on_device"] > 0
    for k in ENGINE_PHASES:
        assert d[k] > 0, k
        assert engine.stats["max_" + k] <= engine.stats[k]
    # nothing outside step() ran: the loop phases belong to LLMServer
    assert d["ns_loop_other"] == d["ns_loop_idle"] == 0
    total = sum(d[k] for k in ENGINE_PHASES)
    assert total <= wall
    assert total >= 0.98 * wall, (total, wall)
    # the new keys leave the partition alone: no key that starts with
    # ns_ but the ten, and none of them among the maxima
    assert {k for k in engine.stats if k.startswith("ns_")} == set(PHASES)
    assert {k for k in engine.stats if k.startswith("max_ns_")} == \
        {"max_" + k for k in PHASES}
    # a launch is part of its family's device phase
    for family, (_, launch_key) in LAUNCHES.items():
        assert 0 < d[launch_key] <= d[f"ns_{family}_device"], family


@pytest.mark.parametrize("max_adapters", [0, 2])
def test_loop_phases_through_a_local_server(max_adapters):
    """The ten phases sum to the stepping thread's wall time on every
    server: a replica steps one engine, and an adapter's request is a
    row of that engine's dispatches (max_adapters > 0), not an engine
    of its own."""
    from ray_tpu.llm.serving import LLMConfig, LLMServer
    model_id = f"tiny-phases-{max_adapters}"
    srv = LLMServer(LLMConfig(
        model_id=model_id, warmup=False,
        engine=_cfg(max_adapters=max_adapters, lora_rank=4)))
    try:
        first = srv.engine_stats()
        assert "ns_loop_other" in first and "clock_ns" in first
        time.sleep(0.3)
        idle = srv.engine_stats()
        # nothing was submitted: the thread sat in rtpu.loop.idle (a
        # wait of 50 ms at most is booked when it ends)
        grown = idle["ns_loop_idle"] - first["ns_loop_idle"]
        assert grown > 0.2e9
        assert grown <= idle["clock_ns"] - first["clock_ns"] + 60e6
        # the housekeeping of an idle iteration is rtpu.loop.other:
        # microseconds each, never the wait
        assert 0 < idle["ns_loop_other"] - first["ns_loop_other"] < 0.02e9
        out = srv.completions({"prompt": list(range(1, 30)),
                               "max_tokens": 4})
        assert len(out["choices"][0]["token_ids"]) == 4
        if max_adapters:
            from ray_tpu.llm import lora
            from ray_tpu.llm.multilora import AdapterRegistry
            adapter = lora.random_adapter(
                jax.random.PRNGKey(7), srv.engine.cfg.model, rank=4,
                alpha=64.0, targets=("wq", "wv", "lm_head"))
            AdapterRegistry(model_id).publish("tenant", adapter)
            tuned = srv.completions({"prompt": list(range(1, 30)),
                                     "max_tokens": 4, "lora": "tenant"})
            assert len(tuned["choices"][0]["token_ids"]) == 4
            assert tuned["choices"][0]["token_ids"] != \
                out["choices"][0]["token_ids"]
            assert srv.loaded_loras() == ["tenant@0"]
        after = srv.engine_stats()
        assert after["ns_loop_other"] > idle["ns_loop_other"]
        assert after["ns_decode_device"] > 0
        # the thread's CPU clock, read from this thread: asleep in
        # rtpu.loop.idle it burns next to none, at work it burns some,
        # and never more than the wall time it worked for
        cpu = [s["step_thread_cpu_ns"] for s in (first, idle, after)]
        assert 0 <= cpu[1] - cpu[0] < 0.1 * grown
        worked = sum(after[k] - idle[k] for k in PHASES
                     if k != "ns_loop_idle")
        assert 0 < cpu[2] - cpu[1] <= worked + 20e6
        # over a window, all ten sum to the thread's wall time, give or
        # take the idle wait (50 ms at most) in progress at either end
        span = after["clock_ns"] - first["clock_ns"]
        total = sum(after[k] - first[k] for k in PHASES)
        assert 0.98 * span - 60e6 <= total <= 1.02 * span + 60e6
    finally:
        srv._stop = True
        srv._wake.set()


def test_dispatch_and_request_counters(engine):
    before = dict(engine.stats)
    n = 5   # more requests than slots: the fifth waits for one
    outs = engine.generate(
        [list(range(2 + i, 30 + 5 * i)) for i in range(n)],
        SamplingParams(max_tokens=6))
    assert all(len(o["token_ids"]) == 6 for o in outs)
    d = _deltas(engine, before)
    bs = engine.cfg.max_batch_size
    assert 0 < d["decode_live_slots"] <= d["decode_dispatches"] * bs
    assert d["decode_live_pages"] >= d["decode_live_slots"]
    # the table the program sweeps: every row, live or not, x its width
    assert d["decode_table_pages"] >= d["decode_live_pages"]
    assert d["decode_table_pages"] % bs == 0
    assert d["decode_steps"] >= d["decode_dispatches"] > 0
    assert 0 < d["prefill_rows_live"] <= d["prefill_rows_padded"]
    assert d["prefill_rows_padded"] <= \
        d["prefill_dispatches"] * engine.prefill_rows
    assert d["prefill_tokens"] + d["prefix_tokens_saved"] == \
        sum(o["prompt_tokens"] for o in outs)
    assert d["prefill_ctx_pages"] >= d["prefill_rows_live"]
    assert d["prefill_attn_pairs"] >= d["prefill_tokens"]
    assert d["admitted"] == d["first_tokens"] == n
    assert d["queue_wait_ns"] > 0 and d["prefill_span_ns"] > 0
    # a model that routes returns its [E] assignment counts with every
    # dispatch (top-k x expert layers a token; the dense prefix and the
    # shared expert route nothing); a dense model has no such keys
    per_token = engine.model.routed_per_token(engine.cfg.model)
    assert ("moe_assign_run" in d) == bool(per_token)
    if per_token:
        assert per_token == 2 * 2       # top-2, 2 of the 3 layers
        assert d["moe_expert_load_sum"] == d["moe_assign_run"]
        assert per_token * d["prefill_tokens"] < d["moe_assign_live"] \
            <= d["moe_assign_run"]


def test_launch_is_notified_once_a_dispatch(engine):
    """What serving's stream pump sleeps on: the engine bumps
    ``launch_gen`` and notifies ``launched`` after every launch, before
    it blocks on a result, so the pump's Python runs beside the program
    and not in the way of the next launch — and once more after a
    booking that another readback's wait follows, never more than once
    a booking."""
    import threading
    before = dict(engine.stats)
    gen0, woken = engine.launch_gen, []

    def waiter():
        with engine.launched:
            woken.append(engine.launched.wait_for(
                lambda: engine.launch_gen > gen0, timeout=30))
    t = threading.Thread(target=waiter)
    t.start()
    engine.generate([list(range(5, 30))],
                    SamplingParams(max_tokens=5, temperature=0.0))
    t.join(30)
    assert woken == [True]
    d = {k: engine.stats[k] - before[k] for k in (
        "prefill_dispatches", "decode_dispatches", "spec_dispatches")}
    assert 0 < sum(d.values()) <= engine.launch_gen - gen0 <= \
        2 * sum(d.values())


def test_decode_live_pages_by_hand():
    """Two requests, decode window 1, pages of 8 tokens: a prompt of 15
    and one of 24 tokens decode three times after their first token, at
    lengths (15, 24), (16, 25), (17, 26): 2+3, 2+4 and 3+4 pages."""
    eng = PagedInferenceEngine(
        _cfg(decode_window=1, enable_prefix_caching=False), rng_seed=0)
    eng.generate([list(range(1, 16)), list(range(50, 74))],
                 SamplingParams(max_tokens=4))
    st = eng.stats
    assert st["decode_dispatches"] == 3 == st["decode_steps"]
    assert st["decode_live_slots"] == 6
    assert st["decode_live_pages"] == 5 + 6 + 7
    # 4 rows x the whole 16-wide table (no bucketing under 48 pages),
    # three times
    assert st["decode_table_pages"] == 3 * 4 * 16
    # the 16-page table is one 128-key block: each live row's sweep copies
    # all of it, clamped, three times
    assert st["decode_swept_pages"] == 3 * 2 * 16
    assert st["prefill_tokens"] == 15 + 24
    # rows of 16 tokens: [0,15) | [0,16) [16,24): 2, 2 and 3 pages
    assert st["prefill_rows_live"] == 3
    assert st["prefill_ctx_pages"] == 2 + 2 + 3
    # causal pairs: 15*16/2, 16*17/2, and 8 tokens over 16 cached
    assert st["prefill_attn_pairs"] == 120 + 136 + (8 * 16 + 36)


def test_programs_carry_their_family_name():
    import numpy as np
    eng = PagedInferenceEngine(_cfg(spec_tokens=2), rng_seed=0)
    mode = (False, False, False)
    fns = {"rtpu_decode_w8": eng._decode_window_fn(8, mode, 16),
           "rtpu_decode_w1": eng._decode_window_fn(1, mode, 4),
           "rtpu_prefill_r2": eng._prefill_rows_fn(2, mode, 16),
           "rtpu_verify_r4": eng._verify_fn(4, 3, 16)}
    for name, fn in fns.items():
        assert fn.__name__ == name
        assert re.fullmatch(r"rtpu_(decode|prefill|verify)_[wr]\d+", name)
        # the reduction's family patterns match the kernels listed after
        # the module's name: the name itself must not read as a kernel
        assert "ragged" not in name
    # the name the profiler's XLA Modules line shows
    hlo = fns["rtpu_verify_r4"].lower(
        eng.params, eng.caches, np.zeros((4, 3), np.int32),
        np.zeros((4, 16), np.int32), np.zeros((4,), np.int32),
        None, None).as_text()
    assert "module @jit_rtpu_verify_r4" in hlo


def test_phases_are_spans_on_the_profilers_host_plane(engine, tmp_path):
    from benchmarks.reduce import xplane
    fed = engine.stats["decode_rows_fed_on_device"]
    with jax.profiler.trace(str(tmp_path)):
        engine.generate([list(range(9, 60))], SamplingParams(max_tokens=20))
    # a decode went out behind an unbooked one inside the session
    assert engine.stats["decode_rows_fed_on_device"] > fed
    planes = xplane.load(xplane.find_xplane(str(tmp_path)))
    host = next(p for p in planes if p["name"] == "host")
    names = {e[0] for e in host["lines"][0]["events"]}
    if not names:
        pytest.skip("the CPU profiler wrote no host plane here")
    assert {PHASES[k] for k in ENGINE_PHASES} <= names
    # a device phase is two spans, a launch and a readback's wait, and
    # no span wears the name they shared
    assert {name for name, _ in LAUNCHES.values()} <= names
    assert {"rtpu.engine.prefill.wait", "rtpu.engine.decode.wait"} <= names
    assert not [n for n in names if n.endswith(".device")]
    # on the trace's clock: each lies inside the session
    events = host["lines"][0]["events"]
    for name in ("rtpu.engine.decode.launch", "rtpu.engine.decode.wait"):
        dev = [e for e in events if e[0] == name]
        assert dev and all(e[1] >= 0 and e[2] > 0 for e in dev)
    # siblings, not children: no launch or wait lies inside another
    # span of the stepping thread, so a gap's name holds one of them
    spans = sorted((e[1], e[1] + e[2]) for e in events
                   if e[0].startswith("rtpu.engine."))
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))


def _stream_server(**over):
    from ray_tpu.llm.serving import LLMConfig, LLMServer
    return LLMServer(LLMConfig(model_id="tiny-streams", warmup=False,
                               engine=_cfg(**over)))


class _NappingSink:
    """A pushed stream's sink whose write takes ``nap`` seconds."""

    def __init__(self, nap):
        self.nap, self.texts = nap, []
        self.over = threading.Event()

    def put(self, item):
        time.sleep(self.nap)            # the transport's write
        self.texts.append(item["choices"][0]["text"])
        return True

    def end(self):
        self.over.set()
        return True

    fail = end

    def closed(self):
        return False


def test_sixteen_streams_count_their_chunks_exactly():
    """Sixteen concurrent streams through a local server, four slots,
    each pushed to a sink: ``stream_chunks`` is the text chunks the
    sinks took, every request has one first chunk, and a sink that
    sleeps in every write shows in the lag (booking -> taken by the
    sink), not in the pump thread's CPU time."""
    srv = _stream_server()
    nap = 0.004
    sinks = [_NappingSink(nap) for _ in range(16)]
    before = srv.engine_stats()
    assert all(before[k] == 0 for k in STREAM_COUNTERS)
    try:
        for i, sink in enumerate(sinks):
            srv.completions_stream(
                {"prompt": list(range(1 + i, 25 + i)),
                 "max_tokens": 9}).attach(sink)
        for sink in sinks:
            assert sink.over.wait(120)
        st = srv.engine_stats()
    finally:
        srv._stop = True
        srv._wake.set()
    got = [sum(map(bool, sink.texts)) for sink in sinks]
    assert all(n >= 1 for n in got)
    assert st["stream_chunks"] == sum(got)
    assert st["stream_first_chunks"] == 16
    # every chunk's lag holds its own sink's nap (and those of the
    # streams served before it in the pass); the first chunks' are
    # among them
    assert st["stream_lag_ns"] >= sum(got) * nap * 1e9
    assert st["stream_first_lag_ns"] >= 16 * nap * 1e9
    assert st["stream_first_lag_ns"] < st["stream_lag_ns"]
    # asleep is not CPU: the pump's CPU time is far under the naps alone
    assert 0 < st["stream_cpu_ns"] < 0.5 * sum(got) * nap * 1e9
    assert 0 < st["stream_passes"] <= st["stream_chunks"]
    assert st["stream_deferred"] == 0
    # the stepping thread's partition is none the worse for them
    assert {k for k in st if k.startswith("ns_")} == set(PHASES)


def test_the_pump_and_the_loop_lose_no_update_between_them():
    """The pump's adds and the stepping thread's are read-modify-writes
    of one dict, each of its own keys, while a third thread takes
    snapshots: at a short switch interval, sixteen pulled streams of 40
    tokens lose no chunk and no token."""
    srv = _stream_server()
    n_streams, n_tokens = 16, 40
    got, snaps = [], []

    def client(i):
        chunks = [ch["choices"][0]["text"] for ch in srv.completions_stream(
            {"prompt": list(range(1 + i, 25 + i)), "max_tokens": n_tokens})]
        got.append(sum(map(bool, chunks)))

    def snapshots():
        while len(got) < n_streams:
            snaps.append(srv.engine_stats()["stream_chunks"])

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        before = srv.engine_stats()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_streams)]
        threads.append(threading.Thread(target=snapshots))
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        st = srv.engine_stats()
    finally:
        sys.setswitchinterval(was)
        srv._stop = True
        srv._wake.set()
    assert len(got) == n_streams
    assert st["stream_chunks"] - before["stream_chunks"] == sum(got)
    assert st["stream_first_chunks"] == n_streams
    assert st["tokens_out"] - before["tokens_out"] == n_streams * n_tokens
    assert snaps == sorted(snaps) and snaps[-1] <= st["stream_chunks"]
    assert st["stream_cpu_ns"] > 0 and st["stream_lag_ns"] > 0


def test_a_streamed_request_has_an_llm_deliver_span():
    """With tracing on, a streamed request's ``llm.request`` gets a
    fourth child: first token booked -> its chunk taken by the
    transport, inside ``llm.decode``'s extent."""
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.core.config import cfg

    class _StubRT:
        def __init__(self):
            self.spans = []

        def record_trace_span(self, rec):
            self.spans.append(rec)

    stub = _StubRT()
    prev_rt = rt_mod.get_runtime_if_exists()
    cfg.override(tracing_enabled=True)
    rt_mod.set_runtime(stub)
    srv = _stream_server()
    try:
        chunks = list(srv.completions_stream(
            {"prompt": list(range(1, 30)), "max_tokens": 12}))
        assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    finally:
        srv._stop = True
        srv._wake.set()
        rt_mod.set_runtime(prev_rt)
        cfg.reset("tracing_enabled")
    (root,) = [s for s in stub.spans if s["name"] == "llm.request"]
    kids = {s["name"]: s for s in stub.spans
            if s.get("parent_id") == root["span_id"]}
    assert set(kids) == {"llm.queue", "llm.prefill", "llm.decode",
                         "llm.deliver"}
    deliver, decode = kids["llm.deliver"], kids["llm.decode"]
    assert deliver["trace_id"] == root["trace_id"]
    assert deliver["start_s"] == pytest.approx(decode["start_s"], abs=1e-6)
    assert 0.0 < deliver["dur_s"] <= decode["dur_s"] + 1e-6


def _brute_key_steps(rows, window, table_pages, page, q_tile, block_keys):
    """(live steps, those with the predicate) of a call over ``rows`` of
    (pos, n), block by block: a block is live while it starts below the
    tile's live pages (the kernel module's own `_tile_pages`), and takes
    the body with the predicate unless every row of the tile attends
    every key of it by the kernel's mask, ``k <= q_pos`` and ``k <
    kv_len``, evaluated key by key."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    block_pages = max(1, block_keys // page)
    steps = masked = 0
    for pos, n in rows:
        for t in range(-(-window // q_tile)):
            pages = int(rpa._tile_pages(pos, n, t, q_tile, page, np))
            q_pos = pos + t * q_tile + np.arange(q_tile)[:, None]
            for b in range(-(-table_pages // block_pages)):
                if b * block_pages >= pages:
                    break
                k = b * block_keys + np.arange(block_keys)[None, :]
                steps += 1
                masked += not ((k <= q_pos) & (k < pos + n)).all()
    return steps, masked


def _cell_model(module, cfg):
    """(model module, one-layer config) at a serving cell's heads."""
    if module == "llama":
        return llama, llama.LlamaConfig(
            vocab_size=512, n_layers=1, mlp_dim=128, max_seq_len=16384,
            **cfg)
    return mla_moe, mla_moe.MlaMoeConfig(
        vocab_size=512, n_layers=1, max_seq_len=16384)


@pytest.mark.parametrize("module,cfg", [
    ("llama", dict(dim=4096, n_heads=32, n_kv_heads=8)),       # doc-QA's
    ("llama", dict(dim=2048, n_heads=16, n_kv_heads=16)),      # OLMoE's
    ("mla_moe", {}),                                           # kanana's
], ids=["gqa", "mha", "latent"])
def test_key_step_counters_are_the_kernels_own_count(module, cfg):
    """`live_key_steps` — what `_book_prefill` adds to
    ``prefill_key_steps`` / ``prefill_key_steps_masked`` — over a seeded
    mix of (pos, n) rows at the widths of the three serving cells and
    every table bucket, against the brute-force count, with the tile and
    block width each model module reports for its kernel."""
    from ray_tpu.ops.ragged_paged_attention import live_key_steps
    rng = np.random.RandomState(32)
    mod, mc = _cell_model(module, cfg)
    chunk, page = 128, 16
    widths = set()
    for table in (4, 8, 16, 32, 64, 128, 256, 512, 1024):
        step = mod.attn_step(mc, chunk, page, table)
        widths.add(step["block_keys"])
        top = table * page - chunk
        rows = [(int(rng.randint(0, top + 1)) if top > 0 else 0,
                 int(rng.randint(1, min(chunk, table * page) + 1)))
                for _ in range(6)] + [(max(top, 0), min(chunk, table * page)),
                                      (0, 0)]
        got = live_key_steps([p for p, _ in rows], [n for _, n in rows],
                             chunk, table, page_size=page, **step)
        assert got == _brute_key_steps(rows, chunk, table, page, **step), \
            (table, step)
        assert 0 < got[1] <= got[0]
    assert len(widths) > 1 and min(widths) == 128   # narrow tables clamp


@pytest.mark.parametrize("cells", [(
    ("llama", dict(dim=4096, n_heads=32, n_kv_heads=8)),       # doc-QA's
    ("llama", dict(dim=2048, n_heads=16, n_kv_heads=16)),      # OLMoE's
    ("llama", dict(dim=4096, n_heads=32, n_kv_heads=4)),       # Mellum's
    ("mla_moe", {}),                                           # kanana's
)], ids=["gqa-mha-gqa8-latent"])
def test_decode_swept_pages_against_a_brute_count(cells):
    for module, cfg in cells:
        _swept_pages_against_a_brute_count(module, cfg)


def _swept_pages_against_a_brute_count(module, cfg):
    """`_swept_pages` — what a decode launch adds to
    ``decode_swept_pages`` — at the heads of the serving cells and every
    table bucket, against the pages the kernel's sweeps step through
    counted block by block: a live row's blocks (those that start below
    its live pages, never more than the grid has steps) x the pages a
    block holds, its masked tail and all; a row of length 0 sweeps
    nothing. (The method on a stand-in for the engine: its model seam,
    its lengths, its page size.)"""
    import types
    mod, mc = _cell_model(module, cfg)
    page, bs = 16, 4
    eng = types.SimpleNamespace(
        cfg=types.SimpleNamespace(page_size=page),
        _lengths=np.zeros(bs, np.int64),
        _attn_step=lambda window, table: mod.attn_step(mc, window, page,
                                                       table))

    def swept(slots, table):
        return PagedInferenceEngine._swept_pages(eng, slots, table)
    rng = np.random.RandomState(48)
    widths = set()
    for table in (4, 16, 64, 128, 256, 512, 1024):
        block_pages = mod.attn_step(mc, 1, page, table)["block_keys"] // page
        widths.add(block_pages * page)
        cap = table * page
        eng._lengths[:] = [cap, 0, int(rng.randint(1, cap + 1)),
                           min(cap, block_pages * page)]
        want = live = 0
        for sl in range(bs):
            pages = -(-int(eng._lengths[sl]) // page)
            live += pages
            for b in range(-(-table // block_pages)):
                if b * block_pages < pages:
                    want += block_pages         # a live step: one block
        assert swept(range(bs), table) == want >= live, table
        assert swept([1], table) == 0
    assert len(widths) > 1 and min(widths) == 128


def test_prefill_step_counts_its_rows_key_steps(engine, monkeypatch):
    """The engine adds, for every prefill dispatch, the count of its live
    rows at the dispatch's table width."""
    from ray_tpu.llm import paged_engine
    want = [0, 0]

    def counted(starts, q_lens, window, table_pages, *, page_size, **step):
        assert page_size == engine.cfg.page_size
        assert step == engine.model.attn_step(
            engine.cfg.model, window, page_size, table_pages, 1)
        got = _brute_key_steps(list(zip(map(int, starts), map(int, q_lens))),
                               window, table_pages, page_size, **step)
        if window > 1:          # a decode launch counts its sweeps too
            want[0] += got[0]
            want[1] += got[1]
        return got
    monkeypatch.setattr(paged_engine, "live_key_steps", counted)
    before = dict(engine.stats)
    engine.generate([list(range(2 + i, 50 + 9 * i)) for i in range(4)],
                    SamplingParams(max_tokens=3))
    d = _deltas(engine, before)
    assert d["prefill_key_steps"] == want[0] >= d["prefill_rows_live"]
    assert d["prefill_key_steps_masked"] == want[1] >= d["prefill_rows_live"]
