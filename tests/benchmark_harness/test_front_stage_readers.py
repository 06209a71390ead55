"""The ten readers of the front path's clock (PR 53; ``benchmarks/
layer_metrics/_front.py``): each on a hand-made ``ctx`` for every serving
cell BENCHMARK.json declares it in — its value, None without its inputs,
None where a series' count falls short of the client's requests (a process
that had not flushed) —, the rule that the stages and what is left of
``front_overhead_ms`` add up to the client's TTFT. The CPU rehearsal of
doc-QA whose traced line holds all ten is ``test_front_stage_rehearsal.py``:
a file of one test sorts to the end of the suite's queue, where this one's
177 cases start in its first seconds — beside ``test_qwen3next_cell.py``'s
rehearsal, whose generator runs out of conversations on a loaded machine."""
import copy

import pytest
from bh_util import (REPO, cell_config, declared_pairs, load_json,
                     read_metric)

ROUTER, LLM = "openai-router", "llm:bench"
# hand-computed from the ctx below, in ms
EXPECTED = {
    "proxy_intake_ms": 3.0,
    "stream_open_ms": 4.0 + 6.0,
    "ingress_to_submit_ms": 12.0,
    "ring_write_ms": 0.2,
    "ring_hop_first_ms": 2.0 + 1.5,
    "ring_hop_ms": 1.0 + 0.8,
    "relay_first_ms": 0.5,
    "proxy_write_first_ms": 0.25,
    "proxy_loop_lag_ms": 0.7,
    "front_unexplained_ms": 50.0 - (12.0 + 20.0 + 3.5 + 0.5 + 0.25),
}
NAMES = tuple(EXPECTED)
PAIRS = declared_pairs(names=NAMES)


class Rec:
    """A client record: sent at ``sent``, first token chunk at ``first``."""

    def __init__(self, sent, first):
        self.sent, self.first = sent, first


# 40 requests of the run, warm-up included, each a first chunk 300 ms after
# its send; one more that never got a token
N = 40
RECORDS = [Rec(i * 0.5, i * 0.5 + 0.3) for i in range(N)] + [Rec(30.0, None)]
# the engine's TTFT: 4 observations of the reference check before the run
# (mean 100 ms), the run's 40 at a mean of 250 ms: front_overhead_ms = 50
TTFT = ({"count": 4, "mean": 0.100},
        {"count": 44, "mean": (4 * 0.100 + N * 0.250) / 44})


def _grown(base_n, base_mean, n, mean_ms):
    """(before, after): a {count, mean} series that held ``base_n``
    observations and gained ``n`` of ``mean_ms``."""
    after = {"count": base_n + n,
             "mean": (base_n * base_mean + n * mean_ms * 1e-3) / (base_n + n)}
    return ({"count": base_n, "mean": base_mean} if base_n else None), after


def _summary(short=None):
    """The two readings of ``metrics_summary()["requests"]``: two earlier
    proxied requests lie before the first (10 ms a stage), the run's 40
    between them. ``short``: a (group, stage, deployment) whose process
    had not flushed its last request."""
    stages = {
        ("front", "intake", ROUTER): 3.0,
        ("front", "open", ROUTER): 4.0, ("front", "open", LLM): 6.0,
        ("front", "to_submit", LLM): 12.0,
        ("front", "first_chunk", LLM): 20.0,
        ("front", "first_hop", LLM): 2.0, ("front", "first_hop", ROUTER): 1.5,
        ("front", "first_relay", ROUTER): 0.5,
        ("front", "first_write", ROUTER): 0.25,
        # every item: 10 chunks a stream, and the router's [DONE] line
        ("chunks", "hop", LLM): 1.0, ("chunks", "hop", ROUTER): 0.8,
        ("chunks", "relay", ROUTER): 0.4, ("chunks", "write", ROUTER): 0.2,
    }
    before = {"proxy": 2.0, "front": {}, "chunks": {}}
    after = {"proxy": 2.0 + N + 1, "front": {}, "chunks": {}}
    for (group, stage, dep), ms in stages.items():
        n = N * (10 if group == "chunks" else 1)
        if (group, stage, dep) == short:
            n = N - 1
        b, a = _grown(2, 0.010, n, ms)
        before[group].setdefault(stage, {})[dep] = b
        after[group].setdefault(stage, {})[dep] = a
    # a deployment of another application that the run never called
    for reading in (before, after):
        reading["front"]["open"]["other"] = {"count": 5, "mean": 0.5}
    lag_b, lag_a = _grown(100, 0.002, 500, 0.7)
    before["loop_lag"] = dict(lag_b, p99=0.01)
    after["loop_lag"] = dict(lag_a, p99=0.01)
    return {"requests": before}, {"requests": after}


# the engine's counters over the WINDOW: 400 chunks whose puts took 80 ms;
# the 25 requests whose first chunk fell in it waited 60 ms for it, where
# the run's 40 waited 20 (stage ``first_chunk``): the sum is the run's
STATS = ({"stream_chunks": 100, "stream_write_ns": 30_000_000,
          "stream_first_chunks": 10, "stream_first_lag_ns": 100_000_000},
         {"stream_chunks": 500, "stream_write_ns": 110_000_000,
          "stream_first_chunks": 35, "stream_first_lag_ns": 1_600_000_000})


def _ctx(cell, **over):
    ctx = {"config": cell_config(cell), "all_records": RECORDS,
           "records": RECORDS[10:], "engine_ttft": TTFT,
           "serve_summary": _summary(), "stats_before": STATS[0],
           "stats_after": STATS[1], "rehearse": False}
    ctx.update(over)
    return ctx


def test_every_reader_is_declared_in_the_six_serving_cells():
    bench = load_json(REPO, "BENCHMARK.json")
    serving = next(m for m in bench["end_to_end"]
                   if m["name"] == "out_tok_s")["workloads"]
    assert len(PAIRS) == len(NAMES) * 6
    for name in NAMES:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"][:6] == serving[:6]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"], entry["layer"]) == (
            "ms", "lower", "program_counter", "out_tok_s",
            "HTTP front and router")


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_the_hand_computed_value(name, cell):
    assert read_metric(name, _ctx(cell)) == pytest.approx(EXPECTED[name],
                                                          rel=1e-9)


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_none_without_its_inputs(name, cell):
    if name == "ring_write_ms":
        # no snapshot; and a program of before PR 53, which counts chunks
        # and not their writes
        assert read_metric(name, _ctx(cell, stats_before=None)) is None
        old = tuple({k: v for k, v in s.items() if k != "stream_write_ns"}
                    for s in STATS)
        assert read_metric(name, _ctx(cell, stats_before=old[0],
                                      stats_after=old[1])) is None
        return
    # an untraced run takes no baseline and no flushed reading
    assert read_metric(name, _ctx(cell, serve_summary=(None, None))) is None
    assert read_metric(name, _ctx(cell, serve_summary=None)) is None
    # a program of before PR 53: the groups it had, none of the clock's
    old = tuple({"router_wait": {"count": 91, "mean": 0.002},
                 "requests": {"proxy": 41.0, "handle": 91.0}}
                for _ in range(2))
    assert read_metric(name, _ctx(cell, serve_summary=old)) is None
    if name != "proxy_loop_lag_ms":     # the loop's watcher needs no client
        assert read_metric(name, _ctx(cell, all_records=[])) is None
    if name == "front_unexplained_ms":
        assert read_metric(name, _ctx(cell, engine_ttft=(None, None))) is None
        # the engine's window counters are not among its inputs
        assert read_metric(name, _ctx(cell, stats_after=None)) == \
            pytest.approx(EXPECTED[name])


SHORT = {
    "proxy_intake_ms": [("front", "intake", ROUTER)],
    "stream_open_ms": [("front", "open", ROUTER), ("front", "open", LLM)],
    "ingress_to_submit_ms": [("front", "to_submit", LLM)],
    "ring_hop_first_ms": [("front", "first_hop", LLM),
                          ("front", "first_hop", ROUTER)],
    "ring_hop_ms": [("chunks", "hop", LLM), ("chunks", "hop", ROUTER)],
    "relay_first_ms": [("front", "first_relay", ROUTER)],
    "proxy_write_first_ms": [("front", "first_write", ROUTER)],
    "front_unexplained_ms": [("front", "to_submit", LLM),
                             ("front", "first_chunk", LLM),
                             ("front", "first_hop", ROUTER),
                             ("front", "first_relay", ROUTER),
                             ("front", "first_write", ROUTER)],
}


@pytest.mark.parametrize("name,cell", [p for p in PAIRS if p[0] in SHORT])
def test_reader_gives_none_where_a_count_falls_short(name, cell):
    """39 of the client's 40 requests in one series: the process that
    holds it had not flushed, and a mean of other requests is no reading."""
    for short in SHORT[name]:
        ctx = _ctx(cell, serve_summary=_summary(short))
        assert read_metric(name, ctx) is None, short
    # another reader's series falling short is no concern of this one's
    others = {s for n, ss in SHORT.items() for s in ss} - set(SHORT[name])
    if name != "front_unexplained_ms":
        for short in sorted(others):
            ctx = _ctx(cell, serve_summary=_summary(short))
            assert read_metric(name, ctx) == pytest.approx(EXPECTED[name])


def test_a_series_born_in_the_run_needs_no_baseline():
    """The first reading may hold no ``front`` group at all (no proxied
    request before the run): the whole series is then the run's."""
    before, after = copy.deepcopy(_summary())
    run_only = {"requests": {"front": {"intake": {ROUTER: {
        "count": N, "mean": 0.003}}}, "loop_lag": {"count": 10,
                                                   "mean": 0.0007}}}
    for first in ({}, None, {"requests": {"proxy": 2.0}}):
        ctx = _ctx("docqa-sessions-1chip", serve_summary=(first, run_only))
        assert read_metric("proxy_intake_ms", ctx) == pytest.approx(3.0)
        assert read_metric("proxy_loop_lag_ms", ctx) == pytest.approx(0.7)
    # no observation between the readings: nothing to report
    ctx = _ctx("docqa-sessions-1chip", serve_summary=(after, after))
    for name in NAMES:
        if name != "ring_write_ms":
            assert read_metric(name, ctx) is None, name


@pytest.mark.parametrize("cell", sorted({c for _, c in PAIRS}))
def test_the_stages_and_the_rest_add_up_to_the_clients_ttft(cell):
    """way in + engine TTFT + the pump's first chunk + both rings + the
    relay + the proxy's write + what no process can stamp = what the
    client waited."""
    ctx = _ctx(cell)
    engine_ms = 250.0
    from benchmarks.layer_metrics._front import stage_ms
    parts = [read_metric(n, ctx) for n in (
        "ingress_to_submit_ms", "ring_hop_first_ms", "relay_first_ms",
        "proxy_write_first_ms", "front_unexplained_ms")]
    first_chunk_ms = stage_ms(ctx, "first_chunk")
    assert first_chunk_ms == pytest.approx(20.0)
    # the window's counters read another population: not a term of the sum
    assert read_metric("first_chunk_lag_ms", ctx) == pytest.approx(60.0)
    client_ms = 1e3 * sum(r.first - r.sent for r in RECORDS if r.first) / N
    assert sum(parts) + first_chunk_ms + engine_ms == pytest.approx(
        client_ms, rel=1e-9)
    assert read_metric("front_overhead_ms", ctx) == pytest.approx(
        client_ms - engine_ms)
