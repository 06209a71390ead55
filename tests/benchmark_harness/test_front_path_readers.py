"""The front path's two readers that compare a histogram of the program's
with the client's records over the whole run: ``front_overhead_ms`` (every
serving cell since PR 52) and ``router_wait_ms`` (PR 52: what came through
the door ``kinds/serve_child.py``'s ``stats`` event opened,
``ctx["serve_summary"]``). Each on a hand-made ``ctx``, once for every cell
BENCHMARK.json declares it in, and None where the histogram does not cover
the client's requests — a process that had not flushed."""
import pytest
from bh_util import cell_config, declared_pairs, load_json, read_metric

NAMES = ("front_overhead_ms", "router_wait_ms")
PAIRS = declared_pairs(names=NAMES)


class Rec:
    """A client record: sent at ``sent``, first token chunk at ``first``."""

    def __init__(self, sent, first):
        self.sent, self.first = sent, first


# 40 requests of the run, warm-up included, each a first chunk 300 ms after
# its send; one more that never got a token
RECORDS = [Rec(i * 0.5, i * 0.5 + 0.3) for i in range(40)] + [Rec(30.0, None)]
# the engine's TTFT histogram: 4 observations of the reference check before
# the run (mean 100 ms), 44 after it: the run's 40 have a mean of 250 ms
TTFT = ({"count": 4, "mean": 0.100},
        {"count": 44, "mean": (4 * 0.100 + 40 * 0.250) / 44})
# the handles' waits: 6 calls of the driver's own before the run (a cold
# start among them: mean 50 ms), then two a request (the proxy's handle,
# the router's) and 5 of the driver's: 85 calls of 2 ms
SUMMARY = ({"router_wait": {"count": 6, "mean": 0.050},
            "requests": {"proxy": 0.0, "handle": 6.0}},
           {"router_wait": {"count": 91,
                            "mean": (6 * 0.050 + 85 * 0.002) / 91},
            "requests": {"proxy": 41.0, "handle": 91.0},
            "handles": {"routers": 3.0, "refreshes": {"cold": 3.0}}})
EXPECTED = {"front_overhead_ms": 50.0, "router_wait_ms": 2.0}


def _ctx(cell, **over):
    ctx = {"config": cell_config(cell), "all_records": RECORDS,
           "records": RECORDS[10:], "engine_ttft": TTFT,
           "serve_summary": SUMMARY, "rehearse": False}
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_the_hand_computed_value(name, cell):
    assert read_metric(name, _ctx(cell)) == pytest.approx(EXPECTED[name],
                                                          rel=1e-9)


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_none_unless_the_histogram_covers_the_requests(
        name, cell):
    key = {"front_overhead_ms": "engine_ttft",
           "router_wait_ms": "serve_summary"}[name]
    # an untraced run takes no baseline and no flushed reading
    assert read_metric(name, _ctx(cell, **{key: (None, None)})) is None
    assert read_metric(name, _ctx(cell, **{key: None})) is None
    assert read_metric(name, _ctx(cell, all_records=[])) is None
    # a replica (a proxy) whose last series had not reached the head: 39
    # of the 40 requests are in the histogram
    short = {"engine_ttft": (TTFT[0], dict(TTFT[1], count=43)),
             "serve_summary": (SUMMARY[0], dict(SUMMARY[1], router_wait={
                 "count": 45, "mean": 0.004}))}[key]
    assert read_metric(name, _ctx(cell, **{key: short})) is None


def test_router_wait_reads_without_a_baseline_series():
    """The first reading may hold no ``router_wait`` at all (no handle had
    called yet): the whole histogram is then the run's."""
    after = {"router_wait": {"count": 80, "mean": 0.003}}
    ctx = _ctx("docqa-sessions-1chip", serve_summary=({}, after))
    assert read_metric("router_wait_ms", ctx) == pytest.approx(3.0)
    ctx = _ctx("docqa-sessions-1chip", serve_summary=(None, after))
    assert read_metric("router_wait_ms", ctx) == pytest.approx(3.0)
    # a summary that carries the other groups and not this one: nothing
    ctx = _ctx("docqa-sessions-1chip", serve_summary=(
        None, {"requests": {"proxy": 41.0}}))
    assert read_metric("router_wait_ms", ctx) is None


def test_the_two_entries_list_every_serving_cell(bench_root):
    bench = load_json(bench_root, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    serving = {w["name"] for w in bench["workloads"]
               if cell_config(w["name"], bench_root)["kind"] != "train"
               and w["name"] in out["workloads"]}
    front = by_name["front_overhead_ms"]
    assert {k: front[k] for k in front if k != "workloads"} == {
        "name": "front_overhead_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "HTTP front and router",
        "moves": "out_tok_s"}
    wait = by_name["router_wait_ms"]
    assert {k: wait[k] for k in wait if k != "workloads"} == {
        "name": "router_wait_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "HTTP front and router",
        "moves": "out_tok_s"}
    # the six of the tree (a cell a later PR appends lists itself)
    assert len(PAIRS) == 12
    assert {c for _, c in PAIRS} <= set(front["workloads"]) <= serving
    assert {c for _, c in PAIRS} <= set(wait["workloads"]) <= serving
