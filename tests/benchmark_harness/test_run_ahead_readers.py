"""PR 41's two per-layer metrics on recorded counters:
``gen_decode_dead_row_share`` (rows x steps the decode program ran for a
request already done, of the rows x steps it ran) and
``mixq_dispatch_overlap_share`` (the Mellum cell's twin of the launches made
beside an outstanding dispatch). Each gives None on a snapshot without its
counter, as the parent commit's is, and has its entry at the end of
BENCHMARK.json."""
import importlib

import pytest
from bh_util import load_json

# A window of 40 decode dispatches, 30 of eight steps and 10 of one, on 64
# rows: 250 steps, 16,000 rows x steps; three requests stopped on a token
# the host could not foresee, each a dead row of one eight-step dispatch.
# 20 prefill dispatches; 54 of the 60 launches went out beside another.
BEFORE = {"decode_dispatches": 100, "decode_steps": 700,
          "decode_dead_rows": 16, "decode_rows_fed_on_device": 5_000,
          "prefill_dispatches": 50, "spec_dispatches": 0,
          "dispatches_overlapped": 90, "mesh": None}
DELTA = {"decode_dispatches": 40, "decode_steps": 250, "decode_dead_rows": 24,
         "decode_rows_fed_on_device": 1_900, "prefill_dispatches": 20,
         "spec_dispatches": 0, "dispatches_overlapped": 54}
AFTER = dict(BEFORE, **{k: BEFORE[k] + v for k, v in DELTA.items()})
CONFIG = {"engine": {"max_batch_size": 64}}
EXPECTED = {"gen_decode_dead_row_share": 100.0 * 24 / (250 * 64),
            "mixq_dispatch_overlap_share": 90.0}
OWN = {"gen_decode_dead_row_share": "decode_dead_rows",
       "mixq_dispatch_overlap_share": "dispatches_overlapped"}


def _ctx(before=BEFORE, after=AFTER):
    return {"stats_before": before, "stats_after": after, "trace": None,
            "config": CONFIG, "rehearse": False}


def _read(name: str, ctx: dict):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(ctx)


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_gives_the_hand_computed_value(name):
    assert _read(name, _ctx()) == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", list(EXPECTED))
@pytest.mark.parametrize("snapshot", ["without_the_counter", "missing",
                                      "nothing_counted"])
def test_reader_gives_none(name, snapshot):
    """On a program that has no such counter (the dead rows: every commit
    before this one), with no snapshot at all, and over a window in which
    nothing was dispatched."""
    if snapshot == "without_the_counter":
        ctx = _ctx(*({k: v for k, v in s.items() if k != OWN[name]}
                     for s in (BEFORE, AFTER)))
    elif snapshot == "missing":
        ctx = _ctx(None, None)
    else:
        ctx = _ctx(BEFORE, BEFORE)
    assert _read(name, ctx) is None


def test_no_dead_row_reads_zero_not_none():
    """A window of decode steps none of which ran a dead row: the counter is
    there and did not move."""
    after = dict(AFTER, decode_dead_rows=BEFORE["decode_dead_rows"])
    assert _read("gen_decode_dead_row_share", _ctx(BEFORE, after)) == 0.0


def test_the_overlap_twin_shares_its_metrics_reader():
    twin = importlib.import_module(
        "benchmarks.layer_metrics.mixq_dispatch_overlap_share")
    base = importlib.import_module(
        "benchmarks.layer_metrics.dispatch_overlap_share")
    assert twin.read is base.read


def test_the_two_entries_close_the_list(bench_root):
    bench = load_json(bench_root, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["gen_decode_dead_row_share"] == {
        "name": "gen_decode_dead_row_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "out_tok_s", "workloads": ["olmoe-gen-sessions-1chip"]}
    assert by_name["mixq_dispatch_overlap_share"] == {
        "name": "mixq_dispatch_overlap_share", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "engine scheduler", "moves": "out_tok_s",
        "workloads": ["mellum-mixed-queue-1chip"]}
    # appended after everything PR 40 left
    assert names.index("mixq_engine_host_share") < \
        names.index("gen_decode_dead_row_share") < \
        names.index("mixq_dispatch_overlap_share")
    assert "engine scheduler" in {
        m["layer"] for m in bench["per_layer"] if m["name"] not in OWN}
