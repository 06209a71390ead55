"""PR 41's two per-layer metrics on recorded counters:
``decode_dead_row_share`` (rows x steps the decode program ran for a
request already done, of the rows x steps it ran) and
``dispatch_overlap_share`` (the launches made beside an outstanding
dispatch), once for every cell BENCHMARK.json declares the reader in, under
that cell's own configuration. Each gives None on a snapshot without its
counter, as the parent commit's is."""
import pytest
from bh_util import cell_config, declared_pairs, load_json, read_metric

# A window of 40 decode dispatches, 30 of eight steps and 10 of one: 250
# steps, on the cell's ``max_batch_size`` rows; three requests stopped on a
# token the host could not foresee, each a dead row of one eight-step
# dispatch. 20 prefill dispatches; 54 of the 60 launches went out beside
# another.
BEFORE = {"decode_dispatches": 100, "decode_steps": 700,
          "decode_dead_rows": 16, "decode_rows_fed_on_device": 5_000,
          "prefill_dispatches": 50, "spec_dispatches": 0,
          "dispatches_overlapped": 90, "mesh": None}
DELTA = {"decode_dispatches": 40, "decode_steps": 250, "decode_dead_rows": 24,
         "decode_rows_fed_on_device": 1_900, "prefill_dispatches": 20,
         "spec_dispatches": 0, "dispatches_overlapped": 54}
AFTER = dict(BEFORE, **{k: BEFORE[k] + v for k, v in DELTA.items()})
EXPECTED = {
    "decode_dead_row_share":
        lambda cfg: 100.0 * 24 / (250 * cfg["engine"]["max_batch_size"]),
    "dispatch_overlap_share": lambda cfg: 90.0}
OWN = {"decode_dead_row_share": "decode_dead_rows",
       "dispatch_overlap_share": "dispatches_overlapped"}
PAIRS = declared_pairs(names=EXPECTED)
OLMOE, MELLUM = "olmoe-gen-sessions-1chip", "mellum-mixed-queue-1chip"


def _ctx(cell, before=BEFORE, after=AFTER):
    return {"stats_before": before, "stats_after": after, "trace": None,
            "config": cell_config(cell), "rehearse": False}


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_the_hand_computed_value(name, cell):
    assert read_metric(name, _ctx(cell)) == pytest.approx(
        EXPECTED[name](cell_config(cell)), rel=1e-12)


@pytest.mark.parametrize("name,cell", PAIRS)
@pytest.mark.parametrize("snapshot", ["without_the_counter", "missing",
                                      "nothing_counted"])
def test_reader_gives_none(name, cell, snapshot):
    """On a program that has no such counter (the dead rows: every commit
    before PR 41), with no snapshot at all, and over a window in which
    nothing was dispatched."""
    if snapshot == "without_the_counter":
        ctx = _ctx(cell, *({k: v for k, v in s.items() if k != OWN[name]}
                           for s in (BEFORE, AFTER)))
    elif snapshot == "missing":
        ctx = _ctx(cell, None, None)
    else:
        ctx = _ctx(cell, BEFORE, BEFORE)
    assert read_metric(name, ctx) is None


def test_no_dead_row_reads_zero_not_none():
    """A window of decode steps none of which ran a dead row: the counter is
    there and did not move. (The ledger reads 0.0 in the OLMoE cell: the
    guard of PR 41's one-step-behind rule, not a dead metric.)"""
    after = dict(AFTER, decode_dead_rows=BEFORE["decode_dead_rows"])
    assert read_metric("decode_dead_row_share",
                       _ctx(OLMOE, BEFORE, after)) == 0.0
    assert read_metric("decode_dead_row_share", _ctx(OLMOE)) == \
        pytest.approx(100.0 * 24 / (250 * 64))


def test_the_two_entries(bench_root):
    bench = load_json(bench_root, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    dead = by_name["decode_dead_row_share"]
    assert {k: dead[k] for k in dead if k != "workloads"} == {
        "name": "decode_dead_row_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "out_tok_s"}
    assert OLMOE in dead["workloads"]
    overlap = by_name["dispatch_overlap_share"]
    assert {k: overlap[k] for k in overlap if k != "workloads"} == {
        "name": "dispatch_overlap_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "out_tok_s"}
    assert MELLUM in overlap["workloads"]     # the cell PR 41 declared it in
    assert "engine scheduler" in {
        m["layer"] for m in bench["per_layer"] if m["name"] not in OWN}
