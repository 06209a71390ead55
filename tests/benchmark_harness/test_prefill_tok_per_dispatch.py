"""PR 45's per-layer metric on recorded counters: prompt tokens a prefill
dispatch carried, ``prefill_tok_per_dispatch``, once for every cell
BENCHMARK.json declares it in (one entry a reader since PR 52). A value
from two snapshots of the engine's counters; None on a snapshot without one
of them, with no snapshot at all, and over a window without a prefill
dispatch."""
import pytest
from bh_util import cell_config, declared_pairs, load_json, read_metric

# A window of 300 prefill dispatches of a 16-row budget at chunk 128: 280
# full ones and 20 that carried a lone question's 100-token suffix.
BEFORE = {"prefill_dispatches": 1_000, "prefill_tokens": 1_900_000,
          "prefill_rows_live": 15_000, "prefill_rows_padded": 15_400,
          "decode_dispatches": 900, "mesh": None}
DELTA = {"prefill_dispatches": 300, "prefill_tokens": 280 * 2_048 + 20 * 100,
         "prefill_rows_live": 280 * 16 + 20,
         "prefill_rows_padded": 280 * 16 + 20, "decode_dispatches": 310}
AFTER = dict(BEFORE, **{k: BEFORE[k] + v for k, v in DELTA.items()})
EXPECTED = (280 * 2_048 + 20 * 100) / 300
NAME = "prefill_tok_per_dispatch"
# the cells PR 45 declared it in: a later PR appends cells, none leaves
CELLS = ("kanana-longdoc-sessions-1chip", "mellum-mixed-queue-1chip",
         "olmoe-gen-sessions-1chip")
PAIRS = declared_pairs(names=(NAME,))


def _ctx(cell, before=BEFORE, after=AFTER):
    return {"stats_before": before, "stats_after": after, "trace": None,
            "config": cell_config(cell), "rehearse": False}


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_the_hand_computed_value(name, cell):
    assert read_metric(name, _ctx(cell)) == pytest.approx(EXPECTED,
                                                          rel=1e-12)


@pytest.mark.parametrize("name,cell", PAIRS)
@pytest.mark.parametrize("snapshot", [
    "without_prefill_tokens", "without_prefill_dispatches", "missing",
    "no_prefill_in_the_window"])
def test_reader_gives_none(name, cell, snapshot):
    if snapshot.startswith("without_"):
        gone = snapshot[len("without_"):]
        ctx = _ctx(cell, *({k: v for k, v in s.items() if k != gone}
                           for s in (BEFORE, AFTER)))
    elif snapshot == "missing":
        ctx = _ctx(cell, None, None)
    else:
        ctx = _ctx(cell, BEFORE, dict(BEFORE, decode_dispatches=1_200))
    assert read_metric(name, ctx) is None


def test_the_entry(bench_root):
    bench = load_json(bench_root, "BENCHMARK.json")
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert {k: m[k] for k in m if k != "workloads"} == {
        "name": NAME, "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "out_tok_s"}
    # each in a cell that reports the metric it moves
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(CELLS) <= set(m["workloads"]) <= set(
        e2e["out_tok_s"]["workloads"])
