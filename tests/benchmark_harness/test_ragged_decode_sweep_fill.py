"""PR 48's per-layer metric on recorded counters: the share of the pages a
decode call's sweeps step through that hold a live sequence's tokens,
``ragged_decode_sweep_fill``, once for every cell BENCHMARK.json declares
it in (one entry a reader since PR 52; one a serving cell whose decode runs
the all-heads body). A value from two snapshots of the engine's counters;
None on a snapshot without ``decode_swept_pages`` (every commit before
PR 48), with no snapshot at all, and over a window without a decode
dispatch."""
import pytest
from bh_util import cell_config, declared_pairs, load_json, read_metric

# A window of 200 decode dispatches of doc-QA's shape at 256 keys (16
# pages) a step: 8 live rows of 190 pages = 12 blocks, 192 pages swept,
# over 32 rows x a 256-page table.
BEFORE = {"decode_dispatches": 1_000, "decode_live_pages": 1_500_000,
          "decode_table_pages": 8_192_000, "decode_swept_pages": 1_540_000,
          "mesh": None}
DELTA = {"decode_dispatches": 200, "decode_live_pages": 200 * 8 * 190,
         "decode_table_pages": 200 * 32 * 256,
         "decode_swept_pages": 200 * 8 * 192}
AFTER = dict(BEFORE, **{k: BEFORE[k] + v for k, v in DELTA.items()})
EXPECTED = 100.0 * 190 / 192
NAME = "ragged_decode_sweep_fill"
# the cells PR 48 declared it in, each with the decode roofline that says
# whether the fill's fall was worth paying
ROOFLINE = {"docqa-sessions-1chip": "ragged_decode_roofline",
            "olmoe-gen-sessions-1chip": "ragged_decode_roofline",
            "qwen3next-growing-sessions-1chip": "full_decode_roofline"}
PAIRS = declared_pairs(names=(NAME,))


def _ctx(cell, before=BEFORE, after=AFTER):
    return {"stats_before": before, "stats_after": after, "trace": None,
            "config": cell_config(cell), "rehearse": False}


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_the_hand_computed_value(name, cell):
    got = read_metric(name, _ctx(cell))
    assert got == pytest.approx(EXPECTED, rel=1e-12)
    assert 0 < got <= 100


@pytest.mark.parametrize("name,cell", PAIRS)
@pytest.mark.parametrize("snapshot", [
    "without_decode_swept_pages", "without_decode_live_pages", "missing",
    "no_decode_in_the_window"])
def test_reader_gives_none(name, cell, snapshot):
    if snapshot.startswith("without_"):
        gone = snapshot[len("without_"):]
        ctx = _ctx(cell, *({k: v for k, v in s.items() if k != gone}
                           for s in (BEFORE, AFTER)))
    elif snapshot == "missing":
        ctx = _ctx(cell, None, None)
    else:
        ctx = _ctx(cell, BEFORE, dict(BEFORE))
    assert read_metric(name, ctx) is None


def test_the_entry(bench_root):
    bench = load_json(bench_root, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    m = by_name[NAME]
    assert {k: m[k] for k in m if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "out_tok_s"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(ROOFLINE) <= set(m["workloads"]) <= set(
        e2e["out_tok_s"]["workloads"])
    for cell, roofline in ROOFLINE.items():
        assert cell in by_name[roofline]["workloads"]
