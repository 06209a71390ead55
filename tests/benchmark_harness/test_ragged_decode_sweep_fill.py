"""PR 48's per-layer metric on recorded counters: the share of the pages a
decode call's sweeps step through that hold a live sequence's tokens,
``ragged_decode_sweep_fill`` and its three twins (``docqa_``, ``grow_``,
``gen_``: one a serving cell whose decode runs the all-heads body). A value
from two snapshots of the engine's counters; None on a snapshot without
``decode_swept_pages`` (every commit before PR 48), with no snapshot at
all, and over a window without a decode dispatch. The three entries close
BENCHMARK.json's list."""
import importlib

import pytest
from bh_util import load_json

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
CELLS = {"docqa_ragged_decode_sweep_fill": "docqa-sessions-1chip",
         "grow_ragged_decode_sweep_fill": "qwen3next-growing-sessions-1chip",
         "gen_ragged_decode_sweep_fill": "olmoe-gen-sessions-1chip"}
NAMES = ["ragged_decode_sweep_fill", *CELLS]


def _ctx(before=BEFORE, after=AFTER):
    return {"stats_before": before, "stats_after": after, "trace": None,
            "config": {"engine": {"max_batch_size": 32}}, "rehearse": False}


def _module(name: str):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_the_hand_computed_value(name):
    got = _module(name).read(_ctx())
    assert got == pytest.approx(EXPECTED, rel=1e-12)
    assert 0 < got <= 100


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("snapshot", [
    "without_decode_swept_pages", "without_decode_live_pages", "missing",
    "no_decode_in_the_window"])
def test_reader_gives_none(name, snapshot):
    if snapshot.startswith("without_"):
        gone = snapshot[len("without_"):]
        ctx = _ctx(*({k: v for k, v in s.items() if k != gone}
                     for s in (BEFORE, AFTER)))
    elif snapshot == "missing":
        ctx = _ctx(None, None)
    else:
        ctx = _ctx(BEFORE, dict(BEFORE))
    assert _module(name).read(ctx) is None


@pytest.mark.parametrize("name", list(CELLS))
def test_a_twin_shares_the_readers_code(name):
    assert _module(name).read is _module("ragged_decode_sweep_fill").read


def test_the_three_entries_close_the_list(bench_root):
    bench = load_json(bench_root, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, cell in CELLS.items():
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "kernels",
            "moves": "out_tok_s", "workloads": [cell]}
    at = [names.index(n) for n in ("grow_prefill_wait_us_per_tok", *CELLS)]
    assert at == list(range(at[0], at[0] + 4))
    # each in a cell that reports the metric it moves, beside the decode
    # roofline that says whether the fill's fall was worth paying
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(CELLS.values()) <= set(e2e["out_tok_s"]["workloads"])
    for name, cell in CELLS.items():
        roofline = name.replace("sweep_fill", "roofline").replace(
            "grow_ragged", "grow_full")
        assert by_name[roofline]["workloads"] == [cell]
