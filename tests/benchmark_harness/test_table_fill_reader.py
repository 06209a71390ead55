"""``ragged_decode_table_fill`` (PR 25) on hand-made counters, and
None where the program has no ``decode_table_pages`` (every earlier
commit) or nothing was counted."""
import importlib
import json
import os

import pytest

# 20 decode dispatches of 32 rows over a 256-wide table = 163,840 table
# pages, of which 34,000 held a live sequence's tokens
BEFORE = {"decode_dispatches": 10, "decode_live_pages": 1_000,
          "decode_table_pages": 81_920, "mesh": None}
AFTER = {"decode_dispatches": 30, "decode_live_pages": 35_000,
         "decode_table_pages": 245_760, "mesh": None}


def _read(ctx: dict, name: str = "ragged_decode_table_fill"):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(ctx)


def test_table_fill_is_live_pages_over_table_pages():
    ctx = {"stats_before": BEFORE, "stats_after": AFTER}
    assert _read(ctx) == pytest.approx(100.0 * 34_000 / 163_840, rel=1e-12)


@pytest.mark.parametrize("before,after", [
    # the parent's counters: live pages, no table pages
    ({k: v for k, v in BEFORE.items() if k != "decode_table_pages"},
     {k: v for k, v in AFTER.items() if k != "decode_table_pages"}),
    (None, None),                   # no snapshot at all
    (BEFORE, BEFORE),               # no decode dispatch in the window
])
def test_table_fill_is_none_without_the_counter(before, after):
    assert _read({"stats_before": before, "stats_after": after}) is None


def test_benchmark_json_declares_it_for_the_docqa_cell():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "ragged_decode_table_fill"]
    assert entry == [{
        "name": "ragged_decode_table_fill", "unit": "%",
        "better": "higher", "source": "program_counter", "layer": "kernels",
        "moves": "out_tok_s", "workloads": ["docqa-sessions-1chip"]}]
