"""PR 45's per-layer metric on recorded counters: prompt tokens a prefill
dispatch carried, ``prefill_tok_per_dispatch`` and its three twins
(``ldoc_``, ``mixq_``, ``gen_``: one a routed serving cell). A value from two
snapshots of the engine's counters; None on a snapshot without one of them,
with no snapshot at all, and over a window without a prefill dispatch. The
three entries follow PR 41's in BENCHMARK.json."""
import importlib

import pytest
from bh_util import load_json

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
CELLS = {"ldoc_prefill_tok_per_dispatch": "kanana-longdoc-sessions-1chip",
         "mixq_prefill_tok_per_dispatch": "mellum-mixed-queue-1chip",
         "gen_prefill_tok_per_dispatch": "olmoe-gen-sessions-1chip"}
NAMES = ["prefill_tok_per_dispatch", *CELLS]


def _ctx(before=BEFORE, after=AFTER):
    return {"stats_before": before, "stats_after": after, "trace": None,
            "config": {"engine": {"max_batch_size": 32}}, "rehearse": False}


def _module(name: str):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


@pytest.mark.parametrize("name", NAMES)
def test_reader_gives_the_hand_computed_value(name):
    assert _module(name).read(_ctx()) == pytest.approx(EXPECTED, rel=1e-12)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("snapshot", [
    "without_prefill_tokens", "without_prefill_dispatches", "missing",
    "no_prefill_in_the_window"])
def test_reader_gives_none(name, snapshot):
    if snapshot.startswith("without_"):
        gone = snapshot[len("without_"):]
        ctx = _ctx(*({k: v for k, v in s.items() if k != gone}
                     for s in (BEFORE, AFTER)))
    elif snapshot == "missing":
        ctx = _ctx(None, None)
    else:
        ctx = _ctx(BEFORE, dict(BEFORE, decode_dispatches=1_200))
    assert _module(name).read(ctx) is None


@pytest.mark.parametrize("name", list(CELLS))
def test_a_twin_shares_the_readers_code(name):
    assert _module(name).read is _module("prefill_tok_per_dispatch").read


def test_the_three_entries_follow_what_pr_41_left(bench_root):
    bench = load_json(bench_root, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, cell in CELLS.items():
        assert by_name[name] == {
            "name": name, "unit": "tokens", "better": "higher",
            "source": "program_counter", "layer": "engine scheduler",
            "moves": "out_tok_s", "workloads": [cell]}
    at = [names.index(n) for n in ("mixq_dispatch_overlap_share", *CELLS)]
    assert at == list(range(at[0], at[0] + 4))
    # each in a cell that reports the metric it moves
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(CELLS.values()) <= set(e2e["out_tok_s"]["workloads"])
