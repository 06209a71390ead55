"""One entry a reader (PR 52): every (metric, cell) pair BENCHMARK.json
declares has a reader file of the metric's name that imports, has ``read``
and gives None — not an exception — on a ``ctx`` without its inputs; every
file under ``benchmarks/layer_metrics/`` is a declared reader, or a two-line
twin whose entry moves another end-to-end metric than its original's; no
pair the benchmark declared before the fold was lost by it; and the names
keep to the fold's rules, on the tree and on a copy with a fifth cell
appended (``bench_root``, ``conftest.py``)."""
import ast
import importlib
import os
import re

import pytest
from bh_util import REPO, cell_config, declared_pairs, load_json, read_metric

DOCQA, TRAIN = "docqa-sessions-1chip", "pretrain-4k-1chip"
OLMOE, KANANA = "olmoe-gen-sessions-1chip", "kanana-longdoc-sessions-1chip"
MELLUM, QWEN = "mellum-mixed-queue-1chip", "qwen3next-growing-sessions-1chip"
LING = "ling-longgen-mixed-1chip"
# what PR 51's BENCHMARK.json declared, a reader and the cells its entry
# and its prefixed twins (docqa_, gen_, ldoc_, mixq_, grow_, lgen_) listed;
# ``decode_step_ms``'s three went with their reader
PARENT = {
    "front_overhead_ms": (DOCQA, QWEN),
    "decode_tok_per_dispatch": (DOCQA, OLMOE),
    "prefix_hit_tok_share": (DOCQA, OLMOE, KANANA, MELLUM, QWEN),
    "ttft_cold_ms": (DOCQA, KANANA),
    "ttft_warm_ms": (DOCQA, KANANA),
    "decode_prog_dev_ms": (DOCQA, OLMOE, KANANA, MELLUM, QWEN, LING),
    "prefill_prog_dev_ms": (DOCQA, KANANA, MELLUM),
    "ragged_attn_dev_share": (DOCQA, OLMOE),
    "device_idle_share": (DOCQA, OLMOE, KANANA, MELLUM, QWEN, LING),
    "train_mfu": (TRAIN,),
    "train_step_dev_ms": (TRAIN,),
    "flash_attention_roofline": (TRAIN,),
    "train_device_idle_share": (TRAIN,),
    "engine_host_share": (DOCQA, OLMOE, KANANA, MELLUM, QWEN),
    "admit_ms_per_request": (DOCQA,),
    "queue_wait_ms": (DOCQA, KANANA, QWEN),
    "prefill_span_ms": (DOCQA, QWEN),
    "decode_slot_occupancy": (DOCQA, OLMOE, KANANA, MELLUM, QWEN),
    "ragged_decode_roofline": (DOCQA, OLMOE),
    "prefill_row_fill": (DOCQA, KANANA),
    "ragged_prefill_roofline": (DOCQA,),
    "ragged_decode_table_fill": (DOCQA,),
    "moe_ffn_dev_share": (OLMOE, KANANA, MELLUM, QWEN),
    "moe_ffn_roofline": (OLMOE, MELLUM, QWEN, LING),
    "moe_live_assign_share": (OLMOE,),
    "moe_load_max_over_mean": (OLMOE, KANANA, MELLUM, QWEN),
    "latent_attn_dev_share": (KANANA,),
    "latent_decode_roofline": (KANANA, LING),
    "latent_prefill_roofline": (KANANA,),
    "prefill_masked_step_share": (KANANA,),
    "prefill_launch_ms": (OLMOE, KANANA),
    "decode_launch_ms": (OLMOE,),
    "step_thread_offcpu_share": (DOCQA, OLMOE, KANANA),
    "stream_cpu_share": (OLMOE, KANANA),
    "stream_lag_ms": (DOCQA, OLMOE, QWEN),
    "first_chunk_lag_ms": (DOCQA, OLMOE),
    "dispatch_overlap_share": (DOCQA, OLMOE, KANANA, MELLUM, QWEN),
    "window_attn_dev_share": (MELLUM,),
    "full_attn_dev_share": (MELLUM, QWEN),
    "window_decode_roofline": (MELLUM,),
    "full_decode_roofline": (MELLUM, QWEN),
    "window_prefill_roofline": (MELLUM,),
    "window_pages_returned_share": (MELLUM,),
    "window_pool_live_share": (MELLUM,),
    "full_pool_live_share": (MELLUM,),
    "prefix_tail_miss_share": (MELLUM,),
    "ttft_short_ms": (MELLUM,),
    "ttft_long_ms": (MELLUM,),
    "decode_dead_row_share": (OLMOE,),
    "prefill_tok_per_dispatch": (OLMOE, KANANA, MELLUM, QWEN),
    "gdn_dev_share": (QWEN,),
    "gdn_decode_roofline": (QWEN,),
    "moe_held_assign_share": (QWEN,),
    "state_hit_tok_share": (QWEN,),
    "state_rerun_tok_per_turn": (QWEN,),
    "state_pool_live_share": (QWEN,),
    "state_snapshot_refused_share": (QWEN,),
    "ttft_turn_ms": (QWEN,),
    "prefill_wait_us_per_tok": (QWEN,),
    "ragged_decode_sweep_fill": (DOCQA, OLMOE, QWEN),
    "kda_dev_share": (LING,),
    "kda_decode_roofline": (LING,),
    "moe_group_hit_share": (LING,),
}
HERE = os.path.join(REPO, "benchmarks", "layer_metrics")
FILES = sorted(f[:-3] for f in os.listdir(HERE)
               if f.endswith(".py") and not f.startswith("_"))
PENDING = ("chat-open-1chip", "chat-open-tp4")
CELL_PREFIX = re.compile(r"^(docqa|gen|ldoc|mixq|grow|lgen)_")


def _entries(root: str = REPO) -> dict:
    """name -> entry, BENCHMARK.json's and the pending cells' files'."""
    found = list(load_json(root, "BENCHMARK.json")["per_layer"])
    for name in PENDING:
        found += load_json(root, "benchmarks", "pending",
                           f"{name}.json")["per_layer"]
    return {m["name"]: m for m in found}


def _twin_of(name: str):
    """The module a two-line file imports ``read`` from, else None."""
    with open(os.path.join(HERE, f"{name}.py")) as f:
        body = ast.parse(f.read()).body
    code = [n for n in body if not (isinstance(n, ast.Expr) and isinstance(
        n.value, ast.Constant))]            # the docstring apart
    if len(code) == 1 and isinstance(code[0], ast.ImportFrom) and [
            a.name for a in code[0].names] == ["read"]:
        return code[0].module
    return None


@pytest.mark.parametrize("name,cell", declared_pairs())
def test_a_declared_pair_has_a_reader_that_finds_nothing_without_inputs(
        name, cell):
    module = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert callable(module.read) and module.__doc__
    cfg = cell_config(cell)
    # no snapshot, no trace, no record: an untraced run's, a rehearsal's
    for ctx in ({}, {"config": cfg, "records": [], "all_records": [],
                     "device": {"kind": "TPU v5 lite"}, "rehearse": False,
                     "stats_before": None, "stats_after": None,
                     "trace": None, "engine_ttft": (None, None),
                     "serve_summary": (None, None)}):
        assert read_metric(name, ctx) is None


@pytest.mark.parametrize("name", FILES)
def test_a_file_is_a_declared_reader_or_a_twin_that_moves_another_metric(
        name):
    entries = _entries()
    assert name in entries, f"{name}.py is listed by no per_layer entry"
    original = _twin_of(name)
    if original is not None:
        assert entries[name]["moves"] != entries[original]["moves"], (
            f"{name} moves what {original} moves: list its cells under "
            f"{original}'s entry instead")
        for key in ("unit", "better", "source", "layer"):
            assert entries[name][key] == entries[original][key], key


@pytest.mark.parametrize("name", list(PARENT))
def test_no_pair_the_parent_declared_was_lost(name):
    declared = {c for n, c in declared_pairs() if n == name}
    assert set(PARENT[name]) <= declared, set(PARENT[name]) - declared


def test_the_parents_list_is_what_the_issue_counted():
    assert len(PARENT) == 63 and sum(map(len, PARENT.values())) == 125


def test_the_names_keep_to_the_fold(bench_root):
    bench = load_json(bench_root, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert not [n for n in names if CELL_PREFIX.match(n)]
    assert "decode_step_ms" not in " ".join(names)
    # no two entries share a reader and a moved metric
    here = os.path.join(bench_root, "benchmarks", "layer_metrics")
    reads = {}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(here, m["name"] + ".py"))
        reader = m["name"]
        if bench_root == REPO:
            reader = _twin_of(m["name"]) or m["name"]
        assert reads.setdefault((reader, m["moves"]), m["name"]) == m["name"]
    # a kernel's share beside the whole step's judge: every serving cell
    # lists a roofline that moves its throughput, the training cell its MFU
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    for cell in out["workloads"]:
        assert [m["name"] for m in bench["per_layer"]
                if m["name"].endswith("_roofline") and m["unit"] == "%"
                and m["moves"] == "out_tok_s" and cell in m["workloads"]]
    assert TRAIN in next(m for m in bench["per_layer"]
                         if m["name"] == "train_mfu")["workloads"]
    assert next(m for m in bench["per_layer"]
                if m["name"] == "engine_host_share")[
        "source"] == "program_counter"
