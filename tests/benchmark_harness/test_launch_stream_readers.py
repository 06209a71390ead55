"""PR 38's fifteen per-layer metrics — a launch apart from a readback, the
stepping thread's waits for the interpreter, a token from its booking to the
transport, the engine's run-ahead — each on a hand-made ``ctx`` with a known
answer and None where the program has no such counter (every earlier
commit); their entries in BENCHMARK.json, on the tree and on a copy with a
fifth cell appended."""
import importlib

import pytest
from bh_util import in_order, load_json

CELLS = {"docqa": "docqa-sessions-1chip", "gen": "olmoe-gen-sessions-1chip",
         "ldoc": "kanana-longdoc-sessions-1chip"}
# metric -> (layer, unit, better, the cells' prefixes), in the order of
# the entries
METRICS = {
    "prefill_launch_ms": ("engine scheduler", "ms", "lower",
                          ("gen", "ldoc")),
    "decode_launch_ms": ("engine scheduler", "ms", "lower", ("gen",)),
    "step_thread_offcpu_share": ("engine scheduler", "%", "lower",
                                 ("docqa", "gen", "ldoc")),
    "stream_cpu_share": ("HTTP front and router", "%", "lower",
                         ("gen", "ldoc")),
    "stream_lag_ms": ("HTTP front and router", "ms", "lower",
                      ("docqa", "gen")),
    "first_chunk_lag_ms": ("HTTP front and router", "ms", "lower",
                           ("docqa", "gen")),
    "dispatch_overlap_share": ("engine scheduler", "%", "higher",
                               ("docqa", "gen", "ldoc")),
}
TWINS = [(name, prefix) for name, (*_, prefixes) in METRICS.items()
         for prefix in prefixes]

# A window of one second, wall ms: admit 4, prefill build 6 and post 2,
# decode build 5 and post 3, telemetry 2, loop other 8: host phases 30;
# prefill device 30 of which launches 12, decode device 40 of which
# launches 6; idle 900. The thread's CPU clock moved by 30: 22 in the host
# phases, 6 in the launches, 2 at the end of the readbacks' waits. 4 prefill
# dispatches, 10 decode and 2 verify dispatches, 8 of the 16 launched beside
# another. 50 chunks lagged 400 ms together, 5 of them first chunks lagging
# 60 ms; the stream threads took 250 ms of CPU.
MS = 1_000_000
DELTA = {
    "ns_admit": 4 * MS, "ns_prefill_build": 6 * MS, "ns_prefill_post": 2 * MS,
    "ns_decode_build": 5 * MS, "ns_decode_post": 3 * MS,
    "ns_telemetry": 2 * MS, "ns_loop_other": 8 * MS,
    "ns_prefill_device": 30 * MS, "ns_decode_device": 40 * MS,
    "ns_loop_idle": 900 * MS,
    "launch_ns_prefill": 12 * MS, "launch_ns_decode": 6 * MS,
    "step_thread_cpu_ns": 30 * MS,
    "max_ns_admit": 2 * MS, "clock_ns": 1000 * MS,
    "prefill_dispatches": 4, "decode_dispatches": 10, "spec_dispatches": 2,
    "dispatches_overlapped": 8,
    "stream_chunks": 50, "stream_lag_ns": 400 * MS,
    "stream_first_chunks": 5, "stream_first_lag_ns": 60 * MS,
    "stream_cpu_ns": 250 * MS,
}
# counters since the engine started: every one stands somewhere already
BEFORE = {k: 1_000 + 7 * i for i, k in enumerate(DELTA)}
AFTER = {k: BEFORE[k] + v for k, v in DELTA.items()}
BEFORE["mesh"] = AFTER["mesh"] = None
EXPECTED = {
    "prefill_launch_ms": 3.0,           # 12 ms in 4 launches
    "decode_launch_ms": 0.5,            # 6 ms in 10 + 2 launches
    # runnable and not running: the host phases' 30 ms and the launches'
    # 18 less the thread's 30 of CPU, of 100 ms worked; the readbacks' 52
    # ms of waiting for the device and the idle 900 stay out
    "step_thread_offcpu_share": 18.0,
    "stream_cpu_share": 25.0,           # 250 ms of CPU in one second
    "stream_lag_ms": 8.0,
    "first_chunk_lag_ms": 12.0,
    "dispatch_overlap_share": 50.0,     # 8 of 16
}


def _ctx(before=BEFORE, after=AFTER):
    return {"stats_before": before, "stats_after": after, "trace": None,
            "rehearse": False}


def _read(name: str, ctx: dict):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(ctx)


@pytest.mark.parametrize("name,prefix", TWINS)
def test_reader_gives_the_hand_computed_value(name, prefix):
    assert _read(f"{prefix}_{name}", _ctx()) == pytest.approx(
        EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name,prefix", TWINS)
def test_reader_gives_none_without_its_counters(name, prefix):
    """On the parent commit's ``engine.stats`` (the phases' wall time and
    the dispatch counts, PR 36's ``dispatches_overlapped`` left out), with
    a snapshot missing, and where nothing was counted."""
    theirs = ("step_thread_", "launch_", "stream_", "dispatches_overlapped")
    old = [{k: v for k, v in s.items() if not k.startswith(theirs)}
           for s in (BEFORE, AFTER)]
    assert _read(f"{prefix}_{name}", _ctx(*old)) is None
    assert _read(f"{prefix}_{name}", _ctx(None, None)) is None
    assert _read(f"{prefix}_{name}", _ctx(BEFORE, BEFORE)) is None


@pytest.mark.parametrize("name,prefix", TWINS)
def test_twin_shares_the_reader_of_its_metric(name, prefix):
    twin = importlib.import_module(f"benchmarks.layer_metrics.{prefix}_{name}")
    base = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert twin.read is base.read


def test_overlap_share_reads_on_pr_36s_program():
    """``dispatches_overlapped`` is older than the other counters: the
    parent's run gives this metric a value and none of the others."""
    mine = ("step_thread_", "launch_", "stream_")
    old = [{k: v for k, v in s.items() if not k.startswith(mine)}
           for s in (BEFORE, AFTER)]
    assert _read("dispatch_overlap_share", _ctx(*old)) == pytest.approx(50.0)


def test_offcpu_share_leaves_the_readback_waits_out():
    """Twice the readbacks' wait moves the denominator alone."""
    after = dict(AFTER)
    after["ns_decode_device"] += 100 * MS
    assert _read("step_thread_offcpu_share", _ctx(BEFORE, after)) == \
        pytest.approx(100.0 * 18 / 200)


def test_the_fifteen_entries(bench_root):
    """Appended, each with its cell, its reader file and the layer's name
    as BENCHMARK.json already has it; nothing closed: later entries may
    follow."""
    bench = load_json(bench_root, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in {f"{p}_{n}" for n, p in TWINS}}
    cells = {w["name"] for w in bench["workloads"]}
    for name, (layer, unit, better, prefixes) in METRICS.items():
        for prefix in prefixes:
            m = by_name[f"{prefix}_{name}"]
            # a later cell may be appended to its list
            assert dict(m, workloads=m["workloads"][:1]) == {
                "name": f"{prefix}_{name}", "unit": unit, "better": better,
                "source": "program_counter", "layer": layer,
                "moves": "out_tok_s", "workloads": [CELLS[prefix]]}
            assert layer in layers and CELLS[prefix] in cells
            importlib.import_module(
                f"benchmarks.layer_metrics.{prefix}_{name}")
    assert len(TWINS) == 15
    assert in_order([f"{p}_{n}" for n, p in TWINS],
                    [m["name"] for m in bench["per_layer"]])
    # after everything PR 33 left: appended, not slipped in
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("ldoc_prefill_masked_step_share") < \
        names.index("gen_prefill_launch_ms")
