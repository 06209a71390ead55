"""PR 38's seven per-layer readers — a launch apart from a readback, the
stepping thread's waits for the interpreter, a token from its booking to the
transport, the engine's run-ahead — each on a hand-made ``ctx`` with a known
answer and None where the program has no such counter (every earlier
commit), once for every cell BENCHMARK.json declares the reader in (one
entry a reader since PR 52: a folded reader still counts once a cell, under
that cell's own configuration); their entries, on the tree and on a copy
with a fifth cell appended."""
import pytest
from bh_util import cell_config, declared_pairs, load_json, read_metric

DOCQA, OLMOE = "docqa-sessions-1chip", "olmoe-gen-sessions-1chip"
KANANA = "kanana-longdoc-sessions-1chip"
# metric -> (layer, unit, better, the cells PR 38 declared it in: a later
# PR appends cells to an entry's list, none leaves it)
METRICS = {
    "prefill_launch_ms": ("engine scheduler", "ms", "lower",
                          (OLMOE, KANANA)),
    "decode_launch_ms": ("engine scheduler", "ms", "lower", (OLMOE,)),
    "step_thread_offcpu_share": ("engine scheduler", "%", "lower",
                                 (DOCQA, OLMOE, KANANA)),
    "stream_cpu_share": ("HTTP front and router", "%", "lower",
                         (OLMOE, KANANA)),
    "stream_lag_ms": ("HTTP front and router", "ms", "lower",
                      (DOCQA, OLMOE)),
    "first_chunk_lag_ms": ("HTTP front and router", "ms", "lower",
                           (DOCQA, OLMOE)),
    "dispatch_overlap_share": ("engine scheduler", "%", "higher",
                               (DOCQA, OLMOE, KANANA)),
}
PAIRS = declared_pairs(names=METRICS)

# A window of one second, wall ms: admit 4, prefill build 6 and post 2,
# decode build 5 and post 3, telemetry 2, loop other 8: host phases 30;
# prefill device 30 of which launches 12, decode device 40 of which
# launches 6; idle 900. The thread's CPU clock moved by 30: 22 in the host
# phases, 6 in the launches, 2 at the end of the readbacks' waits. 4 prefill
# dispatches, 10 decode and 2 verify dispatches, 8 of the 16 launched beside
# another. 50 chunks lagged 400 ms together, 5 of them first chunks lagging
# 60 ms; the stream pump took 250 ms of CPU.
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


def _ctx(before=BEFORE, after=AFTER, cell=None):
    ctx = {"stats_before": before, "stats_after": after, "trace": None,
           "rehearse": False}
    if cell is not None:
        ctx["config"] = cell_config(cell)
    return ctx


_read = read_metric


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_the_hand_computed_value(name, cell):
    assert _read(name, _ctx(cell=cell)) == pytest.approx(
        EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name,cell", PAIRS)
def test_reader_gives_none_without_its_counters(name, cell):
    """On the parent commit's ``engine.stats`` (the phases' wall time and
    the dispatch counts, PR 36's ``dispatches_overlapped`` left out), with
    a snapshot missing, and where nothing was counted."""
    theirs = ("step_thread_", "launch_", "stream_", "dispatches_overlapped")
    old = [{k: v for k, v in s.items() if not k.startswith(theirs)}
           for s in (BEFORE, AFTER)]
    assert _read(name, _ctx(*old, cell=cell)) is None
    assert _read(name, _ctx(None, None, cell=cell)) is None
    assert _read(name, _ctx(BEFORE, BEFORE, cell=cell)) is None


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


def test_the_seven_entries(bench_root):
    """One entry a reader, with the layer's name as BENCHMARK.json already
    has it, listing at least the cells PR 38 declared; nothing closed:
    later cells and entries may follow."""
    bench = load_json(bench_root, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in METRICS}
    cells = {w["name"] for w in bench["workloads"]}
    for name, (layer, unit, better, declared) in METRICS.items():
        m = by_name[name]
        assert {k: m[k] for k in m if k != "workloads"} == {
            "name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": layer,
            "moves": "out_tok_s"}
        assert set(declared) <= set(m["workloads"]) <= cells
        assert layer in layers
    assert len(PAIRS) >= 15 and {n for n, _ in PAIRS} == set(METRICS)
