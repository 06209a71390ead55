"""The reduction from a device trace to numbers, on a hand-made trace with
known answers and on a small trace recorded on the chip."""
import gzip
import json
import os

import pytest

from benchmarks.reduce import xplane

DATA = os.path.join(os.path.dirname(xplane.__file__), "testdata")


def _plane(name, ops, modules=()):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": [[n, s, d, st] for n, s, d, st in ops]},
        {"name": "XLA Modules", "events": [[n, s, d, {}]
                                           for n, s, d in modules]}]}


def test_busy_idle_modules_kernels_and_gaps_by_hand():
    ops = [("while.1:f32[2]", 0, 150, {}),
           ("fusion.2:f32[2]", 50, 100, {}),                      # nested
           ("ragged_paged_attention.3:bf16[4,1,8,64]", 300, 200,
            {"target": "tpu_custom_call"}),
           ("custom-call.4:bf16[2,8]", 600, 100,
            {"target": "tpu_custom_call"}),
           ("fusion.5:f32[2]", 900, 100, {})]
    mods = [("jit_decode(11)", 0, 500), ("jit_decode(11)", 600, 100),
            ("jit_prefill(7)", 900, 100)]
    out = xplane.reduce([_plane("/device:TPU:0", ops, mods),
                         _plane("/host:CPU", [("x", 0, 9999, {})])], chips=1)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(1000e-9)
    # union: [0,150] [300,500] [600,700] [900,1000]
    assert out["busy_s"] == pytest.approx(550e-9)
    assert out["modules"]["jit_decode"] == {
        "count": 2, "total_s": pytest.approx(600e-9),
        "median_s": pytest.approx(300e-9)}
    assert out["modules"]["jit_prefill"]["count"] == 1
    assert out["kernels"]["ragged_attention"]["seconds"] == \
        pytest.approx(200e-9)
    assert out["kernels"]["flash_attention"] == {
        "seconds": pytest.approx(100e-9), "count": 1}
    gaps = out["idle_gaps"]
    assert [g["seconds"] for g in gaps] == pytest.approx(
        [200e-9, 150e-9, 100e-9])
    assert gaps[0]["after"] == "jit_decode" and gaps[0]["before"] == \
        "jit_prefill"
    # shown by kind and shape; the while keeps only what its body does
    # not cover, the two fusions of one shape are shown together
    assert ["ragged_paged_attention:bf16[4,1,8,64]", pytest.approx(200e-9),
            1, "tpu_custom_call"] in out["ops"][:2]
    assert ["fusion:f32[2]", pytest.approx(200e-9), 2, ""] in out["ops"]
    assert ["while:f32[2]", pytest.approx(50e-9), 1, ""] in out["ops"]
    # a program is told by the Pallas calls inside it: the first jit_decode
    # ran a ragged kernel with a query window of 1; no window in its name:
    # one device step
    assert out["families"] == {"decode": {
        "count": 1, "total_s": pytest.approx(500e-9),
        "median_s": pytest.approx(500e-9), "steps": 1}}


def _decode_slice(w1: int, w8: int):
    """A slice of ``w1`` executions of ``jit_rtpu_decode_w1`` (20 us each)
    and ``w8`` of ``jit_rtpu_decode_w8`` (160 us: eight steps of 20), each
    with a ragged decode-shape kernel inside, 100 us apart."""
    ops, mods, t = [], [], 0
    for name, dur in [("jit_rtpu_decode_w1(5)", 20_000)] * w1 + \
            [("jit_rtpu_decode_w8(9)", 160_000)] * w8:
        mods.append((name, t, dur))
        ops.append(("ragged_paged_attention.1:bf16[64,1,16,128]", t + 1_000,
                    dur // 4, {"target": "tpu_custom_call"}))
        t += dur + 100_000
    return [_plane("/device:TPU:0", ops, mods)]


@pytest.mark.parametrize("w1,w8", [(9, 2), (2, 9), (5, 0), (0, 5)])
def test_decode_prog_dev_ms_is_one_number_whatever_the_mixture(w1, w8):
    """Ledger, PR 32, the OLMoE cell: 164.91 on one side and 20.47 on the
    other of one PR: the median execution was a ``decode_w8`` in one slice
    and a ``decode_w1`` in the other. Device time over device steps is 20 us
    a step in every mixture."""
    import importlib
    trace = xplane.reduce(_decode_slice(w1, w8), chips=1)
    fam = trace["families"]["decode"]
    assert fam["count"] == w1 + w8 and fam["steps"] == w1 + 8 * w8
    # the reader of every serving cell, and the pending chat cells' twin
    for name in ("decode_prog_dev_ms", "chat_decode_prog_dev_ms"):
        read = importlib.import_module(
            f"benchmarks.layer_metrics.{name}").read
        assert read({"trace": trace}) == pytest.approx(0.020, rel=1e-9)
    # the median of an execution, what the metric was, is two numbers
    assert fam["median_s"] * 1e3 == pytest.approx(
        0.020 if w1 > w8 else 0.160)
    # a reduction made before ``steps`` was counted: nothing to read
    old = dict(trace, families={"decode": {
        k: v for k, v in fam.items() if k != "steps"}})
    assert read({"trace": old}) is None
    assert read({"trace": None}) is None and read({}) is None


def test_idle_gap_is_named_by_the_engine_phase_and_the_client():
    """The program's ``rtpu.*`` annotations pass ``HOST_SPANS`` and name the
    gap; ``breakdown`` sets what the client saw beside them."""
    from benchmarks.breakdown import breakdown
    for name in ("rtpu.engine.admit", "rtpu.loop.other", "bench_clock_sync.5",
                 "train_step", "report"):
        assert xplane.HOST_SPANS.match(name), name
    for name in ("llm.request", "$threading.py:323 wait", "PjitFunction(f)",
                 "train_step_2"):
        assert not xplane.HOST_SPANS.match(name), name
    ops = [("fusion.1:f32[2]", 0, 100, {}),
           ("fusion.2:f32[2]", 1_100, 100, {}),
           ("fusion.3:f32[2]", 1_500, 100, {})]
    mods = [("jit_rtpu_decode_w1(3)", 0, 100),
            ("jit_rtpu_prefill_r4(4)", 1_100, 100),
            ("jit_rtpu_decode_w1(3)", 1_500, 100)]
    host = {"name": "host", "lines": [{"name": "annotations", "events": [
        ["bench_clock_sync.1000000000", 0.0, 10.0, {}],
        ["rtpu.engine.decode.device", 0.0, 150.0, {}],
        ["rtpu.engine.decode.post", 150.0, 200.0, {}],
        ["rtpu.engine.admit", 350.0, 700.0, {}],
        ["rtpu.engine.prefill.device", 1_050.0, 200.0, {}],
        ["rtpu.loop.other", 1_250.0, 300.0, {}]]}]}
    trace = xplane.reduce([_plane("/device:TPU:0", ops, mods), host], chips=1)
    gaps = trace["idle_gaps"]
    assert [g["host"] for g in gaps] == [["rtpu.engine.admit"],
                                         ["rtpu.loop.other"]]
    assert trace["clock_offset_ns"] == 1_000_000_000

    class Record:
        sent, first, done = 0.5, None, None
    named = breakdown({"trace": trace, "all_records": [Record()],
                       "wall_offset": 0.0})["idle_gaps"]
    assert named[0] == [
        "rtpu.engine.admit.wait1of1.decode_w1-to-prefill_r4",
        pytest.approx(1000e-9)]
    assert named[1][0] == "rtpu.loop.other.wait1of1.prefill_r4-to-decode_w1"
    # the longest names the engine's phases and programs give stay whole
    # where a name is cut to 64 characters
    assert len("rtpu.engine.prefill.device.wait17of24.prefill_r4-to-decode_w8"
               ) <= 64 and all(len(n) <= 64 for n, _ in named)
    # a training run has no client records: the loop's own annotation alone
    assert breakdown({"trace": trace})["idle_gaps"][0][0] == \
        "rtpu.engine.admit.decode_w1-to-prefill_r4"


def test_collectives_exposed_and_worst_chip():
    # chip 0: all-reduce [100,300], compute [0,150] and [250,400]:
    # exposed = [150,250] = 100
    chip0 = _plane("/device:TPU:0", [
        ("fusion.1", 0, 150, {}), ("all-reduce.1", 100, 200, {}),
        ("fusion.2", 250, 150, {})])
    # chip 1: all-reduce [0,400] fully exposed but for compute [0,100]
    chip1 = _plane("/device:TPU:1", [
        ("fusion.1", 0, 100, {}),
        ("all-gather-done.9", 0, 400, {})])
    out = xplane.reduce([chip0, chip1], chips=2)
    assert out["devices"] == 2
    assert out["collective_exposed_s"] == pytest.approx(300e-9)
    assert out["collective_s"] == pytest.approx(400e-9)
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["busy_s_worst"] == pytest.approx(400e-9)


def test_no_device_plane_reads_as_nothing():
    assert xplane.reduce([_plane("/host:CPU", [("x", 0, 5, {})])]) == \
        {"devices": 0}


def test_recorded_chip_trace():
    """A slice of the training cell's trace recorded on one v5e
    (testdata/README.txt says how it was cut)."""
    with gzip.open(os.path.join(DATA, "train_v5e_slice.json.gz"), "rt") as f:
        planes = json.load(f)
    with open(os.path.join(DATA, "train_v5e_slice.expected.json")) as f:
        want = json.load(f)
    out = xplane.reduce(planes, chips=1)
    assert out["devices"] == 1
    for key in ("window_s", "busy_s"):
        assert out[key] == pytest.approx(want[key], rel=1e-9)
    for key in ("modules", "families"):
        for name, m in want[key].items():
            assert out[key][name]["count"] == m["count"]
            assert out[key][name]["median_s"] == pytest.approx(
                m["median_s"], rel=1e-9)
    for group, k in want["kernels"].items():
        assert out["kernels"][group]["count"] == k["count"]
        assert out["kernels"][group]["seconds"] == pytest.approx(
            k["seconds"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
