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
    # ran a ragged kernel with a query window of 1
    assert out["families"] == {"decode": {
        "count": 1, "total_s": pytest.approx(500e-9),
        "median_s": pytest.approx(500e-9)}}


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
