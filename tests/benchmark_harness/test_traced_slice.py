"""The replica's ``trace_start`` / ``trace_stop`` (PR 33) on a stub server
and the CPU's profiler: the slice is traced without the profiler's Python
tracer, the program's annotations still land and pass ``load()``, and the
reduction carries ``engine_stats()`` at the slice's two ends."""
import time

import jax
import pytest

from benchmarks import serve_app
from benchmarks.layer_metrics import _engine, ragged_decode_roofline
from benchmarks.reduce import xplane


class Stub(serve_app.BenchLLMServer):
    def __init__(self):
        self._bench = {"chips": 1}
        self.dispatches = 0

    def engine_stats(self) -> dict:
        return {"decode_dispatches": self.dispatches, "clock_ns": 5,
                "max_ns_admit": 9, "mesh": None, "spec_on": True,
                "device": {"platform": "cpu"}, "memory": [None],
                "compile_cache": {"hits": 0}, "rate": 0.5}


def _work(server) -> None:
    with jax.profiler.TraceAnnotation("rtpu.engine.decode.device"):
        for i in range(200):
            str(i).zfill(5)            # Python calls the tracer would log
        jax.numpy.ones((8, 8)).sum().block_until_ready()
        time.sleep(0.002)
    server.dispatches += 3


def test_slice_has_no_python_events_and_carries_both_snapshots(tmp_path):
    server = Stub()
    server.dispatches = 4
    assert server.trace_start(str(tmp_path / "trace")) == {}
    _work(server)
    out = server.trace_stop()
    # the CPU has no device plane: nothing reduced, the snapshots are there
    assert not out.get("devices") and out["window_s"] > 0
    # whole ``engine_stats()`` snapshots: the closing one also closes the
    # window's counters (``kinds/serve.py``); a delta is of integers only
    assert out["stats_before"]["decode_dispatches"] == 4
    assert out["stats_after"] == dict(server.engine_stats())
    assert _engine.slice_deltas({"trace": out}) == {
        "decode_dispatches": 3, "clock_ns": 0, "max_ns_admit": 0,
        "spec_on": 0}
    # and a reader that needs the device trace finds nothing to read
    assert ragged_decode_roofline.read({
        "trace": out, "config": {}, "device": {"kind": "cpu"}}) is None


def test_python_tracer_is_off_and_the_annotations_pass_load(tmp_path):
    from jax.profiler import ProfileData
    server = Stub()
    server._trace_dir = str(tmp_path / "trace")
    server.trace_start(server._trace_dir)
    _work(server)
    jax.profiler.stop_trace()
    found = xplane.find_xplane(server._trace_dir)
    names = [ev.name for plane in ProfileData.from_file(found).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for ev in line.events]
    if not names:
        pytest.skip("the CPU profiler wrote no host plane here")
    # the Python tracer names its events ``$<file>:<line> <function>``
    assert not [n for n in names if n.startswith("$")]
    host = next(p for p in xplane.load(found) if p["name"] == "host")
    kept = sorted(e[0].rsplit(".", 1)[0] if e[0].startswith("bench_")
                  else e[0] for e in host["lines"][0]["events"])
    assert kept == ["bench_clock_sync", "rtpu.engine.decode.device"]


def test_window_line_names_the_phase_of_a_stall():
    """``kinds/serve.py`` prints ``max_ns_<phase>`` of the window's closing
    snapshot (ROADMAP S0 (d)): the longest occurrence of each phase, and
    which of them fell inside the window."""
    from benchmarks.kinds import serve
    before = {"max_ns_admit": 2_000_000, "max_ns_decode_device": 90_000_000,
              "max_ns_loop_other": 0, "ns_admit": 5, "admitted": 3, "mesh": None, "rate": 0.5}
    after = {"max_ns_admit": 2_000_000, "max_ns_decode_device": 4_120_000_000,
             "max_ns_loop_other": 7_000_000, "ns_admit": 9, "admitted": 10,
             "mesh": None, "rate": 0.7, "tokens_out": 4}
    out = serve._engine_readings(before, after)
    assert out["phase_longest_ms"] == {
        "admit": 2.0, "decode_device": 4120.0, "loop_other": 7.0}
    assert out["phase_longest_in_window"] == ["decode_device", "loop_other"]
    assert out["counters_in_window"] == {"ns_admit": 4, "admitted": 7}
    # the parent's engine (no such keys): an empty reading, not an error
    assert serve._engine_readings({"admitted": 1}, {"admitted": 2}) == {
        "phase_longest_ms": {}, "phase_longest_in_window": [],
        "counters_in_window": {"admitted": 1}}
