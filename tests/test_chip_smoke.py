"""chip_smoke.py's CPU rehearsal (tiny shapes, the real control flow:
init -> serve.run -> HTTP -> shutdown -> reference check) plus the two
guards that keep a missing chip from passing silently. The script is the
driver's proof that the system starts on the chip; this file keeps it
runnable between chip runs. Nothing here measures anything."""
import json
import os
import subprocess
import sys
import time
import types

import pytest

SCRIPT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_smoke.py")


def _run(*args, timeout=240):
    return subprocess.run([sys.executable, SCRIPT, *args],
                          capture_output=True, text=True, timeout=timeout)


def test_rehearsal_serves_and_checks_against_reference():
    """The serve and reference phases end to end on the CPU: exit 0, the
    last line is exactly the contract's object, no compile under
    traffic, and the parent — which launches the chip's owners — never
    imported jax."""
    out = _run("--rehearse", "--phases", "serve,reference")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"      # a rehearsal says so
    by_phase = {ln.get("phase"): ln for ln in lines}
    assert by_phase["parent"]["jax_imported"] is False
    serve = by_phase["serve"]
    assert serve["ok"] and serve["requests"] == 5
    assert serve["in_window_compiles"] == 0
    assert serve["prefix_hits"] > 0
    assert serve["tokens_served"] == by_phase["reference"]["tokens"]
    assert by_phase["reference"]["worst_logit_gap"] <= \
        by_phase["reference"]["margin"]


def test_without_chip_or_rehearsal_switch_it_fails():
    """No accelerator and no --rehearse: the first phase fails, the run
    exits non-zero and prints no result line — nothing catches a phase
    failure into an exit 0. (The test environment pins JAX to the CPU,
    which is exactly a machine without a chip.)"""
    out = _run("--phases", "kernels")
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no accelerator" in out.stderr


def test_worker_granted_a_tpu_raises_when_jax_sees_none(monkeypatch):
    from ray_tpu.util.tpu import require_granted_tpu
    require_granted_tpu({"CPU": 1.0})           # no TPU asked: no check
    require_granted_tpu({"TPU": 1.0})           # JAX_PLATFORMS=cpu: on purpose
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="granted a TPU resource"):
        require_granted_tpu({"CPU": 1.0, "TPU": 1.0})


def test_peaks_table_raises_on_an_unknown_tpu_kind():
    from ray_tpu.parallel.mesh import DEVICE_PEAKS, device_peak
    from ray_tpu.util.profiling import device_peak_flops

    def dev(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)
    assert device_peak(dev("tpu", "TPU v5 lite")) == ("v5e", 197e12)
    assert device_peak_flops(dev("tpu", "TPU v5 lite")) == 197e12
    assert device_peak(dev("cpu", "cpu")) == DEVICE_PEAKS["cpu"]
    assert device_peak_flops(dev("cpu", "cpu")) is None
    with pytest.raises(ValueError, match="TPU v9"):
        device_peak(dev("tpu", "TPU v9"))
    with pytest.raises(ValueError, match="TPU v9"):
        device_peak_flops(dev("tpu", "TPU v9"))


def test_tpu_workers_on_one_host_each_own_their_chips(shutdown_only):
    """One process per chip: two workers granted one chip each of a
    four-chip host are pinned to different chips (libtpu's per-process
    bounds — jax.devices() there is the worker's grant, so an engine's
    leading device slice is its own), a dead worker's chip is handed out
    again, and a worker granted the whole host is not bounded."""
    ray = shutdown_only
    ray.init(num_cpus=2, num_tpus=4)

    @ray.remote(num_tpus=1, num_cpus=0)
    class Replica:
        def chips(self):
            return (os.environ.get("TPU_VISIBLE_CHIPS"),
                    os.environ.get("TPU_CHIPS_PER_PROCESS_BOUNDS"),
                    os.environ.get("JAX_PLATFORMS"))

    def chips_of(actor):
        return ray.get(actor.chips.remote(), timeout=120)

    a, b = Replica.remote(), Replica.remote()
    assert [chips_of(a)[:2], chips_of(b)[:2]] == [("0", "1,1,1"),
                                                  ("1", "1,1,1")]
    # a TPU worker keeps the platform it inherited (the CPU, by name,
    # under test); only chipless workers are pinned by the runtime
    assert chips_of(a)[2] == "cpu"
    ray.kill(a)
    deadline = time.time() + 60
    while ray.available_resources().get("TPU") != 3.0:
        assert time.time() < deadline, "the dead worker's TPU never freed"
        time.sleep(0.1)
    c = Replica.remote()
    assert chips_of(c)[:2] == ("0", "1,1,1")         # handed out again
    pair = Replica.options(num_tpus=2).remote()
    assert chips_of(pair)[:2] == ("2,3", "1,2,1")    # an aligned pair
    for actor in (b, c, pair):
        ray.kill(actor)
    while ray.available_resources().get("TPU") != 4.0:
        assert time.time() < deadline, "TPUs never freed"
        time.sleep(0.1)
    whole = Replica.options(num_tpus=4).remote()
    assert chips_of(whole)[:2] == (None, None)       # the host: unbounded
