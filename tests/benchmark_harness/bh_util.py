"""Shared by the benchmark harness's tests: run ``benchmarks.run`` as the
driver does, in a subprocess, from the repository or from a copy of the
benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def copy_benchmark(dest) -> str:
    """BENCHMARK.json and ``benchmarks/`` copied under ``dest``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(dest)


def add_pending(root: str, name: str) -> None:
    """Paste ``benchmarks/pending/<name>.json`` into the copy's
    BENCHMARK.json, the way the PR that measures the cell will."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    with open(os.path.join(root, "benchmarks", "pending",
                           f"{name}.json")) as f:
        pending = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] += pending.get(key, [])
    for key in ("end_to_end", "per_layer"):
        extra = pending.get(f"{key}_workloads", {})
        for m in bench[key]:
            # a metric without the key is already reported by every cell
            if "workloads" in m:
                m["workloads"] = m["workloads"] + extra.get(m["name"], [])
    with open(path, "w") as f:
        json.dump(bench, f)


def rehearse(workload: str, root: str = REPO, trace: int = 0,
             seconds: float = 2.0, timeout: float = 600.0) -> dict:
    """The driver's command with ``JAX_PLATFORMS=cpu --rehearse``; returns
    the last line of standard output as an object."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    proc = subprocess.run(
        [sys.executable if command[0].startswith("python") else command[0],
         *command[1:], "--workload", workload, "--seed", "3", "--seconds",
         str(seconds), "--trace", str(trace), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
