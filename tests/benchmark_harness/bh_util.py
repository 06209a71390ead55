"""Shared by the benchmark harness's tests: run ``benchmarks.run`` as the
driver does, in a subprocess, from the repository or from a copy of the
benchmark's own files."""
import importlib
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


def declared_pairs(root: str = REPO, names=None) -> list:
    """[(metric, cell)] of BENCHMARK.json's ``per_layer``, in its order: a
    reader counts once a cell it serves. ``names`` narrows it to those
    metrics."""
    bench = load_json(root, "BENCHMARK.json")
    return [(m["name"], cell) for m in bench["per_layer"]
            if names is None or m["name"] in names
            for cell in m["workloads"]]


def cell_config(cell: str, root: str = REPO) -> dict:
    """The configuration file of a cell of BENCHMARK.json, as it is run."""
    bench = load_json(root, "BENCHMARK.json")
    config = next(w["config"] for w in bench["workloads"]
                  if w["name"] == cell)
    return load_json(root, next(c["file"] for c in bench["configs"]
                                if c["name"] == config))


def read_metric(name: str, ctx: dict):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(ctx)


FIFTH_CONFIG, FIFTH_CELL = "fifth-serve-1chip", "fifth-docqa-1chip"
FIFTH_METRIC = "fifth_decode_launch_ms"
# a reader that is there and serves the new cell too: its entry's list grows
FIFTH_SHARED = "ragged_decode_roofline"


def add_fifth_cell(root: str, also=("kanana-longdoc-sessions-1chip",
                                    "olmoe-gen-sessions-1chip")) -> None:
    """What the next configuration's PR does, on a copy: a fifth
    configuration (an existing file under a new name), a cell on it, its
    listing under ``out_tok_s`` and under a reader that is there
    (``FIFTH_SHARED``), and one per-layer metric — a reader file of its
    own — that lists the new cell and the existing cells ``also``.
    Entries and cells are appended and files added; no file that is there
    is edited."""
    here = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(here, "configs", "mistral7b-serve-1chip.json"),
                os.path.join(here, "configs", f"{FIFTH_CONFIG}.json"))
    with open(os.path.join(here, "layer_metrics",
                           f"{FIFTH_METRIC}.py"), "w") as f:
        f.write('"""``decode_launch_ms`` for the fifth cell."""\n'
                "from .decode_launch_ms import read  # noqa: F401\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    donor = next(c for c in bench["configs"]
                 if c["name"] == "mistral7b-serve-1chip")
    bench["configs"].append(dict(
        donor, name=FIFTH_CONFIG,
        file=f"benchmarks/configs/{FIFTH_CONFIG}.json"))
    bench["workloads"].append({
        "name": FIFTH_CELL, "config": FIFTH_CONFIG,
        "traffic": "docqa-sessions", "chips": 1,
        "why": "a synthetic fifth cell: what the next configuration's PR "
               "appends"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "out_tok_s")["workloads"].append(FIFTH_CELL)
    next(m for m in bench["per_layer"]
         if m["name"] == FIFTH_SHARED)["workloads"].append(FIFTH_CELL)
    bench["per_layer"].append({
        "name": FIFTH_METRIC, "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "out_tok_s", "workloads": [*also, FIFTH_CELL]})
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)


def load_json(root: str, *path):
    with open(os.path.join(root, *path)) as f:
        return json.load(f)


def stands_before(names: list, name: str, before: list) -> bool:
    """``before`` are the entries ahead of ``name`` in ``names``, in that
    order: what a PR appended stays where it was appended, and whatever a
    later PR appends after it is no concern of the earlier one's test."""
    return name in names and names[:names.index(name)] == before


def in_order(subset: list, names: list) -> bool:
    """``subset`` is a subsequence of ``names``."""
    rest = iter(names)
    return all(n in rest for n in subset)


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
