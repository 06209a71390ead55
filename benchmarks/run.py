"""One run of one cell:

    python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, when traced,
``breakdown``. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics. Earlier lines carry what
helps a reader and decides nothing (percentiles, the set-up split, the
reference check).

This process drives the load and never imports jax; the process that holds
the chip is a grandchild (``kinds/<kind>_child``). A run that finds no TPU
prints no result and exits non-zero. ``JAX_PLATFORMS=cpu`` with
``--rehearse`` runs the same control flow at toy sizes; its metrics are all
null and its device says ``cpu``.

Everything a cell is made of is found by name: ``configs/``, ``traffic/``,
``cells/``, ``generators/``, ``kinds/``, ``e2e_metrics/``,
``layer_metrics/``. Adding one never edits this file.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def log(msg) -> None:
    print(json.dumps(msg, default=str) if isinstance(msg, dict) else msg,
          flush=True)


def _read(package: str, entries: list, ctx: dict) -> dict:
    metrics = {}
    for m in entries:
        value = importlib.import_module(
            f"benchmarks.{package}.{m['name']}").read(ctx)
        # nothing to read, or nothing finite (a failed request counts as
        # an infinite latency): leave it out
        if value is None or not math.isfinite(value):
            continue
        # a number from a CPU run is never written under a device
        # metric's name
        metrics[m["name"]] = {
            "value": None if ctx["rehearse"] else float(value),
            "unit": m["unit"]}
    return metrics


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (BENCHMARK.json's "
                         "run_seconds when left out)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU (needs JAX_PLATFORMS=cpu)")
    ap.add_argument("--sweep", type=lambda s: [float(x) for x in s.split(",")],
                    help="serve cells: one window per rate_rps against one "
                         "server, a table on the earlier lines")
    a = ap.parse_args()
    if a.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        ap.error("--rehearse runs on the CPU: set JAX_PLATFORMS=cpu")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, here)
    from benchmarks.spec import Cell, load_benchmark
    if a.seconds is None:
        a.seconds = float(load_benchmark()["run_seconds"])
    cell = Cell(a.workload)
    kind = importlib.import_module(f"benchmarks.kinds.{cell.kind}")
    res = kind.run(cell, a, T_PROCESS_START, log)

    device = dict(res["device"])
    on_chip = device["platform"] == "tpu" and device["count"] >= cell.chips
    if not (on_chip or a.rehearse):
        log({"error": f"the cell needs {cell.chips} TPU chip(s), the run "
                      f"was on {device}"})
        return 1
    ctx = res["ctx"]
    ctx["rehearse"] = a.rehearse
    metrics = _read("layer_metrics", cell.per_layer, ctx) if a.trace \
        else _read("e2e_metrics", cell.end_to_end, ctx)
    if a.trace:
        # what tracing costs shows against the untraced runs' last lines
        log({"phase": "end_to_end_while_traced",
             **_read("e2e_metrics", cell.end_to_end, ctx)})
    log({"phase": "checks", **res["checks"]})
    line = {"correct": all(res["checks"].values()),
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics,
            "device": {k: device[k] for k in (
                "platform", "kind", "count", "memory_peak_bytes")}}
    trace = ctx.get("trace")
    if a.trace and trace and trace.get("devices"):
        log({"phase": "trace", **trace})
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        from benchmarks.breakdown import breakdown
        line["breakdown"] = breakdown(ctx)
    print(json.dumps(line), flush=True)
    return 0 if (line["correct"] and res["failed"] == 0) or a.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
