"""The driver a user of the trainer would write, as the child of
``benchmarks.run``: ``ray_tpu.init`` -> ``JaxTrainer(train_fn).fit()``. The
worker the trainer starts is granted the chip and owns it."""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    import ray_tpu
    from benchmarks.spec import ROOT, Cell
    from benchmarks.train_app import train_fn
    from ray_tpu import train

    cell = Cell(a.workload)
    sizes = cell.sizes(a.rehearse)
    cfg, mix = sizes["config"], sizes["traffic"]
    out = os.path.join(ROOT, "chiprun_out", "train", cell.name)
    config = {"seed": a.seed, "seconds": a.seconds, "trace": bool(a.trace),
              "rehearse": a.rehearse, "chips": cell.chips,
              "trace_dir": os.path.join(ROOT, "chiprun_out", "trace",
                                        cell.name),
              "builder": cfg["builder"], "reference": cfg["reference"],
              "mesh": cfg.get("mesh"),
              "train": {**cfg["train"], **{k: mix[k] for k in (
                  "batch", "seq", "report_every")}},
              "model": {k: v for k, v in cfg.items()
                        if not isinstance(v, dict)}}
    info = ray_tpu.init(num_tpus=cell.chips)
    try:
        emit(event="session", session_dir=info["session_dir"])
        result = train.JaxTrainer(
            train_fn, train_loop_config=config,
            scaling_config=train.ScalingConfig(
                num_workers=1, tpus_per_worker=cell.chips),
            run_config=train.RunConfig(name=cell.name, storage_path=out),
        ).fit()
        if result.error is not None:
            raise result.error
        emit(event="result", metrics=result.metrics)
    except BaseException as e:  # noqa: BLE001 — reported, then re-raised
        emit(event="error", error=f"{type(e).__name__}: {e}"[:2000])
        raise
    finally:
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
