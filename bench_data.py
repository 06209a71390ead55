"""Data-plane benchmark: streaming executor vs naive task-per-batch.

Prints one JSON line per metric ({"metric", "value", "unit",
"vs_baseline"} — the bench_core.py/bench_rl.py format), interleaved
A/B reps because this box's perf swings:

  data_streaming_throughput      blocks/s through a map_batches pipeline
      driven by the streaming executor (stage actors on sealed channels)
      vs the task-per-block executor on the SAME plan; vs_baseline =
      streaming/task blocks/s ratio (>= 1 means the channel plane pays
      for itself). The unit string carries the counter-verified
      dispatches/block for both paths (rtpu_data_* — streaming issues
      one run_loop call per stage worker for the whole run, the task
      path pays >= 1 dispatch per block by construction).
  data_streaming_peak_store_bytes   peak store occupancy while streaming
      a SKEWED-block-size workload through a deliberately slow consumer:
      credit backpressure parks producers at the ring limit, so the peak
      stays bounded while the task executor's submission window keeps
      max_tasks_in_flight whole blocks materialized; vs_baseline =
      task_peak/streaming_peak (>= 1 means streaming holds less).

``--quick``: fewer/shorter reps; same line format (wired into the test
suite as a slow-marked smoke so the bench itself can't rot).
``--trace out.json``: flight-record the measured section (stage spans,
per-block seal->wake flow arrows) via the shared bench.flight_report.
"""
import json
import os
import statistics
import sys
import time


def _counters():
    from ray_tpu.data.streaming import metrics_summary
    out = {}
    for path, rec in metrics_summary().get("path", {}).items():
        out[path] = (rec.get("blocks", 0.0), rec.get("dispatches", 0.0))
    return out


def _pipeline(n_rows: int, n_blocks: int):
    import numpy as np

    from ray_tpu import data

    def work(batch):
        # a small but real per-block compute so the bench measures the
        # data plane against useful work, not empty plumbing
        x = np.asarray(batch["id"], np.float64)
        for _ in range(4):
            x = np.sqrt(x * x + 1.0)
        return {"id": batch["id"], "y": x}

    return data.range(n_rows, override_num_blocks=n_blocks) \
        .map_batches(work)


def run_throughput(streaming: bool, n_rows: int, n_blocks: int) -> float:
    """One measured pass: blocks/s consuming the pipeline end to end."""
    ds = _pipeline(n_rows, n_blocks)
    ds._ctx.streaming_executor = "force" if streaming else "off"
    t0 = time.perf_counter()
    blocks = sum(1 for _ in ds.iter_batches(batch_size=None))
    dt = time.perf_counter() - t0
    assert blocks == n_blocks, (blocks, n_blocks)
    return blocks / dt


def run_skew_peak(streaming: bool, n_blocks: int,
                  rows_small: int, rows_big: int) -> int:
    """Peak store bytes streaming a skewed workload through a slow
    consumer (the memory-under-skew acceptance)."""
    import numpy as np

    from ray_tpu import data
    from ray_tpu.core.api import _runtime

    store = _runtime().store

    def make_read(i):
        rows = rows_big if i % 4 == 0 else rows_small
        def read(rows=rows, i=i):
            import numpy as _np
            import pyarrow as pa
            return pa.table({"x": _np.zeros(rows, _np.float64) + i})
        return read

    from ray_tpu.data.dataset import Dataset
    from ray_tpu.data.executor import Read
    ds = Dataset(Read([make_read(i) for i in range(n_blocks)]))
    ds._ctx.streaming_executor = "force" if streaming else "off"
    base = store.bytes_in_use()
    peak = 0
    for batch in ds.iter_batches(batch_size=None):
        peak = max(peak, store.bytes_in_use() - base)
        time.sleep(0.02)   # the slow consumer: producers must park
    return peak


def main(quick: bool = False):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import ray_tpu as ray
    from bench import flight_report, trace_arg

    reps = 2 if quick else 4
    n_rows = 40_000 if quick else 400_000
    n_blocks = 24 if quick else 64
    ray.init(num_cpus=float(max(os.cpu_count() or 2, 3)),
             object_store_memory=512 << 20)
    trace_t0 = time.monotonic_ns()

    # warmup both paths (worker spawn, imports)
    run_throughput(True, n_rows // 4, max(4, n_blocks // 4))
    run_throughput(False, n_rows // 4, max(4, n_blocks // 4))

    before = _counters()
    chan, task = [], []
    for _ in range(reps):
        chan.append(run_throughput(True, n_rows, n_blocks))
        task.append(run_throughput(False, n_rows, n_blocks))
    after = _counters()
    mc, mt = statistics.median(chan), statistics.median(task)

    def dpb(path: str) -> float:
        b0, d0 = before.get(path, (0.0, 0.0))
        b1, d1 = after.get(path, (0.0, 0.0))
        return (d1 - d0) / max(b1 - b0, 1e-9)

    print(json.dumps({
        "metric": "data_streaming_throughput",
        "value": round(mc, 1),
        "unit": (f"blocks/s streaming executor (task-per-block="
                 f"{mt:.1f}; dispatches/block chan={dpb('chan'):.3f} vs "
                 f"task={dpb('task'):.3f}; {n_blocks} blocks x "
                 f"{n_rows // n_blocks} rows, medians of {reps} "
                 f"interleaved reps, {os.cpu_count()} host cores)"),
        "vs_baseline": round(mc / max(mt, 1e-9), 3),
    }))

    skew_blocks = 16 if quick else 32
    small, big = (20_000, 400_000) if quick else (50_000, 1_000_000)
    speak, tpeak = [], []
    for _ in range(max(1, reps // 2)):
        speak.append(run_skew_peak(True, skew_blocks, small, big))
        tpeak.append(run_skew_peak(False, skew_blocks, small, big))
    ms, mt2 = statistics.median(speak), statistics.median(tpeak)
    from ray_tpu.data import DataContext
    window = DataContext.get_current().max_tasks_in_flight
    print(json.dumps({
        "metric": "data_streaming_peak_store_bytes",
        "value": int(ms),
        "unit": (f"peak store bytes, skewed blocks ({big}/{small} rows "
                 f"1:3), slow consumer; task-executor peak={int(mt2)} "
                 f"(window={window} blocks)"),
        "vs_baseline": round(mt2 / max(ms, 1.0), 3),
    }))

    flight_report(trace_arg(sys.argv), trace_t0)
    ray.shutdown()


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
