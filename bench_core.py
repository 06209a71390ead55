"""Core runtime microbenchmarks (reference harness parity:
python/ray/_private/ray_perf.py:95 via release/microbenchmark).

Prints one JSON line per metric plus a combined gate line. Baselines are
the reference's checked-in 2.47.0 numbers (BASELINE.md): single-client
tasks 961/s, 1:1 actor calls sync 1960/s, async 8220/s, gets 10841/s,
put 19.56 GiB/s.

``--quick`` runs a few-hundred-op smoke of the control-plane metrics only
(no put/collective/training hedges): same JSON line format, finishes in
seconds, and is wired into the test suite as a slow-marked regression
canary (tests/test_control_fastpath.py) so control-plane throughput
collapses are visible in-tree, not only in the external bench harness.
"""
import json
import time

import numpy as np


def timed(n, fn):
    t0 = time.perf_counter()
    fn()
    return n / (time.perf_counter() - t0)


def main(quick: bool = False, trace_out: str | None = None):
    import ray_tpu as ray

    # size the pool to the machine: on few-core hosts extra workers just
    # contend (the reference's ray_perf tunes workers per host the same
    # way); prestart them all so cold-start never lands in a timed region
    import os

    from ray_tpu.core.config import cfg
    n_cpus = min(4, max(2, (os.cpu_count() or 2)))
    # production posture for a long-lived cluster: prefault the store (put
    # bandwidth measures memcpy, not first-touch page zeroing) and hand
    # out zero-copy pinned views on get (plasma semantics)
    cfg.override(worker_prestart=n_cpus, store_prefault=True,
                 zero_copy_get=True)
    ray.init(num_cpus=n_cpus, object_store_memory=1 << 30)

    @ray.remote
    def nop():
        return None

    @ray.remote
    class Actor:
        def nop(self):
            return None

        def step(self, x):
            return x

    results = {}

    # --quick: few hundred ops per metric, control-plane metrics only
    n_sync = 100 if quick else 500
    n_async = 400 if quick else 2000
    n_gets = 500 if quick else 3000

    # warmup: worker pool spin-up + code ship; then QUIESCE — on this
    # 1-core box a prestarted worker still finishing its imports steals
    # most of the core from any timed section (wall 3x cpu measured)
    ray.get([nop.remote() for _ in range(20)], timeout=120)
    time.sleep(0.5 if quick else 3.0)

    # --trace: flight-record the measured section (everything after the
    # warmup) and report the wait/dispatch breakdown with the numbers
    trace_t0 = time.monotonic_ns() if trace_out else None

    # single client tasks sync
    def tasks_sync():
        for _ in range(n_sync):
            ray.get(nop.remote(), timeout=60)
    results["single_client_tasks_sync"] = (timed(n_sync, tasks_sync), 961)

    # single client tasks async (batch submit, one drain)
    def tasks_async():
        ray.get([nop.remote() for _ in range(n_async)], timeout=120)
    results["single_client_tasks_async"] = (timed(n_async, tasks_async), 6787)

    a = Actor.remote()
    ray.get(a.nop.remote(), timeout=60)

    def actor_sync():
        for _ in range(n_sync):
            ray.get(a.nop.remote(), timeout=60)
    results["1_1_actor_calls_sync"] = (timed(n_sync, actor_sync), 1960)

    def actor_async():
        ray.get([a.nop.remote() for _ in range(n_async)], timeout=120)
    results["1_1_actor_calls_async"] = (timed(n_async, actor_async), 8220)

    # single client get (small object, repeated)
    ref = ray.put(b"x" * 1024)

    def gets():
        for _ in range(n_gets):
            ray.get(ref, timeout=60)
    results["single_client_get_calls"] = (timed(n_gets, gets), 10841)

    # await-based burst: refs awaited concurrently through the shared
    # completion multiplexer (ObjectRef.__await__ -> core/completion.py)
    # — tracks the async completion fast path the serve handles ride
    import asyncio

    n_await = 200 if quick else 1000

    async def _await_burst():
        await asyncio.gather(*[nop.remote() for _ in range(n_await)])

    def await_burst():
        asyncio.run(_await_burst())
    results["async_burst"] = (timed(n_await, await_burst), 6787)

    # compiled-DAG roundtrip vs the equivalent uncompiled actor chain:
    # the "baseline" here is OUR OWN uncompiled rate measured in the same
    # run, so vs_baseline is the compile speedup (acceptance bar: >= 2x)
    from ray_tpu.dag import InputNode
    d1, d2 = Actor.remote(), Actor.remote()
    ray.get([d1.step.remote(0), d2.step.remote(0)], timeout=60)
    n_dag = 100 if quick else 400

    def chain():
        for i in range(n_dag):
            ray.get(d2.step.remote(d1.step.remote(i)), timeout=60)
    uncompiled_rate = timed(n_dag, chain)
    with InputNode() as inp:
        out = d2.step.bind(d1.step.bind(inp))
    cdag = out.experimental_compile(max_inflight=2)
    cdag.execute(0).get()

    def dag_loop():
        for i in range(n_dag):
            cdag.execute(i).get()
    dag_rate = timed(n_dag, dag_loop)
    cdag.teardown()
    results["compiled_dag_roundtrip"] = (dag_rate, uncompiled_rate)

    if quick:
        _flight_report(trace_out, trace_t0)
        ray.shutdown()
        _report(results)
        return

    # put throughput, steady state. Dropped refs free asynchronously, so
    # between passes poll until the store is EMPTY again — this both
    # guarantees heap regions recycle (each pass rewrites the same bytes,
    # the long-lived-cluster steady state) and rules out silently timing
    # the disk-spill path (spill only triggers above 80% occupancy, which
    # an empty store per 512 MiB pass can never reach). The first ~3
    # passes on this VM crawl on host-side lazy page machinery; time the
    # converged tail and report its true median (zeros chunk = the same
    # workload as the reference's ray_perf put benchmark).
    from ray_tpu.core.api import _runtime
    store = _runtime().store

    resident = store.bytes_in_use()  # earlier benches' live refs

    def settle_empty():
        deadline = time.perf_counter() + 10.0
        while store.bytes_in_use() > resident:
            if time.perf_counter() > deadline:
                raise RuntimeError("put bench: store did not drain; "
                                   "rates would include spill/evict paths")
            time.sleep(0.02)

    # Timed region = the put call alone; the settle between puts (waiting
    # for the async ref-drop free) is a benchmark artifact, not part of
    # the put path a user times. With the store drained, first-fit hands
    # every put the same recycled heap region.
    chunk = np.zeros(128 * 1024 * 1024, dtype=np.uint8)
    rates = []
    for _ in range(12):
        t0 = time.perf_counter()
        ray.put(chunk)
        rates.append((128 / 1024) / (time.perf_counter() - t0))
        settle_empty()
    tail = sorted(rates[5:])  # drop warmup; report the converged median
    gibs = tail[len(tail) // 2]
    results["single_client_put_gigabytes"] = (gibs, 19.56)

    # store-backed collective broadcast (driver rank 0 -> 1 actor rank):
    # bulk bytes ride the object store, the rendezvous actor passes refs
    # only. No reference microbenchmark exists for this; the baseline is a
    # 1 GiB/s target (DCN-class link speed, the bar the store path must
    # clear to be worth using for cross-host weight shuttling).
    from ray_tpu.util import collective as col

    @ray.remote
    class Rank:
        def init_collective_group(self, world, rank, backend, group):
            from ray_tpu.util import collective as c
            c.init_collective_group(world, rank, backend, group)

        def recv_broadcast(self, group, n):
            import numpy as np
            from ray_tpu.util import collective as c
            out = c.broadcast(np.zeros(1), 0, group)
            return out.nbytes

    actor = Rank.remote()
    ref = actor.init_collective_group.remote(2, 1, "shm", "bench")
    col.init_collective_group(2, 0, "shm", "bench")
    ray.get(ref, timeout=60)
    payload = np.zeros(256 * 1024 * 1024, dtype=np.uint8)
    # warmup small
    r = actor.recv_broadcast.remote("bench", 1)
    col.broadcast(np.zeros(2 * 1024 * 1024, dtype=np.uint8), 0, "bench")
    ray.get(r, timeout=60)

    def bcast():
        r = actor.recv_broadcast.remote("bench", len(payload))
        col.broadcast(payload, 0, "bench")
        assert ray.get(r, timeout=120) == len(payload)
    results["collective_broadcast_gigabytes"] = (
        timed(1, bcast) * 256 / 1024, 1.0)
    col.destroy_collective_group("bench")

    _flight_report(trace_out, trace_t0)
    ray.shutdown()

    _report(results)

    # pinned CPU-mesh training-step trend (bench_trend.py): a host-side
    # count of the sharded step's cost that needs no chip
    try:
        import bench_trend
        tps = bench_trend.measure()
        base = (bench_trend.BASELINE_TOKENS_PER_SEC
                or bench_trend._PIN_FILE_DEFAULT)
        print(json.dumps({
            "metric": "cpu_mesh_tokens_per_sec",
            "value": round(tps, 1),
            "unit": "tokens/s (8-dev virtual CPU mesh, pinned config)",
            "vs_baseline": round(tps / base, 3),
        }))
    except Exception as e:  # noqa: BLE001 — the hedge must never fail core
        print(json.dumps({"metric": "cpu_mesh_tokens_per_sec",
                          "value": None, "unit": "tokens/s",
                          "error": str(e)[:200]}))

    # serving dispatch economy: DISPATCHES per generated token on a
    # pinned burst (a count, machine-independent; bench_trend.py);
    # ~1.1 would mean the engine fell back to a dispatch per token.
    try:
        import bench_trend
        dpt = bench_trend.measure_serve_dispatch()
        pin = bench_trend.BASELINE_SERVE_DISPATCH_PER_TOKEN
        print(json.dumps({
            "metric": "serve_dispatches_per_token",
            "value": round(dpt, 4),
            "unit": "device dispatches per generated token (pinned burst)",
            "vs_baseline": round(pin / max(dpt, 1e-9), 3),
        }))
    except Exception as e:  # noqa: BLE001
        print(json.dumps({"metric": "serve_dispatches_per_token",
                          "value": None, "unit": "dispatches/token",
                          "error": str(e)[:200]}))


def _flight_report(trace_out, trace_t0):
    """--trace out.json: export the measured section's flight recording
    and print the wait/dispatch breakdown (shared bench.flight_report)."""
    if not trace_out:
        return  # keep the default path free of bench.py's jax import
    from bench import flight_report
    flight_report(trace_out, trace_t0)


# metrics whose vs_baseline is NOT a vs-reference ratio (self-relative
# speedup, or a tracking scenario with no reference analog): reported,
# but excluded from the worst-ratio gate line
_NON_GATING = {"compiled_dag_roundtrip", "async_burst"}


def _report(results):
    worst = 1e9
    for name, (value, base) in results.items():
        ratio = value / base
        if name not in _NON_GATING:
            worst = min(worst, ratio)
        print(json.dumps({
            "metric": name, "value": round(float(value), 2),
            "unit": ("ops/s (vs uncompiled actor chain)"
                     if name == "compiled_dag_roundtrip"
                     else "GiB/s" if "gigabytes" in name else "ops/s"),
            "vs_baseline": round(ratio, 3),
        }))
    print(json.dumps({
        "metric": "core_microbench_worst_ratio",
        "value": round(worst, 3),
        "unit": "min(ours/reference) across metrics",
        "vs_baseline": round(worst, 3),
    }))


if __name__ == "__main__":
    import sys
    argv = sys.argv[1:]
    out = None
    if "--trace" in argv:
        # lazy: importing bench pulls jax; only pay it when tracing
        from bench import trace_arg
        out = trace_arg(argv)
    main(quick="--quick" in argv, trace_out=out)
