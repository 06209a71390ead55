"""A serving cell, from the parent's side: start the driver child, wait
for the replica, run the cell's generator over HTTP from this process, take
counters before and after the window, and (traced runs) have the replica
trace a steady slice of it."""
from __future__ import annotations

import asyncio
import importlib
import os
import socket
import time

import numpy as np

from ..client import Client, Window
from ..layer_metrics._engine import deltas
from ..proc import Child, child_env
from ..spec import ROOT

MODEL_ID = "bench"          # benchmarks.serve_app.MODEL_ID (that module imports jax)
# traced slice: the window's last 4 s. The replica then takes 20-54 s to stop
# the profiler (26-47 MB of xplane) and answers nothing meanwhile, so the
# counters that close a traced run's window are the ones it takes as the
# slice ends (``trace["stats_after"]``), not those of the ``stats`` call
# after it. Until PR 33 they were the latter, read 22 s and more after the
# window's end with the queue's drain in them: ``gen_decode_slot_occupancy``
# read 68 where the window's own counters give 86.
TRACE_SLICE_S = 4.0
STALL_S = 5.0               # a longer silence prints the workers' logs


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _in_flight(records, t: float) -> int:
    return sum(1 for r in records if r.sent and r.sent <= t < (r.done or 1e18))


def _live_tokens(records, t: float) -> int:
    return sum(r.prompt_tokens + sum(n for at, n in r.chunk_times if at <= t)
               for r in records if r.sent and r.sent <= t < (r.done or 1e18))


def summarize(records, window: Window, slo: dict | None) -> dict:
    """What goes on the earlier lines: percentiles, rates, lateness, and
    what the knee sweep decides by."""
    rs = [r for r in records if window.start <= r.due < window.end]
    ok = [r for r in rs if r.ok]
    ttft = np.array([r.ttft_ms for r in ok])
    tpot = np.array([r.tpot_ms for r in ok if r.tokens > 1])
    late = np.array([(r.sent - r.due) * 1e3 for r in rs])
    pct = lambda x, q: float(np.percentile(x, q)) if len(x) else None  # noqa: E731
    out = {"requests": len(rs), "failed": len(rs) - len(ok),
           "req_per_s": len(ok) / window.seconds,
           "prompt_tokens": int(sum(r.prompt_tokens for r in rs)),
           "out_tokens": int(sum(r.tokens for r in ok)),
           "errors": sorted({r.error for r in rs if r.error})[:5]}
    for name, x in (("ttft_ms", ttft), ("tpot_ms", tpot),
                    ("gen_late_ms", late)):
        out[f"{name}_mean"] = float(np.mean(x)) if len(x) else None
        for q in (50, 75, 90, 99):
            out[f"{name}_p{q}"] = pct(x, q)
    samples = np.linspace(window.start, window.end, 41)[:-1]
    out["in_flight_mean"] = float(np.mean(
        [_in_flight(records, t) for t in samples]))
    out["in_flight_at_end"] = _in_flight(records, window.end)
    # cache the traffic holds: prompt and delivered tokens of the requests
    # in flight (a document shared by a session's questions counts once,
    # because they come in turn)
    live = [_live_tokens(records, t) for t in samples]
    out["kv_live_tokens_mean"] = float(np.mean(live))
    out["kv_live_tokens_max"] = int(max(live))
    # a stall of the whole system shows as no chunk for any request
    chunks = sorted(t for r in records for t, _ in r.chunk_times
                    if window.start <= t < window.end)
    edges = [window.start, *chunks, window.end]
    gap, at = max((b - a, a) for a, b in zip(edges, edges[1:]))
    out["longest_silence_s"] = gap
    out["longest_silence_at_s"] = at - window.start
    if slo and len(rs):
        met = sum(1 for r in ok if r.ttft_ms <= slo["ttft_ms"]
                  and (r.tokens < 2 or r.tpot_ms <= slo["tpot_ms"]))
        out["slo_attainment"] = met / len(rs)
    return out


def _engine_readings(before: dict, after: dict) -> dict:
    """What the engine's counters say of the window, for the ``window``
    line of traced and untraced runs alike (per-layer metrics are printed
    by traced runs only): every integer counter's delta, and the stepping
    thread's longest single occurrence of each phase (``max_ns_<phase>``,
    kept since the engine started) in ms, with the phases whose longest
    fell inside the window — where a stall of seconds (PERF.md §7) was
    spent."""
    d = deltas({"stats_before": before, "stats_after": after})
    longest = "max_ns_"
    return {
        "phase_longest_ms": {k[len(longest):]: v / 1e6
                             for k, v in after.items()
                             if k.startswith(longest)},
        "phase_longest_in_window": sorted(
            k[len(longest):] for k, v in d.items()
            if k.startswith(longest) and v > 0),
        "counters_in_window": {k: v for k, v in d.items()
                               if not k.startswith(longest)}}


async def _window(cell, traffic, vocab, seed, seconds, trace, child,
                  port) -> dict:
    """One warm-up plus one measured window against the running server."""
    gen = importlib.import_module(
        f"benchmarks.generators.{traffic['generator']}")
    loop = asyncio.get_running_loop()
    rng = np.random.default_rng([seed, 1])
    ask = lambda cmd, **kw: loop.run_in_executor(       # noqa: E731
        None, lambda: child.ask(cmd, **kw))
    out: dict = {}
    async with Client(port, MODEL_ID, traffic["request_timeout_s"]) as client:
        window = Window(time.perf_counter() + traffic["warmup_s"] + 1.0,
                        seconds)

        async def control():
            await asyncio.sleep(max(window.start - time.perf_counter(), 0))
            out["before"] = await ask("stats")
            if trace:
                await asyncio.sleep(max(
                    window.end - min(TRACE_SLICE_S, seconds * 0.6)
                    - time.perf_counter(), 0))
                await ask("trace_start", dir=os.path.join(
                    ROOT, "chiprun_out", "trace", cell.name))
            await asyncio.sleep(max(window.end - time.perf_counter(), 0))
            if trace:
                out["trace"] = (await ask("trace_stop",
                                          timeout=600.0))["trace"]
            out["after"] = await ask("stats")

        ctl = asyncio.ensure_future(control())
        await gen.run(traffic, rng, vocab, client, window)
        await ctl
        if trace:
            # the engine's latency histograms reach the head on a ~2 s
            # cadence: read them once they cover the window
            await asyncio.sleep(2.5)
            out["after_flushed"] = await ask("stats")
        # the window's closing counters: a traced run's are taken by the
        # replica as the slice, and with it the window, ends
        out["closing"] = (out.get("trace") or {}).get(
            "stats_after") or out["after"]["stats"]
        out.update(window=window, records=client.records,
                   wall_offset=time.time() - time.perf_counter())
    return out


def run(cell, a, t_process_start: float, log) -> dict:
    sizes = cell.sizes(a.rehearse)
    cfg, traffic = sizes["config"], sizes["traffic"]
    port = _free_port()
    child = Child("benchmarks.kinds.serve_child",
                  ["--workload", cell.name, "--seed", str(a.seed),
                   "--port", str(port)]
                  + (["--rehearse"] if a.rehearse else []),
                  child_env(a.rehearse, cell.chips), log=log)
    failed = True
    try:
        child.read(120, event="session")
        ready = child.read(1150, event="ready")
        if ready["driver_backend_initialized"]:
            raise RuntimeError("the serve driver initialised a JAX backend: "
                               "it would hold the chip its replica needs")
        log({"phase": "ready", "replica_ready_s": ready["replica_ready_s"],
             "split": ready["device"]["split"],
             "reference": ready["reference"]})
        # traced runs compare the engine's TTFT histogram with the client's
        # over the whole run: its baseline, once the reference check's
        # observations have reached the head (~2 s cadence)
        baseline = child.ask("stats", wait_s=2.5) if a.trace else None
        results = []
        for i, rate in enumerate(a.sweep or [None]):
            t = dict(traffic, **({} if rate is None else {"rate_rps": rate}))
            w = asyncio.run(_window(cell, t, cfg["vocab_size"], a.seed + i,
                                    a.seconds, a.trace, child, port))
            w["summary"] = summarize(w["records"], w["window"],
                                     traffic.get("slo"))
            # programs the persistent cache was asked for inside the window
            # (JAX counts those that take over a second to compile)
            w["cache_events"] = {
                k: w["after"]["stats"]["compile_cache"][k]
                - w["before"]["stats"]["compile_cache"][k]
                for k in ("hits", "misses")}
            log({"phase": "window", "rate_rps": t.get("rate_rps"),
                 **w["summary"], "compile_cache_in_window": w["cache_events"],
                 # from the engine's pools (page_nbytes / page_size)
                 "cache_bytes_per_token": ready["device"].get(
                     "cache_bytes_per_token"),
                 **_engine_readings(w["before"]["stats"], w["closing"])})
            if w["summary"]["longest_silence_s"] > STALL_S:
                log(child.worker_log_tails())
            results.append(w)
        child.ask("stop", timeout=60)
        failed = False
    finally:
        if failed:
            log(child.worker_log_tails())
        child.stop()
        child.remove_session_dirs()
    w = results[-1]
    window = w["window"]
    before, after = w["before"]["stats"], w["after"]["stats"]
    in_window = [r for r in w["records"]
                 if window.start <= r.due < window.end]
    compiles = (after["profile"]["in_window_compiles"]
                - before["profile"]["in_window_compiles"])
    device = w["after"]["device"]
    checks = {
        "reference": bool(ready["reference"].get("ok")),
        "token_counts": all(r.ok for r in in_window),
        # by the engine's count of its own programs, and by the compile
        # cache's count of whatever else the replica compiled
        "in_window_compiles": compiles == 0
        and w["cache_events"]["misses"] == 0,
        "mesh_reshard_bytes": after["mesh_reshard_bytes"] == 0,
    }
    return {
        "ctx": {"cell": cell, "config": cfg, "traffic": traffic,
                "records": in_window, "all_records": w["records"],
                "window": window, "stats_before": before,
                "stats_after": w["closing"],
                "engine_ttft": (baseline and baseline.get("ttft"),
                                (w.get("after_flushed") or {}).get("ttft")),
                "serve_summary": (
                    baseline and baseline.get("serve_summary"),
                    (w.get("after_flushed") or {}).get("serve_summary")),
                "trace": w.get("trace"), "wall_offset": w["wall_offset"],
                "device": device, "seconds": a.seconds,
                "setup_s": window.start + w["wall_offset"] - t_process_start},
        "attempted": len(in_window),
        "failed": sum(1 for r in in_window if not r.ok),
        "checks": checks, "device": device,
    }
