"""The driver a user of the serving stack would write, as the child of
``benchmarks.run``: ``ray_tpu.init`` -> ``serve.run`` -> wait for commands
on stdin -> ``serve.shutdown``. It imports jax (the model's config needs
it) but never initialises a backend: the replica that is granted the
chips owns them."""
from __future__ import annotations

import argparse
import json
import signal
import sys
import time


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    import ray_tpu
    from benchmarks.serve_app import MODEL_ID, build_app
    from benchmarks.spec import Cell, resolve
    from benchmarks.tokenizer import OneCharTokenizer
    from ray_tpu import serve
    from ray_tpu.llm.paged_engine import PagedEngineConfig
    from ray_tpu.llm.serving import LLMConfig
    from ray_tpu.serve.api import CONTROLLER_NAME
    from ray_tpu.serve.handle import DeploymentHandle

    cell = Cell(a.workload)
    sizes = cell.sizes(a.rehearse)
    cfg, traffic = sizes["config"], sizes["traffic"]
    engine = dict(cfg["engine"])
    # the cell narrows the context ceiling to what its traffic can reach:
    # that field bounds the warm-up ladder, and every run pays the ladder
    page = engine["page_size"]
    engine["max_pages_per_seq"] = min(
        engine["max_pages_per_seq"],
        -(-int(traffic["max_context_tokens"]) // page))
    builder = resolve(cfg["builder"])(cfg)
    ecfg = PagedEngineConfig(
        model=builder.cfg, mesh=cfg.get("mesh"),
        tokenizer=OneCharTokenizer(cfg["vocab_size"]), **engine)
    llm_cfg = LLMConfig(
        model_id=MODEL_ID, engine=ecfg, tpus_per_replica=cell.chips,
        max_ongoing_requests=cfg["serve"]["max_ongoing_requests"],
        warmup=cfg["serve"]["warmup"],
        warmup_sampled=cfg["serve"]["warmup_sampled"])
    bench = {"seed": a.seed, "chips": cell.chips, "rehearse": a.rehearse,
             "builder": cfg["builder"], "reference": cfg["reference"],
             "model": {k: v for k, v in cfg.items()
                       if not isinstance(v, dict)}}
    info = ray_tpu.init(num_tpus=cell.chips)
    try:
        emit(event="session", session_dir=info["session_dir"],
             engine=engine)
        t0 = time.perf_counter()
        serve.run(build_app(llm_cfg, bench), name="llm", http_port=a.port)
        llm = DeploymentHandle(f"llm:{MODEL_ID}", "llm",
                               ray_tpu.get_actor(CONTROLLER_NAME))

        def call(method, *args, timeout=300.0):
            return llm.options(method_name=method).remote(*args).result(
                timeout_s=timeout)
        # the replica's constructor makes the weights and compiles the
        # warm-up ladder; this call queues behind it
        device = call("device_info", timeout=1100.0)
        replica_s = time.perf_counter() - t0
        check = call("reference_check", cfg["reference_check"],
                     int(traffic["max_context_tokens"]), timeout=600.0)
        from jax._src import xla_bridge
        emit(event="ready", device=device, reference=check,
             replica_ready_s=replica_s,
             driver_backend_initialized=xla_bridge.backends_are_initialized())

        for line in sys.stdin:
            msg = json.loads(line)
            cmd = msg["cmd"]
            if cmd == "stats":
                time.sleep(msg.get("wait_s", 0.0))
                summary = serve.metrics_summary()
                emit(event="stats", stats=call("engine_stats"),
                     device=call("device_info"),
                     ttft=summary.get("ttft"),
                     # the front path's own series, beside the engine's
                     serve_summary={k: summary[k] for k in (
                         "router_wait", "replica_latency", "handles",
                         "requests") if k in summary},
                     t=time.time())
            elif cmd == "trace_start":
                emit(event="trace_start", **call("trace_start", msg["dir"]))
            elif cmd == "trace_stop":
                emit(event="trace_stop", trace=call("trace_stop",
                                                    timeout=600.0))
            elif cmd == "stop":
                break
        emit(event="stop")
    except BaseException as e:  # noqa: BLE001 — reported, then re-raised
        emit(event="error", error=f"{type(e).__name__}: {e}"[:2000])
        raise
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
