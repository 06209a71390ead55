"""Pinned CPU-mesh training-step trend benchmark.

A FIXED model config + FIXED 8-device virtual CPU mesh + FIXED batch,
measured every round, so host-side regressions in the sharded training
path are visible round-over-round without a chip. The absolute number
is meaningless (CPU emulation, never a device metric); the TREND is the
signal.

Prints one JSON line: {"metric": "cpu_mesh_tokens_per_sec", ...} with
vs_baseline against the round-5 pin.

Run directly (it re-execs itself with the CPU-mesh env):
    python bench_trend.py

`python bench_trend.py --history [--dir D] [--out trend.json]` folds the
accumulated per-round bench artifacts (BENCH_r0N.json, BENCHCORE_r0N.json,
BENCH_TPU_*.json, MULTICHIP_r0N.json, ...) into ONE round-over-round
trend table — markdown to stdout, the structured JSON to --out — so a
regression is visible at a glance instead of requiring hand-diffing N
files of three different shapes (single-object, single-object-with-
parsed, and JSON-lines metric records).
"""
import json
import os
import subprocess
import sys
import time

# Pinned at round 5 on the 1-core build box (measured 2026-07-30:
# 773.7 tokens/s). Do not retune without recording a new pin; the point
# is cross-round comparability — vs_baseline ~1.0 means no regression.
BASELINE_TOKENS_PER_SEC = 773.7
_PIN_FILE_DEFAULT = 773.7
# round-5 pin for the serving dispatch-economy scenario (dispatches per
# generated token on the pinned burst; windowed decode + batched prefill)
BASELINE_SERVE_DISPATCH_PER_TOKEN = 0.1172


def _child():
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
    from ray_tpu.parallel.sharding import batch_spec, logical_sharding
    from jax.sharding import NamedSharding

    cfg = llama.LlamaConfig(
        vocab_size=2048, dim=256, n_layers=4, n_heads=8, n_kv_heads=4,
        mlp_dim=512, max_seq_len=512, dtype=jnp.float32, remat=False,
        use_flash=False)
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    batch, seq = 8, 257

    with use_mesh(mesh):
        params = llama.init(jax.random.PRNGKey(0), cfg)
        param_sh = logical_sharding(llama.logical_axes(cfg), mesh)
        params = jax.device_put(params, param_sh)
        opt = optax.adamw(1e-3)
        opt_state = opt.init(params)
        batch_sh = NamedSharding(mesh, batch_spec(mesh))
        tokens = jax.device_put(
            jnp.asarray(np.random.RandomState(0).randint(
                0, cfg.vocab_size, (batch, seq)), jnp.int32), batch_sh)

        def train_step(params, opt_state, tokens):
            def loss_fn(p):
                logits = llama.apply(p, tokens[:, :-1], cfg)
                return llama.cross_entropy_loss(logits, tokens[:, 1:])
            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state2 = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state2, loss

        # opt_state shardings must be PINNED on both sides: its mu/nu
        # leaves inherit the param shardings from opt.init, but with
        # `None` the output placement is left to XLA, which may pick a
        # different sharding than the donated input — the aliased
        # buffers then differ in per-device size and the step fails at
        # dispatch ("Expected aliased input ... to have the same
        # size"). Scalar leaves (adam's count) come back single-device;
        # replicate them onto the mesh so one sharding tree covers the
        # whole state.
        replicated = NamedSharding(mesh, jax.sharding.PartitionSpec())
        opt_sh = jax.tree.map(
            lambda a: a.sharding if isinstance(a.sharding, NamedSharding)
            else replicated, opt_state)
        opt_state = jax.device_put(opt_state, opt_sh)
        step = jax.jit(train_step,
                       in_shardings=(param_sh, opt_sh, batch_sh),
                       out_shardings=(param_sh, opt_sh, None),
                       donate_argnums=(0, 1))
        # compile + warm
        params, opt_state, loss = step(params, opt_state, tokens)
        loss.block_until_ready()
        n_steps = 5
        t0 = time.perf_counter()
        for _ in range(n_steps):
            params, opt_state, loss = step(params, opt_state, tokens)
        loss.block_until_ready()
        dt = time.perf_counter() - t0
    tps = n_steps * batch * (seq - 1) / dt
    print(json.dumps({"_trend_tokens_per_sec": tps}))


def _child_serve():
    """Pinned serving dispatch-economy scenario: device DISPATCHES per
    generated token over a fixed burst (count, not time — identical on
    any machine, so the trend is noise-free). The dispatch-minimal
    engine work (windowed decode, batched prefill, fused sampling)
    shows up here; a regression that reintroduces per-token dispatches
    moves this number ~10x."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.paged_engine import (
        PagedEngineConfig, PagedInferenceEngine,
    )
    from ray_tpu.models import llama

    cfg = PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=256),
        max_batch_size=8, page_size=8, num_pages=256,
        max_pages_per_seq=24, chunk_size=16, prefill_rows=4,
        decode_window=8)
    eng = PagedInferenceEngine(cfg, rng_seed=0)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 250, (24 if i % 2 else 48,)))
               for i in range(12)]
    eng.generate(prompts, SamplingParams(max_tokens=32))
    st = eng.stats
    disp = (st["prefill_dispatches"] + st["decode_dispatches"]
            + st["spec_dispatches"])
    print(json.dumps({"_serve_dispatch_per_token":
                      disp / max(st["tokens_out"], 1)}))


def _run_child(kind: str, result_key: str, extra_env=None) -> float:
    """Re-exec this file as a pinned child and parse one result key."""
    env = dict(os.environ)
    env["_BENCH_TREND_CHILD"] = kind
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=900,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_trend child {kind!r} failed rc={proc.returncode}:\n"
            f"{proc.stdout}\n{proc.stderr}")
    for line in reversed(proc.stdout.splitlines()):
        try:
            rec = json.loads(line)
            if result_key in rec:
                return float(rec[result_key])
        except json.JSONDecodeError:
            continue
    raise RuntimeError(f"no {result_key} line in child output: "
                       f"{proc.stdout}")


def measure_serve_dispatch() -> float:
    """Dispatches per generated token on the pinned burst (child proc)."""
    return _run_child("serve", "_serve_dispatch_per_token")


def measure() -> float:
    """Run the pinned step in a clean CPU-mesh subprocess; returns
    tokens/s."""
    flags = os.environ.get("XLA_FLAGS", "")
    extra = {}
    if "xla_force_host_platform_device_count" not in flags:
        extra["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    return _run_child("1", "_trend_tokens_per_sec", extra)


# --------------------------------------------------------------------- #
# --history: fold per-round bench artifacts into one trend table
# --------------------------------------------------------------------- #

import glob as _glob
import re as _re

_ROUND_RE = _re.compile(r"_r(\d+)")


def _metric_records(path: str):
    """Yield {"metric", "value", "vs_baseline"} records from one bench
    artifact, tolerating all four accumulated shapes: a JSON-lines file
    of metric records (BENCHCORE r05 / BENCH_TPU), a wrapper object with
    a "metrics" list (BENCHCORE r04), a single object carrying a
    "parsed" metric record (BENCH_r0N driver wrapper), and a single
    status object with no metric at all (MULTICHIP dryruns — reported as
    an ok/rc pseudo-metric so a failed dry run still shows)."""
    with open(path) as f:
        text = f.read()
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = None
    if isinstance(obj, dict):
        if isinstance(obj.get("metrics"), list):
            for rec in obj["metrics"]:
                if isinstance(rec, dict) and "metric" in rec:
                    yield rec
        elif isinstance(obj.get("parsed"), dict) \
                and "metric" in obj["parsed"]:
            yield obj["parsed"]
        elif "metric" in obj:
            yield obj
        elif "rc" in obj:
            name = os.path.basename(path).split("_r")[0].lower()
            yield {"metric": f"{name}_ok",
                   "value": 1.0 if obj.get("rc") == 0 else 0.0,
                   "vs_baseline": None}
        return
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            yield rec


def build_history(directory: str) -> dict:
    """Scan `directory` for BENCH*_r*.json / MULTICHIP*_r*.json round
    artifacts and fold them into {"rounds": [..], "metrics": {name:
    {round: {"value", "vs_baseline"}}}}. Later files for the same
    (metric, round) win (e.g. an *_interim refresh)."""
    paths = sorted(_glob.glob(os.path.join(directory, "BENCH*_r*.json"))
                   + _glob.glob(os.path.join(directory,
                                             "MULTICHIP*_r*.json")))
    metrics: dict = {}
    rounds: set = set()
    for path in paths:
        m = _ROUND_RE.search(os.path.basename(path))
        if m is None:
            continue
        rnd = int(m.group(1))
        for rec in _metric_records(path):
            rounds.add(rnd)
            metrics.setdefault(rec["metric"], {})[rnd] = {
                "value": rec.get("value"),
                "vs_baseline": rec.get("vs_baseline"),
            }
    return {"rounds": sorted(rounds), "metrics": metrics,
            "files": len(paths)}


def _fmt_cell(cell) -> str:
    if cell is None:
        return ""
    v, vb = cell.get("value"), cell.get("vs_baseline")
    if v is None:
        return "err"
    s = f"{v:.4g}" if isinstance(v, (int, float)) else str(v)
    if isinstance(vb, (int, float)):
        s += f" ({vb:.2f}x)"
    return s


def history_markdown(hist: dict) -> str:
    """Render build_history() output as one markdown table: metrics x
    rounds, cells `value (vs_baseline x)`."""
    rounds = hist["rounds"]
    lines = ["| metric | " + " | ".join(f"r{r:02d}" for r in rounds)
             + " |",
             "|---" * (len(rounds) + 1) + "|"]
    for name in sorted(hist["metrics"]):
        cells = [_fmt_cell(hist["metrics"][name].get(r)) for r in rounds]
        lines.append(f"| {name} | " + " | ".join(cells) + " |")
    return "\n".join(lines)


def history_main(argv) -> int:
    import argparse
    p = argparse.ArgumentParser(prog="bench_trend.py --history")
    p.add_argument("--history", action="store_true")
    p.add_argument("--dir", default=os.path.dirname(
        os.path.abspath(__file__)))
    p.add_argument("--out", default=None,
                   help="also write the structured JSON here")
    args = p.parse_args(argv)
    hist = build_history(args.dir)
    if not hist["metrics"]:
        print(f"no BENCH*_r*.json artifacts under {args.dir}")
        return 1
    print(history_markdown(hist))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(hist, f, indent=1)
        print(f"\nwrote {args.out} ({hist['files']} files, "
              f"{len(hist['metrics'])} metrics, "
              f"rounds {hist['rounds']})")
    return 0


def main():
    tps = measure()
    base = BASELINE_TOKENS_PER_SEC or _PIN_FILE_DEFAULT
    print(json.dumps({
        "metric": "cpu_mesh_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/s (8-dev virtual CPU mesh, pinned config)",
        "vs_baseline": round(tps / base, 3),
    }))


if __name__ == "__main__":
    kind = os.environ.get("_BENCH_TREND_CHILD")
    if kind == "serve":
        _child_serve()
    elif kind:
        _child()
    elif "--history" in sys.argv[1:]:
        sys.exit(history_main(sys.argv[1:]))
    else:
        main()
