"""Llama training-step MFU on the attached accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "device", ...}. MFU is
the model FLOPs of a step over its wall time and the chip's peak from
the one peaks table (parallel.mesh.DEVICE_PEAKS).

Sized for one v5e chip (16 GiB HBM): ~560M-param llama, bf16 weights and
adam moments, batch 8 x seq 1024, remat on. Needs a chip: with none it
fails, unless JAX_PLATFORMS=cpu is set explicitly, which runs a tiny
model whose number says nothing about a device — the line then names the
CPU it ran on.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def tpu_config():
    """(model, batch, seq) of the one-chip training model: dim 1536 feeds
    the MXU better than dim 1024, and save_attn remat (the default) keeps
    attention out of the recompute path. ~8 GB of params + optimizer
    state in HBM, activations remat'd."""
    from ray_tpu.models import llama
    return llama.LlamaConfig(
        vocab_size=32000, dim=1536, n_layers=14, n_heads=16,
        n_kv_heads=8, mlp_dim=6144, max_seq_len=1024,
        dtype=jnp.bfloat16, remat=True, use_flash=True,
        attn_block_q=512, attn_block_k=512), 8, 1024


def device_info() -> dict:
    """The device as JAX reports it; every result line carries it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_accelerator() -> dict:
    """device_info(), or SystemExit when JAX found no accelerator and the
    CPU was not asked for by name: a benchmark never falls back."""
    info = device_info()
    if info["platform"] == "cpu" and \
            os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            "no accelerator found; set JAX_PLATFORMS=cpu to run the CPU "
            "smoke configuration on purpose")
    return info


def trace_arg(argv) -> "str | None":
    """Shared --trace <out.json> parsing for the bench CLIs."""
    if "--trace" in argv:
        i = argv.index("--trace")
        if i + 1 < len(argv):
            return argv[i + 1]
    return None


def flight_report(trace_out, trace_t0) -> None:
    """Shared bench --trace tail: export the flight recording of the
    measured section (cluster-stitched when a runtime is up, local ring
    otherwise) and print the wait/dispatch breakdown JSON line next to
    the throughput numbers. No-op unless --trace was given."""
    if not trace_out:
        return
    from ray_tpu.core import flight
    from ray_tpu.core import runtime as rt_mod
    rt = rt_mod.get_runtime_if_exists()
    rep = flight.capture_report(rt, trace_t0, trace_out)
    print(json.dumps({
        "metric": "flight_trace",
        "out": trace_out,
        "events": rep["events"],
        "wait_s": rep["wait_s"],
        "counts": rep["counts"],
    }))


def main():
    import optax
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import tpu_topology

    device = require_accelerator()
    on_tpu = device["platform"] == "tpu"
    if on_tpu:
        cfg, batch, seq = tpu_config()
    else:  # explicit CPU smoke configuration — no device number
        cfg = llama.llama_tiny(n_layers=2, dim=64, mlp_dim=128,
                               max_seq_len=128)
        batch, seq = 2, 128
    topo = tpu_topology(jax.devices()[:1])
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, seq + 1)), jnp.int32)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(3e-4, weight_decay=0.0)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state, tokens):
        def loss_fn(p):
            logits = llama.apply(p, tokens[:, :-1], cfg)
            return llama.cross_entropy_loss(logits, tokens[:, 1:])
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    t0 = time.perf_counter()
    for _ in range(3):      # compile + 2 stabilization steps
        params, opt_state, loss = train_step(params, opt_state, tokens)
    loss.block_until_ready()
    setup_s = time.perf_counter() - t0

    n_steps = 10 if on_tpu else 3
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt_state, loss = train_step(params, opt_state, tokens)
    loss.block_until_ready()    # sync point inside the timed region
    dt = (time.perf_counter() - t0) / n_steps

    tokens_per_step = batch * seq
    flops_per_step = cfg.flops_per_token(seq) * tokens_per_step
    mfu = flops_per_step / dt / topo.peak_flops_bf16
    print(json.dumps({
        "metric": "llama_train_mfu",
        # a CPU run checks that the step runs; it measures no device
        "value": round(float(mfu), 4) if on_tpu else None,
        "unit": "fraction_of_peak_bf16",
        "tokens_per_s": round(tokens_per_step / dt) if on_tpu else None,
        "loss": round(float(loss), 3),
        "compile_and_warmup_s": round(setup_s, 2) if on_tpu else None,
        "device": device,
    }))


if __name__ == "__main__":
    main()
