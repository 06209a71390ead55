"""The pre-training job of the training cells, written as the user code it
is in this framework: the trainer starts the worker and grants it the chip,
the user owns the loop. The step is the one ``__graft_entry__.
mesh_train_losses`` builds (params placed by ``logical_sharding``, donated
state, ``optax.adamw``, ``llama.cross_entropy_loss``), fed a new seeded
batch from the host at every step.

Steps are dispatched freely and synced (the loss is read) at each report.
The window runs from the sync that follows the warm-up to the first sync at
or after ``seconds``.
"""
from __future__ import annotations

import time


def train_fn(config: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.spec import resolve
    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
    from ray_tpu.parallel.sharding import batch_spec, logical_sharding
    from ray_tpu.util.compile_cache import enable_compile_cache

    t_start = time.perf_counter()
    enable_compile_cache()
    devs = jax.devices()
    chips, tc, model = config["chips"], config["train"], config["model"]
    if not config["rehearse"] and (devs[0].platform != "tpu"
                                   or len(devs) < chips):
        raise RuntimeError(f"the cell needs {chips} TPU chip(s); JAX found "
                           f"{len(devs)} x {devs[0].platform}")
    devs = devs[:chips]
    builder = resolve(config["builder"])(
        model, remat=tc["remat"], remat_policy=tc["remat_policy"])
    cfg = builder.cfg
    ref = resolve(config["reference"])(model)
    batch, seq, every = tc["batch"], tc["seq"], tc["report_every"]
    data = np.random.default_rng([config["seed"], 2])

    def next_batch():
        return data.integers(0, cfg.vocab_size, (batch, seq + 1),
                             dtype=np.int32)

    mesh = build_mesh(MeshSpec(**(config["mesh"] or {})), devices=devs)
    with use_mesh(mesh):
        param_sh = logical_sharding(llama.logical_axes(cfg), mesh)
        batch_sh = NamedSharding(mesh, batch_spec(mesh))
        repl = NamedSharding(mesh, PartitionSpec())
        params = builder.init_params(config["seed"], param_sh)
        t_weights = time.perf_counter()

        # -- against the plain reference, before the optimizer's state
        # takes the memory: the loss of the first batch, and the gradient
        # norm on a slice of it
        def prog_loss(p, tokens):
            return llama.cross_entropy_loss(
                llama.apply(p, tokens[:, :-1], cfg), tokens[:, 1:])

        def grad_norm(loss_fn):
            return jax.jit(lambda p, t: optax.global_norm(jax.tree.map(
                lambda g: g.astype(jnp.float32), jax.grad(loss_fn)(p, t))))
        first = next_batch()
        piece = first[:1, :tc["grad_check_tokens"] + 1]
        ref_loss = float(jax.jit(ref.loss)(params, first))
        ref_gnorm = float(grad_norm(ref.loss)(params, piece))
        prog_gnorm = float(grad_norm(prog_loss)(params, piece))
        t_reference = time.perf_counter()

        opt = optax.adamw(tc["learning_rate"])
        opt_state = jax.tree.map(
            lambda x: x if isinstance(x.sharding, NamedSharding)
            else jax.device_put(x, repl), opt.init(params))

        def train_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(prog_loss)(params, tokens)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        step = jax.jit(train_step, in_shardings=(param_sh, None, batch_sh),
                       out_shardings=(param_sh, None, None),
                       donate_argnums=(0, 1))

        def run_steps(n, tokens=None):
            nonlocal params, opt_state
            for _ in range(n):
                with jax.profiler.TraceAnnotation("train_step"):
                    params, opt_state, loss = step(
                        params, opt_state,
                        next_batch() if tokens is None else tokens)
                tokens = None
            return float(loss)          # the sync

        loss0 = run_steps(1, first)     # compiles (or loads) the step
        run_steps(2)
        t_open = time.perf_counter()
        open_wall = time.time()
        steps, losses, trace, traced_s = 0, [], None, 0.0
        while True:
            losses.append(run_steps(every))
            steps += every
            now = time.perf_counter()
            with jax.profiler.TraceAnnotation("report"):
                train.report({"step": steps, "loss": losses[-1]})
            if now - t_open - traced_s >= config["seconds"]:
                break
            if config["trace"] and trace is None and \
                    now - t_open >= 0.4 * config["seconds"]:
                # a traced run is not measured end to end: its slice sits
                # between two syncs of its own
                from benchmarks.reduce import xplane
                t0 = time.perf_counter()
                jax.profiler.start_trace(config["trace_dir"])
                with jax.profiler.TraceAnnotation(
                        f"bench_clock_sync.{time.time_ns()}"):
                    time.sleep(0.001)
                run_steps(3)
                jax.profiler.stop_trace()
                trace = xplane.reduce_dir(config["trace_dir"], chips=chips)
                trace["window_s"] = trace.get("window_s") or (
                    time.perf_counter() - t0)
                trace["steps"] = 3
                traced_s = time.perf_counter() - t0
        window_s = now - t_open - traced_s
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        train.report({
            "final": True, "steps": steps, "window_s": window_s,
            "tokens": steps * batch * seq, "open_wall": open_wall,
            "loss0": loss0, "losses": losses, "ref_loss": ref_loss,
            "ref_grad_norm": ref_gnorm, "prog_grad_norm": prog_gnorm,
            "trace": trace,
            "split": {"weights_s": t_weights - t_start,
                      "reference_s": t_reference - t_weights,
                      "compile_and_warm_s": t_open - t_reference},
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind,
                       "count": len(jax.devices()),
                       "memory_peak_bytes": max(
                           (p for p in peaks if p), default=0)}})
