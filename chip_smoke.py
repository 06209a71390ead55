#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip: kernels, serve, reference, train
    python chip_smoke.py --chips 4    # four chips: tp=4 engine and fsdp x tp step
    python chip_smoke.py --rehearse   # CPU, tiny shapes, interpret-mode kernels

Drives the serving path once through the entry points a user calls —
``ray_tpu.init`` -> ``serve.run(build_openai_app([LLMConfig(...)]))`` ->
HTTP completions -> ``shutdown`` — at the published Llama-3-8B widths
(dim 4096, 32 Q / 8 KV heads of 128, mlp 14336, vocab 128256, bf16) with
the depth cut to fit one 16 GB chip and seeded random weights, then checks
every served token against a paged-free, kernel-free reference, and takes
three trainer steps on bench.py's one-chip model.

One process holds a chip at a time, so THIS process never imports jax:
it launches one child per phase, sends the HTTP requests and reads what
the children print. Each phase prints one JSON line; any failure ends the
run with a non-zero code and the tail of every worker log. With no
accelerator the first phase fails and no result line is printed. The last
line of a passing run is the contract's
``{"ok": true, "device": {"platform", "kind", "count"}}``, the device as
the process that held the chip reported it.
"""
import argparse
import glob
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
BUDGET_S = 1150             # the driver allows 1200 s, compilation included
PHASES = ("kernels", "serve", "reference", "train")


def sizes(rehearse: bool) -> dict:
    """Every size of the run. Real: published 8B widths, depth cut to 16
    of 32 layers (2.1 GB embed+head + 0.44 GB/layer = 9.1 GB of weights,
    a 2 GiB KV pool of 2048 pages x 16 tokens, the rest for programs and
    initialisation), page ladder and prefill rows sized to the requests so
    a cold warm-up stays inside the time limit. Rehearsal: same control
    flow at toy widths."""
    if rehearse:
        return dict(
            toy=True, vocab=512,
            model=dict(vocab_size=512, dim=128, n_layers=2, n_heads=8,
                       n_kv_heads=4, mlp_dim=256, max_seq_len=512),
            engine=dict(max_batch_size=4, page_size=8, num_pages=128,
                        max_pages_per_seq=32, chunk_size=32, prefill_rows=2,
                        decode_window=4, page_buckets="on"),
            prompt_lens=(10, 70, 200), shared_prefix=128, shared_tail=40,
            new_tokens=8, margin=1e-3, flash_seq=64, train_steps=3,
            train_tol=1e-4, mesh_layers=2)
    return dict(
        toy=False, vocab=128256,
        model=dict(n_layers=16),
        engine=dict(max_batch_size=8, page_size=16, num_pages=2048,
                    max_pages_per_seq=96, chunk_size=128, prefill_rows=2,
                    decode_window=8, page_buckets="on"),
        prompt_lens=(40, 300, 1500), shared_prefix=1024, shared_tail=200,
        new_tokens=32,
        # bf16 logit margin: a served token must be the reference argmax
        # or within this of it. Logits of seeded random weights are
        # ~N(0,1) over 128256 entries, the top two ~0.2 apart on average,
        # and two bf16 summation orders 16 layers deep differ by a few
        # 1e-2 (first chip run: 153 of 160 tokens the argmax, worst 0.038)
        margin=0.1, flash_seq=1024, train_steps=3, train_tol=2e-2,
        mesh_layers=8)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


# --------------------------------------------------------------------------
# children: everything below this line that imports jax runs in a child
# --------------------------------------------------------------------------

def _model(sz: dict, **over):
    from ray_tpu.models import llama
    kw = {**sz["model"], **over}
    return llama.llama_tiny(**kw) if sz["toy"] else llama.llama3_8b(**kw)


def _cut(mc) -> dict:
    """The depth cut and the widths it leaves untouched, for the output."""
    return dict(model="llama3_8b", n_layers=mc.n_layers, of_layers=32,
                dim=mc.dim, heads=mc.n_heads, kv_heads=mc.n_kv_heads,
                head_dim=mc.head_dim, mlp_dim=mc.mlp_dim,
                vocab=mc.vocab_size, weights="random, PRNGKey(0)")


def _engine_cfg(sz: dict, model, **over):
    from ray_tpu.llm.paged_engine import PagedEngineConfig
    return PagedEngineConfig(model=model, **{**sz["engine"], **over})


def _device(rehearse: bool, need: int = 1) -> dict:
    """The device as JAX reports it; raises unless it is `need` TPU chips
    (a rehearsal takes the CPU it was pinned to)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearse and info["platform"] != "tpu":
        raise RuntimeError(f"no accelerator: JAX found {info}")
    if len(devs) < need:
        raise RuntimeError(f"needs {need} devices, JAX found {info}")
    return info


def _prompts(sz: dict, seed: int, vocab: int) -> list:
    """Seeded token-id prompts: one per length, plus two that share a
    page-aligned prefix (the prefix cache's case)."""
    import numpy as np
    rng = np.random.RandomState(seed)

    def ids(n):
        # skip the ByteTokenizer's BOS/EOS ids (256/257): EOS would stop
        # a request early for a reason that is not the model's
        return [int(t) for t in rng.randint(258, vocab, (n,))]
    out = [ids(n) for n in sz["prompt_lens"]]
    shared = ids(sz["shared_prefix"])
    out += [shared + ids(sz["shared_tail"]),
            shared + ids(sz["shared_tail"] + 7)]
    return out


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-9))


def _timed(fn, *args):
    """(result, first-call seconds, second-call seconds): the first call
    compiles, the second only runs."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, t1 - t0, time.perf_counter() - t1


def child_kernels(a) -> None:
    """Flash fwd/bwd and ragged prefill/verify/decode on the device at the
    smoke's widths against their jnp oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.flash_attention import flash_attention, mha_reference
    from ray_tpu.ops.ragged_paged_attention import (
        ragged_decode_attention, ragged_paged_attention,
        ragged_paged_reference,
    )
    sz = sizes(a.rehearse)
    device = _device(a.rehearse)
    mc = _model(sz)
    ec = sz["engine"]
    interpret = a.rehearse
    h, kvh, d, dt = mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.dtype
    tol = 1e-4 if dt == jnp.float32 else 2e-2
    rng = np.random.RandomState(a.seed)
    checks = {}

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape), dt)

    # flash attention forward and backward
    s = sz["flash_seq"]
    q, k, v, g = (rand(2, s, h, d), rand(2, s, kvh, d), rand(2, s, kvh, d),
                  rand(2, s, h, d))

    def loss(attn):
        def f(q, k, v):
            return (attn(q, k, v).astype(jnp.float32)
                    * g.astype(jnp.float32)).sum()
        return f
    flash = lambda q, k, v: flash_attention(       # noqa: E731
        q, k, v, True, None, mc.attn_block_q, mc.attn_block_k, interpret)
    f32 = lambda x: x.astype(jnp.float32)           # noqa: E731
    oracle = lambda q, k, v: mha_reference(         # noqa: E731
        f32(q), f32(k), f32(v), causal=True)
    out, c_s, r_s = _timed(jax.jit(flash), q, k, v)
    checks["flash_fwd"] = (_rel_err(out, jax.jit(oracle)(q, k, v)), c_s, r_s)
    grads, c_s, r_s = _timed(
        jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2))), q, k, v)
    want = jax.jit(jax.grad(loss(oracle), argnums=(0, 1, 2)))(q, k, v)
    checks["flash_bwd"] = (
        max(_rel_err(x, y) for x, y in zip(grads, want)), c_s, r_s)

    # ragged paged attention: the engine's three dispatch shapes
    page, maxp, c = ec["page_size"], ec["max_pages_per_seq"], ec["chunk_size"]
    pool = 4 * maxp
    kp, vp = rand(pool, page, kvh * d), rand(pool, page, kvh * d)
    ctx = maxp * page

    def ragged(name, rows, q_window, starts, q_lens):
        qq = rand(rows, q_window, h, d)
        bt = jnp.asarray(np.stack([rng.permutation(np.arange(1, pool))[:maxp]
                                   for _ in range(rows)]), jnp.int32)
        st = jnp.asarray(starts, jnp.int32)
        ql = jnp.asarray(q_lens, jnp.int32)
        kern = jax.jit(lambda *x: ragged_paged_attention(
            *x, interpret=interpret))
        got, c_s, r_s = _timed(kern, qq, kp, vp, bt, st, ql)
        want = jax.jit(ragged_paged_reference)(qq, kp, vp, bt, st, ql)
        err = max(_rel_err(got[r, :n], want[r, :n])
                  for r, n in enumerate(q_lens) if n)
        checks[name] = (err, c_s, r_s)

    ragged("ragged_prefill", 2, c, [0, ctx - 2 * c - 3], [c, c - 5])
    ragged("ragged_verify", 4, 5, [3, ctx // 2, ctx - 9, 0], [5, 5, 5, 0])
    rows = ec["max_batch_size"]
    lengths = jnp.asarray(
        [0, 1, page, ctx] + list(rng.randint(2, ctx, (rows - 4,))),
        jnp.int32)[:rows]
    qd = rand(rows, h, d)
    bt = jnp.asarray(np.stack([rng.permutation(np.arange(1, pool))[:maxp]
                               for _ in range(rows)]), jnp.int32)
    got, c_s, r_s = _timed(
        jax.jit(lambda *x: ragged_decode_attention(*x, interpret=interpret)),
        qd, kp, vp, bt, lengths)
    want = jax.jit(ragged_paged_reference)(
        qd[:, None], kp, vp, bt, jnp.maximum(lengths - 1, 0),
        jnp.minimum(lengths, 1))[:, 0]
    live = np.asarray(lengths) > 0
    checks["ragged_decode"] = (_rel_err(got[live], want[live]), c_s, r_s)

    bad = {k: e for k, (e, _, _) in checks.items()
           if not (np.isfinite(e) and e <= tol)}
    emit(phase="kernels", ok=not bad, device=device, interpret=interpret,
         widths=dict(heads=h, kv_heads=kvh, head_dim=d,
                     dtype=jnp.dtype(dt).name),
         tolerance=tol,
         checks={k: dict(rel_err=e, first_call_s=round(cs, 3),
                         run_s=round(rs, 5))
                 for k, (e, cs, rs) in checks.items()})
    if bad:
        raise RuntimeError(f"kernel parity failed: {bad}")


def child_serve(a) -> None:
    """The driver a user would write: init, serve.run, wait, shutdown.
    Imports jax (the model's config needs it) but never initialises a
    backend — the replica that was granted the TPU owns the chip."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.openai_api import build_openai_app
    from ray_tpu.llm.serving import LLMConfig
    from ray_tpu.serve.api import CONTROLLER_NAME
    from ray_tpu.serve.handle import DeploymentHandle

    sz = sizes(a.rehearse)
    mc = _model(sz)
    emit(phase="cut", **_cut(mc), engine=sz["engine"])
    info = ray_tpu.init(num_tpus=1)
    try:
        app = build_openai_app([LLMConfig(
            model_id="smoke", engine=_engine_cfg(sz, mc),
            tpus_per_replica=1, max_ongoing_requests=16)])
        t0 = time.perf_counter()
        serve.run(app, name="llm", http_port=a.port)
        # the replica's constructor builds the weights and compiles the
        # whole warm-up ladder; this call queues behind it
        llm = DeploymentHandle("llm:smoke", "llm",
                               ray_tpu.get_actor(CONTROLLER_NAME))
        stats = llm.options(method_name="engine_stats").remote().result(
            timeout_s=a.ready_timeout)
        from jax._src import xla_bridge
        emit(event="ready", session_dir=info["session_dir"],
             ready_s=round(time.perf_counter() - t0, 1), stats=stats,
             driver_backend_initialized=xla_bridge.backends_are_initialized())
        sys.stdin.readline()    # until the parent says stop (or is gone)
        emit(event="final", stats=llm.options(
            method_name="engine_stats").remote().result(timeout_s=120))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def child_reference(a) -> None:
    """Teacher-forced logits for prompt + served tokens with mha_reference
    (no flash kernel, no paging, no engine): every served token must be
    the reference argmax or within the stated margin of it."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    sz = sizes(a.rehearse)
    device = _device(a.rehearse)
    with open(os.path.join(OUT, "served.json")) as f:
        served = json.load(f)
    mc = dataclasses.replace(_model(sz), use_flash=False, remat=False)
    # LLMServer builds its engine with rng_seed 0
    params = llama.init(jax.random.PRNGKey(0), mc)
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in served)
    width = -(-longest // 128) * 128        # one shape, one compile

    @jax.jit
    def rows_for(params, tokens, first, picked):
        logits = llama.apply(params, tokens[None], mc)[0]       # [S, V]
        rows = jax.lax.dynamic_slice_in_dim(logits, first, picked.shape[0])
        return rows.max(axis=-1), jnp.take_along_axis(
            rows, picked[:, None], axis=-1)[:, 0]

    n_new = max(len(r["tokens"]) for r in served)
    worst, exact, total = 0.0, 0, 0
    t0 = time.perf_counter()
    for r in served:
        n = len(r["tokens"])
        toks = np.zeros((width,), np.int32)
        toks[:len(r["prompt"]) + n] = r["prompt"] + r["tokens"]
        picked = np.zeros((n_new,), np.int32)
        picked[:n] = r["tokens"]
        top, chosen = rows_for(params, toks, len(r["prompt"]) - 1, picked)
        gap = np.asarray(top - chosen)[:n]
        if not np.isfinite(gap).all():
            raise RuntimeError("non-finite reference logits")
        worst = max(worst, float(gap.max()))
        exact += int((gap == 0).sum())
        total += n
    ok = worst <= sz["margin"]
    emit(phase="reference", ok=ok, device=device, tokens=total,
         argmax_matches=exact, worst_logit_gap=worst, margin=sz["margin"],
         padded_len=width, wall_s=round(time.perf_counter() - t0, 1))
    if not ok:
        raise RuntimeError(
            f"a served token is {worst:.4f} below the reference argmax "
            f"(margin {sz['margin']})")


def _train_fn(config: dict) -> None:
    """Runs in the trainer's worker (granted the TPU): the same seeded
    steps with the flash kernels and with the jnp reference attention."""
    import dataclasses

    import jax

    from __graft_entry__ import mesh_train_losses
    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec
    if config["rehearse"]:
        cfg, batch, seq = llama.llama_tiny(max_seq_len=64), 2, 64
    else:
        from bench import tpu_config
        cfg, batch, seq = tpu_config()
    devs = jax.devices()
    report = {"device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs)},
              "model": dict(dim=cfg.dim, n_layers=cfg.n_layers,
                            n_heads=cfg.n_heads, head_dim=cfg.head_dim,
                            batch=batch, seq=seq)}
    for name, use_flash in (("flash", True), ("reference", False)):
        losses, walls = mesh_train_losses(
            MeshSpec(), devs[:1], dataclasses.replace(cfg, use_flash=use_flash),
            batch, seq + 1, steps=config["steps"])
        report[name] = {"losses": losses, "step_wall_s": walls}
    stats = devs[0].memory_stats() or {}
    report["peak_hbm_bytes"] = stats.get("peak_bytes_in_use")
    train.report(report)


def child_train(a) -> None:
    """Three steps through ray_tpu.train's trainer on one TPU worker."""
    import numpy as np

    import ray_tpu
    from ray_tpu import train
    sz = sizes(a.rehearse)
    info = ray_tpu.init(num_tpus=1)
    try:
        emit(event="session", session_dir=info["session_dir"])
        result = train.JaxTrainer(
            _train_fn,
            train_loop_config={"rehearse": a.rehearse,
                               "steps": sz["train_steps"]},
            scaling_config=train.ScalingConfig(num_workers=1,
                                               tpus_per_worker=1),
            run_config=train.RunConfig(
                name="chip_smoke", storage_path=os.path.join(OUT, "train")),
        ).fit()
        if result.error is not None:
            raise result.error
        m = result.metrics
        flash, ref = m["flash"]["losses"], m["reference"]["losses"]
        diff = max(abs(x - y) / abs(y) for x, y in zip(flash, ref))
        ok = bool(np.isfinite(flash + ref).all()) and diff <= sz["train_tol"]
        if not a.rehearse and m["device"]["platform"] != "tpu":
            raise RuntimeError(f"train worker ran on {m['device']}")
        emit(phase="train", ok=ok, width="not full width", device=m["device"],
             model=m["model"], flash=m["flash"], reference=m["reference"],
             max_rel_loss_diff=diff, tolerance=sz["train_tol"],
             peak_hbm_bytes=m["peak_hbm_bytes"])
        if not ok:
            raise RuntimeError(
                f"flash and reference losses differ by {diff:.4g} "
                f"(tolerance {sz['train_tol']}): {flash} vs {ref}")
    finally:
        ray_tpu.shutdown()


def child_mesh(a) -> None:
    """Four chips, one process: a tp=4 engine beside a one-device engine
    on the same weights and prompts, then one fsdp=2 x tp=2 train step
    beside the single-device step."""
    import dataclasses
    import gc

    import jax
    import numpy as np

    from __graft_entry__ import mesh_train_losses
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.paged_engine import PagedInferenceEngine
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec
    sz = sizes(a.rehearse)
    device = _device(a.rehearse, need=4)
    devs = jax.devices()[:4]
    mc = _model(sz, n_layers=sz["mesh_layers"])
    emit(phase="cut", **_cut(mc), engine=sz["engine"])
    params = llama.init(jax.random.PRNGKey(0), mc)
    prompts = _prompts(sz, a.seed, mc.vocab_size)
    sp = SamplingParams(max_tokens=sz["new_tokens"], temperature=0.0,
                        logprobs=1)
    interpret = a.rehearse

    def in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]

    t0 = time.perf_counter()
    one = PagedInferenceEngine(_engine_cfg(sz, mc), params=params,
                               interpret=interpret)
    want = one.generate(prompts, sp)
    one_s = time.perf_counter() - t0
    del one
    gc.collect()
    before = in_use()
    t0 = time.perf_counter()
    eng = PagedInferenceEngine(_engine_cfg(sz, mc, mesh={"tp": 4}),
                               params=params, interpret=interpret)
    # what the engine committed to each device: its shard of the weights
    # and of the KV pool (the unsharded weights are still alive in both
    # readings, so device 0 is comparable)
    spread = [None if x is None else x - y
              for x, y in zip(in_use(), before)]
    del params
    gc.collect()
    got = eng.generate(prompts, sp)
    tp_s = time.perf_counter() - t0

    # greedy tokens identical; a first divergence must be a near-tie —
    # both engines saw the same context there, so the two chosen tokens'
    # log-probabilities may differ by no more than the logit margin
    identical, worst_tie = 0, 0.0
    for w, g in zip(want, got):
        if w["token_ids"] == g["token_ids"]:
            identical += 1
            continue
        i = next(i for i, (x, y) in enumerate(
            zip(w["token_ids"], g["token_ids"])) if x != y)
        worst_tie = max(worst_tie,
                        abs(w["logprobs"][i] - g["logprobs"][i]))
    reshard = eng.stats["mesh_reshard_bytes"]
    sharded = spread[0] is None or (
        min(spread) > 0 and max(spread) < 2 * min(spread))
    ok = worst_tie <= sz["margin"] and reshard == 0 and sharded
    emit(phase="tp4_engine", ok=ok, device=device,
         mesh=dict(eng.mesh.shape), requests=len(prompts),
         token_identical=identical, worst_near_tie=worst_tie,
         margin=sz["margin"], mesh_reshard_bytes=reshard,
         engine_bytes_per_device=spread,
         one_device_wall_s=round(one_s, 1), tp4_wall_s=round(tp_s, 1),
         stats={k: eng.stats[k] for k in (
             "prefill_dispatches", "decode_dispatches", "tokens_out",
             "prefix_hits", "mesh_dispatches")})
    if not ok:
        raise RuntimeError("tp=4 engine disagrees with one device, moved "
                           "committed buffers, or sits on one device")
    del eng
    gc.collect()

    # one full train step at fsdp=2 x tp=2 with the flash kernels on,
    # against the same step on one device
    if a.rehearse:
        cfg, batch, seq = llama.llama_tiny(n_heads=4, n_kv_heads=4,
                                           max_seq_len=64), 4, 33
    else:
        from bench import tpu_config
        cfg, batch, seq = tpu_config()
        cfg, seq = dataclasses.replace(cfg, n_layers=4), seq + 1
    single, _ = mesh_train_losses(MeshSpec(), devs[:1], cfg, batch, seq,
                                  steps=2)
    meshed, _ = mesh_train_losses(MeshSpec(fsdp=2, tp=2), devs, cfg, batch,
                                  seq, steps=2)
    diff = max(abs(x - y) / abs(y) for x, y in zip(meshed, single))
    ok = bool(np.isfinite(meshed + single).all()) and diff <= sz["train_tol"]
    emit(phase="fsdp2_tp2_train", ok=ok, device=device, width="not full width",
         model=dict(dim=cfg.dim, n_layers=cfg.n_layers, n_heads=cfg.n_heads,
                    head_dim=cfg.head_dim, batch=batch, seq=seq - 1),
         mesh_losses=meshed, one_device_losses=single,
         max_rel_loss_diff=diff, tolerance=sz["train_tol"])
    if not ok:
        raise RuntimeError(f"fsdp x tp losses {meshed} vs one device {single}")


CHILDREN = {"kernels": child_kernels, "serve": child_serve,
            "reference": child_reference, "train": child_train,
            "mesh": child_mesh}


# --------------------------------------------------------------------------
# parent: launches, sends HTTP, reads results — never imports jax
# --------------------------------------------------------------------------

class Run:
    """The parent's state: deadline, children started, logs to bring back."""

    def __init__(self, a):
        self.a = a
        self.deadline = time.monotonic() + BUDGET_S
        self.procs: list = []
        self.session_dirs: list = []

    def left(self) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise TimeoutError(f"chip_smoke.py is out of its {BUDGET_S} s")
        return left

    def env(self) -> dict:
        env = dict(os.environ)
        from ray_tpu.util.compile_cache import CACHE_ENV, compile_cache_dir
        env[CACHE_ENV] = compile_cache_dir()
        if self.a.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            if self.a.chips > 1:
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "") + " --xla_force_host_platform"
                    f"_device_count={self.a.chips}").strip()
        return env

    def spawn(self, phase: str, *extra) -> subprocess.Popen:
        cmd = [sys.executable, os.path.abspath(__file__), "--child", phase,
               "--seed", str(self.a.seed), *extra]
        if self.a.rehearse:
            cmd.append("--rehearse")
        proc = subprocess.Popen(
            cmd, cwd=HERE, env=self.env(), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, start_new_session=True)
        self.procs.append(proc)
        return proc

    def read_line(self, proc, what: str) -> dict:
        """The child's next JSON line, echoed on our stdout; raises when
        the child ends or the deadline passes first."""
        box: list = []
        t = threading.Thread(target=lambda: box.append(proc.stdout.readline()),
                             daemon=True)
        t.start()
        t.join(self.left())
        if not box:
            raise TimeoutError(f"timed out waiting for {what}")
        line = box[0].strip()
        if not line:
            raise RuntimeError(
                f"{what}: child ended first (exit code {proc.wait()})")
        if not line.startswith("{"):
            print(line, flush=True)
            return self.read_line(proc, what)
        msg = json.loads(line)
        if "session_dir" in msg:
            self.session_dirs.append(msg["session_dir"])
        if "event" in msg:
            # a child's message to this process: kept whole beside the
            # logs, digested into the phase's own line
            with open(os.path.join(OUT, f"{msg['event']}.json"), "w") as f:
                f.write(line)
        else:
            print(line, flush=True)
        return msg

    def finish(self, proc, what: str) -> None:
        proc.stdin.close()
        rest = proc.stdout.read()
        try:
            code = proc.wait(timeout=self.left())
        except subprocess.TimeoutExpired:
            raise TimeoutError(f"{what}: child did not end") from None
        if rest.strip():
            print(rest.strip(), flush=True)
        if code != 0:
            raise RuntimeError(f"{what}: child exited with code {code}")

    def read_until(self, proc, what: str, **match) -> dict:
        """The child's first JSON line carrying `match`; earlier lines are
        echoed like any other."""
        msg = self.read_line(proc, what)
        while any(msg.get(k) != v for k, v in match.items()):
            msg = self.read_line(proc, what)
        return msg

    def run_child(self, child: str, last_phase: str = "") -> dict:
        """Run a child to its end; returns its `last_phase` result line
        (a child that fails exits non-zero before or after printing it)."""
        t0 = time.perf_counter()
        proc = self.spawn(child)
        msg = self.read_until(proc, child, phase=last_phase or child)
        self.finish(proc, child)
        if not msg.get("ok"):
            raise RuntimeError(f"{child} failed: {msg}")
        emit(phase=f"{child}_wall", wall_s=round(time.perf_counter() - t0, 1))
        return msg

    def stop_all(self) -> None:
        """Stop every process this run started: ask, then kill the group."""
        for proc in self.procs:
            if proc.poll() is None:
                os.killpg(proc.pid, signal.SIGTERM)
        for proc in self.procs:
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()

    def collect_logs(self, show: bool) -> None:
        """Copy every worker log into the directory the chip tool brings
        back; on failure also print the tail of each — what went wrong
        inside a replica is otherwise invisible."""
        dest = os.path.join(OUT, "logs")
        os.makedirs(dest, exist_ok=True)
        for sdir in self.session_dirs:
            for path in sorted(glob.glob(os.path.join(sdir, "worker-*.log"))):
                name = f"{os.path.basename(sdir)}-{os.path.basename(path)}"
                shutil.copy(path, os.path.join(dest, name))
                if show:
                    with open(path, errors="replace") as f:
                        tail = f.readlines()[-50:]
                    print(f"--- last lines of {path}\n" + "".join(tail),
                          file=sys.stderr, flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _complete(port: int, prompt: list, new_tokens: int, timeout: float):
    body = json.dumps({"model": "smoke", "prompt": prompt,
                       "max_tokens": new_tokens, "temperature": 0.0})
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/llm/v1/completions", data=body.encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def phase_serve(run: Run) -> dict:
    """init -> serve.run -> HTTP completions (from here) -> shutdown."""
    a = run.a
    sz = sizes(a.rehearse)
    vocab = sz["vocab"]
    t0 = time.perf_counter()
    port = _free_port()
    proc = run.spawn("serve", "--port", str(port),
                     "--ready-timeout", str(int(run.left())))
    ready = run.read_until(proc, "the replica to warm up", event="ready")
    if ready["driver_backend_initialized"]:
        raise RuntimeError("the serve driver initialised a JAX backend: it "
                           "would hold the chip its replica needs")
    warm = ready["stats"]
    prompts = _prompts(sz, a.seed, vocab)
    results: list = [None] * len(prompts)

    def send(i):
        try:
            results[i] = _complete(port, prompts[i], sz["new_tokens"],
                                   run.left())
        except Exception as e:  # noqa: BLE001 — re-raised below, per request
            results[i] = e

    t_req = time.perf_counter()
    threads = [threading.Thread(target=send, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    req_s = time.perf_counter() - t_req
    for i, r in enumerate(results):
        if isinstance(r, Exception):
            raise RuntimeError(f"request {i} failed: {r!r}") from r
    served = []
    for p, r in zip(prompts, results):
        toks = r["choices"][0]["token_ids"]
        if not toks or len(toks) > sz["new_tokens"] or \
                not all(0 <= t < vocab for t in toks) or \
                r["usage"]["prompt_tokens"] != len(p):
            raise RuntimeError(f"malformed completion: {r}")
        served.append({"prompt": p, "tokens": toks})
    with open(os.path.join(OUT, "served.json"), "w") as f:
        json.dump(served, f)

    proc.stdin.write("stop\n")
    proc.stdin.flush()
    final = run.read_until(proc, "the replica's final stats",
                           event="final")["stats"]
    run.finish(proc, "serve")
    prof, dev = final["profile"], final["device"]
    tokens = sum(len(s["tokens"]) for s in served)
    peak = [m and m.get("peak_bytes_in_use") for m in final["memory"]]
    problems = []
    if not a.rehearse and dev["platform"] != "tpu":
        problems.append(f"the replica ran on {dev}")
    if prof["in_window_compiles"] != 0:
        problems.append(f"{prof['in_window_compiles']} compiles under traffic")
    if final["tokens_out"] != tokens:
        problems.append(f"engine counted {final['tokens_out']} tokens, "
                        f"HTTP returned {tokens}")
    for counter in ("prefill_dispatches", "decode_dispatches", "prefix_hits"):
        if final[counter] <= 0:
            problems.append(f"{counter} is {final[counter]}")
    emit(phase="serve", ok=not problems, device=dev,
         path="init -> serve.run(build_openai_app) -> HTTP -> shutdown",
         requests=len(served), prompt_tokens=[len(p) for p in prompts],
         tokens_served=tokens, ready_s=ready["ready_s"],
         requests_wall_s=round(req_s, 2),
         warmup_compile_s=warm["profile"]["compile_s"],
         programs_compiled=warm["profile"]["compiles"],
         in_window_compiles=prof["in_window_compiles"],
         execute_s=prof["execute_s"], steps=prof["steps"],
         dispatches=prof["dispatches"], prefix_hits=final["prefix_hits"],
         prefix_tokens_saved=final["prefix_tokens_saved"],
         peak_hbm_bytes=peak, compile_cache=final["compile_cache"],
         wall_s=round(time.perf_counter() - t0, 1))
    if problems:
        raise RuntimeError("; ".join(problems))
    return dev


def main_parent(a) -> int:
    os.makedirs(OUT, exist_ok=True)
    run = Run(a)
    failed = True
    try:
        if a.chips == 4:
            device = run.run_child("mesh", "fsdp2_tp2_train")["device"]
        else:
            device = None
            for phase in a.phases:
                if phase == "serve":
                    device = phase_serve(run)
                else:
                    dev = run.run_child(phase)["device"]
                    device = device or dev
        failed = False
    finally:
        run.stop_all()
        run.collect_logs(show=failed)
    emit(phase="parent", jax_imported="jax" in sys.modules)
    emit(ok=True, device=device)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the four-chip phase and nothing else")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: JAX_PLATFORMS=cpu, tiny shapes, "
                         "interpret-mode kernels")
    ap.add_argument("--phases", type=lambda s: tuple(s.split(",")),
                    default=PHASES, help="subset of " + ",".join(PHASES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--child", choices=tuple(CHILDREN), help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--ready-timeout", type=float, default=BUDGET_S,
                    help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.child:
        # a SIGTERM from the parent unwinds through the finally blocks
        signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
        sys.path.insert(0, HERE)
        CHILDREN[a.child](a)
        return 0
    if "reference" in a.phases and "serve" not in a.phases:
        ap.error("the reference phase checks what the serve phase returned")
    sys.path.insert(0, HERE)
    return main_parent(a)


if __name__ == "__main__":
    sys.exit(main())
