"""The deployment the serving cells run: the program's ``LLMServer`` with
the hooks only the process that holds the chip can offer.

``BenchLLMServer`` changes none of ``LLMServer``'s request paths. It builds
the engine's weights on the device from the seed (``LLMServer`` would
draw them from ``PRNGKey(0)`` leaf by leaf), and adds ``trace_start`` /
``trace_stop`` (``jax.profiler`` around a steady slice of the window, reduced
here because the parent never imports jax), ``reference_check`` and
``device_info``. The engine is built and warmed by ``LLMServer`` itself, and
the app is what ``build_openai_app`` returns with the class swapped, so a
change to the program's warm-up or deployment options moves ``setup_s``.
"""
from __future__ import annotations

import time

from ray_tpu.llm.serving import LLMConfig, LLMServer

MODEL_ID = "bench"
# the reference check's document, as a share of the room the context leaves
CONTEXT_SHARE = 0.97


class BenchLLMServer(LLMServer):
    def __init__(self, cfg: LLMConfig, bench: dict):
        self._bench = bench
        self._split = {}
        super().__init__(cfg, None)

    # -- engine construction: weights from the seed, on the device --------

    def _build_engine(self, params):
        """The program's own assembly and warm-up (``LLMServer`` picks the
        engine class and the warm-up modes from the config), given weights
        made here from the seed."""
        import jax

        from benchmarks.spec import resolve
        from ray_tpu.util.compile_cache import enable_compile_cache
        b = self._bench
        devs = jax.devices()
        if not b["rehearse"] and (devs[0].platform != "tpu"
                                  or len(devs) < b["chips"]):
            raise RuntimeError(
                f"the cell needs {b['chips']} TPU chip(s); JAX found "
                f"{len(devs)} x {devs[0].platform}")
        enable_compile_cache()
        builder = resolve(b["builder"])(b["model"])
        mesh = self.engine_cfg.mesh
        t0 = time.perf_counter()
        params = builder.init_params(b["seed"], mesh and builder.mesh_shardings(
            mesh, devs[:b["chips"]]))
        t1 = time.perf_counter()
        eng = super()._build_engine(params)
        self._split = {"weights_s": t1 - t0,
                       "ladder_s": time.perf_counter() - t1}
        return eng

    # -- hooks --------------------------------------------------------------

    def device_info(self) -> dict:
        import jax
        devs = jax.devices()[:self._bench["chips"]]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(jax.devices()),
                "memory_peak_bytes": max((p for p in peaks if p), default=0),
                "memory_limit_bytes": (devs[0].memory_stats() or {}).get(
                    "bytes_limit"),
                "split": self._split,
                # what a cached token costs, from the pools as the engine
                # built them: whatever the layers hold (keys and values, a
                # latent, nothing at all), padded as it is stored
                "cache_bytes_per_token": (
                    self.engine.page_nbytes // self.engine_cfg.page_size)}

    def trace_start(self, out_dir: str) -> dict:
        """Starts the profiler in this process, the one that holds the chip.

        Without the profiler's Python tracer: with it every Python call of
        the replica's threads is an event, the stepping thread runs
        1.3-2.4x slower between dispatches, and the slice's idle share and
        host shares measure the tracer (ledger, PR 32: the OLMoE cell's idle
        share 86 traced where the untraced counters give a slot occupancy
        of 95). The host tracer stays at its level, so the program's
        ``TraceAnnotation``s (``rtpu.*``) and this file's still land."""
        import jax
        self._trace_dir = out_dir
        self._trace_t0 = time.perf_counter()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=options)
        # the trace counts from the session's start: note the wall clock
        # inside an annotation, so that the reduction can place the
        # client's spans (another process) on the trace's clock
        with jax.profiler.TraceAnnotation(
                f"bench_clock_sync.{time.time_ns()}"):
            time.sleep(0.001)
        # the counters at the slice's two ends: a reader that divides a
        # counter by a kernel's traced time takes both from these seconds
        self._trace_stats = self.engine_stats()
        return {}

    def trace_stop(self) -> dict:
        """Stops the profiler and reduces the trace here (the parent never
        imports jax); returns the reduction with ``engine_stats()`` at the
        slice's two ends (``stats_before`` / ``stats_after``), not the trace.
        The closing snapshot is taken before anything else: the slice ends
        where the window does, stopping the profiler takes this process
        20-54 s, and the window's counters have to close on time."""
        import os

        import jax

        from benchmarks.reduce import xplane
        after = self.engine_stats()
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        xplane_bytes = os.path.getsize(xplane.find_xplane(self._trace_dir))
        out = xplane.reduce_dir(self._trace_dir, chips=self._bench["chips"])
        out["window_s"] = out.get("window_s") or t0 - self._trace_t0
        out.update(stats_before=self._trace_stats, stats_after=after,
                   # what the slice costs the replica once it has ended
                   stop_s=t1 - t0, reduce_s=time.perf_counter() - t1,
                   xplane_bytes=xplane_bytes)
        return out

    def reference_check(self, spec: dict, context_tokens: int) -> dict:
        """Serves seeded prompts through the normal request path, then
        teacher-forces prompt + served tokens through the plain reference:
        every served token must be the reference's argmax or within
        ``logit_margin`` of it.

        Two groups of prompts: ``samples`` short distinct ones, and one
        document as long as the cell's traffic gets (CONTEXT_SHARE of what
        its ``max_context_tokens`` leave beside suffix and answer) asked
        twice with a new suffix each time, so that the second ask is a
        prefix-cache hit (required where the cache is on) decoded over a
        full-length cache.

        The margin: logits of seeded random weights are about N(0, 1) over
        the vocabulary with the top two ~0.2 apart on average; two bf16
        summation orders 16-32 layers deep differ by a few 1e-2 (PR 22
        measured 0.038 worst on the chip at 16 layers), and a path
        computed in a lower precision than bf16 moves logits by more than
        0.1. Logits are compared and not tokens, because with random
        weights the largest logit changes on rounding."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.spec import resolve
        b = self._bench
        rng = np.random.default_rng([b["seed"], 0xC0FFEE])
        vocab = b["model"]["vocab_size"]
        n_new = spec["new_tokens"]
        draw = lambda n: rng.integers(0, vocab, int(n)).tolist()  # noqa: E731
        lo, hi = spec["prompt_tokens"]
        suffix = spec["context_suffix_tokens"]
        doc = draw(CONTEXT_SHARE * (context_tokens - suffix - n_new))
        groups = {"short": [draw(n) for n in
                            np.linspace(lo, hi, spec["samples"])],
                  "context": [doc + draw(suffix) for _ in range(2)]}
        t0 = time.perf_counter()
        served, saved = {}, {}
        for name, prompts in groups.items():
            before = self.engine.stats["prefix_tokens_saved"]
            served[name] = [self.completions(
                {"prompt": p, "max_tokens": n_new, "temperature": 0.0}
            )["choices"][0]["token_ids"] for p in prompts]
            saved[name] = self.engine.stats["prefix_tokens_saved"] - before
        t1 = time.perf_counter()
        ref = resolve(b["reference"])(b["model"])

        @jax.jit
        def gaps(params, tokens, first, picked):
            rows = ref.head(params, jax.lax.dynamic_slice_in_dim(
                ref.hidden(params, tokens), first, n_new))
            chosen = jnp.take_along_axis(rows, picked[:, None], -1)[:, 0]
            return rows.max(-1) - chosen

        out = {"margin": spec["logit_margin"], "tokens": 0,
               "argmax_matches": 0, "worst_logit_gap": {},
               "context_prompt_tokens": len(groups["context"][0]),
               "context_prefix_tokens_saved": saved["context"]}
        for name, prompts in groups.items():
            # one width to a group: two compiles in all
            width = -(-(max(map(len, prompts)) + n_new) // 128) * 128
            worst = 0.0
            for p, toks in zip(prompts, served[name]):
                if len(toks) != n_new:
                    return {"ok": False, "why": f"served {len(toks)} tokens, "
                            f"asked {n_new}"}
                seq = np.zeros((width,), np.int32)
                seq[:len(p) + n_new] = p + toks
                gap = np.asarray(gaps(self.engine.params, seq, len(p) - 1,
                                      np.asarray(toks, np.int32)))
                if not np.isfinite(gap).all():
                    return {"ok": False,
                            "why": "non-finite reference logits"}
                worst = max(worst, float(gap.max()))
                out["argmax_matches"] += int((gap == 0).sum())
                out["tokens"] += n_new
            out["worst_logit_gap"][name] = worst
        cached = (not self.engine_cfg.enable_prefix_caching
                  or saved["context"] > 0)
        out["ok"] = cached and max(
            out["worst_logit_gap"].values()) <= spec["logit_margin"]
        if not cached:
            out["why"] = "the repeated document was not a prefix-cache hit"
        out.update(serve_s=t1 - t0, reference_s=time.perf_counter() - t1)
        return out


def build_app(llm_cfg: LLMConfig, bench: dict):
    """``build_openai_app([llm_cfg])`` — the program's router, proxy and
    deployment options — with BenchLLMServer in LLMServer's place."""
    import dataclasses

    from ray_tpu.llm.openai_api import build_openai_app
    app = build_openai_app([llm_cfg])
    (llm,) = app.ingress.children()
    llm.spec = dataclasses.replace(llm.spec, func_or_class=BenchLLMServer,
                                   init_args=(llm_cfg, bench))
    return app
