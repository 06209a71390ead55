"""Attention-kernel microbench: paged attention dispatch across the
three program families (prefill chunk / verify window / decode), at a
LONG block table (the regime ROADMAP item 1 targets).

Prints one JSON line per metric (folded into ``bench_trend.py
--history`` like every BENCH*_r* artifact):

- ``kernel_<family>_full_ms`` / ``kernel_<family>_bucket_ms`` — the
  plain-JAX fallback's per-dispatch wall at the full block-table width
  vs the power-of-two page bucket covering the live length
  (paged_engine._page_bucket). ``vs_baseline`` on the bucket metric is
  full/bucket (>1 = bucketing wins). CPU-meaningful: the fallback IS
  the CPU path.
- ``kernel_prefill_ttft_ratio`` — engine-level: median time-to-first-
  token for a short prompt on a max_pages=64 engine, page_buckets off
  vs auto, interleaved in-process (ABAB) so host noise hits both arms.
- on TPU additionally ``ragged_kernel_<family>_ms`` — the real Pallas
  ragged kernel per dispatch (on CPU the kernel only runs under
  interpret=True, whose wall measures the interpreter, so it is
  skipped).

``--quick`` shrinks reps to a smoke (wired as a slow-marked test).
"""
import json
import statistics
import sys
import time

import numpy as np

from bench import require_accelerator


def _timed_ms(fn, reps):
    """Median per-call wall (ms); fn must block until the result is
    materialized."""
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def _emit(metric, value, unit, vs=None):
    from bench import device_info
    print(json.dumps({"metric": metric, "value": round(float(value), 4),
                      "unit": unit, "vs_baseline":
                      None if vs is None else round(float(vs), 4),
                      "device": device_info()}))


def _family_benches(quick: bool, on_tpu: bool):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = llama.llama_tiny(vocab_size=512, dim=128, n_layers=2, n_heads=8,
                           n_kv_heads=4, mlp_dim=256, max_seq_len=1024)
    page, maxp, P = 16, 64, 128
    live_pages = 8                       # true length: 8 of 64 pages
    params = llama.init(jax.random.PRNGKey(0), cfg)
    caches = llama.init_paged_cache(cfg, P, page)
    rng = np.random.RandomState(0)
    reps = 5 if quick else 15
    bt_full = np.zeros((maxp,), np.int32)
    bt_full[:live_pages] = rng.permutation(np.arange(1, live_pages + 1))

    impl = "pallas ragged kernel" if on_tpu else "jnp fallback"

    def run_family(family, width):
        bt = jnp.asarray(bt_full[:width][None])
        if family == "prefill":
            chunk = jnp.asarray(rng.randint(1, 500, (1, 2 * page)),
                                jnp.int32)
            start = jnp.int32((live_pages - 2) * page)

            @jax.jit
            def fn(c):
                lg, _, _ = llama.prefill_paged_chunk(
                    params, chunk, c, bt[0], start, cfg, page_size=page)
                return lg
        elif family == "verify":
            toks = jnp.asarray(rng.randint(1, 500, (1, 8)), jnp.int32)
            starts = jnp.asarray([(live_pages - 1) * page + 2], jnp.int32)

            @jax.jit
            def fn(c):
                lg, _, _ = llama.verify_paged_rows(
                    params, toks, c, bt, starts, cfg, page_size=page)
                return lg
        else:                            # decode
            toks = jnp.asarray(rng.randint(1, 500, (1, 1)), jnp.int32)
            lens = jnp.asarray([(live_pages - 1) * page + 3], jnp.int32)

            @jax.jit
            def fn(c):
                lg, _, _ = llama.decode_paged(
                    params, toks, c, bt, lens, cfg, page_size=page)
                return lg
        np.asarray(fn(caches))           # compile outside the timed region
        return _timed_ms(lambda: np.asarray(fn(caches)), reps)

    for family in ("prefill", "verify", "decode"):
        full = run_family(family, maxp)
        bucket = run_family(family, live_pages)
        _emit(f"kernel_{family}_full_ms", full, f"ms/dispatch {impl}, "
              f"64-page table, {live_pages} live")
        _emit(f"kernel_{family}_bucket_ms", bucket,
              f"ms/dispatch {impl}, {live_pages}-page bucket",
              vs=full / bucket if bucket else None)
    if on_tpu:
        from ray_tpu.ops.ragged_paged_attention import ragged_paged_attention
        q = jnp.asarray(rng.randn(1, 2 * page, cfg.n_heads, cfg.head_dim),
                        jnp.float32)
        kp, vp = caches[0]["k"], caches[0]["v"]
        bt = jnp.asarray(bt_full[None])
        starts = jnp.asarray([(live_pages - 2) * page], jnp.int32)
        qlens = jnp.asarray([2 * page], jnp.int32)
        fn = jax.jit(lambda: ragged_paged_attention(
            q, kp, vp, bt, starts, qlens))
        np.asarray(fn())
        _emit("ragged_kernel_prefill_ms",
              _timed_ms(lambda: np.asarray(fn()), reps),
              "ms/call Pallas ragged kernel, 32q x 8 live pages")


def _engine_ttft(quick: bool):
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.paged_engine import (
        PagedEngineConfig, PagedInferenceEngine,
    )
    from ray_tpu.models import llama

    def mk(buckets):
        return PagedInferenceEngine(PagedEngineConfig(
            model=llama.llama_tiny(vocab_size=512, max_seq_len=1024),
            max_batch_size=2, page_size=16, num_pages=256,
            max_pages_per_seq=64, chunk_size=32, page_buckets=buckets),
            rng_seed=0)

    rng = np.random.RandomState(1)
    prompt = list(rng.randint(1, 500, (24,)))
    sp = SamplingParams(max_tokens=1)
    eng_off, eng_on = mk("off"), mk("auto")
    for e in (eng_off, eng_on):          # compile both arms' programs
        e.generate([prompt], sp)
    reps = 3 if quick else 9
    offs, ons = [], []
    for _ in range(reps):                # interleaved: noise hits both
        t0 = time.perf_counter()
        eng_off.generate([prompt], sp)
        offs.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        eng_on.generate([prompt], sp)
        ons.append(time.perf_counter() - t0)
    off_med, on_med = statistics.median(offs), statistics.median(ons)
    _emit("kernel_prefill_ttft_full_ms", off_med * 1e3,
          "ms short-prompt TTFT, 64-page table, buckets off")
    _emit("kernel_prefill_ttft_ratio", off_med / on_med,
          "buckets-off / buckets-auto median TTFT (>1 = bucketing wins)",
          vs=off_med / on_med)


def main(quick: bool = False):
    on_tpu = require_accelerator()["platform"] == "tpu"
    _family_benches(quick, on_tpu)
    _engine_ttft(quick)


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
