"""The paged forwards' q / k / v projections (models/llama.py `_qkv` with
its ``fence``: the flat projections finished before they are cut into
heads) against the training forward, which takes `_qkv` without it.

Two sequences go through `prefill_paged_rows` chunk by chunk as two rows
of one dispatch, then `decode_paged`, then `verify_paged_rows`, and every
logit that comes back is held to `apply` on the same tokens — for a dense
config, a QK-norm mixture of experts and a config with sliding layers,
each with and without a LoRA slot table (the deltas are added between the
projections and the fence; `apply` gets them merged into its weights).
Both sides compute in float32 and differ in the order of their sums
alone: the tolerance is tests/test_window_layers.py's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import lora
from ray_tpu.llm.multilora.slots import AdapterSlotTable
from ray_tpu.models import llama

TOL = 1e-4
PAGE, CHUNK, WINDOW = 8, 16, 16
PROMPT, STEPS, S1 = 2 * CHUNK + 5, 3, 3

CONFIGS = {
    "dense": dict(),
    "qk_norm_moe": dict(qk_norm=True, moe_experts=4, moe_top_k=2),
    "sliding": dict(n_layers=3, layer_types=("sliding", "sliding", "full"),
                    sliding_window=WINDOW),
}


def _adapters(cfg):
    return [lora.random_adapter(jax.random.PRNGKey(7), cfg, rank=4,
                                alpha=64.0, targets=("wq", "wv", "lm_head")),
            lora.random_adapter(jax.random.PRNGKey(9), cfg, rank=2,
                                alpha=32.0, targets=("wq", "wk", "wv", "wo"))]


@pytest.mark.parametrize("with_lora", [False, True], ids=["base", "lora"])
@pytest.mark.parametrize("name", list(CONFIGS))
def test_paged_forwards_give_the_logits_apply_gives(name, with_lora):
    cfg = llama.llama_tiny(use_flash=False, max_seq_len=256, **CONFIGS[name])
    params = llama.init(jax.random.PRNGKey(1), cfg)
    total = PROMPT + STEPS + S1
    seqs = np.random.default_rng(5).integers(
        1, cfg.vocab_size, (2, total)).astype(np.int32)
    kw, merged = {}, [params, params]
    if with_lora:
        adapters = _adapters(cfg)
        table = AdapterSlotTable(cfg, max_adapters=3, max_rank=4)
        for slot, adapter in enumerate(adapters, start=1):
            table.load(slot, adapter)
        kw = dict(lora=table.tree, slots=jnp.asarray([1, 2]))
        merged = [lora.merge(params, a) for a in adapters]
    want = np.stack([np.asarray(llama.apply(p, jnp.asarray(s)[None], cfg))[0]
                     for p, s in zip(merged, seqs)])      # [2, total, V]
    if with_lora:       # the adapters move what is compared
        assert np.abs(want[0] - np.asarray(llama.apply(
            params, jnp.asarray(seqs[:1]), cfg))[0]).max() > 100 * TOL

    # a row's pages: its own run of the pool(s), page 0 the sink
    pages = -(-total // PAGE)
    full = 1 + np.arange(2 * pages, dtype=np.int32).reshape(2, pages)
    tables, ring = jnp.asarray(full), 0
    if cfg.sliding_window:
        ring = llama.window_ring_pages(cfg, PAGE, CHUNK)
        tables = (tables, jnp.asarray(
            1 + np.arange(2 * ring, dtype=np.int32).reshape(2, ring)))
    caches = llama.init_paged_cache(cfg, 2 * pages + 1, PAGE, 2 * ring + 1)

    def check(got, positions, what):
        np.testing.assert_allclose(
            np.asarray(got), want[:, positions], atol=TOL, rtol=0,
            err_msg=f"{what} at {positions}")

    for pos in range(0, PROMPT, CHUNK):
        n = min(CHUNK, PROMPT - pos)
        chunks = np.zeros((2, CHUNK), np.int32)
        chunks[:, :n] = seqs[:, pos:pos + n]
        last, caches, _ = llama.prefill_paged_rows(
            params, jnp.asarray(chunks), caches, tables,
            jnp.full((2,), pos, jnp.int32), jnp.full((2,), n, jnp.int32),
            cfg, page_size=PAGE, **kw)
        check(last, pos + n - 1, "prefill_paged_rows")
    for pos in range(PROMPT, PROMPT + STEPS):
        logits, caches, _ = llama.decode_paged(
            params, jnp.asarray(seqs[:, pos:pos + 1]), caches, tables,
            jnp.full((2,), pos, jnp.int32), cfg, page_size=PAGE, **kw)
        check(logits, pos, "decode_paged")
    pos = PROMPT + STEPS
    logits, caches, _ = llama.verify_paged_rows(
        params, jnp.asarray(seqs[:, pos:pos + S1]), caches, tables,
        jnp.full((2,), pos, jnp.int32), cfg, page_size=PAGE, **kw)
    check(logits, slice(pos, pos + S1), "verify_paged_rows")
