"""AOT compiles of the serving and training kernels for a DESCRIBED v5e
(no chip attached): the TPU compiler is installed in the sandbox and
refuses here what it would refuse on the machine — VMEM over the scoped
limit, misaligned slices, a Mosaic kernel it is asked to partition.
Interpret-mode parity tests cannot see any of that. Shapes are the
published Llama-3-8B widths (32 Q / 8 KV heads of 128) at every
chunk_size x page_size the engine's defaults and bench_serve.py use.
A compile that passes is not a chip run: nothing here measures anything.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from ray_tpu.ops.flash_attention import _flash_bwd, _flash_fwd
from ray_tpu.ops.ragged_paged_attention import (
    ragged_decode_attention, ragged_paged_attention,
)
from ray_tpu.parallel.mesh import use_mesh
from ray_tpu.parallel.sharding import logical_spec

H, KVH, D = 32, 8, 128
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e():
    """The described 2x2 v5e topology; skips where libtpu cannot describe
    it. The persistent compile cache (conftest turns it on) is off around
    these compiles: an entry written for a described chip cannot be read
    back without one, and the next run would warn and compile again."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


# how a compiled custom call carries its `vmem_limit_bytes`
_SCOPE = '"scoped_memory_configs":[{"memory_space":"1","offset":"0",' \
         '"size":"%d"}]' % (32 * 2 ** 20)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sh)
            for (s, dt), sh in zip(shapes, sharding)]
    return jax.jit(fn).lower(*args).compile()


def _paged_shapes(rows, q_window, page, max_pages, pool=256, h=H, kvh=KVH,
                  d=D):
    pages = ((pool, page, kvh * d), BF16)      # as the engine stores them
    return [((rows, q_window, h, d), BF16), pages, pages,
            ((rows, max_pages), jnp.int32), ((rows,), jnp.int32),
            ((rows,), jnp.int32)]


@pytest.mark.parametrize("rows,q_window,page,max_pages", [
    (4, 128, 16, 64),      # engine defaults: chunk 128, page 16
    (4, 128, 128, 16),
    (1, 256, 16, 128),     # bench_serve.py's TPU chunk
    (1, 256, 128, 16),
    (8, 5, 16, 64),        # verify window: 1 + spec_tokens=4 drafts
])
def test_ragged_window_compiles_at_8b_widths(v5e, rows, q_window, page,
                                             max_pages):
    one = SingleDeviceSharding(v5e.devices[0])
    text = _compile(
        ragged_paged_attention,
        *_paged_shapes(rows, q_window, page, max_pages),
        sharding=[one] * 6).as_text()
    assert "tpu_custom_call" in text


def test_ragged_decode_compiles_at_8b_widths(v5e):
    one = SingleDeviceSharding(v5e.devices[0])
    shapes = _paged_shapes(8, 1, 16, 128)
    shapes[0] = ((8, H, D), BF16)
    text = _compile(ragged_decode_attention, *shapes[:5],
                    sharding=[one] * 5).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("h,kvh", [(H, KVH), (H // 4, KVH // 4)],
                         ids=["1chip", "tp4_shard"])
@pytest.mark.parametrize("rows,q_window,max_pages", [
    (32, 1, 256),          # decode: max_batch_size rows, the doc-QA bucket
    (1, 128, 256),         # prefill: one chunk_size row, two 64-query tiles
    (32, 1, 4),            # the bucket floor: a table under one 8-page block
    (1, 128, 8),           # a first chunk: the table holds one 128-key block
    (1, 128, 32),          # 512 keys: the block is clamped to the table
    (1, 128, 1024),        # the widest bucket any cell warms
], ids=["decode", "prefill", "decode_floor", "prefill_floor", "prefill_32",
        "prefill_1024"])
def test_ragged_compiles_at_the_benchmark_cells_shapes(v5e, rows, q_window,
                                                       max_pages, h, kvh):
    """The shapes `docqa-sessions-1chip` and the pending chat cells run
    (BENCHMARK.json; page 16: a decode step gathers 8 pages = 128 keys, a
    prefill step up to 64 = 1,024 under the stated ``vmem_limit_bytes``),
    whole and as one tp=4 shard sees them (8 Q / 2 KV heads)."""
    one = SingleDeviceSharding(v5e.devices[0])
    text = _compile(
        ragged_paged_attention,
        *_paged_shapes(rows, q_window, 16, max_pages, pool=3328, h=h,
                       kvh=kvh),
        sharding=[one] * 6).as_text()
    # the custom call's name and result shape are what the benchmark's
    # trace reduction tells the kernel and its two shapes by, and they
    # survive the kernel entry's inner jit
    assert re.search(rf"%ragged_paged_attention[.\d]* = "
                     rf"bf16\[{rows},{q_window},{h},{D}\].*tpu_custom_call",
                     text)
    # the per-kv-head body states its VMEM scope; the all-heads decode
    # body is compiled under the compiler's own at every derived width
    assert (_SCOPE in text) == (q_window > 1)


@pytest.mark.parametrize("rows,h,kvh,d,max_pages,keys", [
    (32, 32, 8, 128, 256, 256),      # doc-QA's bucket at 2-4k contexts
    (32, 32, 8, 128, 512, 512),      # ... and its widest (max_pages_per_seq)
    (32, 32, 4, 128, 1024, 1024),    # Mellum's full layers
    (64, 16, 2, 256, 1024, 1024),    # Qwen3-Next: page_buckets off
    (64, 16, 16, 128, 128, 128),     # OLMoE: the program it had
    (64, 16, 16, 128, 256, 256),     # ... and its widest table
    (32, 8, 2, 128, 1024, 1024),     # a tp=4 shard's heads, the widest table
], ids=["docqa", "docqa_512", "mellum_full", "qwen3next", "olmoe",
        "olmoe_256", "tp4_shard_1024"])
def test_all_heads_decode_compiles_at_the_derived_width(v5e, rows, h, kvh, d,
                                                        max_pages, keys):
    """The decode shape (query window 1: the all-heads body) of the four
    serving configurations that run it, at the key block `window_step`
    derives from their heads and tables (PR 48: up to 2 MiB of K and V a
    step, within a sixteenth of the table), compiled for the described
    v5e inside the compiler's own VMEM scope — no ``vmem_limit_bytes``."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    assert rpa.window_step(1, h, kvh, d, page_size=16, table_pages=max_pages,
                           itemsize=2) == {"q_tile": 1, "block_keys": keys}
    one = SingleDeviceSharding(v5e.devices[0])
    text = _compile(
        ragged_paged_attention,
        *_paged_shapes(rows, 1, 16, max_pages, pool=4096, h=h, kvh=kvh, d=d),
        sharding=[one] * 6).as_text()
    assert re.search(rf"%ragged_paged_attention[.\d]* = "
                     rf"bf16\[{rows},1,{h},{d}\].*tpu_custom_call", text)
    assert _SCOPE not in text and '"scoped_memory_configs":[]' in text


@pytest.mark.parametrize("rows,q_window,max_pages", [
    (32, 1, 1024),         # decode: max_batch_size rows, the longest table
    (4, 128, 1024),        # prefill: four chunk_size rows, 32-row tiles
    (32, 1, 4),            # the ladder's floor and a middle bucket, both
    (32, 1, 64),           # windows: the block is clamped to the table
    (4, 128, 4),
    (4, 128, 64),
], ids=["decode", "prefill", "decode_4", "decode_64", "prefill_4",
        "prefill_64"])
def test_latent_form_compiles_at_the_kanana_cells_shapes(v5e, rows,
                                                         q_window,
                                                         max_pages):
    """`kanana-longdoc-sessions-1chip`: 32 heads on ONE latent kv head of
    640 lanes (576 used), values its first 512. A 576-lane pool is refused
    here (a page copy must be whole 128-lane tiles: PERF.md §6, PR 31)."""
    from ray_tpu.ops.ragged_paged_attention import ragged_latent_attention
    one = SingleDeviceSharding(v5e.devices[0])

    def fn(lanes):
        return _compile(
            functools.partial(ragged_latent_attention, v_width=512,
                              scale=192 ** -0.5),
            ((rows, q_window, H, lanes), BF16), ((25600, 16, lanes), BF16),
            ((rows, max_pages), jnp.int32), ((rows,), jnp.int32),
            ((rows,), jnp.int32), sharding=[one] * 5).as_text()
    # the name's prefix and the result's [rows, window, heads, Dv] are what
    # reduce/families/*.json and reduce/kernels/*.json tell it by
    text = fn(640)
    assert re.search(rf"%ragged_paged_attention_latent[.\d]* = "
                     rf"bf16\[{rows},{q_window},{H},512\].*tpu_custom_call",
                     text)
    assert _SCOPE in text
    if (q_window, max_pages) == (1, 1024):
        with pytest.raises(Exception, match="aligned to tiling"):
            fn(576)


@pytest.mark.parametrize("shape", [
    dict(q_window=128, heads=H, kv_heads=1, d=640, v_width=512),   # kanana
    dict(q_window=1, heads=H, kv_heads=1, d=640, v_width=512),
    dict(q_window=128, heads=H, kv_heads=KVH, d=D),                # doc-QA
    dict(q_window=128, heads=16, kv_heads=16, d=D),                # OLMoE
    dict(q_window=5, heads=H, kv_heads=KVH, d=D),                  # verify
    dict(q_window=1, heads=H, kv_heads=KVH, d=D),         # the all-heads body
    dict(q_window=1, heads=H, kv_heads=4, d=D),           # Mellum's full
    dict(q_window=1, heads=16, kv_heads=2, d=256),        # Qwen3-Next
    dict(q_window=1, heads=16, kv_heads=16, d=D),         # OLMoE
    dict(q_window=8, heads=16, kv_heads=16, d=D),         # its widest verify
], ids=["latent_prefill", "latent_decode", "gqa_prefill", "mha_prefill",
        "gqa_verify", "gqa_decode", "gqa8_decode", "gqa_of_256_decode",
        "mha_decode", "mha_verify"])
@pytest.mark.parametrize("table_pages", [4, 32, 256, 1024])
def test_the_stated_vmem_budget_covers_the_declared_buffers(shape,
                                                            table_pages):
    """What `_ragged_call` declares at the widths `window_step` derives —
    two slots a pool of the block buffer, the f32 accumulators and softmax
    state (m and l lie over 128 lanes), the q and out blocks twice (the
    pipeline double-buffers them) — is inside the `vmem_limit_bytes` it
    states (the per-kv-head body) or the compiler's own scope (the
    all-heads body, which states none), with room for the score tile; the
    derivation's own estimate counts at least as much; and the block is as
    wide as the budget, the body's own limit (the score tile's elements;
    a step's bytes and a sixteenth of the table) and the table allow. (The
    compiles above run under those scopes.)"""
    from ray_tpu.ops import ragged_paged_attention as rpa
    latent = "v_width" in shape
    step = rpa.window_step(**shape, page_size=16, table_pages=table_pages,
                           itemsize=2)
    q_tile, keys = step["q_tile"], step["block_keys"]
    h, kvh, d = shape["heads"], shape["kv_heads"], shape["d"]
    d_v = shape.get("v_width", d)
    pools = 1 if latent else 2
    all_heads = not latent and rpa._all_heads(q_tile, h // kvh)
    slots = 1 if all_heads else kvh
    rows = q_tile * h // slots
    declared = (pools * 2 * keys * kvh * d * 2             # block buffers
                + slots * rows * (d_v + 2 * 128) * 4       # acc, m, l
                + 2 * q_tile * h * (d + d_v) * 2)          # q, out blocks
    scores = rows * keys * 4
    budget = rpa._DEFAULT_VMEM_SCOPE if all_heads else rpa._VMEM_BUDGET

    def estimate(keys):
        return rpa._step_vmem_bytes(q_tile, h, kvh, d, d_v, pools, keys, 2,
                                    all_heads)
    assert declared + scores <= estimate(keys) <= budget
    assert 128 <= keys <= rpa._MAX_BLOCK_KEYS
    assert keys == 128 or keys // 2 < table_pages * 16
    if keys < min(rpa._MAX_BLOCK_KEYS, table_pages * 16):
        assert estimate(2 * keys) > budget or (
            pools * kvh * d * 2 * 2 * keys > rpa._ALL_HEADS_STEP_BYTES
            or rpa._ALL_HEADS_TABLE_BLOCKS * 2 * keys > table_pages * 16
            if all_heads else rows * 2 * keys > rpa._SCORE_TILE_ELEMS)


def test_flash_fwd_bwd_compile_at_8b_widths(v5e):
    one = SingleDeviceSharding(v5e.devices[0])
    q, kv = ((2, 1024, H, D), BF16), ((2, 1024, KVH, D), BF16)
    kw = dict(causal=True, scale=D ** -0.5, block_q=512, block_k=512,
              interpret=False)

    def fwd_bwd(q, k, v, g):
        out, lse = _flash_fwd(q, k, v, kw["causal"], kw["scale"],
                              kw["block_q"], kw["block_k"], False)
        return _flash_bwd(q, k, v, out, lse, g, **kw)

    text = _compile(fwd_bwd, q, kv, kv, q, sharding=[one] * 4).as_text()
    assert text.count("tpu_custom_call") >= 3      # fwd, dq, dkv


def test_tp4_decode_step_keeps_its_kernels_in_shard_map(v5e, monkeypatch):
    """The engine's tp layout on the 2x2 topology: weights by
    llama.logical_axes, KV pages sharded over kv_heads. The bare kernel
    on such operands is refused ("Mosaic kernels cannot be automatically
    partitioned"); the model's decode step — where every kernel call
    goes through shard_kernel — compiles, keeps one per-shard custom
    call per layer, and gathers no page pool."""
    from ray_tpu.models import llama
    from ray_tpu.parallel.sharding import logical_sharding

    mesh = Mesh(np.asarray(v5e.devices).reshape(4), ("tp",))
    mc = llama.llama3_8b(n_layers=2)
    rows, page, max_pages, pool = 8, 16, 128, 256
    # this process sees the CPU: steer the kernel-vs-reference dispatch
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)
    with use_mesh(mesh):
        repl = NamedSharding(mesh, logical_spec(()))
        kv = NamedSharding(mesh, logical_spec((None, None, "kv_heads")))
        heads = NamedSharding(mesh, logical_spec((None, "heads")))
        bare = _paged_shapes(rows, 1, page, max_pages, pool)
        bare[0] = ((rows, H, D), BF16)
        with pytest.raises(NotImplementedError, match="shard_map"):
            _compile(ragged_decode_attention, *bare[:5],
                     sharding=[heads, kv, kv, repl, repl])

        def sds(shape, dtype, sharding):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        params = jax.tree.map(
            lambda s, sh: sds(s.shape, s.dtype, sh),
            jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), mc)),
            logical_sharding(llama.logical_axes(mc)))
        caches = [{n: sds((pool, page, KVH * D), BF16, kv) for n in "kv"}
                  for _ in range(mc.n_layers)]
        text = jax.jit(functools.partial(
            llama.decode_paged, cfg=mc, page_size=page)).lower(
            params, sds((rows, 1), jnp.int32, repl), caches,
            sds((rows, max_pages), jnp.int32, repl),
            sds((rows,), jnp.int32, repl)).compile().as_text()
    assert text.count("tpu_custom_call") == mc.n_layers
    gathered_pool = f"bf16[{pool},{page},{KVH * D}]"
    assert not [ln for ln in text.splitlines()
                if "all-gather" in ln and gathered_pool in ln]


# -- OLMoE-1B-7B widths (BENCHMARK.json olmoe-gen-sessions-1chip): MHA with
# 16 KV heads (groups = 1), 64 experts of 1024, top-8 ----------------------

OLMOE = dict(vocab_size=50304, dim=2048, n_heads=16, n_kv_heads=16,
             mlp_dim=1024, max_seq_len=4096, rope_theta=1e4, qk_norm=True,
             moe_experts=64, moe_top_k=8, moe_renormalize=False)


@pytest.mark.parametrize("rows,q_window", [(64, 1), (1, 128), (8, 5)],
                         ids=["decode", "prefill", "verify"])
def test_ragged_compiles_at_olmoe_shapes(v5e, rows, q_window):
    """groups = 1: a kv head's score tile would have one query row at
    decode (five at a verify window), so all 16 heads go through one
    block-diagonal product there and through their own 128-row tiles at
    prefill; a 128-key block is 1 MiB of K + V. max_batch_size 64 rows,
    the cell's 128-page table (2048-token contexts), its 3456-page
    pool."""
    one = SingleDeviceSharding(v5e.devices[0])
    text = _compile(
        ragged_paged_attention,
        *_paged_shapes(rows, q_window, 16, 128, pool=3456, h=16, kvh=16),
        sharding=[one] * 6).as_text()
    assert re.search(rf"%ragged_paged_attention[.\d]* = "
                     rf"bf16\[{rows},{q_window},16,{D}\].*tpu_custom_call",
                     text)


# (experts held, hidden, one expert's width, top-k, decode rows, prefill
# chunk-rows of 128 tokens) of the routed serving configurations whose
# expert blocks PR 54 changed (Qwen3-Next's were whole experts before)
EXPERT_WIDTHS = {
    "olmoe": (64, 2048, 1024, 8, 64, 8),
    "mellum": (64, 2304, 896, 8, 32, 8),
    "kanana": (128, 2048, 768, 6, 32, 16),
    "ling": (64, 2560, 768, 8, 128, 16),
}


@pytest.mark.parametrize("shape", ["decode", "prefill"])
@pytest.mark.parametrize("widths", EXPERT_WIDTHS.values(), ids=EXPERT_WIDTHS)
def test_grouped_ffn_compiles_at_the_cells_widths(v5e, widths, shape):
    """The expert SwiGLU's two kernels at each configuration's decode rows
    x top-k (16- or 32-row tiles) and at its prefill dispatch's tokens x
    top-k (128-row tiles), reading one layer of the layers' weight stacks
    in place as the serving paths do: whole-expert blocks, two buffers of
    them, compile inside the VMEM scope `_vmem_scope` gives the call —
    none, and so the compiler's default, wherever the buffers fit that.
    Their names are what the benchmark's trace reduction finds them by."""
    from ray_tpu.ops import grouped_matmul as gmm
    one = SingleDeviceSharding(v5e.devices[0])
    e, d, f, top_k, rows, chunk_rows = widths
    assignments = (rows if shape == "decode" else chunk_rows * 128) * top_k
    tm = gmm.tile_rows(assignments, e)
    assert tm in ((128,) if shape == "prefill" else (16, 32))
    tiles = gmm.num_tiles(assignments, e, tm)
    text = _compile(
        functools.partial(gmm.grouped_ffn, tm=tm, layer=1, interpret=False),
        ((tiles * tm, d), BF16), ((2, e, d, f), BF16), ((2, e, d, f), BF16),
        ((2, e, f, d), BF16), ((e,), jnp.int32), ((tiles,), jnp.int32),
        ((1,), jnp.int32), sharding=[one] * 7).as_text()
    assert not re.search(rf"= bf16\[(1,)?{e},{d},{f}\]", text)   # no copy
    for name, k, n, operands in (("grouped_swiglu", d, f, 2),
                                 ("grouped_matmul", f, d, 1)):
        call = re.search(rf"%{name}[.\d]* = .*tpu_custom_call.*", text)
        scope = int(re.search(r'"memory_space":"1","offset":"\d+",'
                              r'"size":"(\d+)"', call.group(0))[1])
        stated = gmm._vmem_scope(tm, k, n, operands, 2)
        # unstated, a call is given what it uses
        assert scope == stated if stated else scope <= gmm._VMEM_DEFAULT


def test_olmoe_decode_layer_compiles_at_64_rows(v5e, monkeypatch):
    """One decode layer body of the OLMoE config at max_batch_size 64:
    QK-norm, the ragged kernel at groups = 1, the float32 router, the
    layout's one-hot / cumulative sum / gathers and both grouped kernels,
    with the per-expert load as the program's third result."""
    from ray_tpu.models import llama

    one = SingleDeviceSharding(v5e.devices[0])
    mc = llama.LlamaConfig(n_layers=1, **OLMOE)
    rows, page, max_pages, pool = 64, 16, 128, 3456
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = jax.tree.map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), mc)))

    def compile_with(num_pages):
        caches = [{n: sds((num_pages, page, 16 * D), BF16) for n in "kv"}]
        return jax.jit(functools.partial(
            llama.decode_paged, cfg=mc, page_size=page)).lower(
            params, sds((rows, 1), jnp.int32), caches,
            sds((rows, max_pages), jnp.int32),
            sds((rows,), jnp.int32)).compile()
    compiled = compile_with(pool)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3      # attention, two expert
    # the pools are not padded in HBM: a page more costs the program's
    # arguments exactly page x KVH x D bf16 values for K and for V
    more = compile_with(pool + 128).memory_analysis().argument_size_in_bytes
    assert more - compiled.memory_analysis().argument_size_in_bytes == \
        2 * 128 * page * 16 * D * 2
    assert jax.tree.leaves(compiled.out_info)[-1].shape == (64,)


# -- the whole prefill program of a dispatch (models/llama.py
# prefill_paged_rows): four rows through each layer together ---------------

MISTRAL = dict(vocab_size=32768, dim=4096, n_heads=32, n_kv_heads=8,
               mlp_dim=14336, max_seq_len=4096, rope_theta=1e6)


@pytest.mark.parametrize("widths,pool,max_pages", [
    (MISTRAL, 3328, 256), (OLMOE, 3456, 128)], ids=["mistral", "olmoe"])
def test_prefill_r4_program_compiles_at_the_cells_widths(
        v5e, monkeypatch, widths, pool, max_pages):
    """The R = 4 prefill body of `docqa-sessions-1chip` and
    `olmoe-gen-sessions-1chip` at two layers: 512 tokens share each
    layer's matmuls (and OLMoE's 4096 assignments one layout), the
    ragged kernel is still called once a row at [1, 128, H, D] — the
    shape the benchmark's prefill roofline counts calls by — no stacked
    weight is copied whole to be sliced, and the program's temporaries
    stay far under what the cells leave free (~2.5 GB of 16)."""
    from ray_tpu.models import llama

    one = SingleDeviceSharding(v5e.devices[0])
    mc = llama.LlamaConfig(n_layers=2, **widths)
    rows, chunk, page = 4, 128, 16
    h, kvh = mc.n_heads, mc.n_kv_heads
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = jax.tree.map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), mc)))
    caches = [{n: sds((pool, page, kvh * D), BF16) for n in "kv"}
              for _ in range(mc.n_layers)]
    compiled = jax.jit(functools.partial(
        llama.prefill_paged_rows, cfg=mc, page_size=page),
        donate_argnums=(2,)).lower(
        params, sds((rows, chunk), jnp.int32), caches,
        sds((rows, max_pages), jnp.int32), sds((rows,), jnp.int32),
        sds((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(r"%ragged_paged_attention[.\d]* = (bf16\[[\d,]*\])",
                       text)
    assert calls == [f"bf16[1,{chunk},{h},{D}]"] * (rows * mc.n_layers)
    assert " while(" not in text
    stacks = {"bf16[" + ",".join(map(str, s.shape)) + "]"
              for s in jax.tree.leaves(params["layers"]) if s.ndim >= 3}
    copied = [ln.strip()[:160] for ln in text.splitlines()
              if re.search(r" = \S+ (copy|copy-start)\(", ln)
              and any(ln.split(" = ")[1].startswith(s) for s in stacks)]
    assert not copied, copied
    assert jax.tree.leaves(compiled.out_info)[0].shape == (
        rows, mc.vocab_size)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# ---------------------------------------------------------------------------
# Mellum2 (mellum-mixed-queue-1chip): the window form and the programs
# ---------------------------------------------------------------------------

MELLUM = dict(vocab_size=98304, dim=2304, n_heads=32, n_kv_heads=4,
              head_dim=128, mlp_dim=896, moe_experts=64, moe_top_k=8,
              moe_renormalize=True, sliding_window=1024, norm_eps=1e-6,
              max_seq_len=131072, rope_theta=5e5)
# window 1,024 + prefill_rows 4 x chunk 128 + a page, in pages of 16
MELLUM_RING = 97


@pytest.mark.parametrize("rows,q_window", [(32, 1), (4, 128)],
                         ids=["decode", "prefill"])
def test_window_form_compiles_at_the_mellum_cells_shapes(v5e, rows,
                                                         q_window):
    """The sliding layers' kernel of `mellum-mixed-queue-1chip`: 32 / 4
    heads of 128 over the fixed 97-page ring (the same at every context),
    32 decode rows and four 128-token chunk-rows, under a name of its own
    (the trace reduction tells the two kinds of layer by it). The sweep's
    grid depends on the window alone: 9 steps of 128 keys a decode row, 6
    of 256 a 64-query prefill tile."""
    from ray_tpu.ops import ragged_paged_attention as rpa
    one = SingleDeviceSharding(v5e.devices[0])
    text = _compile(
        functools.partial(ragged_paged_attention, window=1024),
        *_paged_shapes(rows, q_window, 16, MELLUM_RING, pool=4096, h=32,
                       kvh=4), sharding=[one] * 6).as_text()
    assert re.search(rf"%ragged_paged_attention_window[.\d]* = "
                     rf"bf16\[{rows},{q_window},32,{D}\].*tpu_custom_call",
                     text)
    step = rpa.window_step(q_window, 32, 4, D, page_size=16,
                           table_pages=MELLUM_RING, itemsize=2, window=1024)
    assert step == ({"q_tile": 1, "block_keys": 128} if q_window == 1
                    else {"q_tile": 64, "block_keys": 256})
    assert rpa.window_sweep_steps(step["q_tile"], 1024,
                                  step["block_keys"]) == (
        9 if q_window == 1 else 6)
    assert rpa.window_table_pages(1024, 16, 4 * 128) == MELLUM_RING


def test_full_layers_table_compiles_at_the_mellum_cells_heads(v5e):
    """The full layers' kernel at 32 / 4 heads over the 1,024-page table
    (the widest bucket): the plain form, eight query heads a KV head."""
    one = SingleDeviceSharding(v5e.devices[0])
    for rows, q_window in ((32, 1), (1, 128)):
        text = _compile(
            ragged_paged_attention,
            *_paged_shapes(rows, q_window, 16, 1024, pool=25600, h=32,
                           kvh=4), sharding=[one] * 6).as_text()
        assert re.search(rf"%ragged_paged_attention[.\d]* = "
                         rf"bf16\[{rows},{q_window},32,{D}\]", text)


@pytest.mark.parametrize("family", ["prefill_r4", "decode_w1"])
def test_mellum_programs_compile_at_the_cells_widths(v5e, monkeypatch,
                                                     family):
    """`rtpu_prefill_r4` and `rtpu_decode_w1` bodies of
    `mellum-mixed-queue-1chip` at one period of layers (three sliding, one
    full): hidden 2304 = 18 lanes of 128, experts of 896 = 7, q of 4096
    under a hidden size of 2304, two pools of two sizes, a pair of tables.
    Each kind of layer calls its own kernel, once a row in prefill."""
    from ray_tpu.models import llama

    one = SingleDeviceSharding(v5e.devices[0])
    mc = llama.LlamaConfig(
        n_layers=4, layer_types=("sliding",) * 3 + ("full",),
        rope_yarn=llama.Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782),
        **MELLUM)
    page, pool, wpool, max_pages = 16, 25600, 4096, 1024
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params = jax.tree.map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), mc)))
    assert params["layers"]["wq"].shape == (4, 2304, 4096)
    caches = jax.tree.map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(lambda: llama.init_paged_cache(mc, pool, page,
                                                      wpool)))
    assert [c["k"].shape[0] for c in caches] == [wpool] * 3 + [pool]
    if family == "prefill_r4":
        rows, chunk = 4, 128
        tables = (sds((rows, max_pages), jnp.int32),
                  sds((rows, MELLUM_RING), jnp.int32))
        compiled = jax.jit(functools.partial(
            llama.prefill_paged_rows, cfg=mc, page_size=page),
            donate_argnums=(2,)).lower(
            params, sds((rows, chunk), jnp.int32), caches, tables,
            sds((rows,), jnp.int32), sds((rows,), jnp.int32)).compile()
        shape, per_layer = f"bf16[1,{chunk},32,{D}]", rows
    else:
        rows = 32
        tables = (sds((rows, max_pages), jnp.int32),
                  sds((rows, MELLUM_RING), jnp.int32))
        compiled = jax.jit(functools.partial(
            llama.decode_paged, cfg=mc, page_size=page),
            donate_argnums=(2,)).lower(
            params, sds((rows, 1), jnp.int32), caches, tables,
            sds((rows,), jnp.int32)).compile()
        shape, per_layer = f"bf16[{rows},1,32,{D}]", 1
    text = compiled.as_text()
    window = re.findall(
        r"%ragged_paged_attention_window[.\d]* = (bf16\[[\d,]*\])", text)
    full = re.findall(r"%ragged_paged_attention[.\d]* = (bf16\[[\d,]*\])",
                      text)
    assert window == [shape] * (3 * per_layer)
    assert full == [shape] * per_layer
    assert jax.tree.leaves(compiled.out_info)[0].shape == (
        rows, mc.vocab_size)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# ---------------------------------------------------------------------------
# No paged program copies a projection weight (PERF.md §3, program families)
# ---------------------------------------------------------------------------

_SERVING = ("mistral7b", "olmoe7b", "mellum2-12b", "kanana2-30b",
            "qwen3next-80b", "ling3flash-125b")


def _serving_model(name, n_layers, experts=False):
    """(model module, config, engine settings) of
    ``benchmarks/configs/<name>-serve-1chip.json`` at its attention widths
    and ``n_layers`` layers. Unless ``experts``, they are left out where a
    dense MLP runs the same attention block (models/llama.py: their
    compile time buys nothing for a check of the projections); mla_moe
    keeps one dense and one expert layer, as its parameter tree has both."""
    import dataclasses
    import json

    from benchmarks import spec
    from ray_tpu.llm.paged_engine import model_module
    with open(os.path.join(spec.ROOT, "benchmarks", "configs",
                           f"{name}-serve-1chip.json")) as f:
        model = json.load(f)
    cfg = spec.resolve(model["builder"])(model).cfg
    cut = {"n_layers": n_layers}
    if hasattr(cfg, "full_interval"):   # GDN layers, then one full layer
        cut["full_interval"] = n_layers
    if hasattr(cfg, "mla_interval"):    # KDA layers, then one latent layer
        cut["mla_interval"] = n_layers
    if hasattr(cfg, "layer_types"):
        if not experts:
            cut["moe_experts"] = 0
        if cfg.layer_types:        # one layer of each kind at least
            cut["layer_types"] = cfg.layer_types[2:2 + n_layers]
    return model_module(cfg), dataclasses.replace(cfg, **cut), model["engine"]


# what only moves data: through these a copy's operand is followed back to
# the program's parameter (a fusion counts when the compiler named it for
# nothing but such steps, as in `slice_bitcast_fusion`)
_MOVES = {"get-tuple-element", "bitcast", "reshape", "slice", "transpose",
          "copy", "copy-start", "copy-done", "slice-start", "slice-done"}
_MOVING_FUSION = re.compile(r"(?:slice|bitcast|copy|transpose|_)+fusion")


def _weight_copies(text, weights):
    """Lines of the entry computation that copy or transpose — a ``copy``,
    or a fusion the compiler named ``copy…`` / ``transpose…`` — one of
    ``weights`` ({name: shape}): a result with as many elements as the
    weight, made from that weight's parameter by nothing but moves (an
    activation of the same size comes out of a matmul or a kernel)."""
    ops = {}
    for ln in text[text.index("ENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \(*\w+\[([\d,]*)\]\S*.*? "
                     r"([\w-]+)\((.*)", ln)
        if m:
            ops[m[1]] = (m[3], m[2], re.findall(r"%([\w.-]+)", m[4]), ln)

    def source(name):
        """The weight whose parameter ``name`` is a moved copy of."""
        op, _, operands, ln = ops[name]
        if op == "parameter":
            key = re.match(r"params__(?:[a-z]+_)?layers____(\w+?)__", name)
            return key and key[1] in weights and key[1]
        if not (op in _MOVES
                or op == "fusion" and _MOVING_FUSION.match(name)
                or op == "custom-call" and "ConcatBitcast" in ln):
            return None
        return next(filter(None, (source(o) for o in operands if o in ops)),
                    None)
    sizes = {math.prod(shape) for shape in weights.values()}
    found = []
    for name, (op, dims, _, ln) in ops.items():
        if (op in ("copy", "transpose") or op == "fusion"
                and name.startswith(("copy", "transpose"))) \
                and math.prod(map(int, filter(None, dims.split(",")))) \
                in sizes and (weight := source(name)):
            found.append(f"{weight}: {ln.strip()[:140]}")
    return found


def _paged_program(v5e, monkeypatch, module, cfg, engine, family, rows,
                   ring):
    """(`decode_paged` or `prefill_paged_rows` of ``rows`` x 128 tokens
    compiled for one described chip over the cell's pools and tables — a
    config with sliding layers has a second pool and a table of ``ring``
    pages —, its projection weights {name: shape})."""
    from ray_tpu.models import llama, mla_moe, qwen3_next
    from ray_tpu.ops import gated_delta

    # (models/ling_hybrid.py runs mla_moe's and qwen3_next's pieces)
    one = SingleDeviceSharding(v5e.devices[0])
    page, max_pages = engine["page_size"], engine["max_pages_per_seq"]
    # mla_moe's and qwen3_next's experts are llama's
    for owner in (llama, mla_moe, qwen3_next, gated_delta):
        monkeypatch.setattr(owner, "_on_tpu", lambda: True)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def shaped(make):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype),
                            jax.eval_shape(make))
    params = shaped(lambda: module.init(jax.random.PRNGKey(0), cfg))
    window = [engine["num_window_pages"]] \
        if getattr(cfg, "sliding_window", 0) else []
    state = "state" in module.cache_layers(cfg)
    if state:       # a slot a decode row, and the cell's snapshot pool
        window = [engine["max_batch_size"], engine["num_state_snapshots"]]
    caches = shaped(lambda: module.init_paged_cache(
        cfg, engine["num_pages"], page, *window))
    tables = sds((rows, max_pages))
    if state:       # the state rows of a decode, a prefill's [rows, 5]
        tables = (tables, sds((rows, 1) if family == "decode" else (rows, 5)))
    elif window:
        tables = (tables, sds((rows, ring)))
    if family == "decode":
        fn, args = module.decode_paged, (sds((rows, 1)), caches, tables,
                                         sds((rows,)))
    else:
        fn, args = module.prefill_paged_rows, (
            sds((rows, 128)), caches, tables, sds((rows,)), sds((rows,)))
    compiled = jax.jit(functools.partial(fn, cfg=cfg, page_size=page),
                       donate_argnums=(2,)).lower(params, *args).compile()
    weights = {k: a.shape[1:] for stack in (
        "layers", "dense_layers", "full_layers", "gdn_layers", "mla_layers",
        "kda_layers")
               for k, a in params.get(stack, {}).items()
               if k in ("wq", "wk", "wv", "wo", "wkv_a", "w_uk", "w_uv",
                        "w_qkvz", "w_out", "w_f", "w_g")}
    assert {"wq", "wo"} <= set(weights)
    return compiled, weights


@pytest.mark.parametrize("family", ["decode", "prefill_r4"])
@pytest.mark.parametrize("name,n_layers", [
    (n, 2) for n in _SERVING] + [("mistral7b", 20)],
    ids=[*_SERVING, "mistral7b_full_depth"])
def test_no_paged_program_copies_a_projection_weight(v5e, monkeypatch, name,
                                                     n_layers, family):
    """`decode_paged` at ``max_batch_size`` rows and `prefill_paged_rows`
    at 4 x 128 of every serving configuration: each layer's projection
    weights are read out of their stacks where they lie. Until PR 44 the
    compiler folded the reshape into heads into the q and k projections
    and transposed all of ``wq`` and ``wk`` for it, once a layer in every
    dispatch (`copy bf16[4096,4096]`, 32 MB, at Mistral's widths)."""
    module, cfg, engine = _serving_model(name, n_layers)
    rows = engine["max_batch_size"] if family == "decode" else 4
    compiled, weights = _paged_program(v5e, monkeypatch, module, cfg, engine,
                                       family, rows, MELLUM_RING)
    copied = _weight_copies(compiled.as_text(), weights)
    assert not copied, copied


# ---------------------------------------------------------------------------
# The top rung of a routed model's prefill ladder (PR 45): the row budget
# the engine derives from the model's routing, with the experts in
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,rows", [
    ("olmoe7b", 8), ("mellum2-12b", 8), ("kanana2-30b", 16),
    ("qwen3next-80b", 16), ("ling3flash-125b", 16)])
def test_the_derived_top_rung_compiles_with_its_experts(v5e, monkeypatch,
                                                        name, rows):
    """`prefill_paged_rows` at the rows `derived_prefill_rows` gives the
    configuration's routing (1,024 tokens for 64 experts top-8, the cap of
    2,048 for 128 experts top-6), experts in, two layers: the grouped
    kernels at 128-row tiles and the attention kernels at that many rows
    compile for the chip, no projection weight is copied, and the program's
    temporaries stay under 1 GiB beside pools and weights that fill the
    chip. Mellum's ring at 8 rows fits the cell's window pool (at 16 it
    would not: the engine halves a derived budget until it does)."""
    from ray_tpu.llm.paged_engine import derived_prefill_rows
    from ray_tpu.ops import ragged_paged_attention as rpa

    module, cfg, engine = _serving_model(name, 2, experts=True)
    assert derived_prefill_rows(module.expert_routing(cfg), 128) == rows
    ring = 0
    if getattr(cfg, "sliding_window", 0):
        def need(r):
            return engine["max_batch_size"] * rpa.window_table_pages(
                cfg.sliding_window, engine["page_size"], r * 128) \
                + 2 * r * 128 // engine["page_size"] + 1
        assert need(rows) <= engine["num_window_pages"] < need(2 * rows)
        ring = rpa.window_table_pages(cfg.sliding_window,
                                      engine["page_size"], rows * 128)
    compiled, weights = _paged_program(v5e, monkeypatch, module, cfg, engine,
                                       "prefill", rows, ring)
    text = compiled.as_text()
    assert "grouped_swiglu" in text and "grouped_matmul" in text
    copied = _weight_copies(text, weights)
    assert not copied, copied
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
