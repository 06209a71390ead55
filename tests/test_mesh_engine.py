"""Mesh-parallel paged serving engine: NamedSharding tensor-parallel
decode must be BIT-IDENTICAL to single-chip greedy across every dispatch
family, with zero involuntary reshards in steady state (the engine pins
in/out shardings on each jitted family, so any buffer drifting off its
pinned placement is a bug the mesh_reshard_bytes counter must catch).

The mesh is virtual: conftest forces 8 host-platform devices, so tp=2/
tp=4 shardings exercise the real GSPMD partitioner on CPU.
"""
import jax
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.models import llama

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >=2 (virtual) devices")


def _cfg(mesh=None, **over):
    base = dict(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=256),
        max_batch_size=4, page_size=8, num_pages=128,
        max_pages_per_seq=16, chunk_size=16)
    base.update(over)
    return PagedEngineConfig(mesh=mesh, **base)


def _prompts(rng, lens=(16, 32, 24)):
    return [list(rng.randint(1, 250, (n,))) for n in lens]


GREEDY = SamplingParams(max_tokens=16, temperature=0.0)
GREEDY_LP = SamplingParams(max_tokens=16, temperature=0.0, logprobs=1)


def test_mesh_off_counters_stay_zero():
    eng = PagedInferenceEngine(_cfg(), rng_seed=0)
    eng.generate(_prompts(np.random.RandomState(0)), GREEDY)
    assert eng.mesh is None
    for k in ("mesh_dispatches", "mesh_input_bytes",
              "mesh_output_bytes", "mesh_reshard_bytes"):
        assert eng.stats[k] == 0, (k, eng.stats[k])


def test_tp2_greedy_bit_identical_and_zero_reshards():
    """The tentpole invariant: tp-sharded prefill+decode produce the
    same tokens as single-chip, logprobs to tolerance, and no dispatch
    commits a buffer off its pinned sharding."""
    rng = np.random.RandomState(1)
    prompts = _prompts(rng)
    ref = PagedInferenceEngine(_cfg(), rng_seed=0).generate(
        prompts, GREEDY_LP)
    eng = PagedInferenceEngine(_cfg(mesh={"tp": 2}), rng_seed=0)
    assert dict(eng.mesh.shape)["tp"] == 2
    out = eng.generate(prompts, GREEDY_LP)
    assert [o["token_ids"] for o in out] == [o["token_ids"] for o in ref]
    for o, r in zip(out, ref):
        np.testing.assert_allclose(o["logprobs"], r["logprobs"],
                                   atol=1e-5)
    assert eng.stats["mesh_dispatches"] > 0
    assert eng.stats["mesh_reshard_bytes"] == 0, eng.stats
    # accounted transfers: token ids in, tokens/logps out — nonzero but
    # tiny relative to the sharded weights/KV, which never move
    assert 0 < eng.stats["mesh_input_bytes"] < 1 << 20
    assert 0 < eng.stats["mesh_output_bytes"] < 1 << 20


def test_tp2_decode_behind_a_decode_is_fed_on_the_mesh():
    """A decode launched behind an unbooked decode under the mesh: the
    rows' last tokens and the mask that says which rows start from them
    are two more replicated arguments of the pinned signature, the tokens
    are the unsharded engine's, and nothing drifts off its sharding."""
    prompts = _prompts(np.random.RandomState(4))
    sp = SamplingParams(max_tokens=30, temperature=0.0)
    ref = PagedInferenceEngine(_cfg(), rng_seed=0)
    eng = PagedInferenceEngine(_cfg(mesh={"tp": 2}), rng_seed=0)
    want = [o["token_ids"] for o in ref.generate(prompts, sp)]
    assert [o["token_ids"] for o in eng.generate(prompts, sp)] == want
    for e in (ref, eng):
        # 29 tokens to come, 8 + 1 + 8 + 1 + 8 + 1 + 2: every dispatch
        # after the first goes out behind the one before it
        assert e.stats["decode_dispatches"] == 7
        assert e.stats["decode_rows_fed_on_device"] == 6 * len(prompts)
        assert e.stats["decode_dead_rows"] == 0
    assert eng.stats["mesh_reshard_bytes"] == 0, eng.stats
    repl = eng._shardings["repl"]
    assert repl.is_equivalent_to(eng._last.sharding, eng._last.ndim)


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >=4 (virtual) devices")
def test_tp4_greedy_bit_identical():
    """>=4-way sharding: tp must divide n_kv_heads and vocab, so this
    arm runs a 4-kv-head / 256-vocab tiny config; same bit-identity +
    zero-reshard bar."""
    model = llama.llama_tiny(vocab_size=256, max_seq_len=256,
                             n_kv_heads=4)
    over = dict(model=model)
    rng = np.random.RandomState(5)
    prompts = _prompts(rng)
    ref = PagedInferenceEngine(_cfg(**over), rng_seed=0).generate(
        prompts, GREEDY)
    eng = PagedInferenceEngine(_cfg(mesh={"tp": 4}, **over), rng_seed=0)
    out = eng.generate(prompts, GREEDY)
    assert [o["token_ids"] for o in out] == [o["token_ids"] for o in ref]
    assert eng.stats["mesh_reshard_bytes"] == 0, eng.stats


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >=4 (virtual) devices")
def test_tp4_kernels_run_per_shard_under_shard_map():
    """With the Pallas kernels on (interpret mode here, Mosaic on the
    chip) every kernel call under the mesh sits inside shard_map —
    Mosaic kernels cannot be partitioned automatically
    (tests/test_chip_compile.py holds the compile) — and the per-shard
    result is token-identical to the one-device kernel engine."""
    model = llama.llama_tiny(vocab_size=256, max_seq_len=256,
                             n_kv_heads=4)
    over = dict(model=model, chunk_size=32)
    prompts = _prompts(np.random.RandomState(6), (16, 70, 24))
    ref = PagedInferenceEngine(_cfg(**over), rng_seed=0,
                               interpret=True).generate(prompts, GREEDY)
    eng = PagedInferenceEngine(_cfg(mesh={"tp": 4}, **over), rng_seed=0,
                               interpret=True)
    out = eng.generate(prompts, GREEDY)
    assert [o["token_ids"] for o in out] == [o["token_ids"] for o in ref]
    assert eng.stats["mesh_reshard_bytes"] == 0, eng.stats


def test_tp2_dispatch_shardings_are_pinned():
    """Every compiled family carries the engine's pinned shardings:
    params/caches enter sharded, plain operands replicated — compiled
    once, no per-call re-layout."""
    eng = PagedInferenceEngine(_cfg(mesh={"tp": 2}), rng_seed=0)
    eng.generate(_prompts(np.random.RandomState(2), (16,)), GREEDY)
    kv = eng._shardings["caches"][0]["k"]
    for layer in eng.caches:
        for arr in layer.values():
            assert kv.is_equivalent_to(arr.sharding, arr.ndim)
    want = eng._shardings["params"]
    got = jax.tree.map(
        lambda leaf, sh: sh.is_equivalent_to(leaf.sharding, leaf.ndim),
        eng.params, want)
    assert all(jax.tree.leaves(got))


def test_tp2_mixed_tenant_lora_parity():
    """Multi-LoRA slot table sharded over the same mesh: a mixed batch
    (base + adapter rows) matches single-chip token-for-token."""
    from ray_tpu.llm import lora
    cfg_kw = dict(max_adapters=3, lora_rank=4)
    mc = _cfg(**cfg_kw).model
    adapter = lora.random_adapter(
        jax.random.PRNGKey(7), mc, rank=4, alpha=8.0,
        targets=("wq", "wv"))
    rng = np.random.RandomState(3)
    prompts = _prompts(rng)

    def run(mesh):
        eng = PagedInferenceEngine(_cfg(mesh=mesh, **cfg_kw), rng_seed=0)
        eng.lora.load(1, adapter)
        reqs = [eng.submit(p, GREEDY_LP,
                           adapter_slot=(1 if i == 1 else 0))
                for i, p in enumerate(prompts)]
        while not all(r.done for r in reqs):
            eng.step()
        return eng, reqs

    eref, rref = run(None)
    emesh, rmesh = run({"tp": 2})
    for a, b in zip(rref, rmesh):
        assert list(a.out_ids) == list(b.out_ids)
        np.testing.assert_allclose(a.out_logps, b.out_logps, atol=1e-5)
    assert emesh.stats["mesh_reshard_bytes"] == 0
    # the slot-table rows landed sharded like the base weights they
    # add onto (B shards its output dim over tp)
    axes = emesh.lora.logical_axes()
    assert axes["wq.B"][-1] == "heads"


def test_tp2_spec_decode_parity():
    """Self-speculative verify family under the mesh: same recipe as
    test_warmup_covers_every_burst_program (mixed burst, then the
    self-similar prompt solo so every slot carries a draft)."""
    rng = np.random.RandomState(3)
    over = dict(prefill_rows=3, decode_window=4, spec_tokens=6)
    burst = [list(rng.randint(1, 250, (n,))) for n in (5, 17, 33)]
    burst.append([7, 8, 9] * 6)
    sp = SamplingParams(max_tokens=24, temperature=0.0)

    def run(mesh):
        eng = PagedInferenceEngine(_cfg(mesh=mesh, **over), rng_seed=0)
        eng.generate(burst, sp)
        solo = eng.generate([[7, 8, 9] * 6], sp)
        return eng, solo[0]["token_ids"]

    eref, toks_ref = run(None)
    emesh, toks_mesh = run({"tp": 2})
    assert eref.stats["spec_dispatches"] > 0
    assert emesh.stats["spec_dispatches"] > 0
    assert toks_ref == toks_mesh
    assert emesh.stats["mesh_reshard_bytes"] == 0


def test_prefix_export_import_across_mesh_boundary():
    """Sealed KV payloads are mesh-agnostic: pages exported from a
    tp-sharded engine import into a single-chip engine (and vice versa)
    and decode to the same tokens — the PD handoff may pair replicas
    with different meshes."""
    rng = np.random.RandomState(4)
    prompt = list(rng.randint(1, 250, (30,)))
    sp = SamplingParams(max_tokens=12, temperature=0.0)

    ref = PagedInferenceEngine(_cfg(), rng_seed=0).generate(
        [prompt], sp)[0]["token_ids"]
    for src_mesh, dst_mesh in (({"tp": 2}, None), (None, {"tp": 2}),
                               ({"tp": 2}, {"tp": 2})):
        src = PagedInferenceEngine(_cfg(mesh=src_mesh), rng_seed=0)
        payload = src.prefill_export(prompt, sp)
        dst = PagedInferenceEngine(_cfg(mesh=dst_mesh), rng_seed=0)
        req = dst.import_prefill(payload, sp)
        while not req.done:
            dst.step()
        got = list(req.out_ids)  # first_token is seeded by the import
        assert got == ref, (src_mesh, dst_mesh)
        assert dst.stats["mesh_reshard_bytes"] == 0


def test_mesh_tp_must_divide_heads():
    with pytest.raises(ValueError, match="must divide"):
        PagedInferenceEngine(_cfg(mesh={"tp": 3}), rng_seed=0)


def test_llmserver_engine_stats_reports_mesh():
    from ray_tpu.llm.serving import LLMConfig, LLMServer
    srv = LLMServer(LLMConfig(model_id="tiny-mesh",
                              engine=_cfg(mesh={"tp": 2}), warmup=False))
    try:
        st = srv.engine_stats()
        assert st["mesh"] == {"pp": 1, "dp": 1, "fsdp": 1, "ep": 1,
                              "sp": 1, "tp": 2}
        assert st["mesh_reshard_bytes"] == 0
    finally:
        srv._stop = True
        srv._wake.set()
