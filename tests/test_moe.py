"""Mixture of experts (models/llama.py ``_moe_ffn`` over
ops/grouped_matmul.py): dropless top-k routing against the plain reference
(benchmarks/reference/olmoe_decoder.py) on seeded weights — forward,
chunked paged prefill then paged decode through the engine, gradients —
independence of a sequence from whatever shares its dispatch, the Pallas
kernels in interpret mode, expert parallelism, and the engine's counters.

Tolerances. Everything here is float32 on the CPU, where the program and
the reference differ only in summation order (grouped rows against a loop
over experts, a paged cache against a full forward): logits of O(1) agree
to a few 1e-6, and 2e-4 leaves room for 2-layer accumulation while a bf16
step anywhere (3 significant digits, 1e-2 on such logits) fails it. The
ROUTED EXPERT SETS must be identical, which the comparisons imply: one
swapped expert moves a logit by 1e-2 or more.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference.olmoe_decoder import OlmoeDecoder
from ray_tpu.llm.paged_engine import (
    PagedEngineConfig, PagedInferenceEngine, SamplingParams,
)
from ray_tpu.models import llama
from ray_tpu.models.llama import _moe_ffn
from ray_tpu.ops import grouped_matmul as gmm
from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
from ray_tpu.parallel.sharding import logical_sharding

TOL = dict(rtol=2e-4, atol=2e-4)
SIZES = {"8x2": (8, 2), "64x8": (64, 8)}


def _moe_cfg(**kw):
    defaults = dict(vocab_size=128, dim=32, n_layers=2, n_heads=4,
                    n_kv_heads=2, mlp_dim=64, max_seq_len=64,
                    moe_experts=4, moe_top_k=2)
    defaults.update(kw)
    return llama.llama_tiny(**defaults)


def _olmoe(experts: int, top_k: int, **kw):
    """OLMoE's block at a tiny size: QK-norm, MHA, no renormalisation;
    and the same sizes as the reference reads them."""
    cfg = llama.llama_tiny(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
        mlp_dim=32, max_seq_len=256, rope_theta=1e4, qk_norm=True,
        moe_experts=experts, moe_top_k=top_k, moe_renormalize=False, **kw)
    model = {"hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 4, "intermediate_size": 32,
             "num_experts": experts, "num_experts_per_tok": top_k,
             "norm_topk_prob": False, "rope_theta": 1e4,
             "rms_norm_eps": cfg.norm_eps, "vocab_size": 256}
    return cfg, model


def _seeded(cfg, seed=0):
    """Seeded weights with norm gains off 1, so that a norm left out or
    applied to the wrong vector shows."""
    params = llama.init(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        if name in params["layers"]:
            key, sub = jax.random.split(key)
            g = params["layers"][name]
            params["layers"][name] = g + 0.3 * jax.random.normal(
                sub, g.shape, g.dtype)
    return params


def _layer0(params):
    return jax.tree.map(lambda a: a[0], params["layers"])


def _tokens(seed, shape, vocab=256):
    return jnp.asarray(np.random.RandomState(seed).randint(0, vocab, shape),
                       jnp.int32)


# -- the expert layer alone --------------------------------------------------

def test_moe_ffn_matches_dense_expert_eval():
    """The grouped output equals the direct per-token mixture
    sum_j gate_j * expert_{sel_j}(h), gates renormalised (the default)."""
    cfg = _moe_cfg()
    rng = np.random.RandomState(0)
    E, D, F = cfg.moe_experts, cfg.dim, cfg.mlp_dim
    p = {
        "w_router": jnp.asarray(rng.randn(D, E), jnp.float32),
        "w_gate": jnp.asarray(rng.randn(E, D, F) * 0.1, jnp.float32),
        "w_up": jnp.asarray(rng.randn(E, D, F) * 0.1, jnp.float32),
        "w_down": jnp.asarray(rng.randn(E, F, D) * 0.1, jnp.float32),
    }
    h = jnp.asarray(rng.randn(2, 8, D), jnp.float32)
    out, aux, load = _moe_ffn(h, p, cfg)
    assert np.isfinite(float(aux))
    assert int(load.sum()) == 2 * 8 * cfg.moe_top_k

    ht = np.asarray(h).reshape(-1, D)
    probs = np.asarray(jax.nn.softmax(ht @ np.asarray(p["w_router"])))
    want = np.zeros_like(ht)
    for t in range(ht.shape[0]):
        sel = np.argsort(-probs[t])[:cfg.moe_top_k]
        gates = probs[t][sel] / probs[t][sel].sum()
        for g, e in zip(gates, sel):
            a = ht[t] @ np.asarray(p["w_gate"][e])
            silu = a / (1 + np.exp(-a))
            b = ht[t] @ np.asarray(p["w_up"][e])
            want[t] += g * ((silu * b) @ np.asarray(p["w_down"][e]))
    np.testing.assert_allclose(np.asarray(out).reshape(-1, D), want, **TOL)


def test_all_tokens_to_one_expert_lose_nothing():
    """A router that sends every token to expert 2 first (and 0 second):
    48 assignments in one group, none in two others. Capacity dispatch
    zeroed the overflow; dropless, every token equals its own mixture."""
    cfg = _moe_cfg()
    rng = np.random.RandomState(1)
    E, D, F = cfg.moe_experts, cfg.dim, cfg.mlp_dim
    p = {"w_router": jnp.zeros((D, E), jnp.float32),
         "w_gate": jnp.asarray(rng.randn(E, D, F) * 0.1, jnp.float32),
         "w_up": jnp.asarray(rng.randn(E, D, F) * 0.1, jnp.float32),
         "w_down": jnp.asarray(rng.randn(E, F, D) * 0.1, jnp.float32)}
    # positive inputs and a router of positive columns 2 > 0 > others
    h = jnp.asarray(np.abs(rng.randn(3, 16, D)) + 0.1, jnp.float32)
    p["w_router"] = p["w_router"].at[:, 2].set(1.0).at[:, 0].set(0.5)
    out, _, load = _moe_ffn(h, p, cfg)
    assert np.asarray(load).tolist() == [48, 0, 48, 0]

    probs = jax.nn.softmax(h @ p["w_router"], -1)
    gates = probs[..., [2, 0]] / probs[..., [2, 0]].sum(-1, keepdims=True)

    def expert(e):
        return (jax.nn.silu(h @ p["w_gate"][e]) * (h @ p["w_up"][e])
                ) @ p["w_down"][e]
    want = gates[..., :1] * expert(2) + gates[..., 1:] * expert(0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)
    assert not np.any(np.all(np.asarray(out) == 0, axis=-1))


@pytest.mark.parametrize("experts,top_k", SIZES.values(), ids=SIZES)
def test_kernels_in_interpret_mode_match_ragged_dot(experts, top_k):
    """The two Pallas kernels over the tile-aligned layout (dead tiles,
    padding rows, one layer of a stack read in place) against the
    ``jax.lax.ragged_dot`` path that is the CPU fallback, and the
    gradient of the custom_vjp against that path's own."""
    cfg, _ = _olmoe(experts, top_k)
    p = _layer0(_seeded(cfg))
    h = jax.random.normal(jax.random.PRNGKey(3), (3, 16, cfg.dim))
    want, _, load = _moe_ffn(h, p, cfg)
    got, _, load_k = _moe_ffn(h, p, cfg, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    assert np.array_equal(np.asarray(load), np.asarray(load_k))

    # the serving paths' form: the stacks and a layer index
    stacked = llama._layer_params(_seeded(cfg), 1)
    assert stacked["w_gate"].ndim == 4 and stacked["expert_layer"] == 1
    p1 = jax.tree.map(lambda a: a[1], _seeded(cfg)["layers"])
    np.testing.assert_allclose(
        np.asarray(_moe_ffn(h, stacked, cfg, interpret=True)[0]),
        np.asarray(_moe_ffn(h, p1, cfg)[0]), **TOL)

    def loss(h, w, interpret):
        return jnp.sum(_moe_ffn(h, dict(p, w_down=w), cfg,
                                interpret=interpret)[0] ** 2)
    for a, b in zip(jax.grad(loss, (0, 1))(h, p["w_down"], True),
                    jax.grad(loss, (0, 1))(h, p["w_down"], False)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


@pytest.mark.parametrize("tm", [16, 128])
@pytest.mark.parametrize("d,f", [(256, 128), (384, 256)])
def test_kernels_in_interpret_mode_match_ragged_dot_over_dead_tiles(d, f,
                                                                    tm):
    """The kernels by themselves over a layout with an empty expert, a
    group of more than one tile and dead tiles past ``n_live``, at the
    smallest and the largest row tile, one layer of a stack read in place
    in whole-expert blocks, against ``jax.lax.ragged_dot``."""
    experts = 4
    rng = np.random.RandomState(tm + d)
    # expert 2 gets nothing; expert 1 more than one tile; 3 is a remainder
    counts = [tm // 2, tm + 3, 0, 5]
    expert_of = rng.permutation(np.repeat(np.arange(experts), counts))
    row_of, padded, tile_expert, n_live = gmm.group_layout(
        jnp.asarray(expert_of, jnp.int32), None, experts, tm)
    tiles = gmm.num_tiles(len(expert_of), experts, tm)
    assert np.asarray(padded).tolist() == [tm, 2 * tm, 0, tm]
    assert int(n_live[0]) == 4 < tiles
    x = np.zeros((tiles * tm, d), np.float32)
    x[np.asarray(row_of)] = rng.randn(len(expert_of), d)
    w = [jnp.asarray(rng.randn(2, experts, *shape) * 0.1, jnp.float32)
         for shape in ((d, f), (d, f), (f, d))]
    got = gmm.grouped_ffn(jnp.asarray(x), *w, padded, tile_expert, n_live,
                          tm, 1, True)
    want = gmm.grouped_ffn_reference(jnp.asarray(x), *w, padded, 1)
    live = int(n_live[0]) * tm
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[:live], **TOL)
    assert np.abs(np.asarray(want)[np.asarray(row_of)]).min() > 0


@pytest.mark.parametrize("name,stated", [
    ("olmoe7b", True), ("mellum2-12b", True), ("kanana2-30b", False),
    ("qwen3next-80b", False), ("ling3flash-125b", True)])
def test_the_vmem_scope_at_the_published_widths(name, stated):
    """`_vmem_scope` at each serving configuration's (hidden, expert
    width), both calls, decode and prefill tiles: two buffers of whole
    experts fit what a call may state; the down call, one operand, fits
    the compiler's default everywhere and so do both calls of kanana and
    Qwen3-Next, which therefore state nothing (kanana's prefill keeps a
    layer's expert rows in VMEM beside the default scope and loses them
    beside a larger one: PERF.md section 6, PR 54); a fused call that
    states a scope states what its buffers need, not the ceiling."""
    cfg = json.loads((pathlib.Path(__file__).parent.parent / "benchmarks"
                      / "configs" / f"{name}-serve-1chip.json").read_text())
    d = cfg["hidden_size"]
    f = cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    for tm in (16, 32, 128):
        assert gmm._vmem_scope(tm, f, d, 1, 2) is None
    fused = gmm._vmem_scope(128, d, f, 2, 2)
    assert (fused is not None) == stated
    if stated:
        assert 4 * d * f * 2 < fused <= gmm._VMEM_SCOPE
        assert fused < 4 * d * f * 2 + 4 * 2 ** 20


def test_an_expert_beyond_the_vmem_scope_is_refused_by_name():
    """Two buffers of Mixtral's fused pair, 2 x [4096, 14336], are 448
    MiB: the call says so with its shapes where it is traced, before the
    chip's compiler could fail on an allocation."""
    with pytest.raises(ValueError, match=r"\[4096, 14336\].*rows of K"):
        gmm._vmem_scope(16, 4096, 14336, 2, 2)
    x = jnp.zeros((16, 4096), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((1, 2, 4096, 14336), jnp.bfloat16)
    with pytest.raises(ValueError, match="grouped matmul"):
        jax.eval_shape(
            lambda x, w: gmm._gmm_call(
                x, (w, w), jnp.zeros((1,), jnp.int32),
                jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
                tm=16, interpret=True), x, w)


def test_group_layout_is_tile_aligned_and_stable():
    expert_of = jnp.asarray([3, 0, 3, 3, 1, 0, 3, 9], jnp.int32)
    owned = expert_of < 4                       # 9: another shard's expert
    row_of, padded, tile_expert, n_live = gmm.group_layout(
        expert_of, owned, 4, 2)
    assert np.asarray(padded).tolist() == [2, 2, 0, 4]
    # groups start at whole tiles, members keep their order
    assert np.asarray(row_of)[:7].tolist() == [4, 0, 5, 6, 2, 1, 7]
    assert int(row_of[7]) == gmm.num_tiles(8, 4, 2) * 2      # dropped
    assert int(n_live[0]) == 4
    assert np.asarray(tile_expert)[:4].tolist() == [0, 1, 3, 3]
    assert set(np.asarray(tile_expert)[4:].tolist()) == {3}  # dead tiles


# -- the model against the plain reference ----------------------------------

@pytest.mark.parametrize("experts,top_k", SIZES.values(), ids=SIZES)
def test_apply_logits_match_the_reference(experts, top_k):
    cfg, model = _olmoe(experts, top_k)
    params = _seeded(cfg)
    tokens = _tokens(0, (2, 48))
    got = llama.apply(params, tokens, cfg)
    ref = OlmoeDecoder(model)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(got[b]), np.asarray(ref.logits(params, tokens[b])),
            **TOL)


def test_routed_expert_sets_match_the_reference():
    """The program's own routing of a layer's input against the
    reference's ``routing``: the same experts, the same weights, no
    renormalisation (the weights sum to less than one)."""
    cfg, model = _olmoe(64, 8)
    p = _layer0(_seeded(cfg))
    h = jax.random.normal(jax.random.PRNGKey(5), (40, cfg.dim))
    top_p, top_e = OlmoeDecoder(model).routing(h, p["w_router"])
    probs = jax.nn.softmax(h @ p["w_router"], -1)
    got_p, got_e = jax.lax.top_k(probs, 8)
    assert np.array_equal(np.asarray(got_e), np.asarray(top_e))
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(top_p),
                               rtol=1e-6)
    assert float(top_p.sum(-1).max()) < 0.9
    # and the layer's load counts exactly those experts
    load = _moe_ffn(h[None], p, cfg)[2]
    assert np.array_equal(np.asarray(load),
                          np.bincount(np.asarray(top_e).ravel(),
                                      minlength=64))


def test_loss_gradient_matches_the_reference():
    """d loss / d every parameter against jax.grad of the reference's
    loss. Gradients of O(1e-2..1) agree to float32 summation order; the
    router's gradient flows through the gate weights only (top_k's
    indices carry none), in both."""
    cfg, model = _olmoe(8, 2)
    params = _seeded(cfg)
    tokens = _tokens(1, (2, 25))
    ref = OlmoeDecoder(model)

    def loss(p):
        return llama.cross_entropy_loss(llama.apply(p, tokens[:, :-1], cfg),
                                        tokens[:, 1:])
    got = jax.grad(loss)(params)
    want = jax.grad(lambda p: ref.loss(p, tokens))(params)
    np.testing.assert_allclose(float(loss(params)),
                               float(ref.loss(params, tokens)), rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    for (path, a), b in zip(flat_got, flat_want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))
    assert float(jnp.abs(got["layers"]["w_router"]).max()) > 0


def _engine(cfg, params, **kw):
    defaults = dict(model=cfg, max_batch_size=4, page_size=8, num_pages=64,
                    max_pages_per_seq=16, chunk_size=16, prefill_rows=2,
                    decode_window=4, enable_prefix_caching=False)
    defaults.update(kw)
    return PagedInferenceEngine(PagedEngineConfig(**defaults), params)


def _serve(eng, prompts, n_new):
    reqs = [eng.submit(p, SamplingParams(max_tokens=n_new, temperature=0.0))
            for p in prompts]
    eng.run_until_done(reqs)
    return [r.out_ids for r in reqs]


@pytest.mark.parametrize("experts,top_k", SIZES.values(), ids=SIZES)
def test_engine_prefill_then_decode_matches_the_reference(experts, top_k):
    """Chunked paged prefill (a 40-token prompt is three 16-token chunks,
    the last one padded) then windowed paged decode through
    PagedInferenceEngine, idle decode rows beside the live ones, against
    the reference's full forward over prompt + served tokens: logits, not
    tokens — every served token must be the reference's argmax up to TOL
    (the gap between the reference's largest logit and the served token's
    is 0 or float32 noise)."""
    cfg, model = _olmoe(experts, top_k)
    params = _seeded(cfg)
    eng = _engine(cfg, params)
    prompts = [_tokens(7, (40,)).tolist(), _tokens(8, (9,)).tolist()]
    served = _serve(eng, prompts, 6)
    ref = OlmoeDecoder(model)
    for prompt, out in zip(prompts, served):
        assert len(out) == 6
        logits = np.asarray(ref.logits(
            params, jnp.asarray(prompt + out, jnp.int32)))
        rows = logits[len(prompt) - 1:len(prompt) - 1 + 6]
        gap = rows.max(-1) - rows[np.arange(6), out]
        assert gap.max() <= TOL["atol"], gap


def test_paged_logits_match_the_reference():
    """The same path one level down, where the logits themselves can be
    compared: two prefill chunks then two decode steps over the pages."""
    cfg, model = _olmoe(8, 2)
    params = _seeded(cfg)
    page = 8
    caches = llama.init_paged_cache(cfg, 16, page)
    seq = _tokens(9, (26,))
    bt = jnp.arange(1, 9, dtype=jnp.int32)
    want = np.asarray(OlmoeDecoder(model).logits(params, seq))
    lg0, caches, load = llama.prefill_paged_chunk(
        params, seq[None, :16], caches, bt, jnp.int32(0), cfg,
        page_size=page)
    assert int(load.sum()) == 16 * cfg.moe_top_k * cfg.n_layers
    chunk1 = jnp.zeros((1, 16), jnp.int32).at[0, :8].set(seq[16:24])
    lg1, caches, _ = llama.prefill_paged_chunk(
        params, chunk1, caches, bt, jnp.int32(16), cfg, page_size=page,
        true_chunk_len=jnp.int32(8))
    np.testing.assert_allclose(np.asarray(lg0), want[:16], **TOL)
    np.testing.assert_allclose(np.asarray(lg1[:8]), want[16:24], **TOL)
    for pos in (24, 25):
        toks = jnp.zeros((4, 1), jnp.int32).at[2, 0].set(seq[pos])
        bts = jnp.zeros((4, 8), jnp.int32).at[2].set(bt)
        lens = jnp.zeros((4,), jnp.int32).at[2].set(pos)
        lg, caches, load = llama.decode_paged(
            params, toks, caches, bts, lens, cfg, page_size=page)
        np.testing.assert_allclose(np.asarray(lg[2]), want[pos], **TOL)
        assert int(load.sum()) == 4 * cfg.moe_top_k * cfg.n_layers


def test_a_sequence_does_not_depend_on_what_shares_its_dispatch():
    """Capacity dispatch made a row's logits depend on its neighbours
    (and on idle rows and pad tokens, which took slots). Dropless: the
    same sequence alone, beside other rows, and in a chunk with pad
    tokens gives the same logits, to float32 summation order."""
    cfg, _ = _olmoe(8, 2)
    params = _seeded(cfg)
    seq = _tokens(11, (1, 24))
    alone = np.asarray(llama.apply(params, seq, cfg)[0])
    others = jnp.concatenate([_tokens(12, (3, 24)), seq,
                              jnp.zeros((2, 24), jnp.int32)])
    np.testing.assert_allclose(
        np.asarray(llama.apply(params, others, cfg)[3]), alone, **TOL)
    # one padded chunk through the paged path: 24 real tokens of 32
    caches = llama.init_paged_cache(cfg, 8, 8)
    chunk = jnp.zeros((1, 32), jnp.int32).at[0, :24].set(seq[0])
    lg, _, _ = llama.prefill_paged_chunk(
        params, chunk, caches, jnp.arange(1, 5, dtype=jnp.int32),
        jnp.int32(0), cfg, page_size=8, true_chunk_len=jnp.int32(24))
    np.testing.assert_allclose(np.asarray(lg[:24]), alone, **TOL)
    # a decode row beside idle rows and beside other live rows
    eng_a = _engine(cfg, params)
    eng_b = _engine(cfg, params)
    prompt = seq[0].tolist()
    only = _serve(eng_a, [prompt], 5)[0]
    crowd = _serve(eng_b, [_tokens(13, (30,)).tolist(), prompt,
                           _tokens(14, (5,)).tolist()], 5)[1]
    assert only == crowd


# -- the engine's counters ---------------------------------------------------

def test_engine_counts_routed_work_for_moe_configs_only():
    cfg, _ = _olmoe(8, 2)
    eng = _engine(cfg, _seeded(cfg))
    _serve(eng, [_tokens(15, (20,)).tolist()], 5)
    st = eng.stats
    per_token = cfg.moe_top_k * cfg.n_layers
    # prefill: 2 chunk-rows of 16 ran for 20 tokens; decode: 4 rows a
    # step ran for 1 live row
    assert st["moe_assign_run"] == st["moe_expert_load_sum"]
    assert st["moe_assign_run"] == per_token * (
        st["prefill_rows_padded"] * 16 + 4 * st["decode_steps"])
    assert st["moe_assign_live"] == per_token * (20 + st["decode_steps"])
    dispatches = st["prefill_dispatches"] + st["decode_dispatches"]
    assert st["moe_expert_load_max"] >= st["moe_expert_load_sum"] / 8
    assert st["moe_expert_load_max"] <= st["moe_expert_load_sum"]
    assert dispatches >= 2

    dense = llama.llama_tiny(vocab_size=256, max_seq_len=256)
    eng = _engine(dense, llama.init(jax.random.PRNGKey(0), dense))
    _serve(eng, [_tokens(15, (20,)).tolist()], 3)
    assert not [k for k in eng.stats if k.startswith("moe_")]


def test_num_params_counts_what_init_makes():
    for cfg in (_olmoe(8, 2)[0], _moe_cfg(), llama.llama_tiny()):
        params = llama.init(jax.random.PRNGKey(0), cfg)
        assert cfg.num_params() == sum(
            a.size for a in jax.tree.leaves(params))


# -- training and expert parallelism ----------------------------------------

@pytest.mark.slow
def test_moe_model_trains_and_aux_flows():
    cfg = _moe_cfg()
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 17)),
        jnp.int32)

    import optax
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            logits, aux = llama.apply_with_aux(p, tokens[:, :-1], cfg)
            ce = llama.cross_entropy_loss(logits, tokens[:, 1:])
            return ce + cfg.moe_aux_weight * aux, (ce, aux)
        (loss, (ce, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, ce, aux

    router0 = np.asarray(params["layers"]["w_router"]).copy()
    ces = []
    for _ in range(10):
        params, opt_state, ce, aux = step(params, opt_state)
        ces.append(float(ce))
        assert np.isfinite(float(aux)) and float(aux) > 0
    assert ces[-1] < ces[0] * 0.9, ces
    # router weights actually receive gradient: they moved from init
    router_delta = np.abs(np.asarray(params["layers"]["w_router"]) - router0)
    assert router_delta.max() > 1e-6, "router never updated"


@pytest.mark.parametrize("spec", [dict(ep=4, dp=2), dict(ep=2, tp=2, dp=2)],
                         ids=["ep4_dp2", "ep2_tp2_dp2"])
def test_moe_sharded_over_ep_matches_unsharded(spec):
    """Each shard runs its own experts (and its slice of their width)
    over the tokens it sees, and the shards' parts are summed."""
    cfg = _moe_cfg()
    mesh = build_mesh(MeshSpec(**spec))
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 16)),
        jnp.int32)
    want = llama.apply(params, tokens, cfg)

    with use_mesh(mesh):
        sh = logical_sharding(llama.logical_axes(cfg), mesh)
        sharded = jax.device_put(params, sh)
        got = jax.jit(lambda p, t: llama.apply(p, t, cfg))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
