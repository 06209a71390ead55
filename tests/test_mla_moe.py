"""models/mla_moe.py — latent attention (MLA) with sigmoid-routed and
shared experts behind a dense prefix — against its plain reference
(benchmarks/reference/deepseek_v3_decoder.py: float32, expanded attention,
every token through every expert), at toy sizes on the CPU: the paged
forwards through the latent cache, the absorbed against the expanded
algebra, the router's selection bias and scale, the dense first layer, what
the module refuses, and the latent bytes the benchmark counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_mla
from benchmarks.models.deepseek_v3 import Builder
from benchmarks.reference.deepseek_v3_decoder import DeepseekV3Decoder
from ray_tpu.llm.paged_engine import PagedEngineConfig, PagedInferenceEngine
from ray_tpu.models import mla_moe

PAGE = 8
# the published keys at toy sizes (the cell's rehearsal sizes)
TOY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
           first_k_dense_replace=1, num_attention_heads=4,
           qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
           v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
           n_routed_experts=8, num_experts_per_tok=2,
           moe_intermediate_size=32, n_shared_experts=2,
           routed_scaling_factor=2.448, max_position_embeddings=1024,
           rope_theta=1e6, rms_norm_eps=1e-6, torch_dtype="float32")


def _toy(dtype):
    model = dict(TOY, torch_dtype=dtype)
    builder = Builder(model)
    return model, builder.cfg, builder.init_params(5)


def _tokens(n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 512, (n,)),
                       jnp.int32)


def _serve(cfg, params, toks, n_prefill, interpret=False, damage=None):
    """Chunked prefill of ``toks[:n_prefill]`` (two 16-token rows a
    dispatch, consecutive chunks of the one sequence, the last chunk
    partial) then one decode step a remaining token, all through the
    latent page cache. Returns the logits [len(toks) - n_prefill + 1, V]
    that predict the tokens after position n_prefill - 1. ``damage``
    rewrites the pools between prefill and decode."""
    caches = mla_moe.init_paged_cache(cfg, 24, PAGE)
    bt = jnp.arange(1, 17, dtype=jnp.int32)[None]
    chunk, out = 16, []
    for start in range(0, n_prefill, 2 * chunk):
        rows = [toks[s:s + chunk] for s in (start, start + chunk)
                if s < n_prefill]
        lens = [min(len(r), n_prefill - s) for r, s in
                zip(rows, (start, start + chunk))]
        chunks = jnp.stack([jnp.pad(r[:n], (0, chunk - n))
                            for r, n in zip(rows, lens)])
        last, caches, _ = mla_moe.prefill_paged_rows(
            params, chunks, caches, jnp.tile(bt, (len(rows), 1)),
            jnp.asarray([start, start + chunk][:len(rows)]),
            jnp.asarray(lens), cfg, page_size=PAGE, interpret=interpret)
    out.append(last[-1])
    if damage is not None:
        caches = [{n: damage(a) for n, a in layer.items()}
                  for layer in caches]
    for pos in range(n_prefill, len(toks)):
        logits, caches, _ = mla_moe.decode_paged(
            params, toks[pos:pos + 1][None], caches, bt,
            jnp.asarray([pos]), cfg, page_size=PAGE, interpret=interpret)
        out.append(logits[0])
    return jnp.stack(out)


# float32 program against the float32 reference: the two differ in the
# order of their sums alone (absorbed against expanded products, chunked
# against whole softmax), a few 1e-6 on logits of ~4; 1e-4 leaves room
F32_TOL = 1e-4
# bf16 program against the float32 reference at these toy sizes, WITH THE
# ROUTING MADE DECISIVE (a selection bias of +4 / +3 on two experts): where
# the sixth and seventh experts lie close, bf16 and float32 select
# differently and the logits jump by 1-2 (benchmarks/serve_app_routed.py has
# what that does on the chip) — a jump no tolerance on logits can tell from
# a fault. With it: the worst gap over weight seeds 0-7 is 0.043-0.117; with
# the cache rounded below bf16 (float8_e4m3, 3 mantissa bits) 0.166-0.537,
# with the absorbed query product rounded so 0.199-0.480. The line is
# between the readings.
BF16_TOL = 0.14


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["oracle", "kernel"])
def test_paged_forwards_match_the_reference_in_float32(interpret):
    model, cfg, params = _toy("float32")
    toks = _tokens(48)
    want = DeepseekV3Decoder(model).logits(params, toks)
    got = _serve(cfg, params, toks, 41, interpret=interpret)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[40:]),
                               atol=F32_TOL, rtol=0)


def _below_bf16(x):
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def test_bf16_holds_the_tolerance_and_a_lower_precision_does_not(monkeypatch):
    model = dict(TOY, torch_dtype="bfloat16")
    cfg, params = Builder(model).cfg, Builder(model).init_params(3)
    params["layers"]["router_bias"] = jnp.zeros((2, 8)).at[:, 1].set(
        4.0).at[:, 6].set(3.0)
    toks = _tokens(48, seed=3)
    want = np.asarray(DeepseekV3Decoder(model).logits(params, toks))[40:]
    gap = lambda got: float(np.abs(np.asarray(got) - want).max())  # noqa: E731
    assert gap(_serve(cfg, params, toks, 41)) <= BF16_TOL
    # the cache held below bf16: the decode steps read damaged latents
    assert gap(_serve(cfg, params, toks, 41, damage=_below_bf16)) > BF16_TOL
    # the absorbed query product (q_nope W_UK) computed below bf16
    absorbed = mla_moe._absorbed

    def low(h, p, cfg, cos, sin):
        q, entry = absorbed(h, p, cfg, cos, sin)
        return _below_bf16(q), entry
    monkeypatch.setattr(mla_moe, "_absorbed", low)
    assert gap(_serve(cfg, params, toks, 41)) > BF16_TOL


def test_absorbed_attention_is_the_expanded_one():
    """``apply`` expands per-head keys and values from the latents; the
    paged forwards fold W_UK into the query and apply W_UV after."""
    _, cfg, params = _toy("float32")
    toks = _tokens(40, seed=1)
    full = mla_moe.apply(params, toks[None], cfg)[0]
    got = _serve(cfg, params, toks, 33)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[32:]),
                               atol=2e-5, rtol=0)
    # a verify window over the same cache: every fed position's logits
    caches = mla_moe.init_paged_cache(cfg, 24, PAGE)
    bt = jnp.arange(1, 17, dtype=jnp.int32)[None]
    _, caches, _ = mla_moe.prefill_paged_rows(
        params, toks[None, :32], caches, bt, jnp.asarray([0]),
        jnp.asarray([32]), cfg, page_size=PAGE)
    logits, _, load = mla_moe.verify_paged_rows(
        params, toks[None, 32:37], caches, bt, jnp.asarray([32]), cfg,
        page_size=PAGE)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(full[32:37]), atol=2e-5, rtol=0)
    assert int(load.sum()) == 5 * mla_moe.routed_per_token(cfg)


def test_routing_selects_by_score_plus_bias_and_weighs_by_score():
    model, cfg, params = _toy("float32")
    p = mla_moe._layer_params(params, 1, cfg)
    z = jax.random.normal(jax.random.PRNGKey(2), (1, 64, cfg.dim))
    # a bias that decides: expert 5 always in, expert 0 never
    p["router_bias"] = jnp.zeros((8,)).at[5].set(4.0).at[0].set(-4.0)
    weights, idx = mla_moe.route(z, p, cfg)
    scores = jax.nn.sigmoid(z @ p["w_router"])
    assert bool((idx == 5).any(-1).all()) and not bool((idx == 0).any())
    by_score = jax.lax.top_k(scores, 2)[1]
    assert not np.array_equal(np.sort(idx, -1), np.sort(by_score, -1))
    # the weights are the scores WITHOUT the bias, normalised over the
    # selected experts and scaled: they sum to routed_scaling_factor
    picked = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.asarray(2.448 * picked / picked.sum(-1, keepdims=True)),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.448,
                               rtol=1e-5)
    ref_w, ref_e = DeepseekV3Decoder(model).routing(
        z[0], p["w_router"], p["router_bias"])
    assert np.array_equal(np.asarray(ref_e), np.asarray(idx[0]))
    np.testing.assert_allclose(np.asarray(ref_w), np.asarray(weights[0]),
                               rtol=1e-5)
    # the block: routed experts by hand + the shared expert ONCE + x
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, cfg.dim))
    y, load = mla_moe._ffn_block(x, p, cfg, False)
    zz = mla_moe.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    w2, i2 = mla_moe.route(zz, p, cfg)
    li = p["expert_layer"]
    want = x + mla_moe._swiglu(zz, p["ws_gate"], p["ws_up"], p["ws_down"])
    for j in range(2):
        e = i2[0, :, j]
        h = jnp.einsum("sd,sdf->sf", zz[0], p["w_gate"][li][e])
        u = jnp.einsum("sd,sdf->sf", zz[0], p["w_up"][li][e])
        want = want + (w2[0, :, j, None] * jnp.einsum(
            "sf,sfd->sd", jax.nn.silu(h) * u, p["w_down"][li][e]))[None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert int(load.sum()) == 64 * 2 and load.shape == (8,)


def test_the_first_layer_is_dense_and_routes_nothing():
    _, cfg, params = _toy("float32")
    p0 = mla_moe._layer_params(params, 0, cfg)
    assert "w_router" not in p0 and p0["w_gate"].shape == (64, 128)
    assert params["layers"]["w_gate"].shape == (2, 8, 64, 32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, cfg.dim))
    y, load = mla_moe._ffn_block(x, p0, cfg, False)
    z = mla_moe.rms_norm(x, p0["mlp_norm"], cfg.norm_eps)
    want = x + (jax.nn.silu(z @ p0["w_gate"]) * (z @ p0["w_up"])
                ) @ p0["w_down"]
    assert load is None
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-6)
    # a model that is all dense prefix has no load, as a dense llama
    dense = mla_moe.mla_moe_tiny(n_layers=1, n_dense_layers=1)
    assert mla_moe.routed_per_token(dense) == 0
    assert mla_moe.routed_per_token(cfg) == 2 * 2


def test_the_engine_serves_it_and_counts_its_pages_as_the_benchmark_does():
    model, cfg, params = _toy("float32")
    eng = PagedInferenceEngine(PagedEngineConfig(
        model=cfg, max_batch_size=2, page_size=PAGE, num_pages=32,
        max_pages_per_seq=16, chunk_size=16), params=params)
    assert [set(layer) for layer in eng.caches] == [{"ckv"}] * 3
    # 40 latent values a token pad to one 128-lane tile, float32 here
    assert eng.page_nbytes == 3 * PAGE * flops_mla.pool_bytes_per_token_layer(
        model, dtype_bytes=4) == 3 * PAGE * 128 * 4
    assert flops_mla.latent_bytes_per_token_layer(model, 4) == 40 * 4
    toks = _tokens(30, seed=6)
    from ray_tpu.llm import SamplingParams
    out = eng.generate([list(map(int, toks))],
                       SamplingParams(max_tokens=5))[0]
    ids = list(map(int, toks))
    for tok in out["token_ids"]:
        logits = mla_moe.apply(params, jnp.asarray([ids]), cfg)[0, -1]
        assert tok == int(jnp.argmax(logits))
        ids.append(tok)
    assert eng.stats["moe_assign_run"] > 0


def test_what_it_does_not_compute_is_refused_by_the_field_that_asks():
    cfg = mla_moe.mla_moe_tiny()
    kw = dict(model=cfg, max_batch_size=2, page_size=PAGE, num_pages=16,
              max_pages_per_seq=8, chunk_size=16)
    with pytest.raises(ValueError, match="max_adapters"):
        PagedInferenceEngine(PagedEngineConfig(max_adapters=2, **kw))
    with pytest.raises(NotImplementedError, match="PagedEngineConfig.mesh"):
        PagedInferenceEngine(PagedEngineConfig(mesh={"tp": 2}, **kw))
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            Builder(dict(TOY, **{key: value}))
