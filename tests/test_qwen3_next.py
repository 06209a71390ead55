"""models/qwen3_next.py through the paged engine against the benchmark's
plain reference (benchmarks/reference/qwen3_next_decoder.py), at a small
size in float32: one period (three gated-delta-rule layers, one gated
attention layer), hidden 64, 8 experts top-2 with 4 held.

Tolerances. Logits here are ~N(0, 1.5^2) (largest ~4). Program and
reference are both float32 and differ in the ORDER of their sums alone —
the chunked WY form against the token scan, a paged softmax against a
dense one, tokens grouped by expert against every token through every
expert: 1e-5 measured, 2e-4 allowed (LOGIT_TOL). A state, a convolution
tail, a rotation or a gate that is wrong moves logits by 1e-1 and more.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.models.qwen3_next import Builder
from benchmarks.reference.qwen3_next_decoder import Qwen3NextDecoder
from ray_tpu.llm.engine import _Request
from ray_tpu.llm.kv_cache import KVCache, STATE_COUNTERS
from ray_tpu.llm.paged_engine import (PagedEngineConfig,
                                      PagedInferenceEngine, SamplingParams,
                                      derived_prefill_rows)
from ray_tpu.models import qwen3_next as qn
from ray_tpu.ops import gated_delta as gd

LOGIT_TOL = 2e-4
PAGE, CHUNK = 8, 32

MODEL = dict(
    hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, partial_rotary_factor=0.25, rope_theta=1e7,
    full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=4,
    experts_held=[0, 4], experts_routed=8, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_hidden_layers=4, rms_norm_eps=1e-6, vocab_size=256,
    max_position_embeddings=512, torch_dtype="float32")


@pytest.fixture(scope="module")
def model():
    builder = Builder(MODEL)
    assert builder.cfg == qn.qwen3_next_tiny()
    return builder.cfg, builder.init_params(3), Qwen3NextDecoder(MODEL)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _engine(cfg, params, **over):
    kw = dict(model=cfg, max_batch_size=4, page_size=PAGE, num_pages=128,
              num_state_snapshots=6, max_pages_per_seq=32, chunk_size=CHUNK,
              prefill_rows=4, decode_window=4)
    kw.update(over)
    return PagedInferenceEngine(PagedEngineConfig(**kw), params)


# ---------------------------------------------------------------------------
# The forwards, table by table: logits at every position returned
# ---------------------------------------------------------------------------

def _prefill_then_decode(cfg, params, prompt, extra, dispatches):
    """The prompt through ``prefill_paged_rows`` as ``dispatches`` (each a
    list of (start, tokens) rows of ONE sequence in slot 0), then ``extra``
    through ``decode_paged`` a token at a time. Returns the logits at the
    last position of each dispatch's last row and after each decode."""
    caches = qn.init_paged_cache(cfg, 32, PAGE, state_slots=2,
                                 state_snapshots=1)
    table = np.zeros((1, 16), np.int32)
    table[0] = np.arange(1, 17)
    out, started = [], False
    for rows in dispatches:
        r = len(rows)
        chunks = np.zeros((r, CHUNK), np.int32)
        st = np.zeros((r, 5), np.int32)
        for i, (pos, n) in enumerate(rows):
            chunks[i, :n] = prompt[pos:pos + n]
            st[i, qn.LOAD] = 1
            st[i, qn.MODE] = qn.CHAIN if i else (
                qn.CONTINUE if started else qn.FRESH)
            st[i, qn.STORE] = 1 if i + 1 == r else 0
        started = True
        logits, caches, _ = qn.prefill_paged_rows(
            params, jnp.asarray(chunks), caches,
            (jnp.asarray(np.repeat(table, r, 0)), jnp.asarray(st)),
            jnp.asarray([p for p, _ in rows]),
            jnp.asarray([n for _, n in rows]), cfg, page_size=PAGE)
        out.append(logits[-1])
    pos = len(prompt)
    for tok in extra:
        logits, caches, _ = qn.decode_paged(
            params, jnp.asarray([[tok]]), caches,
            (jnp.asarray(table), jnp.asarray([1])), jnp.asarray([pos]),
            cfg, page_size=PAGE)
        out.append(logits[0])
        pos += 1
    return out


@pytest.mark.parametrize("n_prompt,dispatches", [
    # a chunk a dispatch, the last one short
    (77, [[(0, 32)], [(32, 32)], [(64, 13)]]),
    # every chunk a row of ONE dispatch: the state and the convolution's
    # tail are chained inside each layer call
    (77, [[(0, 32), (32, 32), (64, 13)]]),
    # rows cut at a page boundary (where a snapshot is taken), and a last
    # row shorter than the convolution's tail: the new tail is drawn from
    # the old tail and the row
    (66, [[(0, 32), (32, 24)], [(56, 8), (64, 2)]]),
    (33, [[(0, 32), (32, 1)]]),
], ids=["a_dispatch_a_chunk", "rows_of_one_dispatch", "cut_at_pages",
        "one_token_row"])
def test_prefill_then_decode_gives_the_reference_logits(model, n_prompt,
                                                        dispatches):
    cfg, params, ref = model
    seq = _tokens(n_prompt + 5, seed=n_prompt)
    want = ref.logits(params, jnp.asarray(seq))
    got = _prefill_then_decode(cfg, params, seq[:n_prompt], seq[n_prompt:],
                               dispatches)
    ends = [rows[-1][0] + rows[-1][1] - 1 for rows in dispatches] + list(
        range(n_prompt, n_prompt + 5))
    for logits, at in zip(got, ends):
        np.testing.assert_allclose(logits, want[at], atol=LOGIT_TOL, rtol=0)


def test_the_model_module_is_the_reference(model):
    """``apply`` (no cache) against the independent reference."""
    cfg, params, ref = model
    seq = jnp.asarray(_tokens(96, seed=1))
    np.testing.assert_allclose(qn.apply(params, seq[None], cfg)[0],
                               ref.logits(params, seq), atol=LOGIT_TOL,
                               rtol=0)


# ---------------------------------------------------------------------------
# Through the engine: tokens and their log-probabilities
# ---------------------------------------------------------------------------

def _served(eng, prompt, n, **params):
    out = eng.generate([prompt], SamplingParams(
        max_tokens=n, temperature=0.0, logprobs=True, **params))[0]
    return out["token_ids"], out["logprobs"]


def _reference_greedy(ref, params, prompt, toks):
    """Log-probabilities the reference gives the served tokens, and whether
    each is its argmax."""
    logits = ref.logits(params, jnp.asarray(prompt + toks))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    logp = jax.nn.log_softmax(rows, axis=-1)
    idx = jnp.asarray(toks)
    return (np.asarray(jnp.take_along_axis(logp, idx[:, None], 1)[:, 0]),
            bool((rows.argmax(-1) == idx).all()))


@pytest.mark.parametrize("n_prompt", [77, 64, 20])
def test_the_engine_serves_the_reference(model, n_prompt):
    cfg, params, ref = model
    eng = _engine(cfg, params)
    prompt = _tokens(n_prompt, seed=n_prompt + 1)
    toks, lps = _served(eng, prompt, 9)
    want, greedy = _reference_greedy(ref, params, prompt, toks)
    assert greedy
    np.testing.assert_allclose(lps, want, atol=LOGIT_TOL, rtol=0)
    # what the programs hand back beside the tokens: every assignment
    # routed (2 a token a layer, pads and idle rows too), those that fell
    # on the 4 held of 8, and the distinct held experts a layer a decode
    # step reached (at most 4 of them, in each of 4 layers)
    st = eng.stats
    assert st["moe_expert_load_sum"] == st["moe_assign_run"]
    assert 0 < st["moe_assign_held"] < st["moe_expert_load_sum"]
    assert 0 < st["moe_held_hit_decode"] <= 4 * 4 * st["decode_steps"]


def test_a_later_turn_resumes_a_snapshot_and_serves_what_a_cold_run_does(
        model):
    cfg, params, ref = model
    eng = _engine(cfg, params)
    first = _tokens(77, seed=5)
    toks, _ = _served(eng, first, 6)
    turn = first + toks + _tokens(20, seed=6)
    got = _served(eng, turn, 7)
    st = eng.stats
    # resumed where the first prompt's last whole page ended: 72 of 103
    assert st["state_snapshot_hits"] == 1 and st["state_hit_tokens"] == 72
    assert st["prefix_tokens_saved"] == 72
    # the full pages of the answer beyond it were found and not trusted
    assert st["state_pages_untrusted"] >= 1
    cold = _served(_engine(cfg, params), turn, 7)
    assert got[0] == cold[0]
    np.testing.assert_allclose(got[1], cold[1], atol=LOGIT_TOL, rtol=0)
    want, greedy = _reference_greedy(ref, params, turn, got[0])
    assert greedy
    np.testing.assert_allclose(got[1], want, atol=LOGIT_TOL, rtol=0)


def test_a_long_prompt_asked_again_resumes_where_a_dispatch_left_it(model):
    """A document under two questions shares no end-of-prompt snapshot:
    the second resumes at the last dispatch boundary inside what is
    shared."""
    cfg, params, ref = model
    eng = _engine(cfg, params, prefill_rows=2)
    doc = _tokens(150, seed=7)
    _served(eng, doc + _tokens(10, seed=8), 3)
    ask = doc + _tokens(12, seed=9)
    toks, lps = _served(eng, ask, 5)
    assert eng.stats["state_hit_tokens"] == 128     # two dispatches of 64
    want, greedy = _reference_greedy(ref, params, ask, toks)
    assert greedy
    np.testing.assert_allclose(lps, want, atol=LOGIT_TOL, rtol=0)


def test_a_preempted_request_resumes_from_its_snapshot(model):
    """A pool gone dry ends a request early (the engine's preemption); what
    it had, sent again, resumes the prompt's snapshot and goes on as the
    reference does."""
    cfg, params, ref = model
    eng = _engine(cfg, params, num_pages=13, decode_window=2)
    prompt = _tokens(77, seed=11)
    toks, _ = _served(eng, prompt, 40)
    assert len(toks) < 40           # 12 pages hold 96 tokens
    more, lps = _served(eng, prompt + toks[:3], 4)
    assert eng.stats["state_snapshot_hits"] == 1
    want, greedy = _reference_greedy(ref, params, prompt + toks[:3], more)
    assert greedy
    np.testing.assert_allclose(lps, want, atol=LOGIT_TOL, rtol=0)


def test_a_dead_decode_step_harms_nobody(model):
    """A decode runs one dispatch ahead of its booking: a row whose stop
    token the host sees a dispatch late has advanced its state by dead
    steps. Its slot is released, no snapshot is taken in decode, and the
    slot's next tenant starts fresh: it is served as on a new engine."""
    cfg, params, ref = model
    eng = _engine(cfg, params, max_batch_size=1, decode_window=2)
    prompt = _tokens(40, seed=13)
    free_run, _ = _served(eng, prompt, 8)
    eng = _engine(cfg, params, max_batch_size=1, decode_window=2)
    stopped, _ = _served(eng, prompt, 8, stop_token_ids=[free_run[2]])
    assert stopped == free_run[:3]
    assert eng.stats["decode_dead_rows"] > 0
    taken = eng.stats["state_snapshots_taken"]
    nxt = _tokens(50, seed=14)
    toks, lps = _served(eng, nxt, 6)
    assert eng.stats["state_snapshots_taken"] == taken + 1  # the prompt's
    want, greedy = _reference_greedy(ref, params, nxt, toks)
    assert greedy
    np.testing.assert_allclose(lps, want, atol=LOGIT_TOL, rtol=0)


def test_many_sessions_at_once(model):
    cfg, params, ref = model
    eng = _engine(cfg, params)
    prompts = [_tokens(n, seed=20 + n) for n in (70, 33, 90, 48, 61)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=7,
                                                temperature=0.0))
    for p, o in zip(prompts, outs):
        assert _reference_greedy(ref, params, p, o["token_ids"])[1]


# ---------------------------------------------------------------------------
# The kernels against the token-by-token scan (interpret mode)
# ---------------------------------------------------------------------------

def _gdn_inputs(r, c, nk, nv, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def l2(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (l2(jax.random.normal(ks[0], (r, c, nk, dk))) * dk ** -0.5,
            l2(jax.random.normal(ks[1], (r, c, nk, dk))),
            jax.random.normal(ks[2], (r, c, nv, dv)),
            -0.5 * jax.random.uniform(ks[3], (r, c, nv)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (r, c, nv))),
            jax.random.normal(ks[5], (r, nv, dk, dv)))


@pytest.mark.parametrize("c", [128, 64, 32])
def test_the_chunked_kernel_is_the_scan(c):
    """Non-zero initial states, uneven segments (a short row, a pad row),
    rows chained and not. Float32 in interpret mode: the two differ in
    the order of their sums, 1e-6 measured, 1e-4 allowed."""
    q, k, v, g, beta, s0 = _gdn_inputs(4, c, 2, 4, 32, 32, seed=c)
    lens = jnp.asarray([c, c // 3, c, 0])
    live = (jnp.arange(c)[None, :] < lens[:, None])[..., None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    chain = jnp.asarray([0, 1, 0, 1])
    want_o, want_s = gd.gated_delta_rows_reference(q, k, v, g, beta, s0,
                                                   chain)
    got_o, got_s = gd.gated_delta_prefill(q, k, v, g, beta, s0, chain,
                                          interpret=True)
    np.testing.assert_allclose(jnp.where(live[..., None], got_o, 0),
                               jnp.where(live[..., None], want_o, 0),
                               atol=1e-4, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4, rtol=0)
    # a pad row hands its state on untouched; a chained one took it over
    np.testing.assert_allclose(got_s[3], got_s[2], atol=0, rtol=0)


def test_the_decode_update_is_the_scan():
    q, k, v, g, beta, _ = _gdn_inputs(1, 5, 2, 4, 32, 32, seed=9)
    states = jax.random.normal(jax.random.PRNGKey(1), (6, 4, 32, 32))
    rows = jnp.asarray([3, 0, 1, 5, 0])      # two idle rows on the sink
    want_o, want_s = gd.gated_delta_decode(states, rows, q[0], k[0], v[0],
                                           g[0], beta[0])
    got_o, got_s = gd.gated_delta_decode(states, rows, q[0], k[0], v[0],
                                         g[0], beta[0], interpret=True)
    live = np.asarray([0, 2, 3])
    np.testing.assert_allclose(got_o[live], want_o[live], atol=1e-5, rtol=0)
    np.testing.assert_allclose(got_s[1:], want_s[1:], atol=1e-5, rtol=0)
    # the slots no row named are as they were
    np.testing.assert_array_equal(got_s[jnp.asarray([2, 4])],
                                  states[jnp.asarray([2, 4])])


def test_the_engine_runs_the_kernels_in_interpret_mode(model):
    cfg, params, ref = model
    eng = PagedInferenceEngine(PagedEngineConfig(
        model=cfg, max_batch_size=2, page_size=PAGE, num_pages=64,
        num_state_snapshots=2, max_pages_per_seq=16, chunk_size=CHUNK,
        prefill_rows=2, decode_window=2), params, interpret=True)
    prompt = _tokens(45, seed=31)
    toks, lps = _served(eng, prompt, 4)
    want, greedy = _reference_greedy(ref, params, prompt, toks)
    assert greedy
    np.testing.assert_allclose(lps, want, atol=LOGIT_TOL, rtol=0)


# ---------------------------------------------------------------------------
# One chip's share of the experts (model-configs guide, section 4)
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of 8 experts each: the parts their expert layers
    give, plus the shared expert counted once, are what the uncut
    reference gives for the whole layer."""
    whole = dict(MODEL, num_experts=8, experts_held=[0, 8])
    cfg = Builder(whole).cfg
    params = Builder(whole).init_params(4)
    ref = Qwen3NextDecoder(whole)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 64))
    layer = 1
    p_ref = ref.layer_params(params, layer)
    want = ref.moe(x, p_ref, layer)
    p = qn._layer_params(params, layer, cfg)
    z = qn.norm(x[None], p["mlp_norm"], cfg.norm_eps)
    weights, idx = qn.route(z, p, cfg)
    parts = 0.0
    for lo in range(0, 8, 2):
        share = dict(p, **{n: p[n][:, lo:lo + 2]
                           for n in ("w_gate", "w_up", "w_down")})
        parts = parts + qn.routed_experts(
            z, idx, weights, share, 8, cfg.mlp_dim, held=(lo, lo + 2))
    shared = jax.nn.sigmoid(z @ p["w_sg"]) * qn._swiglu(
        z, p["ws_gate"], p["ws_up"], p["ws_down"])
    np.testing.assert_allclose((parts + shared)[0], want, atol=1e-4, rtol=0)
    # and one share alone is what the reference gives for that share
    np.testing.assert_allclose(
        qn.routed_experts(z, idx, weights, dict(p, **{
            n: p[n][:, 2:4] for n in ("w_gate", "w_up", "w_down")}), 8,
            cfg.mlp_dim, held=(2, 4))[0],
        Qwen3NextDecoder(dict(whole, num_experts=2, experts_held=[2, 4])
                         ).moe(x, dict(p_ref, **{
                             n: p_ref[n][:, 2:4] for n in (
                                 "w_gate", "w_up", "w_down")}), layer,
                               shared=False),
        atol=1e-4, rtol=0)


def test_a_prefill_dispatch_is_sized_by_the_experts_held():
    # the experts routed over set a held expert's mean group; none held,
    # nothing to stream
    assert derived_prefill_rows((512, 10, 128), 128) == 16
    assert derived_prefill_rows((512, 10, 512), 128) == 16
    assert derived_prefill_rows((64, 8, 16), 128) == 8
    assert derived_prefill_rows((512, 10, 0), 128) == 4
    assert qn.expert_routing(qn.qwen3_next_tiny()) == (8, 2, 4)


# ---------------------------------------------------------------------------
# The state kind alone
# ---------------------------------------------------------------------------

class _Hybrid:
    """As much of a model module as KVCache asks."""

    @staticmethod
    def cache_window(_):
        return 0

    @staticmethod
    def cache_layers(_):
        return ["state", "state", "state", "full"]


def _cache(**over):
    kw = dict(model=None, max_batch_size=2, page_size=PAGE, num_pages=64,
              num_state_snapshots=2, max_pages_per_seq=16, chunk_size=CHUNK)
    kw.update(over)
    stats = dict.fromkeys(STATE_COUNTERS + (
        "prefix_hits", "prefix_misses", "prefix_evictions",
        "prefix_tokens_saved"), 0)
    return KVCache(PagedEngineConfig(**kw), _Hybrid, stats, 4), stats


def _prefill(cache, req, slot):
    """Admit req and book its whole prompt as the engine would."""
    assert cache.admit(req, slot)
    pos, rows = req.prefill_pos, []
    while pos < len(req.prompt_ids):
        n = cache.row_tokens(req, pos)
        rows.append((req, pos, n))
        pos += n
    table = cache.tables([slot] * len(rows), 16, prefill=rows)[1]
    cache.booked_prefill(rows)
    return rows, table


def test_snapshots_are_filed_hit_reclaimed_and_released_to_baseline():
    cache, st = _cache()
    space = cache.state.space
    a = _Request(0, _tokens(77, seed=1), None)
    rows, table = _prefill(cache, a, 0)
    # rows end where the last whole page does: 32, 32, 8, then the rest
    assert [(p, n) for _, p, n in rows] == [(0, 32), (32, 32), (64, 8),
                                            (72, 5)]
    assert table[:, 1].tolist() == [cache.state.FRESH] + [
        cache.state.CHAIN] * 3
    assert table[:, 3].tolist() == [0, 0, 0, 1]     # the last row stores
    sid = int(table[2, 4])
    assert sid > 0 and table[:, 4].tolist() == [0, 0, sid, 0]
    assert space.hash_to_page[cache.prompt_hashes(a)[8]] == sid
    assert space.parked() == 1 and space.refs[sid] == 0
    a.slot = 0
    cache.release(a)
    # a later turn: resumes behind the snapshot, pinned until launched
    b = _Request(1, a.prompt_ids + _tokens(30, seed=2), None)
    assert cache.admit(b, 1)
    assert (b.prefill_pos, b.state_snap, space.refs[sid]) == (72, sid, 1)
    assert st["state_hit_tokens"] == 72 and st["state_rerun_tokens"] == 77 + 35
    table = cache.tables([1], 16, prefill=[(b, 72, 32)])[1]
    assert table[0, :3].tolist() == [2, cache.state.RESUME, sid]
    assert space.refs[sid] == 0 and b.state_snap == 0
    # two more prompts: the pool of two reclaims the oldest snapshot
    cache.release(b)
    for i, seed in enumerate((3, 4)):
        c = _Request(2 + i, _tokens(40, seed=seed), None)
        _prefill(cache, c, 0)
        c.slot = 0
        cache.release(c)
    # (b filed one where its dispatch ended, and released it unbooked)
    assert st["state_evictions"] == 1 and st["state_snapshots_taken"] == 4
    assert cache.prompt_hashes(a)[8] not in space.hash_to_page
    # baseline: no reference left, every snapshot free or parked, the
    # slots' rows zero, every page back
    assert not space.refs.any()
    assert len(space.free) + space.parked() == space.num_pages - 1
    assert not cache.state.table.any()
    assert cache.index.live() == 0


def test_a_dry_pool_refuses_the_snapshot_never_the_request():
    cache, st = _cache(num_state_snapshots=1)
    a = _Request(0, _tokens(40, seed=1), None)
    _prefill(cache, a, 0)
    b = _Request(1, a.prompt_ids + _tokens(9, seed=2), None)
    assert cache.admit(b, 1) and b.state_snap      # pins the only one
    a.slot = 0
    cache.release(a)
    c = _Request(2, _tokens(40, seed=3), None)
    rows, table = _prefill(cache, c, 0)
    assert st["state_snapshots_refused"] == 1 and not table[:, 4].any()
    assert len(rows) == 2 and c.prefill_pos == 0


def test_the_cache_takes_a_layers_kind_from_the_model_not_from_a_probe():
    cache, _ = _cache()
    assert cache.layer_kinds == ["state", "state", "state", "full"]
    assert cache.two_kinds and cache.align == PAGE
    assert cache.pool_args() == {"state_slots": 2, "state_snapshots": 2}
    with pytest.raises(ValueError, match="spec_tokens"):
        _cache(spec_tokens=2)
    from ray_tpu.models import llama
    plain, _ = KVCache(PagedEngineConfig(
        model=llama.llama_tiny(), num_pages=16), llama, {}, 4), None
    assert plain.state is None and plain.align == 128
    assert plain.layer_kinds == ["full"] * llama.llama_tiny().n_layers
    with pytest.raises(ValueError, match="num_state_snapshots"):
        KVCache(PagedEngineConfig(model=llama.llama_tiny(), num_pages=16,
                                  num_state_snapshots=1), llama, {}, 4)


def test_what_the_engine_refuses_over_states(model):
    cfg, params, _ = model
    eng = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="two-kind"):
        eng.prefill_export(_tokens(10), SamplingParams(max_tokens=1))
    with pytest.raises(ValueError, match="kv_spill"):
        _engine(cfg, params, kv_spill=True)
    with pytest.raises(ValueError, match="spec_tokens"):
        _engine(cfg, params, spec_tokens=2)
    with pytest.raises(NotImplementedError, match="mesh"):
        _engine(cfg, params, mesh={"tp": 1})
    assert eng.state_nbytes == 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert dataclasses.replace(cfg, experts_held=None).held == (0, 8)
