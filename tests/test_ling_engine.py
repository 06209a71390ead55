"""models/ling_hybrid.py through ``PagedInferenceEngine``: tokens and their
log-probabilities against the plain reference, a resumed snapshot, tables of
128 rows, and what the engine refuses over this model (tests/ling_util.py
has the configuration and the tolerance)."""
import numpy as np
import pytest
from ling_util import (LOGIT_TOL, PAGE, SamplingParams, build, engine,
                       reference_greedy, served, tokens)

from ray_tpu.models import ling_hybrid as lh


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.mark.parametrize("n_prompt,interpret", [(77, False), (64, False),
                                                (45, True)])
def test_the_engine_serves_the_reference(model, n_prompt, interpret):
    """(The last case runs the kernels in interpret mode.)"""
    cfg, params, ref = model
    eng = engine(cfg, params, interpret=interpret,
                  **(dict(max_batch_size=2, num_pages=64, prefill_rows=2,
                          decode_window=2) if interpret else {}))
    prompt = tokens(n_prompt, seed=n_prompt + 1)
    toks, lps = served(eng, prompt, 9 if not interpret else 4)
    want, greedy = reference_greedy(ref, params, prompt, toks)
    assert greedy
    np.testing.assert_allclose(lps, want, atol=LOGIT_TOL, rtol=0)
    # what the programs hand back beside the tokens: every assignment
    # routed (2 a token in each of 3 expert layers, pads and idle rows
    # too), those that fell on the 4 held of 16, the distinct held experts
    # a layer a decode step reached, and the tokens x layers whose two
    # groups of four include the held one (about half)
    st = eng.stats
    assert st["moe_expert_load_sum"] == st["moe_assign_run"]
    assert 0 < st["moe_assign_held"] < st["moe_expert_load_sum"]
    assert 0 < st["moe_held_hit_decode"] <= 4 * 3 * st["decode_steps"]
    tokens_layers = st["moe_expert_load_sum"] // 2
    assert 0.2 < st["moe_group_hits"] / tokens_layers < 0.8
    # an assignment held implies its token's groups include the held one
    assert st["moe_assign_held"] <= 2 * st["moe_group_hits"]


def test_a_later_ask_resumes_a_snapshot_and_serves_what_a_cold_run_does(
        model):
    cfg, params, ref = model
    eng = engine(cfg, params, prefill_rows=2)
    doc = tokens(150, seed=7)
    served(eng, doc + tokens(10, seed=8), 3)
    ask = doc + tokens(12, seed=9)
    got = served(eng, ask, 5)
    st = eng.stats
    assert st["state_snapshot_hits"] == 1
    assert st["state_hit_tokens"] == 128      # two dispatches of 64
    assert st["prefix_tokens_saved"] == 128   # the latent pages with it
    cold = served(engine(cfg, params), ask, 5)
    assert got[0] == cold[0]
    np.testing.assert_allclose(got[1], cold[1], atol=LOGIT_TOL, rtol=0)
    want, greedy = reference_greedy(ref, params, ask, got[0])
    assert greedy
    np.testing.assert_allclose(got[1], want, atol=LOGIT_TOL, rtol=0)


def test_a_second_ask_resumes_behind_more_short_prompts_than_the_pool_holds(
        model):
    """The pool of 6 files 2 snapshots inside the document (a dispatch of
    64 tokens each) and 9 prompt ends before the document comes again:
    the ends nobody extends are what it gives up (the parent's order gave
    up the two inside the document first, and re-ran it)."""
    cfg, params, ref = model
    eng = engine(cfg, params, prefill_rows=2)
    doc = tokens(150, seed=17)
    served(eng, doc + tokens(10, seed=18), 2)
    for i in range(8):
        served(eng, tokens(12 + i, seed=20 + i), 2)
    ask = doc + tokens(12, seed=19)
    got = served(eng, ask, 5)
    st = eng.stats
    # 2 + 1 of the first ask, 8 ends, the second ask's end: 6 too many
    assert st["state_snapshots_taken"] == 12
    assert st["state_evictions_end"] == 6 and st["state_evictions_mid"] == 0
    assert st["state_snapshot_hits"] == 1 and st["state_hit_tokens"] == 128
    cold = served(engine(cfg, params), ask, 5)
    assert got[0] == cold[0]
    np.testing.assert_allclose(got[1], cold[1], atol=LOGIT_TOL, rtol=0)
    want, greedy = reference_greedy(ref, params, ask, got[0])
    assert greedy
    np.testing.assert_allclose(got[1], want, atol=LOGIT_TOL, rtol=0)


def test_tables_of_128_rows(model):
    """``max_batch_size`` 128, twice any other cell's: the decode tables,
    129 slot rows a KDA layer, and more sequences at once than a dispatch
    has prefill rows."""
    cfg, params, ref = model
    eng = engine(cfg, params, max_batch_size=128, num_pages=512,
                  max_pages_per_seq=8, num_state_snapshots=8)
    assert eng.caches[0]["S"].shape[0] == 129
    assert eng.caches[2]["ckv"].shape == (512, PAGE, 128)
    assert eng.cache.state.table.shape == (128, 1)
    prompts = [tokens(20 + i % 9, seed=100 + i) for i in range(40)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=4,
                                                temperature=0.0))
    assert eng.stats["admitted"] == 40
    for p, o in list(zip(prompts, outs))[::8]:
        assert reference_greedy(ref, params, p, o["token_ids"])[1]


def test_what_the_engine_refuses_over_this_model(model):
    cfg, params, _ = model
    eng = engine(cfg, params)
    with pytest.raises(NotImplementedError, match="two-kind"):
        eng.prefill_export(tokens(10), SamplingParams(max_tokens=1))
    with pytest.raises(ValueError, match="kv_spill"):
        engine(cfg, params, kv_spill=True)
    with pytest.raises(ValueError, match="spec_tokens"):
        engine(cfg, params, spec_tokens=2)
    with pytest.raises(NotImplementedError, match="ling_hybrid.py"):
        engine(cfg, params, mesh={"tp": 1})
    with pytest.raises(ValueError, match="ling_hybrid"):
        engine(cfg, params, max_adapters=1, lora_targets=("wq",))
    # what the engine counts of the two kinds: a slot's bytes over the
    # three KDA layers, a page's over the one latent layer (40 values in
    # 128 lanes)
    assert eng.state_nbytes == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert eng.page_nbytes == PAGE * 128 * 4
    assert eng.cache.layer_kinds == ["state", "state", "full", "state"]
    assert lh.expert_routing(cfg) == (16, 2, 4)
