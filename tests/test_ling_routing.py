"""One chip's share of the experts under group-limited routing
(models/mla_moe.py `route` / `_ffn_block` as models/ling_hybrid.py runs
them; the model-configs guide's section 4 share test)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from ling_util import MODEL

from benchmarks.models.ling_hybrid import Builder
from benchmarks.reference.ling_hybrid_decoder import LingHybridDecoder
from ray_tpu.models import ling_hybrid as lh
from ray_tpu.models import mla_moe

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight shares of one group of 4 of 32 experts each, top-4 of the 4
    best of 8 groups: the parts their expert layers give, plus the shared
    expert counted once, are what the uncut reference gives for the whole
    layer; and one share alone is the reference's for that share."""
    whole = dict(MODEL, num_experts=32, experts_routed=32,
                 experts_held=[0, 32], n_group=8, topk_group=4,
                 num_experts_per_tok=4)
    cfg = Builder(whole).cfg
    params = Builder(whole).init_params(4)
    ref = LingHybridDecoder(whole)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 64))
    layer, li = 2, 1                # the second of the expert layers
    p_ref = ref.layer_params(params, layer)
    want = ref.moe(x, p_ref, li)
    p = lh._layer_params(params, layer, cfg)
    names = ("w_gate", "w_up", "w_down")
    parts, held_hits = 0.0, 0
    for lo in range(0, 32, 4):
        share_cfg = dataclasses.replace(cfg, experts_held=(lo, lo + 4))
        share = dict(p, **{n: p[n][:, lo:lo + 4] for n in names})
        y, load = mla_moe._ffn_block(x[None], share, share_cfg, False)
        z = mla_moe.rms_norm(x[None], p["mlp_norm"], cfg.norm_eps)
        shared = mla_moe._swiglu(z, p["ws_gate"], p["ws_up"], p["ws_down"])
        parts = parts + (y - x[None] - shared)
        # [E] loads, the distinct held experts hit, the tokens whose four
        # groups include this share's
        assert load.shape == (34,) and int(load[:32].sum()) == 40 * 4
        assert int(load[lo:lo + 4].sum()) <= 4 * int(load[33])
        held_hits += int(load[33])
        if lo == 8:
            np.testing.assert_allclose(
                (y - x[None] - shared)[0],
                LingHybridDecoder(dict(whole, num_experts=4,
                                       experts_held=[8, 12])).moe(
                    x, dict(p_ref, **{n: p_ref[n][:, 8:12] for n in names}),
                    li, shared=False), atol=1e-4, rtol=0)
    np.testing.assert_allclose((parts + shared)[0], want, atol=1e-4, rtol=0)
    # every token chose 4 of the 8 groups
    assert held_hits == 40 * 4


def test_group_selection_by_hand():
    """Two tokens whose best experts lie in different places: the top-2 of
    ALL eight experts and the top-2 inside the best 2 of 4 groups differ
    where one group holds the single best expert and little else."""
    cfg = lh.ling_hybrid_tiny(moe_experts=8, n_group=4, topk_group=2,
                              moe_top_k=2, experts_held=None)
    # scores after the sigmoid, by construction: logits = logit(scores)
    scores = np.array([
        # group 0: the best expert alone (0.9 + 0.1); groups 1 and 2 hold
        # pairs (0.8 + 0.7, 0.6 + 0.6) that outscore it as groups
        [[0.9, 0.1, 0.8, 0.7, 0.6, 0.6, 0.2, 0.2],
         # no conflict: the top-2 lie in the two best groups
         [0.9, 0.8, 0.1, 0.1, 0.7, 0.3, 0.2, 0.2]]], np.float32)
    logits = np.log(scores / (1 - scores))
    d = 8
    p = {"w_router": jnp.eye(d, dtype=jnp.float32),
         "router_bias": jnp.zeros((d,), jnp.float32)}
    weights, idx = mla_moe.route(jnp.asarray(logits), p, cfg)
    assert sorted(np.asarray(idx[0, 0]).tolist()) == [2, 3]   # not expert 0
    assert sorted(np.asarray(idx[0, 1]).tolist()) == [0, 1]
    np.testing.assert_allclose(
        np.sort(np.asarray(weights[0, 0])),
        2.5 * np.array([0.7, 0.8]) / 1.5, rtol=1e-5)
    ungrouped = dataclasses.replace(cfg, n_group=1, topk_group=1)
    _, flat = mla_moe.route(jnp.asarray(logits), p, ungrouped)
    assert sorted(np.asarray(flat[0, 0]).tolist()) == [0, 2]
    # the bias moves the selection (a group's score and an expert's rank)
    # and never the weights: group 2's pair now leads (0.9 + 0.9) and its
    # experts outrank group 1's, at their unbiased scores of 0.6 each
    biased = dict(p, router_bias=jnp.asarray(
        [0, 0, 0, 0, 0.3, 0.3, 0, 0], jnp.float32))
    weights, idx = mla_moe.route(jnp.asarray(logits), biased, cfg)
    assert sorted(np.asarray(idx[0, 0]).tolist()) == [4, 5]
    np.testing.assert_allclose(np.asarray(weights[0, 0]), [1.25, 1.25],
                               rtol=1e-5)
    # the reference selects the same
    ref = LingHybridDecoder(dict(MODEL, n_group=4, topk_group=2,
                                 num_experts_per_tok=2))
    _, ref_idx = ref.routing(jnp.asarray(logits[0]), p["w_router"],
                             p["router_bias"])
    assert sorted(np.asarray(ref_idx[0]).tolist()) == [2, 3]
