"""Shared by the tests of models/ling_hybrid.py (``tests/test_ling_*.py``):
the small configuration, its weights and reference, an engine over it, and
what a served answer is held to. The tests are spread over files of few
cases each so that they sort to the END of the suite's queue (`--dist
loadfile` takes files largest first): a long new file in the middle of
the queue shifts the schedule under two store-drain tests that are
sensitive to it (.claude/skills/verify/SKILL.md)."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.models.ling_hybrid import Builder
from benchmarks.reference.ling_hybrid_decoder import LingHybridDecoder
from ray_tpu.llm.paged_engine import (PagedEngineConfig,
                                      PagedInferenceEngine, SamplingParams)
from ray_tpu.models import ling_hybrid as lh

LOGIT_TOL = 2e-4
PAGE, CHUNK = 8, 32

MODEL = dict(
    model_type="bailing_hybrid", hidden_size=64, head_dim=16,
    num_attention_heads=4, num_key_value_heads=4, layer_group_size=3,
    first_k_dense_replace=2, dense_layers_kept=1, num_hidden_layers=4,
    kv_lora_rank=32, qk_head_dim=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, rotary_dim=8, v_head_dim=16, rope_theta=6000000,
    short_conv_kernel_size=4, kda_lower_bound=-5, intermediate_size=128,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    num_shared_experts=1, num_experts=4, experts_held=[0, 4],
    experts_routed=16, n_group=4, topk_group=2, num_experts_per_tok=2,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6, vocab_size=256,
    max_position_embeddings=512, torch_dtype="float32",
    expert_swiglu_limit_list=[0, 0, 0, 0, 4],
    share_expert_swiglu_limit_list=[0, 0, 0, 0, 5])


def build():
    """(config, weights, reference) of MODEL."""
    builder = Builder(MODEL)
    assert builder.cfg == lh.ling_hybrid_tiny()
    return builder.cfg, builder.init_params(3), LingHybridDecoder(MODEL)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def engine(cfg, params, **over):
    kw = dict(model=cfg, max_batch_size=4, page_size=PAGE, num_pages=128,
              num_state_snapshots=6, max_pages_per_seq=32, chunk_size=CHUNK,
              prefill_rows=4, decode_window=4)
    kw.update(over)
    interpret = kw.pop("interpret", False)
    return PagedInferenceEngine(PagedEngineConfig(**kw), params,
                                interpret=interpret)


def served(eng, prompt, n, **params):
    out = eng.generate([prompt], SamplingParams(
        max_tokens=n, temperature=0.0, logprobs=True, **params))[0]
    return out["token_ids"], out["logprobs"]


def reference_greedy(ref, params, prompt, toks):
    logits = ref.logits(params, jnp.asarray(prompt + toks))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    logp = jax.nn.log_softmax(rows, axis=-1)
    idx = jnp.asarray(toks)
    return (np.asarray(jnp.take_along_axis(logp, idx[:, None], 1)[:, 0]),
            bool((rows.argmax(-1) == idx).all()))
