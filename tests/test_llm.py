"""LLM engine tests: decode-vs-full-forward consistency, continuous
batching, serving deployment, batch processor (reference parity:
llm/tests — engine correctness and the serve/batch surfaces)."""
import jax
import numpy as np
import pytest

from ray_tpu.llm import (
    ByteTokenizer, PagedEngineConfig, PagedInferenceEngine, SamplingParams,
)
from ray_tpu.models import llama


def _engine_cfg(max_seq_len: int, max_batch_size: int) -> PagedEngineConfig:
    """A tiny paged engine: pages of 16, a sequence of max_seq_len."""
    return PagedEngineConfig(
        model=llama.llama_tiny(vocab_size=258, max_seq_len=max_seq_len),
        max_batch_size=max_batch_size, page_size=16, num_pages=64,
        max_pages_per_seq=max_seq_len // 16, chunk_size=32)


@pytest.fixture(scope="module")
def engine():
    return PagedInferenceEngine(_engine_cfg(128, 4), rng_seed=0)


@pytest.mark.slow
def test_greedy_matches_full_forward(engine):
    """Greedy engine output must equal step-by-step argmax with the full
    (uncached) forward."""
    tok = engine.tokenizer
    prompt_ids = tok.encode("hello")
    out = engine.generate([prompt_ids],
                          SamplingParams(max_tokens=8))[0]

    ids = list(prompt_ids)
    want = []
    for _ in range(8):
        logits = llama.apply(engine.params,
                             np.asarray([ids], np.int32)[..., :],
                             engine.cfg.model)
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        want.append(nxt)
        ids.append(nxt)
        if nxt == tok.eos_id:
            break
    assert out["token_ids"] == want


def test_continuous_batching_capacity_exceeded(engine):
    """More requests than slots: all must finish, outputs independent of
    co-scheduling (greedy = deterministic)."""
    tok = engine.tokenizer
    prompts = [f"req {i}" for i in range(7)]  # > max_batch_size=4
    outs = engine.generate(prompts, SamplingParams(max_tokens=6))
    assert len(outs) == 7
    solo = engine.generate([prompts[3]], SamplingParams(max_tokens=6))[0]
    assert outs[3]["token_ids"] == solo["token_ids"]


def test_varied_sampling_params(engine):
    outs = engine.generate(
        ["abc", "def"],
        [SamplingParams(max_tokens=3),
         SamplingParams(max_tokens=9, temperature=0.8, top_k=5)])
    assert len(outs[0]["token_ids"]) == 3
    assert len(outs[1]["token_ids"]) == 9


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode("héllo")
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "héllo"


@pytest.mark.slow
def test_llm_serve_deployment(ray_start_regular):
    from ray_tpu import serve
    from ray_tpu.llm.serving import LLMConfig, build_llm_deployment

    cfg = LLMConfig(
        model_id="tiny",
        engine=_engine_cfg(64, 2))
    app = build_llm_deployment(cfg)
    try:
        handle = serve.run(app, name="llm")
        resp = handle.remote({"prompt": "hi", "max_tokens": 4}).result(
            timeout_s=120)
        assert resp["model"] == "tiny"
        assert len(resp["choices"]) == 1
        assert resp["usage"]["completion_tokens"] == 4
    finally:
        serve.shutdown()


@pytest.mark.slow
def test_batch_processor(ray_start_regular):
    from ray_tpu import data as rd
    from ray_tpu.llm.batch import ProcessorConfig, build_llm_processor

    proc = build_llm_processor(ProcessorConfig(
        engine=_engine_cfg(64, 2),
        sampling=SamplingParams(max_tokens=4)))
    ds = rd.from_items([{"prompt": "a"}, {"prompt": "b"}])
    out = proc(ds).take_all()
    assert len(out) == 2
    assert all(o["num_generated_tokens"] == 4 for o in out)


@pytest.mark.slow
def test_completions_logprobs_and_echo(ray_start_regular):
    """OpenAI-surface logprobs + echo on /v1/completions (reference:
    the OpenAI completions params the llm router accepts)."""
    from ray_tpu import serve
    from ray_tpu.llm.serving import LLMConfig, LLMServer

    app = serve.deployment(LLMServer).options(
        name="llm-lp").bind(LLMConfig(model_id="tiny", warmup=False))
    h = serve.run(app, name="lp")
    try:
        out = h.options(method_name="completions").remote(
            {"prompt": [5, 6, 7], "max_tokens": 6,
             "logprobs": 1, "echo": True}).result(timeout_s=180)
        ch = out["choices"][0]
        lp = ch["logprobs"]
        assert len(lp["token_logprobs"]) == out["usage"][
            "completion_tokens"]
        assert all(v <= 0 for v in lp["token_logprobs"])
        assert len(lp["tokens"]) == len(lp["token_logprobs"])
        # echo prepends the prompt text to the completion
        plain = h.options(method_name="completions").remote(
            {"prompt": [5, 6, 7], "max_tokens": 6}).result(timeout_s=120)
        assert ch["text"].endswith(plain["choices"][0]["text"])
        assert len(ch["text"]) > len(plain["choices"][0]["text"])
        assert "logprobs" not in plain["choices"][0]
    finally:
        serve.delete("lp")
