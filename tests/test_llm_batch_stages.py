"""LLM batch stage chains (reference: llm/_internal/batch/stages/ —
chat_template_stage.py, tokenize_stage.py, vllm_engine_stage.py,
http_request_stage.py; processor/base.py:104)."""
import pytest

import ray_tpu
from ray_tpu import data as rdata
from ray_tpu.llm import PagedEngineConfig, SamplingParams
from ray_tpu.llm.batch import (ChatTemplateStage, DetokenizeStage,
                               EngineStage, HttpRequestStage,
                               ProcessorConfig, TokenizeStage,
                               build_llm_processor)
from ray_tpu.models import llama


@pytest.fixture
def ray4():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield
    # serve teardown FIRST: after ray_tpu.shutdown a serve call would
    # have nothing to talk to (and must never boot a fresh cluster)
    from ray_tpu import serve
    serve.shutdown()
    ray_tpu.shutdown()


def _ecfg():
    return PagedEngineConfig(model=llama.llama_tiny(max_seq_len=64),
                             max_batch_size=2, page_size=16, num_pages=32,
                             max_pages_per_seq=4, chunk_size=32)


@pytest.mark.slow
def test_chat_template_tokenize_engine_chain(ray4):
    cfg = ProcessorConfig(engine=_ecfg(),
                          sampling=SamplingParams(max_tokens=4))
    proc = build_llm_processor(cfg, stages=[
        ChatTemplateStage(), TokenizeStage(), EngineStage(cfg)])
    assert proc.list_stage_names() == ["ChatTemplate", "Tokenize",
                                       "Engine"]
    ds = rdata.from_items([
        {"messages": [{"role": "user", "content": "hi"}]},
        {"messages": [{"role": "user", "content": "yo"}]},
    ])
    rows = proc(ds).take_all()
    assert len(rows) == 2
    for r in rows:
        assert "<|user|>" in r["prompt"]          # template applied
        assert isinstance(r["input_ids"], list)   # tokenized
        assert r["generated_text"] is not None    # engine ran
        assert r["num_generated_tokens"] >= 1


def test_detokenize_roundtrip(ray4):
    from ray_tpu.llm.tokenizer import get_tokenizer
    tok = get_tokenizer(None)
    ds = rdata.from_items([{"generated_ids": tok.encode("hello",
                                                        add_bos=False)}])
    rows = DetokenizeStage()(ds).take_all()
    assert rows[0]["generated_text"] == "hello"


@pytest.mark.slow
def test_engine_stage_autoscaling_pool(ray4):
    """concurrency=(min,max): engines run in an autoscaling actor pool."""
    cfg = ProcessorConfig(engine=_ecfg(),
                          sampling=SamplingParams(max_tokens=3),
                          concurrency=(1, 2))
    proc = build_llm_processor(cfg)
    ds = rdata.from_items([{"prompt": f"p{i}"} for i in range(6)],
                          override_num_blocks=3)
    rows = proc(ds).take_all()
    assert len(rows) == 6
    assert all(r["generated_text"] is not None for r in rows)
    assert all(isinstance(r["generated_ids"], list) for r in rows)


@pytest.mark.slow
def test_http_request_stage_against_serve(ray4):
    """HTTP stage fans rows out to a local OpenAI-compatible app."""
    from ray_tpu import serve
    from ray_tpu.llm.openai_api import build_openai_app
    from ray_tpu.llm.paged_engine import PagedEngineConfig
    from ray_tpu.llm.serving import LLMConfig
    econf = PagedEngineConfig(model=llama.llama_tiny(max_seq_len=128),
                              max_batch_size=2, page_size=16,
                              num_pages=64, max_pages_per_seq=8,
                              chunk_size=32)
    app = build_openai_app([LLMConfig(model_id="tiny", engine=econf)])
    serve.run(app, name="oai-batch", http_port=18361)

    stage = HttpRequestStage(
        "http://127.0.0.1:18361/oai-batch/v1/completions",
        payload_fn=lambda row: {"model": "tiny", "prompt": row["prompt"],
                                "max_tokens": 3})
    ds = rdata.from_items([{"prompt": "a"}, {"prompt": "b"}])
    rows = stage(ds).take_all()
    assert len(rows) == 2
    for r in rows:
        assert r["response"]["object"] == "text_completion"
        assert r["response"]["choices"][0]["text"] is not None
