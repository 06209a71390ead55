"""OpenAI-compatible API + LoRA multiplexing tests (reference:
llm/_internal/serve routers + multi-LoRA)."""
import json
import urllib.request

import jax
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.llm import lora
from ray_tpu.llm.openai_api import (OpenAIRouter, apply_chat_template,
                                    build_openai_app)
from ray_tpu.llm.paged_engine import PagedEngineConfig
from ray_tpu.llm.serving import LLMConfig
from ray_tpu.models import llama


def _tiny_cfg():
    return llama.llama_tiny(n_layers=2, dim=64, mlp_dim=128, n_heads=4,
                            n_kv_heads=4, max_seq_len=256)


@pytest.fixture
def ray(ray_start_regular):
    yield ray_start_regular
    serve.shutdown()


def test_chat_template():
    text = apply_chat_template([
        {"role": "system", "content": "be brief"},
        {"role": "user", "content": "hi"}])
    assert "<|system|>\nbe brief" in text
    assert text.endswith("<|assistant|>\n")


def test_lora_merge_changes_outputs():
    cfg = _tiny_cfg()
    params = llama.init(jax.random.PRNGKey(0), cfg)
    adapter = lora.random_adapter(jax.random.PRNGKey(1), cfg, rank=4)
    merged = lora.merge(params, adapter)
    toks = np.arange(8, dtype=np.int32)[None, :]
    base = llama.apply(params, toks, cfg)
    tuned = llama.apply(merged, toks, cfg)
    assert not np.allclose(np.asarray(base), np.asarray(tuned))
    # untouched leaves are shared, not copied
    assert merged["embed"] is params["embed"]
    # roundtrip through bytes
    back = lora.adapter_from_bytes(lora.adapter_to_bytes(adapter))
    merged2 = lora.merge(params, back)
    np.testing.assert_allclose(np.asarray(merged["layers"]["wq"]),
                               np.asarray(merged2["layers"]["wq"]))


def test_router_admits_what_its_model_deployments_admit():
    """The front door's admission budget is the ingress deployment's
    max_ongoing_requests: at the default of 16 the router shed a 64-row
    engine's 64 streams with 429 (PERF.md §6, PR 27)."""
    econf = PagedEngineConfig(model=_tiny_cfg(), max_batch_size=2)
    app = build_openai_app([
        LLMConfig(model_id="a", engine=econf, max_ongoing_requests=128),
        LLMConfig(model_id="b", engine=econf, max_ongoing_requests=40)])
    assert app.ingress.spec.max_ongoing_requests == 168
    assert [c.spec.max_ongoing_requests
            for c in app.ingress.children()] == [128, 40]


@pytest.mark.slow
def test_openai_completions_and_models(ray, tmp_path):
    cfg = _tiny_cfg()
    econf = PagedEngineConfig(model=cfg, max_batch_size=2, page_size=16,
                              num_pages=64, max_pages_per_seq=8,
                              chunk_size=32)
    app = build_openai_app([LLMConfig(model_id="tiny", engine=econf)])
    h = serve.run(app, name="llm")

    models = h.options(method_name="v1_models").remote().result(
        timeout_s=120)
    assert models["data"][0]["id"] == "tiny"

    out = h.options(method_name="v1_completions").remote(
        {"model": "tiny", "prompt": "hello", "max_tokens": 6}).result(
        timeout_s=300)
    assert out["object"] == "text_completion"
    assert out["usage"]["completion_tokens"] > 0
    assert "id" in out and out["model"] == "tiny"

    chat = h.options(method_name="v1_chat_completions").remote(
        {"model": "tiny", "max_tokens": 4,
         "messages": [{"role": "user", "content": "hi"}]}).result(
        timeout_s=300)
    assert chat["object"] == "chat.completion"
    assert chat["choices"][0]["message"]["role"] == "assistant"


@pytest.mark.slow
def test_openai_streaming_sse(ray):
    cfg = _tiny_cfg()
    econf = PagedEngineConfig(model=cfg, max_batch_size=2, page_size=16,
                              num_pages=64, max_pages_per_seq=8,
                              chunk_size=32)
    app = build_openai_app([LLMConfig(model_id="tiny", engine=econf)])
    h = serve.run(app, name="llm-s")
    gen = h.options(method_name="v1_completions", stream=True).remote(
        {"model": "tiny", "prompt": "abc", "max_tokens": 5,
         "stream": True})
    lines = list(gen)
    assert lines[-1] == "data: [DONE]\n\n"
    payloads = [json.loads(l[6:]) for l in lines[:-1]]
    text = "".join(p["choices"][0]["text"] for p in payloads)
    assert len(text) > 0
    assert payloads[-1]["choices"][0]["finish_reason"] in ("stop", "length")


@pytest.mark.slow
def test_openai_http_path_routing(ray):
    cfg = _tiny_cfg()
    econf = PagedEngineConfig(model=cfg, max_batch_size=2, page_size=16,
                              num_pages=64, max_pages_per_seq=8,
                              chunk_size=32)
    app = build_openai_app([LLMConfig(model_id="tiny", engine=econf)])
    # not 18123: test_serve.py binds it, and under xdist both run at once
    serve.run(app, name="oai", http_port=18127)
    req = urllib.request.Request(
        "http://127.0.0.1:18127/oai/v1/completions",
        data=json.dumps({"model": "tiny", "prompt": "xy",
                         "max_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        out = json.loads(r.read())
    assert out["object"] == "text_completion"
    with urllib.request.urlopen(
            "http://127.0.0.1:18127/oai/v1/models", timeout=60) as r:
        models = json.loads(r.read())
    assert models["data"][0]["id"] == "tiny"


@pytest.mark.slow
def test_lora_multiplexed_serving(ray):
    from ray_tpu.llm.multilora import AdapterRegistry
    cfg = _tiny_cfg()
    # strong adapter incl. lm_head: random untrained weights sit in an
    # attractor that weak deltas don't dislodge under greedy decode
    adapter = lora.random_adapter(jax.random.PRNGKey(7), cfg, rank=4,
                                  alpha=64.0,
                                  targets=("wq", "wv", "lm_head"))
    assert AdapterRegistry("tiny").publish("myadapter", adapter) == 0

    econf = PagedEngineConfig(model=cfg, max_batch_size=2, page_size=16,
                              num_pages=64, max_pages_per_seq=8,
                              chunk_size=32, max_adapters=2, lora_rank=4)
    app = build_openai_app([LLMConfig(model_id="tiny", engine=econf)])
    h = serve.run(app, name="llm-lora")

    base = h.options(method_name="v1_completions").remote(
        {"model": "tiny", "prompt": "hello world", "max_tokens": 8,
         "temperature": 0.0}).result(timeout_s=300)
    tuned = h.options(method_name="v1_completions").remote(
        {"model": "tiny:myadapter", "prompt": "hello world",
         "max_tokens": 8, "temperature": 0.0}).result(timeout_s=300)
    # greedy decode through the adapter's slot must differ from base
    assert base["choices"][0]["text"] != tuned["choices"][0]["text"]

    with pytest.raises(Exception):
        h.options(method_name="v1_completions").remote(
            {"model": "tiny:missing", "prompt": "x",
             "max_tokens": 2}).result(timeout_s=120)


def test_lora_request_without_slot_table_is_refused():
    """One engine a replica: a deployment whose engine has no adapter
    slot table (max_adapters == 0) refuses a request that names a LoRA,
    and says which setting would serve it."""
    from ray_tpu.llm.serving import LLMServer
    econf = PagedEngineConfig(model=_tiny_cfg(), max_batch_size=2,
                              page_size=16, num_pages=16,
                              max_pages_per_seq=4, chunk_size=32)
    srv = LLMServer(LLMConfig(model_id="tiny", engine=econf, warmup=False))
    try:
        assert srv.engine.lora is None and srv.loaded_loras() == []
        for request in ({"model": "tiny:myadapter", "prompt": "x"},
                        {"lora": "myadapter", "prompt": "x"}):
            with pytest.raises(ValueError,
                               match="PagedEngineConfig.max_adapters"):
                srv.completions(request)
        assert not srv.engine.has_work()       # nothing was admitted
        out = srv.completions({"model": "tiny", "prompt": "x",
                               "max_tokens": 2})
        assert out["usage"]["completion_tokens"] == 2
    finally:
        srv._stop = True
        srv._wake.set()
        srv._thread.join(timeout=10)
    assert not srv._thread.is_alive()
