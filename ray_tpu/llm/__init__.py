"""ray_tpu.llm — LLM serving and batch inference.

Reference parity: python/ray/llm (serve.llm vllm_engine.py:180 VLLMEngine /
llm_server.py:409, batch processor/base.py:104). The external vLLM engine is
replaced by ONE JAX-native continuous-batching engine, paged_engine.py:
a paged KV cache with block tables, the ragged Pallas attention family,
chunked prefill so admission never stalls decode, a prefix cache, batched
multi-LoRA and an optional mesh. Jitted prefill/decode over the whole
batch, in-jit sampling — attention/matmuls stay on the MXU, the Python
loop only admits/retires requests and allocates pages. Serving, batch
inference and the PD pools all run it.

    from ray_tpu import llm
    engine = llm.PagedInferenceEngine(llm.PagedEngineConfig(model=cfg), params)
    out = engine.generate(["hello"], llm.SamplingParams(max_tokens=16))

Serving: llm.serving.build_llm_deployment(...) -> a Serve app exposing an
OpenAI-style completions API. Batch: llm.batch.build_llm_processor(...)
maps a Dataset through tokenize -> generate -> detokenize stages
(reference: data/llm.py:248).
"""
from .engine import SamplingParams
from .paged_engine import PagedEngineConfig, PagedInferenceEngine
from .tokenizer import ByteTokenizer, get_tokenizer

__all__ = ["PagedEngineConfig", "PagedInferenceEngine", "SamplingParams",
           "ByteTokenizer", "get_tokenizer", "serving", "batch", "lora",
           "multilora", "openai_api"]

from . import serving, batch, lora, multilora, openai_api  # noqa: E402
