"""data.llm analog: batch inference processors over Datasets.

Reference parity: python/ray/data/llm.py:248 build_llm_processor,
llm/_internal/batch/processor/base.py:104 (Processor = an ordered chain
of stages wrapped by user preprocess/postprocess), and the stage family
under llm/_internal/batch/stages/ (chat_template_stage.py,
tokenize_stage.py, vllm_engine_stage.py, http_request_stage.py).

TPU-first shape: every stage is a Dataset transform; the engine stage is
a stateful map_batches over an AUTOSCALING actor pool (one engine per
actor — model init + XLA compiles paid once per actor, pool size scales
(min,max) with queue depth via data/executor.py), and the HTTP stage
fans rows out to any OpenAI-compatible endpoint (e.g. a ray_tpu serve
app or a disaggregated P/D deployment).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

from .engine import SamplingParams
from .paged_engine import PagedEngineConfig, PagedInferenceEngine

_ENGINE_CACHE: dict[str, PagedInferenceEngine] = {}


def _get_engine(cfg: PagedEngineConfig) -> PagedInferenceEngine:
    key = repr(cfg)
    if key not in _ENGINE_CACHE:
        _ENGINE_CACHE[key] = PagedInferenceEngine(cfg)
    return _ENGINE_CACHE[key]


@dataclasses.dataclass
class ProcessorConfig:
    """(reference: processor/base.py:21 + OfflineProcessorConfig:55)"""
    engine: Optional[PagedEngineConfig] = None
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    prompt_column: str = "prompt"
    output_column: str = "generated_text"
    batch_size: int = 8
    # engine actor pool (reference: OfflineProcessorConfig concurrency);
    # a (min, max) tuple autoscales with queue depth
    concurrency: Any = None


# --------------------------------------------------------------------- #
# stages (reference: llm/_internal/batch/stages/)
# --------------------------------------------------------------------- #

class Stage:
    """One Dataset -> Dataset transform with a name (reference:
    stages/base.py StatefulStage)."""

    name = "stage"

    def __call__(self, ds):
        raise NotImplementedError


class ChatTemplateStage(Stage):
    """messages column -> prompt column via the chat template (reference:
    stages/chat_template_stage.py)."""

    name = "ChatTemplate"

    def __init__(self, messages_column: str = "messages",
                 prompt_column: str = "prompt"):
        self.messages_column = messages_column
        self.prompt_column = prompt_column

    def __call__(self, ds):
        mc, pc = self.messages_column, self.prompt_column

        def apply(row: dict) -> dict:
            from .openai_api import apply_chat_template
            out = dict(row)
            out[pc] = apply_chat_template(list(row[mc]))
            return out

        return ds.map(apply)


class TokenizeStage(Stage):
    """prompt -> token ids (reference: stages/tokenize_stage.py Tokenize
    half). The engine consumes raw prompts too, but pre-tokenizing lets
    the pipeline dedupe/sort by length before engine admission."""

    name = "Tokenize"

    def __init__(self, prompt_column: str = "prompt",
                 ids_column: str = "input_ids", tokenizer: Any = None):
        self.prompt_column = prompt_column
        self.ids_column = ids_column
        self.tokenizer = tokenizer

    def __call__(self, ds):
        pc, ic = self.prompt_column, self.ids_column
        tok_spec = self.tokenizer

        def apply_batch(batch: dict) -> dict:
            from .tokenizer import get_tokenizer
            tok = get_tokenizer(tok_spec)  # built once per BLOCK, not row
            out = dict(batch)
            out[ic] = [tok.encode(str(p)) for p in batch[pc]]
            return out

        return ds.map_batches(apply_batch)


class DetokenizeStage(Stage):
    """token ids -> text (reference: tokenize_stage.py Detokenize
    half)."""

    name = "Detokenize"

    def __init__(self, ids_column: str = "generated_ids",
                 text_column: str = "generated_text",
                 tokenizer: Any = None):
        self.ids_column = ids_column
        self.text_column = text_column
        self.tokenizer = tokenizer

    def __call__(self, ds):
        ic, tc = self.ids_column, self.text_column
        tok_spec = self.tokenizer

        def apply_batch(batch: dict) -> dict:
            from .tokenizer import get_tokenizer
            tok = get_tokenizer(tok_spec)  # built once per BLOCK, not row
            out = dict(batch)
            out[tc] = [tok.decode(list(ids)) for ids in batch[ic]]
            return out

        return ds.map_batches(apply_batch)


def _default_engine_cfg(cfg: ProcessorConfig) -> PagedEngineConfig:
    from ..models import llama
    return cfg.engine or PagedEngineConfig(model=llama.llama_tiny(),
                                           max_batch_size=cfg.batch_size)


def _engine_batch(engine, sampling, prompt_column, output_column,
                  batch: dict) -> dict:
    """The one batch->result shaping both engine paths share."""
    prompts = [str(p) for p in batch[prompt_column]]
    outs = engine.generate(prompts, sampling)
    result = dict(batch)
    result[output_column] = [o["text"] for o in outs]
    result["generated_ids"] = [list(o["token_ids"]) for o in outs]
    result["num_generated_tokens"] = [len(o["token_ids"]) for o in outs]
    return result


class LLMPredictor:
    """Stateful pool member for ``Dataset.map_batches(LLMPredictor,
    concurrency=N, fn_constructor_args=(engine_cfg, sampling))``: builds
    its engine ONCE per pool actor (model init + XLA compiles paid once),
    then generates per batch (reference: vllm_engine_stage.py — one vLLM
    engine per stage actor).

    The offline batch-inference workhorse. Under the streaming executor
    (data/streaming, the default), the pool becomes a stage of
    long-lived workers fed over sealed channels: each predictor owns a
    deterministic stripe of the block sequence (worker ``w`` processes
    idxs ``w mod W`` in order — what keeps the pipeline deadlock-free
    and results bit-identical), streaming through its engine with no
    per-block task dispatches — at document scale the control-plane
    bill drops from one dispatch per block to one ``run_loop`` call per
    predictor for the whole run (rtpu_data_* counters prove it)."""

    def __init__(self, engine_cfg=None, sampling=None,
                 prompt_column: str = "prompt",
                 output_column: str = "generated_text"):
        if engine_cfg is None:
            engine_cfg = _default_engine_cfg(ProcessorConfig())
        self.engine = PagedInferenceEngine(engine_cfg)
        self.sampling = sampling if sampling is not None \
            else SamplingParams()
        self.pc = prompt_column
        self.oc = output_column

    def __call__(self, batch: dict) -> dict:
        return _engine_batch(self.engine, self.sampling, self.pc,
                             self.oc, batch)


#: backwards-compat alias (pre-streaming name)
_EngineActor = LLMPredictor


class EngineStage(Stage):
    """The LLM stage (reference: vllm_engine_stage.py). With
    ``cfg.concurrency`` the engines run in a (min,max)-autoscaling actor
    pool; without, a cached engine per worker process via plain
    map_batches."""

    name = "Engine"

    def __init__(self, cfg: ProcessorConfig):
        self.cfg = cfg

    def __call__(self, ds):
        cfg = self.cfg
        engine_cfg = _default_engine_cfg(cfg)
        if cfg.concurrency is not None:
            return ds.map_batches(
                LLMPredictor, concurrency=cfg.concurrency,
                fn_constructor_args=(engine_cfg, cfg.sampling,
                                     cfg.prompt_column,
                                     cfg.output_column))

        def run_engine(batch: dict) -> dict:
            # engines cache per worker process: model init + XLA compiles
            # are paid once, not once per block
            return _engine_batch(_get_engine(engine_cfg), cfg.sampling,
                                 cfg.prompt_column, cfg.output_column,
                                 batch)

        return ds.map_batches(run_engine)


class HttpRequestStage(Stage):
    """POST each row's payload to an OpenAI-compatible endpoint
    (reference: stages/http_request_stage.py — concurrent requests with
    retry on transient failures). Rows of a block fan out over a thread
    pool; 429/5xx and socket errors retry with exponential backoff."""

    name = "HttpRequest"

    def __init__(self, url: str, payload_fn: Callable[[dict], dict],
                 output_column: str = "response",
                 timeout_s: float = 120.0, headers: Optional[dict] = None,
                 max_retries: int = 3, requests_per_block: int = 8):
        self.url = url
        self.payload_fn = payload_fn
        self.output_column = output_column
        self.timeout_s = timeout_s
        self.headers = headers or {}
        self.max_retries = max_retries
        self.requests_per_block = requests_per_block

    def __call__(self, ds):
        url, payload_fn = self.url, self.payload_fn
        oc, timeout_s = self.output_column, self.timeout_s
        headers = self.headers
        retries, width = self.max_retries, self.requests_per_block

        def one(payload: dict):
            import json as _json
            import time as _time
            import urllib.error
            import urllib.request
            delay = 0.5
            for attempt in range(retries + 1):
                try:
                    req = urllib.request.Request(
                        url, data=_json.dumps(payload).encode(),
                        headers={"Content-Type": "application/json",
                                 **headers})
                    with urllib.request.urlopen(req,
                                                timeout=timeout_s) as r:
                        return _json.loads(r.read())
                except urllib.error.HTTPError as e:
                    # 4xx (except 429) is the caller's bug: no retry
                    if e.code not in (429, 500, 502, 503, 504) \
                            or attempt == retries:
                        raise
                except (urllib.error.URLError, OSError):
                    if attempt == retries:
                        raise
                _time.sleep(delay)
                delay = min(delay * 2, 8.0)

        def apply_batch(batch: dict) -> dict:
            import concurrent.futures as cf
            n = len(next(iter(batch.values())))
            rows = [{k: batch[k][i] for k in batch} for i in range(n)]
            with cf.ThreadPoolExecutor(max_workers=width) as pool:
                resp = list(pool.map(
                    lambda row: one(payload_fn(row)), rows))
            out = dict(batch)
            out[oc] = resp
            return out

        return ds.map_batches(apply_batch)


# --------------------------------------------------------------------- #
# processor
# --------------------------------------------------------------------- #

class Processor:
    """(reference: processor/base.py:104) `__call__(Dataset) -> Dataset`:
    user preprocess -> ordered stages -> user postprocess."""

    def __init__(self, cfg: ProcessorConfig,
                 preprocess: Optional[Callable] = None,
                 postprocess: Optional[Callable] = None,
                 stages: Optional[list] = None):
        self.cfg = cfg
        self.preprocess = preprocess
        self.postprocess = postprocess
        self.stages: list[Stage] = (list(stages) if stages is not None
                                    else [EngineStage(cfg)])

    def list_stage_names(self) -> list[str]:
        return [s.name for s in self.stages]

    def __call__(self, ds):
        if self.preprocess is not None:
            ds = ds.map(self.preprocess)
        for stage in self.stages:
            ds = stage(ds)
        if self.postprocess is not None:
            ds = ds.map(self.postprocess)
        return ds


def build_llm_processor(config: ProcessorConfig,
                        preprocess: Optional[Callable] = None,
                        postprocess: Optional[Callable] = None,
                        stages: Optional[list] = None) -> Processor:
    """(reference: data/llm.py:248). Default = one EngineStage; pass
    ``stages`` for custom chains, e.g.::

        build_llm_processor(cfg, stages=[
            ChatTemplateStage(), EngineStage(cfg)])
    """
    return Processor(config, preprocess, postprocess, stages)
