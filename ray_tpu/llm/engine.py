"""JAX-native continuous-batching inference engine.

The vLLM replacement (reference: llm/_internal/serve/deployments/llm/vllm/
vllm_engine.py:180 — engine loop, scheduling, sampling; here re-designed for
XLA): a fixed pool of batch *slots* backs a slot-indexed KV cache; prefill
and decode are two jitted programs with static shapes (prompt lengths bucket
to powers of two to bound recompiles); sampling (greedy/temperature/top-k)
runs in-jit. The Python-side loop only admits requests into free slots and
retires finished ones — all math stays compiled.

Continuous batching: new requests join the running batch at any step; a
finished slot frees immediately. Decode cost is one [B, 1] step per token
over all active slots.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import llama
from .tokenizer import get_tokenizer


@dataclasses.dataclass
class SamplingParams:
    """(reference: vLLM SamplingParams surface)"""
    max_tokens: int = 64
    temperature: float = 0.0          # 0 = greedy
    top_k: int = 0                    # 0 = no top-k
    stop_token_ids: tuple = ()
    seed: int = 0
    # > 0: return the chosen token's log-probability per generated token
    # (model-natural log_softmax, not temperature-scaled; top-N
    # alternatives are not reported). Paged engine only.
    logprobs: int = 0


@dataclasses.dataclass
class EngineConfig:
    model: llama.LlamaConfig
    max_batch_size: int = 8
    max_seq_len: int = 1024
    prefill_buckets: tuple = (32, 64, 128, 256, 512, 1024)
    tokenizer: Any = None


@dataclasses.dataclass
class _Request:
    """One in-flight generation (shared by both engines)."""
    rid: int
    prompt_ids: list[int]
    params: SamplingParams
    out_ids: list[int] = dataclasses.field(default_factory=list)
    out_logps: list[float] = dataclasses.field(default_factory=list)
    slot: int = -1
    pages: list[int] = dataclasses.field(default_factory=list)
    prefill_pos: int = 0          # prompt tokens already prefilled (paged)
    # prompt tokens the prefix cache served (paged; the request's share of
    # stats["prefix_tokens_saved"], an argument of its llm.prefill span)
    prefix_tokens_saved: int = 0
    # multi-LoRA (paged engine, cfg.max_adapters): the slot-table row
    # this request's dispatches gather — 0 = base model. Pinned for the
    # request's whole life: a hot-swap to a newer adapter version lands
    # in a different slot, so in-flight requests finish on the version
    # they were admitted with.
    adapter_slot: int = 0
    # prefix-cache chain seed (paged engine): empty for base traffic;
    # serving salts it with (adapter_id, version) so cached pages and
    # cluster-directory entries can never match across tenants
    prefix_salt: bytes = b""
    # content-hash chain of the prompt's FULL pages (paged engine prefix
    # caching); computed lazily at admission, None until then
    page_hashes: Optional[list] = None
    # cache heat plane (llm/chainstats.py): the per-chain stats slot
    # this request's prompt family resolved to; -1 = untracked
    chain_slot: int = -1
    done: bool = False
    submit_t: float = 0.0
    first_token_t: float = 0.0    # TTFT = first_token_t - submit_t
    # telemetry (llm/telemetry.py): admission time, wall-clock submit
    # (spans use wall time), serve request id, and the submitter's trace
    # context so the engine thread can emit an llm.request span
    admit_t: float = 0.0
    submit_wall: float = 0.0
    request_id: str = ""
    trace_ctx: Optional[tuple] = None
    event: threading.Event = dataclasses.field(
        default_factory=threading.Event)


def sample_logits(logits: jax.Array, rng: jax.Array, temperature: float,
                  top_k: int) -> jax.Array:
    """In-jit sampling over [B, V] logits (greedy / temperature / top-k)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def sample_logits_batch(logits: jax.Array, rng: jax.Array,
                        temps: jax.Array, top_ks: jax.Array, *,
                        any_sampled: bool = True,
                        any_topk: bool = True,
                        want_logp: bool = True):
    """Per-ROW sampling over [B, V] logits with per-row params, fully
    in-jit (no shape depends on the params, so one compiled program covers
    every request mix — the piece that lets sampling fuse into the decode
    step instead of costing a host round-trip per token).

    temps[b] <= 0 selects greedy for that row; top_ks[b] > 0 masks to that
    row's top-k logits, honored exactly for any k (per-row threshold from
    one full sort — the same cost the scalar sample_logits path paid).
    any_sampled/any_topk are STATIC hints the caller derives from the
    batch at dispatch time (it keys its jit cache on them): all-greedy
    batches skip the categorical entirely, no-top-k batches skip the sort.
    """
    def chosen_logp(tok):
        # model-natural log-probability of the chosen token (OpenAI
        # logprobs semantics): from the RAW logits, not the
        # temperature/top-k-processed ones. want_logp is STATIC like
        # any_sampled: batches with no logprobs request skip the
        # full-vocab log_softmax entirely (same design rule that lets
        # all-greedy batches skip the categorical).
        if not want_logp:
            return None
        lsm = jax.nn.log_softmax(logits, axis=-1)
        return jnp.take_along_axis(lsm, tok[:, None], axis=-1)[:, 0]

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not any_sampled:
        return greedy, chosen_logp(greedy)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if any_topk:
        v = logits.shape[-1]
        svals = jnp.sort(scaled, axis=-1)                 # [B, V] asc
        k_idx = v - jnp.clip(top_ks, 1, v)
        kth = jnp.take_along_axis(svals, k_idx[:, None], axis=1)
        scaled = jnp.where((top_ks[:, None] > 0) & (scaled < kth),
                           -1e30, scaled)
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    tok = jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)
    return tok, chosen_logp(tok)


class _EngineBase:
    """Request intake, sampling dispatch and result shaping shared by the
    dense-slot and paged engines (the engine-loop surface of the reference's
    VLLMEngine). Subclasses provide step()/has_work() and the two compiled
    programs; they must maintain self.cfg (with .max_seq_len), self._lock,
    self._pending, self._active, self._rng, self.tokenizer."""

    telemetry_kind = "dense"

    def generate(self, prompts, params=None) -> list[dict]:
        """Blocking batch generation; returns [{text, token_ids,
        prompt_tokens, ttft_s, finish_reason}] in prompt order."""
        if params is None:
            params = SamplingParams()
        plist = params if isinstance(params, list) else \
            [params] * len(prompts)
        reqs = [self.submit(p, sp) for p, sp in zip(prompts, plist)]
        while not all(r.done for r in reqs):
            self.step()
        return [self._result(r) for r in reqs]

    def submit(self, prompt, params: SamplingParams,
               adapter_slot: int = 0,
               prefix_salt: bytes = b"") -> _Request:
        import time
        ids = (self.tokenizer.encode(prompt) if isinstance(prompt, str)
               else list(prompt))
        # keep the prompt (up to the cache capacity) and clamp max_tokens
        # to the remaining room — never silently discard the prompt
        ids = ids[: self.cfg.max_seq_len - 2]
        if not ids:
            raise ValueError("empty prompt")
        if adapter_slot:
            table = getattr(self, "lora", None)
            if table is None:
                raise ValueError(
                    "adapter_slot requires a paged engine with "
                    "PagedEngineConfig.max_adapters > 0")
            if not 0 < adapter_slot < table.max_adapters:
                raise ValueError(
                    f"adapter_slot {adapter_slot} outside the slot "
                    f"table [1, {table.max_adapters})")
        capacity = self.cfg.max_seq_len - 1 - len(ids)
        if params.max_tokens > capacity:
            params = dataclasses.replace(params,
                                         max_tokens=max(1, capacity))
        from . import telemetry
        with self._lock:
            req = _Request(self._next_rid, ids, params)
            req.adapter_slot = int(adapter_slot)
            req.prefix_salt = bytes(prefix_salt)
            req.submit_t = time.perf_counter()
            self._next_rid += 1
            # stamp trace/request identity BEFORE publishing: once req is
            # in _pending a concurrently stepping engine thread can retire
            # a short request and emit its span/metrics immediately
            telemetry.on_submit(self, req)
            self._pending.append(req)
        return req

    def _finish_request(self, req: _Request, finish=None):
        """Retire a request: mark done, wake waiters, emit telemetry
        (TTFT/ITL/e2e observations + the request's trace span)."""
        if req.done:
            return
        req.done = True
        req.event.set()
        from . import telemetry
        telemetry.on_finish(self, req, finish)

    def has_work(self) -> bool:
        return bool(self._pending or self._active)

    def run_until_done(self, reqs: list[_Request]):
        while not all(r.done for r in reqs):
            self.step()

    def _sample_one(self, logits, params: SamplingParams):
        self._rng, sub = jax.random.split(self._rng)
        return np.asarray(sample_logits(logits, sub, params.temperature,
                                        params.top_k))

    def _sample_next_tokens(self, logits, rng) -> dict[int, int]:
        """Per-slot next token, batching slots that share sampling params."""
        by_temp: dict[tuple, list[int]] = {}
        for slot, req in self._active.items():
            by_temp.setdefault(
                (req.params.temperature, req.params.top_k), []).append(slot)
        next_tokens: dict[int, int] = {}
        for (temp, top_k), slots in by_temp.items():
            sampled = np.asarray(sample_logits(
                logits[jnp.asarray(slots)], rng, temp, top_k))
            for s, t in zip(slots, sampled):
                next_tokens[s] = int(t)
        return next_tokens

    def _eos_id(self):
        return getattr(self.tokenizer, "eos_id",
                       getattr(self.tokenizer, "eos_token_id", None))

    def _result(self, req: _Request) -> dict:
        eos = getattr(self.tokenizer, "eos_id", None)
        trimmed = [t for t in req.out_ids if t != eos]
        return {
            "text": self.tokenizer.decode(trimmed),
            "token_ids": req.out_ids,
            "prompt_tokens": len(req.prompt_ids),
            "ttft_s": (req.first_token_t - req.submit_t
                       if req.first_token_t else None),
            "finish_reason": ("stop" if eos is not None and eos in req.out_ids
                              else "length"),
            "logprobs": (list(req.out_logps) if req.params.logprobs
                         and req.out_logps else None),
        }


class InferenceEngine(_EngineBase):
    """Synchronous engine; the serving layer runs it on a background thread
    and exposes an async API (reference: VLLMEngine's engine loop)."""

    def __init__(self, cfg: EngineConfig, params: Optional[dict] = None,
                 rng_seed: int = 0):
        self.cfg = cfg
        self.model_cfg = cfg.model
        self.tokenizer = get_tokenizer(cfg.tokenizer)
        if params is None:
            params = llama.init(jax.random.PRNGKey(rng_seed), cfg.model)
        self.params = params
        self.cache = llama.init_slot_cache(cfg.model, cfg.max_batch_size,
                                           cfg.max_seq_len)
        self._free_slots = deque(range(cfg.max_batch_size))
        self._active: dict[int, _Request] = {}      # slot -> request
        self._pending: deque[_Request] = deque()
        self._next_rid = 0
        self._rng = jax.random.PRNGKey(rng_seed)
        self._lock = threading.Lock()
        # observability: dispatch/token counts (paged engine parity;
        # telemetry ships deltas from here to the Prometheus counters)
        self.stats = {"prefill_dispatches": 0, "decode_dispatches": 0,
                      "tokens_out": 0}

        mc = cfg.model
        max_len = cfg.max_seq_len

        @jax.jit
        def _prefill(params, cache, tokens, slot, true_len):
            """tokens [1, S] (right-padded to a bucket) -> writes K/V into
            the slot's cache row, sets its length to true_len, and returns
            the logits at the last REAL prompt position [V]. Pad positions'
            K/V land beyond true_len and are never attended (decode masks
            k_pos <= length) before being overwritten."""
            logits, ks, vs = llama.apply_with_kv(params, tokens, mc)
            cache_k = jax.lax.dynamic_update_slice(
                cache["k"], ks[:, 0:1].astype(cache["k"].dtype),
                (0, slot, 0, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(
                cache["v"], vs[:, 0:1].astype(cache["v"].dtype),
                (0, slot, 0, 0, 0))
            lengths = cache["lengths"].at[slot].set(true_len)
            last = jax.lax.dynamic_index_in_dim(logits[0], true_len - 1, 0,
                                                keepdims=False)
            return last, {"k": cache_k, "v": cache_v, "lengths": lengths}

        @jax.jit
        def _decode(params, cache, tokens, active):
            """tokens [B] -> (logits [B, V], cache); inactive rows don't
            advance their length."""
            logits, new_cache = llama.decode_batched(
                params, tokens[:, None], cache, mc)
            lengths = jnp.where(active, new_cache["lengths"],
                                cache["lengths"])
            lengths = jnp.minimum(lengths, max_len - 1)
            return logits, {"k": new_cache["k"], "v": new_cache["v"],
                            "lengths": lengths}

        self._prefill_fn = _prefill
        self._decode_fn = _decode

    # -- engine loop -------------------------------------------------------

    def step(self):
        """One engine iteration: admit pending prompts (prefill), then one
        batched decode step over all active slots."""
        self._admit()
        if not self._active:
            return
        bs = self.cfg.max_batch_size
        tokens = np.zeros((bs,), np.int32)
        active = np.zeros((bs,), bool)
        for slot, req in self._active.items():
            tokens[slot] = req.out_ids[-1]
            active[slot] = True
        self._rng, sub = jax.random.split(self._rng)
        logits, self.cache = self._decode_fn(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(active))
        self.stats["decode_dispatches"] += 1
        self._sample_and_retire(logits, sub)
        from . import telemetry
        telemetry.on_step(self)

    def _admit(self):
        with self._lock:
            from . import telemetry
            while self._pending and self._free_slots:
                req = self._pending.popleft()
                slot = self._free_slots.popleft()
                req.slot = slot
                self._active[slot] = req
                telemetry.on_admit(self, req)
                self._do_prefill(req)

    def _bucket(self, n: int) -> int:
        for b in self.cfg.prefill_buckets:
            if n <= b:
                return min(b, self.cfg.max_seq_len)
        return self.cfg.max_seq_len

    def _do_prefill(self, req: _Request):
        import time
        ids = req.prompt_ids
        bucket = self._bucket(len(ids))
        padded = ids + [0] * (bucket - len(ids))
        last_logits, self.cache = self._prefill_fn(
            self.params, self.cache, jnp.asarray([padded], jnp.int32),
            req.slot, len(ids))
        first = self._sample_one(last_logits[None, :], req.params)
        req.out_ids.append(int(first[0]))
        req.first_token_t = time.perf_counter()
        self.stats["prefill_dispatches"] += 1
        self.stats["tokens_out"] += 1
        from . import telemetry
        telemetry.on_first_token(self, req)

    def _sample_and_retire(self, logits, rng):
        next_tokens = self._sample_next_tokens(logits, rng)
        eos = self._eos_id()
        for slot in list(self._active):
            req = self._active[slot]
            tok = next_tokens[slot]
            req.out_ids.append(tok)
            self.stats["tokens_out"] += 1
            stop = (len(req.out_ids) >= req.params.max_tokens
                    or tok == eos or tok in req.params.stop_token_ids
                    or int(self.cache["lengths"][slot])
                    >= self.cfg.max_seq_len - 1)
            if stop:
                self._finish_request(req)
                del self._active[slot]
                self._free_slots.append(slot)
