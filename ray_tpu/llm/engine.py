"""What a request to the serving engine is made of: its sampling
parameters, its in-flight record, and the in-jit sampler.

The engine itself is ``paged_engine.PagedInferenceEngine`` — the one
engine an LLM replica, a batch-inference stage and the PD pools run
(reference: llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:180
— engine loop, scheduling, sampling; here re-designed for XLA). Sampling
(greedy / temperature / top-k, per row) runs inside its jitted programs.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import jax
import jax.numpy as jnp


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
    # alternatives are not reported).
    logprobs: int = 0


@dataclasses.dataclass
class _Request:
    """One in-flight generation."""
    rid: int
    prompt_ids: list[int]
    params: SamplingParams
    out_ids: list[int] = dataclasses.field(default_factory=list)
    out_logps: list[float] = dataclasses.field(default_factory=list)
    slot: int = -1
    pages: list[int] = dataclasses.field(default_factory=list)
    # paged engine over a model with sliding-window layers: the window
    # pool's pages of logical pages wlo, wlo + 1, ... (those behind the
    # window have been handed back)
    wpages: list[int] = dataclasses.field(default_factory=list)
    wlo: int = 0
    # paged engine over a model with recurrent-state layers
    # (kv_cache.StateSlots): the snapshot pinned to resume from, the one a
    # launched prefill rows filed and their booking publishes ({pages of
    # the prompt behind it: id}; ids of the snapshot pool, 0: none), and
    # whether a row has loaded the slot's state
    state_snap: int = 0
    state_taken: dict = dataclasses.field(default_factory=dict)
    state_started: bool = False
    prefill_pos: int = 0          # prompt tokens already prefilled
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
    # perf_counter_ns stamps of a token's way out (paged engine +
    # llm/serving.py's stream pump): the booking that put the newest
    # token and the first on the host, and the instant the stream's sink
    # had taken the chunk that carried the first
    token_ns: int = 0
    first_token_ns: int = 0
    first_chunk_ns: int = 0
    # telemetry (llm/telemetry.py): admission time, wall-clock submit
    # (spans use wall time), serve request id, and the submitter's trace
    # context so the engine thread can emit an llm.request span
    admit_t: float = 0.0
    submit_wall: float = 0.0
    request_id: str = ""
    trace_ctx: Optional[tuple] = None
    # (app, deployment) where the request came through a proxy of this
    # host: whose front stages it is observed under (serve/metrics.py)
    front: Optional[tuple] = None
    event: threading.Event = dataclasses.field(
        default_factory=threading.Event)


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
