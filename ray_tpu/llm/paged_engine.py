"""Paged-KV continuous-batching engine: the one engine of the serving,
batch-inference and PD paths.

vLLM-analog re-designed for XLA (reference role:
llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:180): the KV cache
is a pool of fixed-size pages shared by all sequences; each request owns a
block table of page ids, so cache capacity is bounded by TOKENS IN FLIGHT,
not max_batch x max_seq_len, and attention (Pallas,
ops/ragged_paged_attention.py) reads only the pages a sequence actually
uses.

Two families of jitted programs with static shapes, keyed by unroll factor:
  - chunked prefill: up to `prefill_rows` page-aligned chunk-rows per
    dispatch (one batched forward: the rows share each layer's weights,
    and consecutive rows may be consecutive chunks of one prompt; bounded
    work — a long prompt can no longer stall every decode slot; vLLM's
    chunked-prefill role);
  - windowed decode: `decode_window` tokens for every decode-ready slot
    per dispatch (lax.scan feeds each step's sampled tokens back in
    on-device; window 1 while prompts are pending keeps TTFT low).

Sampling is fused into both programs (sample_logits_batch), so the only
device->host traffic of a dispatch is the sampled token block. The Python
loop does admission, page allocation and retirement; all math stays
compiled. Cache buffers are donated through every program so XLA updates
pages in place, and that chain of donations is what orders the programs
on the device.

A dispatch is launched as soon as the host knows what it needs, not once
the one before it has been read back (step()): a prefill is built from
host state alone, and a decode takes the last tokens of the decode before
it from the device, where that one left them, and reckons lengths and
pages from what it allowed each row. So up to three dispatches are
outstanding (a decode, the prefill behind it, the next decode), and a
readback runs one dispatch behind, beside the programs queued after it.

Where the stepping thread's time goes is counted in ``stats["ns_*"]``
(PHASES below) and, under a profiler session, drawn as ``rtpu.engine.*``
spans on the device trace's clock. ``ns_<family>_device`` holds two
kinds of span: a launch (``rtpu.engine.<family>.launch``: the jitted
call, which waits for the interpreter alone and is also counted in
``launch_ns_<family>``) and the blocking part of a readback
(``rtpu.engine.<family>.wait``: the device's answer). The other phases
mostly run while a dispatch is outstanding, so their share of the
thread's time (the benchmark's ``engine_host_share``) is what the host
costs a step, not what the device waits for (PERF.md §3).
"""
from __future__ import annotations

import dataclasses
import sys
import threading
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import flight
from ..ops.grouped_matmul import MAX_TILE_ROWS
from ..ops.ragged_paged_attention import live_key_steps
from ..util.compile_cache import enable_compile_cache
from ..util.profiling import StepProfiler, phase
from . import telemetry
from .engine import (  # noqa: F401 — SamplingParams re-exported
    SamplingParams, _Request, sample_logits_batch,
)
from .kv_cache import (STATE_COUNTERS, WINDOW_COUNTERS, KVCache, WindowPages,
                       window_need)
from .tokenizer import get_tokenizer


def model_module(model_cfg):
    """The module that defines a model config's class: the engine's one
    seam to a model. It calls, by these names and nothing else of the
    module: ``init``, ``cache_layers`` (the cache kind of each layer:
    ``full`` or ``window`` pages, or a recurrent ``state``),
    ``cache_window`` (keys a sliding layer keeps behind a query; 0 where
    no layer slides), ``init_paged_cache`` (a list, one dict of named
    pools a layer, which the engine never interprets: page pools
    [P, page, ...] with P ``num_pages``, or the keyword ``window_pages``
    in a sliding layer; a state layer's arrays by slot and by snapshot,
    sized by the keywords ``state_slots`` and ``state_snapshots`` —
    KVCache.pool_args), ``window_ring_pages`` (a model with sliding
    layers only: the width of their table), ``decode_paged``,
    ``prefill_paged_rows``, ``verify_paged_rows`` (each takes one block
    table, or with a second kind the pair (full, that kind's):
    KVCache.tables), ``routed_per_token``, ``expert_routing``,
    ``experts_held`` ([lo, hi) of the routed experts whose weights this
    replica has; where that is a share of them the programs hand back a
    load of E + 1 entries, the last the distinct held experts reached, a
    layer a step, and E + 2 where the router selects groups first:
    models/llama.py ``held_load``),
    ``attn_step``,
    ``lora_targets`` and, under a mesh, ``check_mesh`` (which may refuse)
    and then ``logical_axes`` and ``cache_logical_axes`` (models/llama.py,
    models/mla_moe.py, models/qwen3_next.py, models/ling_hybrid.py)."""
    return sys.modules[type(model_cfg).__module__]


@dataclasses.dataclass
class PagedEngineConfig:
    # any model config whose module is a model_module (above)
    model: Any
    max_batch_size: int = 8
    page_size: int = 16
    num_pages: int = 512
    # a model with sliding-window layers (model_module's cache_window):
    # pages of the second pool, the one those layers' keys and values
    # live in. A sequence holds there the pages of one window and of the
    # dispatch in flight and hands back the ones behind them; what the
    # prefix cache has published of those stays until the pool's LRU
    # reclaims it. At least max_batch_size rings (the engine says how
    # many when it refuses fewer). 0 for every other model.
    num_window_pages: int = 0
    # a model with recurrent-state layers (model_module's cache_layers):
    # snapshots the pool holds of a sequence's states at a page boundary
    # of its prompt, which a later request with that prefix resumes from
    # (kv_cache.StateSlots). A sequence's live state is in its decode
    # slot and needs none. 0 for every other model.
    num_state_snapshots: int = 0
    max_pages_per_seq: int = 64
    # prefill chunk (page multiple); up to prefill_rows chunks per step
    chunk_size: int = 128
    # dispatch batching: chunk-rows packed per prefill dispatch and
    # decode steps unrolled (lax.scan) per decode dispatch. A dispatch's
    # launch-to-readback round trip is 3-8 ms on a locally attached v5e
    # against programs of 9-35 ms (PERF.md §5). It is hidden: the next
    # dispatch is queued on the device before this one's tokens come
    # back, a decode behind a decode too (step()). What decode_window
    # still amortizes is the host's work a dispatch, and the stream
    # pump's a chunk.
    # decode_window only applies when no prefill is pending (window 1
    # keeps TTFT low while prompts are still entering the batch).
    # prefill_rows None: the engine derives it from the model's routing
    # (derived_prefill_rows) and its pools (kv_cache.window_need): 4 for a
    # dense model, more for one whose experts' weights a dispatch streams.
    prefill_rows: Optional[int] = None
    decode_window: int = 8
    # speculative decoding (prompt-lookup n-gram drafts, greedy only):
    # propose up to spec_tokens continuation tokens by matching the last
    # spec_ngram tokens against the sequence's own history, verify them
    # all in ONE dispatch (models/llama.py verify_paged_rows) and accept
    # the longest agreeing prefix — up to spec_tokens+1 tokens per
    # dispatch on self-similar text, never a wrong token (the accept rule
    # reproduces exact greedy). It competes with the decode window: an
    # acceptance EMA falls back to windowed decode when drafts stop
    # landing (with periodic re-probes), so enabling it is never worse
    # than the window by more than the probe overhead. Worth it when
    # spec_tokens > decode_window, or on real hardware where one wide
    # verify is one model-step of compute vs w serial steps. 0 disables.
    spec_tokens: int = 0
    spec_ngram: int = 2
    # block-table page bucketing: every dispatch slices its block tables
    # to the smallest power-of-two page bucket (floor 4, clamped to
    # max_pages_per_seq) that covers the live pages PLUS the dispatch's
    # write window, so both the plain-JAX fallback's prefix gather and
    # the ragged kernel's page grid scale with TRUE sequence length
    # instead of pool capacity. Each bucket is one more static program
    # per family (same trick as the prefill-row buckets; warmup compiles
    # the whole ladder), so "auto" engages it only when
    # max_pages_per_seq >= 48 — short tables don't amortize the extra
    # programs' compiles (measured: a 40-page table loses more to the
    # extra XLA compiles than the narrower gathers win back on CI-scale
    # models). "on"/"off" force it.
    page_buckets: str = "auto"
    # batched multi-LoRA (llm/multilora): > 0 builds a fixed-shape
    # resident-adapter slot table of this many slots (slot 0 = base) and
    # threads per-row adapter_slot ids through every dispatch, so ONE
    # compiled program serves a mixed-tenant batch. Shapes are static —
    # no new program per adapter mix — and slot 0 padding is an exact
    # +0.0, so base traffic through a lora-enabled engine stays
    # bit-identical. 0 disables (no extra args traced at all).
    max_adapters: int = 0
    # rank ceiling of the slot table; lower-rank adapters zero-pad
    # (exact — padded lanes contribute 0·0 terms)
    lora_rank: int = 8
    lora_targets: tuple = ("wq", "wk", "wv", "wo", "lm_head")
    # automatic prefix caching (vLLM-style block-hash reuse, llm/kv_cache.py):
    # retired requests park their full KV pages in a content-addressed LRU
    # instead of freeing them; a later request whose prompt shares a
    # page-aligned prefix maps them into its block table and starts chunked
    # prefill at the first uncached chunk. Shared pages are refcounted and
    # read-only (every write lands past the cached region); the LRU is
    # reclaimed page by page under allocation pressure.
    enable_prefix_caching: bool = True
    # cache heat plane (llm/chainstats.py): fixed-memory per-chain stats
    # keyed by chain-head hash — hits/misses/evictions/imports per
    # prompt family, with a hard cardinality cap and an __overflow__
    # sink (à la obs/tsdb.py tsdb_max_series) so prompt diversity can
    # never grow engine memory. Pure observation: engine outputs are
    # bit-identical with the table on or off. 0 disables. top_k bounds
    # how many chains telemetry ships / the prefix directory publishes.
    chain_stats_slots: int = 256
    chain_stats_top_k: int = 8
    # tiered KV-cache (llm/tiering.py): demote an evicted refcount-0
    # cached page's KV to a host spill tier instead of freeing it, and
    # promote spilled runs back at admission time before cold prefill.
    # Heat-gated by the chain-stats table (min_hits / max_idle_s) and
    # byte-budgeted (kv_spill_max_bytes; coldest chains expire first).
    # Off by default: with kv_spill off the engine reproduces legacy
    # eviction accounting exactly — pages free, nothing is captured,
    # every spill counter stays zero.
    kv_spill: bool = False
    kv_spill_max_bytes: int = 64 << 20
    kv_spill_min_hits: int = 0
    kv_spill_max_idle_s: float = 0.0
    # mesh-parallel serving (parallel/mesh.py MeshSpec or its dict form,
    # e.g. {"tp": 4} or {"dp": 2, "tp": 2}): weights, the LoRA slot
    # table and the paged KV pool are placed with explicit NamedShardings
    # (KV over kv-heads on tp, block tables / token ids replicated) and
    # every program family compiles with in/out shardings pinned, so
    # steady-state decode moves NO bytes between devices beyond the
    # token-id inputs and sampled-token outputs (counter-verified:
    # stats["mesh_reshard_bytes"] stays 0). None = single-device engine,
    # exactly the pre-mesh traces.
    mesh: Any = None
    tokenizer: Any = None

    def __post_init__(self):
        if self.chunk_size % self.page_size:
            raise ValueError("chunk_size must be a multiple of page_size")
        if (self.prefill_rows is not None and self.prefill_rows < 1) \
                or self.decode_window < 1:
            raise ValueError("prefill_rows and decode_window must be >= 1")
        if self.page_buckets not in ("auto", "on", "off"):
            raise ValueError("page_buckets must be 'auto', 'on' or 'off'")
        if self.chain_stats_slots < 0 or self.chain_stats_top_k < 1:
            raise ValueError("chain_stats_slots must be >= 0 and "
                             "chain_stats_top_k >= 1")
        if self.kv_spill and not self.enable_prefix_caching:
            raise ValueError("kv_spill requires enable_prefix_caching "
                             "(the tier holds content-hashed pages)")
        if self.kv_spill and self.kv_spill_max_bytes <= 0:
            raise ValueError("kv_spill_max_bytes must be > 0")

    @property
    def max_seq_len(self) -> int:
        return self.max_pages_per_seq * self.page_size


# Chunk-rows of a prefill dispatch where the user set none
# (derived_prefill_rows): a dense model's, and the most a routed model's
# dispatch is grown to (2,048 tokens at the default chunk: what bounds a
# decoding row's wait for its next token behind the program).
_DENSE_PREFILL_ROWS = 4
_MAX_PREFILL_ROWS = 16


def derived_prefill_rows(routing: tuple, chunk_size: int) -> int:
    """Chunk-rows of a prefill dispatch where the user set none, from the
    model's ``expert_routing`` (experts, top-k, experts held). A dense
    model: 4 (512 tokens at the default chunk run its matmuls near the
    chip's peak). A model with routed experts streams every held expert's
    weights once a layer a dispatch however few rows each gets, so its
    dispatch is grown until it pays for the stream: the smallest power of
    two of rows at which an expert's mean group (rows x chunk x top-k /
    experts: what a held expert gets, whoever holds the rest) fills the
    grouped kernel's largest row tile, from the dense 4 up to
    _MAX_PREFILL_ROWS."""
    experts, top_k, held = routing
    rows = _DENSE_PREFILL_ROWS
    while held and rows < _MAX_PREFILL_ROWS and \
            rows * chunk_size * top_k < MAX_TILE_ROWS * experts:
        rows *= 2
    return rows


# Host phases of the stepping thread: engine.stats key -> span name
# (util/profiling.phase). The rtpu.engine.* phases partition step();
# the rtpu.loop.* phases are LLMServer._loop's time outside step():
# idle is the wait for work alone, other is everything else. The ten
# sum to the thread's wall time. A *_device key is worn by two spans:
# the blocking readback named here and the launch of LAUNCHES.
PHASES = {
    "ns_admit": "rtpu.engine.admit",
    "ns_prefill_build": "rtpu.engine.prefill.build",
    "ns_prefill_device": "rtpu.engine.prefill.wait",
    "ns_prefill_post": "rtpu.engine.prefill.post",
    "ns_decode_build": "rtpu.engine.decode.build",
    "ns_decode_device": "rtpu.engine.decode.wait",
    "ns_decode_post": "rtpu.engine.decode.post",
    "ns_telemetry": "rtpu.engine.telemetry",
    "ns_loop_other": "rtpu.loop.other",
    "ns_loop_idle": "rtpu.loop.idle",
}
# A launch (the jitted call up to the stream pump's wake-up), by family: its
# span, and the counter it grows beside ns_<family>_device.
LAUNCHES = {
    family: (f"rtpu.engine.{family}.launch", f"launch_ns_{family}")
    for family in ("prefill", "decode")}
# What the stream pump counts of a token's way out of the replica
# (llm/serving.py LLMServer._pump, the one thread that serves every open
# stream); the stepping thread only stamps the bookings
# (_Request.token_ns).
STREAM_COUNTERS = ("stream_chunks", "stream_lag_ns", "stream_first_chunks",
                   "stream_first_lag_ns", "stream_cpu_ns", "stream_passes",
                   "stream_deferred", "stream_write_ns")


@dataclasses.dataclass
class _Launched:
    """A dispatch launched and not yet read back."""
    family: str     # "prefill" | "decode": whose phases its booking is
    outs: tuple     # device arrays: tokens, logprobs | None, load | None
    host: dict      # the launch's own state, as its booking's keywords

    def ahead_of(self, req: _Request) -> Optional[int]:
        """Tokens this unbooked decode holds for ``req`` (what its launch
        allowed the row); None where ``req`` is no row of it."""
        if self.family != "decode" or \
                self.host["reqs"].get(req.slot) is not req:
            return None
        return self.host["allow"][req.slot]


class PagedInferenceEngine:
    """Paged engine stepped by one thread; serving runs it on a
    background thread (reference: the engine-loop surface of
    VLLMEngine)."""

    telemetry_kind = "paged"

    def __init__(self, cfg: PagedEngineConfig, params: Optional[dict] = None,
                 rng_seed: int = 0, interpret: bool = False):
        self.cfg = cfg
        mc = cfg.model
        # every program family below goes through the persistent compile
        # cache, wherever this engine runs (replica worker or driver)
        enable_compile_cache()
        self.tokenizer = get_tokenizer(cfg.tokenizer)
        self.model = model_module(mc)
        if params is None:
            params = self.model.init(jax.random.PRNGKey(rng_seed), mc)
        self.params = params
        # chunk-rows a prefill dispatch may carry: what the user set, or
        # derived from the model's routing; a derived budget gives way to
        # the window pool of a model with sliding layers, halved until the
        # pool holds its rings (the cache refuses one the user set)
        self._routing = tuple(self.model.expert_routing(mc))
        self.prefill_rows = cfg.prefill_rows or derived_prefill_rows(
            self._routing, cfg.chunk_size)
        while not cfg.prefill_rows and self.prefill_rows > 1 and \
                cfg.num_window_pages < window_need(
                    cfg, self.model, self.prefill_rows)[1]:
            self.prefill_rows //= 2
        # counters, filled in below: the cache books into the same dict
        self.stats: dict = {}
        # who holds which page, whatever kinds of layer the model has
        # (llm/kv_cache.py); the pools, on the device, are self.caches
        self.cache = KVCache(cfg, self.model, self.stats, self.prefill_rows)
        self.window = int(self.model.cache_window(mc))  # keys kept; 0: all
        if cfg.kv_spill and self.cache.two_kinds:
            raise ValueError(
                "kv_spill over a two-kind cache (window pages or states "
                "beside full pages): the spill tier moves one kind of "
                "page (ROADMAP R2)")
        self.caches = self.model.init_paged_cache(
            mc, cfg.num_pages, cfg.page_size, **self.cache.pool_args())
        self._free_slots = deque(range(cfg.max_batch_size))
        # a slot's tokens in the cache or written there by a program
        # launched: a decode's launch moves it by what it allows the row
        # (no booking does), a verify dispatch's booking by what it kept
        self._lengths = np.zeros((cfg.max_batch_size,), np.int32)
        self._active: dict[int, _Request] = {}
        self._prefilling: list[_Request] = []   # admitted, prompt not done
        self._pending: deque[_Request] = deque()
        # launched, not yet read back, oldest first: at most three, and
        # one between two step() calls (step())
        self._inflight: deque[_Launched] = deque()
        self._prefix_on = self.cache.prefix_on
        # per-chain heat table (llm/chainstats.py): observation only, no
        # policy path reads it (the cache tells it of hits and evictions)
        self.chains = None
        # bytes one page holds over every pool of every layer (with a
        # window: of every full layer, what a token of a long sequence
        # costs for good; window_page_nbytes is what it costs while a
        # window holds it)
        # (a state layer's arrays are no pages: what one slot, and one
        # snapshot, holds over them is state_nbytes)
        per_layer = [sum(int(pool.nbytes) // pool.shape[0]
                         for pool in layer.values()) for layer in self.caches]
        def of_kind(kind):
            return sum(n for n, k in zip(per_layer, self.cache.layer_kinds)
                       if k == kind)
        self.window_page_nbytes = of_kind("window")
        self.page_nbytes = page_nbytes = of_kind("full")
        # a state layer has two arrays of each: by slot and by snapshot
        self.state_nbytes = of_kind("state") // 2
        if self._prefix_on and cfg.chain_stats_slots > 0:
            from .chainstats import ChainStatsTable
            self.chains = ChainStatsTable(cfg.chain_stats_slots,
                                          page_nbytes)
        # host spill tier (cfg.kv_spill, llm/tiering.py): demoted page
        # KV staged host-side / materialized to the object store by the
        # serving loop; all tier mutations happen under self._lock on
        # the same call paths that mutate the hot-cache structures
        self.spill = None
        # longest known head-rooted hash run per chain slot — what
        # proactive re-warm promotes (bounded: chain_stats_slots runs
        # of at most max_pages_per_seq 16-byte hashes)
        self._chain_runs: dict[int, list[bytes]] = {}
        if self._prefix_on and cfg.kv_spill:
            from .tiering import SpillPolicy, SpillTier
            self.spill = SpillTier(
                cfg.kv_spill_max_bytes, page_nbytes,
                SpillPolicy(min_hits=cfg.kv_spill_min_hits,
                            max_idle_s=cfg.kv_spill_max_idle_s))
            self.spill.bind_chains(self.chains)
        self.cache.log.chains = self.chains
        if self.spill is not None:
            self.cache.log.demote = self._maybe_demote
            self.cache.promote = self._promote_for_locked
        self._next_rid = 0
        # resident-adapter slot table (cfg.max_adapters): device arrays
        # every dispatch gathers per-row; loads are donated scatters the
        # caller serializes against stepping (serving's step lock)
        self.lora = None
        if cfg.max_adapters > 0:
            unknown = set(cfg.lora_targets) - set(
                self.model.lora_targets(mc))
            if unknown:
                raise ValueError(
                    f"PagedEngineConfig.max_adapters > 0 with lora_targets "
                    f"{sorted(unknown)}: {self.model.__name__} adapts only "
                    f"{self.model.lora_targets(mc)}")
            from .multilora.slots import AdapterSlotTable
            self.lora = AdapterSlotTable(mc, cfg.max_adapters,
                                         cfg.lora_rank, cfg.lora_targets)
        # mesh-parallel placement (cfg.mesh): committed NamedShardings
        # for weights / KV pool / slot table, and the pinned in/out
        # sharding tuples every program family compiles with
        self.mesh = None
        self._shardings = None
        if cfg.mesh is not None:
            self._init_mesh()
        self._rng_base = jax.random.PRNGKey(rng_seed ^ 0x5EED)
        self._rng_ctr = 0
        # [B] last tokens of the newest decode program, on the device and
        # never read back: every decode takes it (_decode_window_fn's
        # ``prev``), and the rows that continue an unbooked decode start
        # from it
        self._last = jnp.zeros((cfg.max_batch_size,), jnp.int32)
        if self.mesh is not None:
            self._last = jax.device_put(self._last, self._shardings["repl"])
        self._lock = threading.Lock()
        # notified when a dispatch has been launched (_notify_launch):
        # what serving's stream pump sleeps on (llm/serving.py _pump)
        self.launched = threading.Condition()
        self.launch_gen = 0
        self._interpret = interpret
        # block-table width bucketing (cfg.page_buckets): "auto" engages
        # only when the table is long enough that gathering max_pages on
        # every dispatch dominates (threshold 48 pages)
        self._bucketing = cfg.page_buckets == "on" or (
            cfg.page_buckets == "auto" and cfg.max_pages_per_seq >= 48)
        # jitted programs, keyed by (static unroll factor, sampling mode,
        # block-table page bucket): unroll = decode window / prefill row
        # count; mode = the (any_sampled, any_topk) pair so all-greedy
        # batches compile without the categorical and no-top-k batches
        # without the sort; the page bucket (_page_bucket) is the table
        # width the dispatch was sliced to. Cache pytrees are donated
        # through every one so XLA updates pages in place.
        self._import_fns: dict = {}     # page scatters (_import_fn)
        self._decode_win_fns: dict[tuple, Any] = {}
        self._prefill_rows_fns: dict[tuple, Any] = {}
        self._verify_fns: dict[tuple, Any] = {}
        # observability: dispatches per program family, spec accept stats
        self.stats.update({
            "prefill_dispatches": 0, "decode_dispatches": 0,
            # launches made while another dispatch was outstanding: how often
            # step() runs ahead
            "dispatches_overlapped": 0,
            # of a decode launched behind an unbooked decode: rows whose first
            # token came from the device (the last tokens that decode left
            # there), and rows x steps run for a request the booking before
            # found done (a stop the host could not foresee)
            "decode_rows_fed_on_device": 0, "decode_dead_rows": 0,
            "spec_dispatches": 0, "spec_proposed": 0, "spec_accepted": 0,
            "tokens_out": 0,
            # prefix cache: full prompt pages served from cache vs computed by
            # prefill, LRU pages reclaimed under pressure, and prompt tokens
            # whose prefill was skipped entirely
            "prefix_hits": 0, "prefix_misses": 0, "prefix_evictions": 0,
            "prefix_tokens_saved": 0,
            # pages seeded from ANOTHER replica's cache via the cluster prefix
            # directory (import_prefix), and cached pages gathered FOR a peer
            # (export_prefix)
            "prefix_imported_pages": 0, "prefix_exported_pages": 0,
            # spill tier (cfg.kv_spill): pages/bytes captured into the host
            # tier, demote decisions that kept a tier copy (captures + clean
            # re-evictions), pages promoted back into HBM (admission-time,
            # re-warm, or cross-replica via the directory), pages expired from
            # the tier (budget/teardown), and validate-on-promote drops
            # (stale/corrupt tier content: cost a cold prefill, nothing else).
            # All permanently 0 while kv_spill is off.
            "spill_pages": 0, "spill_bytes": 0, "spill_demotions": 0,
            "spill_promotions": 0, "spill_expired": 0, "spill_drops": 0,
            # mesh-parallel dispatch accounting (cfg.mesh): host<->device bytes
            # a dispatch legitimately moves (token-id/table inputs,
            # sampled-token outputs) vs bytes that would move because a
            # committed buffer drifted off its pinned sharding. The reshard
            # counter staying 0 IS the zero-involuntary-reshard contract; all
            # permanently 0 while mesh is off.
            "mesh_dispatches": 0, "mesh_input_bytes": 0,
            "mesh_output_bytes": 0, "mesh_reshard_bytes": 0,
            # work decided at the dispatch, summed over dispatches: slots and
            # KV pages the decode program had live (of max_batch_size rows x
            # the table's width it runs: decode_table_pages; each live row's
            # rounded up to the kernel's key block, the pages a call's sweeps
            # step through: decode_swept_pages), device steps (the
            # window w), prefill rows live / run (the power-of-two bucket),
            # prompt tokens prefilled, the pages their rows attend and the
            # causal (query, key) pairs they score. Each is read by a per-layer
            # metric of the benchmark (PERF.md §3)
            "decode_live_slots": 0, "decode_live_pages": 0,
            "decode_table_pages": 0, "decode_swept_pages": 0,
            "decode_steps": 0, "prefill_rows_live": 0,
            "prefill_rows_padded": 0, "prefill_tokens": 0,
            "prefill_ctx_pages": 0, "prefill_attn_pairs": 0,
            # live grid steps one layer's window kernel swept for the prefill
            # rows, and those of them whose block needed the live-key predicate
            "prefill_key_steps": 0, "prefill_key_steps_masked": 0,
            # request stamps, exact: submit -> admit and admit -> first token,
            # summed over requests
            "admitted": 0, "queue_wait_ns": 0, "first_tokens": 0,
            "prefill_span_ns": 0})
        # the stepping thread's time by phase (util/profiling.phase):
        # ns_<phase> sums, max_ns_<phase> keeps the longest occurrence.
        # The eight engine phases partition step(); the two loop phases
        # are LLMServer._loop's time outside it. Over any window their
        # sum is the thread's wall time.
        for key in PHASES:
            self.stats[key] = self.stats["max_" + key] = 0
        for _, also in LAUNCHES.values():
            self.stats[also] = 0
        self.stats.update(dict.fromkeys(STREAM_COUNTERS, 0))
        # perf_counter_ns of the booking in progress: the stamp of the
        # tokens it puts on the host (_Request.token_ns)
        self._book_ns = 0
        # an MoE config's expert layer computes every row and token a
        # program runs, live or not (_moe_account); a dense config has no
        # such keys
        if self.model.routed_per_token(mc):
            self.stats.update(moe_assign_live=0, moe_assign_run=0,
                              moe_expert_load_sum=0, moe_expert_load_max=0)
        # a replica that holds a share of the routed experts: the
        # assignments that fell on them, the busiest of them, the
        # distinct ones a decode step reached, and (a router that selects
        # groups first) the tokens x layers whose groups include a held
        # one — beside moe_assign_held, what tells "half the tokens bring
        # two assignments" from "every token brings one"
        self._held = tuple(self.model.experts_held(mc))
        self._share = self._routing[2] < self._routing[0]
        if self._share:
            self.stats.update(moe_assign_held=0, moe_held_load_max=0,
                              moe_held_hit_decode=0, moe_group_hits=0)
        # a model with layers of two kinds: what the cache counts of each
        if self.cache.window is not None:
            self.stats.update(dict.fromkeys(WINDOW_COUNTERS, 0))
        if self.cache.state is not None:
            self.stats.update(dict.fromkeys(STATE_COUNTERS, 0))
        # speculation controller: EMA of tokens-per-slot-per-spec-dispatch
        # (starts optimistic), plus a cooldown of windowed dispatches
        # before re-probing once the EMA drops below the window
        self._spec_gain = float(cfg.spec_tokens + 1)
        self._spec_cooldown = 0
        self._spec_cooldown_len = 8    # doubles per failed probe, to 256
        # step profiler (util/profiling.py): counts the programs
        # compiled, per family and static key (profile_summary)
        self.profiler = StepProfiler("paged_engine")
        # programs compiled by warmup(): profiler.compiles beyond this
        # count compiled under traffic (profile_summary)
        self.warm_programs = 0

    # -- what a prefill dispatch carries ------------------------------------

    def _prefill_row_ladder(self) -> list[int]:
        """Every row count a prefill program is built for (ascending,
        the budget last): a launch runs the smallest that holds its rows
        and warm-up compiles them all. Powers of two up to the budget;
        for a routed model's budget above 4 one rung more, not a longer
        ladder — {1, 4, budget}: a pad row there costs its own FLOPs and
        no weight stream (the rows share each layer's), and every rung is
        one more program a page bucket to warm."""
        top = self.prefill_rows
        if self._routing[2] and top > _DENSE_PREFILL_ROWS:
            return [1, _DENSE_PREFILL_ROWS, top]
        return [1 << i for i in range((top - 1).bit_length())] + [top]

    def _refuse_two_kinds(self, what: str):
        if self.cache.two_kinds:
            raise NotImplementedError(
                f"{what} over a two-kind cache (window pages or states "
                "beside full pages): a payload carries one kind of page "
                "(ROADMAP R2)")

    # -- mesh-parallel placement (cfg.mesh) --------------------------------

    def _init_mesh(self):
        """Build the device mesh and commit weights, KV pool and the
        adapter slot table onto it at the shardings _mesh_shardings
        pins."""
        import math
        from ..parallel.mesh import MeshSpec, build_mesh
        spec = self.cfg.mesh
        if isinstance(spec, dict):
            spec = MeshSpec(**spec)
        # an engine's mesh spec names how many chips it WANTS, not how
        # many the process sees: take the leading slice so tp=2 works on
        # an 8-device host
        devices = jax.devices()
        want = math.prod(
            getattr(spec, a) for a in ("pp", "dp", "fsdp", "ep", "sp", "tp"))
        if 0 < want <= len(devices):
            devices = devices[:want]
        self.mesh = build_mesh(spec, devices=devices)
        sh = self._shardings = self._mesh_shardings()
        self.params = jax.device_put(self.params, sh["params"])
        self.caches = jax.device_put(self.caches, sh["caches"])
        if self.lora is not None:
            self.lora.shard(self.mesh, sh["lora"])

    def _mesh_shardings(self) -> dict:
        """The explicit NamedShardings of everything committed to
        self.mesh: KV pages shard over kv-heads on tp, weights follow
        the model's logical_axes, block tables / token ids stay replicated.
        These are what every program family compiles with (in == out for
        the donated caches, so page updates keep aliasing in place — an
        unconstrained output sharding breaks donation, the way it once
        did for sharded opt_state)."""
        from ..parallel import sharding as shardlib
        from ..parallel.mesh import use_mesh
        mc, model = self.cfg.model, self.model
        model.check_mesh(mc, dict(zip(self.mesh.axis_names,
                                      self.mesh.devices.shape)))
        with use_mesh(self.mesh):
            repl = shardlib.named_sharding(())
            pshard = shardlib.logical_sharding(model.logical_axes(mc))
            pools = {name: shardlib.named_sharding(axes) for name, axes
                     in model.cache_logical_axes(mc).items()}
            cshard = [dict(pools) for _ in self.caches]
            lshard = repl
            if self.lora is not None:
                lshard = shardlib.logical_sharding(
                    self.lora.logical_axes())
        return {"params": pshard, "caches": cshard,
                "lora": lshard, "repl": repl}

    def _mesh_scope(self):
        """Context manager making self.mesh the current mesh for jax work
        on this thread (dispatch, trace-time constrain() resolution,
        import scatters); a no-op nullcontext off-mesh."""
        if self.mesh is None:
            import contextlib
            return contextlib.nullcontext()
        from ..parallel.mesh import use_mesh
        return use_mesh(self.mesh)

    def _family_jit(self, run, n_plain: int, name: str, n_out: int = 3):
        """jit a dispatch family with the donated caches at arg 1, under
        ``name`` (family and static window / rows: ``rtpu_decode_w8``,
        ``rtpu_prefill_r4``, ``rtpu_verify_r2``), which the profiler's
        ``XLA Modules`` line shows as ``jit_<name>``: a trace then says
        which program ran on either side of an idle gap. With a
        mesh: every in/out sharding pinned — params/caches/lora at their
        committed placements, the n_plain host-array args (token ids,
        block tables, lengths, rng, temps) replicated, outputs (sampled
        tokens, logprobs, an MoE config's per-expert load; ``n_out`` of
        them: a decode program's fourth is its rows' last tokens, which
        the next decode takes as an input) replicated and the cache
        outputs bit-matching their inputs so donation aliases. Pinning
        is what guarantees the compiled program never inserts an
        involuntary reshard of a committed buffer: any transfer beyond
        the declared host arrays would need an in/out sharding this
        signature forbids."""
        run.__name__ = run.__qualname__ = name
        if self.mesh is None:
            return jax.jit(run, donate_argnums=(1,))
        sh = self._shardings
        ins = (sh["params"], sh["caches"]) + (sh["repl"],) * n_plain + (
            sh["lora"], sh["repl"])
        outs = (sh["repl"],) * n_out + (sh["caches"],)
        return jax.jit(run, donate_argnums=(1,), in_shardings=ins,
                       out_shardings=outs)

    def _mesh_account(self, host_in: int, host_out: int):
        """Per-dispatch transfer accounting (mesh on only): declared
        host->device input bytes and device->host output bytes, plus a
        walk of every committed tree (params, caches, slot table)
        checking each leaf still sits at its pinned sharding — a leaf
        that drifted counts its full nbytes as involuntary-reshard
        traffic. Cheap (pure Python attribute compares), and the walk IS
        the counter-verification the zero-reshard contract is asserted
        against."""
        if self.mesh is None:
            return
        st = self.stats
        st["mesh_dispatches"] += 1
        st["mesh_input_bytes"] += int(host_in)
        st["mesh_output_bytes"] += int(host_out)
        sh = self._shardings
        bad = 0
        for tree, shtree in ((self.params, sh["params"]),
                             (self.caches, sh["caches"])):
            for leaf, want in zip(jax.tree.leaves(tree),
                                  jax.tree.leaves(shtree)):
                if not want.is_equivalent_to(leaf.sharding, leaf.ndim):
                    bad += int(leaf.nbytes)
        if self.lora is not None and self._shardings["lora"] is not None:
            for leaf, want in zip(jax.tree.leaves(self.lora.tree),
                                  jax.tree.leaves(sh["lora"])):
                if not want.is_equivalent_to(leaf.sharding, leaf.ndim):
                    bad += int(leaf.nbytes)
        st["mesh_reshard_bytes"] += bad

    @staticmethod
    def _sampling_mode(reqs) -> tuple:
        reqs = list(reqs)
        any_sampled = any(r.params.temperature > 0 for r in reqs)
        any_topk = any_sampled and any(
            r.params.top_k > 0 and r.params.temperature > 0 for r in reqs)
        # third static key: only batches containing a logprobs request
        # compile + pay the full-vocab log_softmax (engine.py
        # chosen_logp); everyone else runs the lean program
        want_logp = any(r.params.logprobs for r in reqs)
        return any_sampled, any_topk, want_logp

    # -- block-table page buckets -----------------------------------------

    _PAGE_BUCKET_FLOOR = 4

    def _page_bucket(self, need_pages: int) -> int:
        """Block-table width for a dispatch that must address
        ``need_pages`` logical pages (live prefix + every position the
        dispatch writes — a write past the width would CLAMP into the
        last column and clobber a live page instead of routing to the
        zero/sink entries beyond a row's allocation). Power-of-two,
        floored at 4 (tiny programs don't amortize their compile),
        clamped to max_pages_per_seq; the full width when bucketing is
        off, so every dispatch shape matches the unbucketed engine."""
        maxp = self.cfg.max_pages_per_seq
        if not self._bucketing:
            return maxp
        need = max(int(need_pages), 1)
        return min(maxp, max(self._PAGE_BUCKET_FLOOR,
                             1 << (need - 1).bit_length()))

    def _page_bucket_ladder(self) -> list[int]:
        """Every width _page_bucket can return (ascending) — what warmup
        must compile for the no-mid-burst-compiles contract to hold."""
        maxp = self.cfg.max_pages_per_seq
        if not self._bucketing:
            return [maxp]
        out = []
        b = self._PAGE_BUCKET_FLOOR
        while b < maxp:
            out.append(b)
            b <<= 1
        out.append(maxp)
        return out

    def _decode_window_fn(self, w: int, mode: tuple, pages: int):
        """One dispatch = w decode steps for every slot: lax.scan unrolls
        decode+sample, feeding each step's sampled tokens straight back in
        on-device. Only the [B, w] token block crosses back to the host
        (with an MoE config, also the [E] per-expert assignment counts of
        the dispatch, summed over layers and steps: _moe_account). The
        rows' last tokens stay on the device as one more output, [B]:
        the next decode dispatch takes it as ``prev`` and starts the rows
        that ``fed`` marks from it, so it can be launched before this
        one's tokens are on the host (step()); the other rows start from
        ``tok0``, the host's.
        ``pages`` is the block-table width this program was built for
        (_page_bucket): part of the static key, like w and the mode."""
        fn = self._decode_win_fns.get((w, mode, pages))
        if fn is None:
            mc, page = self.cfg.model, self.cfg.page_size
            model, interpret = self.model, self._interpret
            any_sampled, any_topk, want_logp = mode

            def run(p, c, tok0, bt, ln0, key, ctr, temps, top_ks, prev, fed,
                    lora=None, slots=None):
                def body(carry, i):
                    toks, lens, caches = carry
                    logits, caches, load = model.decode_paged(
                        p, toks[:, None], caches, bt, lens, mc,
                        page_size=page, interpret=interpret,
                        lora=lora, slots=slots)
                    sub = jax.random.fold_in(
                        jax.random.fold_in(key, ctr), i)
                    nxt, lp = sample_logits_batch(
                        logits, sub, temps, top_ks,
                        any_sampled=any_sampled, any_topk=any_topk,
                        want_logp=want_logp)
                    return (nxt, lens + 1, caches), (
                        nxt, lp if want_logp else None, load)

                (last, _, c), (out, lps, load) = jax.lax.scan(
                    body, (jnp.where(fed, prev, tok0), ln0, c),
                    jnp.arange(w))
                # [B, w] tokens (and logprobs); the steps' loads summed
                return (out.T, None if lps is None else lps.T,
                        None if load is None else load.sum(0), last, c)

            fn = self._family_jit(run, 9, f"rtpu_decode_w{w}", n_out=4)
            self._decode_win_fns[(w, mode, pages)] = fn
        return fn

    def _prefill_rows_fn(self, r: int, mode: tuple, pages: int):
        """One dispatch = r prefill chunk-rows + in-jit sampling of each
        row's last-token logits (used only for prompt-completing rows).
        ``pages`` = block-table width (static key, see
        _decode_window_fn)."""
        fn = self._prefill_rows_fns.get((r, mode, pages))
        if fn is None:
            mc, page = self.cfg.model, self.cfg.page_size
            model, interpret = self.model, self._interpret
            any_sampled, any_topk, want_logp = mode

            def run(p, c, chunks, bts, sps, tls, key, ctr, temps, top_ks,
                    lora=None, slots=None):
                last, c, load = model.prefill_paged_rows(
                    p, chunks, c, bts, sps, tls, mc, page_size=page,
                    interpret=interpret, lora=lora, slots=slots)
                toks, lps = sample_logits_batch(
                    last, jax.random.fold_in(key, ctr), temps, top_ks,
                    any_sampled=any_sampled, any_topk=any_topk,
                    want_logp=want_logp)
                return toks, lps, load, c

            fn = self._family_jit(run, 8, f"rtpu_prefill_r{r}")
            self._prefill_rows_fns[(r, mode, pages)] = fn
        return fn

    def _verify_fn(self, r: int, s1: int, pages: int,
                   want_logp: bool = False):
        """One dispatch = verify r rows of s1 = 1+drafts tokens; returns
        the model's greedy next token AT each fed position [r, s1] (and
        its log-probability when the batch asked for logprobs — a
        static key, like the sampling modes and the ``pages``
        block-table width)."""
        fn = self._verify_fns.get((r, s1, pages, want_logp))
        if fn is None:
            mc, page = self.cfg.model, self.cfg.page_size
            model, interpret = self.model, self._interpret

            def run(p, c, toks, bts, starts, lora=None, slots=None):
                logits, c, load = model.verify_paged_rows(
                    p, toks, c, bts, starts, mc, page_size=page,
                    interpret=interpret, lora=lora, slots=slots)
                y = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if not want_logp:
                    return y, None, load, c
                lp = jnp.take_along_axis(
                    jax.nn.log_softmax(logits, axis=-1), y[..., None],
                    axis=-1)[..., 0]
                return y, lp, load, c

            fn = self._family_jit(run, 3, f"rtpu_verify_r{r}")
            self._verify_fns[(r, s1, pages, want_logp)] = fn
        return fn

    # -- multi-LoRA slot plumbing (cfg.max_adapters; llm/multilora) --------

    def _lora_args(self, slots) -> tuple:
        """Trailing (lora_tree, slots) args for a dispatch: (None, None)
        when the table is disabled — the jitted programs then trace the
        exact pre-LoRA math."""
        if self.lora is None:
            return (None, None)
        return (self.lora.tree, np.asarray(slots, np.int32))

    def load_adapter_slot(self, slot: int, adapter) -> None:
        """Install an adapter into a slot table row (None clears it).
        CALLER must serialize against the stepping thread (serving.py's
        step lock): the donated row scatters invalidate the old table
        buffers, same contract as import_prefix."""
        if self.lora is None:
            raise ValueError(
                "engine built without a slot table "
                "(PagedEngineConfig.max_adapters == 0)")
        self.lora.load(slot, adapter)

    def adapter_slots_in_use(self) -> dict:
        """{slot: live request count} over pending+prefilling+active —
        what the manager's LRU must NOT evict (a resident adapter with
        in-flight requests is pinned to its admitted version)."""
        with self._lock:
            counts: dict[int, int] = {}
            for req in (list(self._pending) + list(self._prefilling)
                        + list(self._active.values())):
                s = getattr(req, "adapter_slot", 0)
                if s:
                    counts[s] = counts.get(s, 0) + 1
            return counts

    # -- public API --------------------------------------------------------

    def warmup(self, sample_modes=((False, False),),
               families=("prefill", "decode", "verify")) -> float:
        """Compile every program family this engine dispatches, BEFORE
        serving traffic; returns seconds spent.

        The reference's serving engine does the same at deployment time
        (vLLM profiles and captures its execution graphs during engine
        init, before the server admits requests — vllm_engine.py:180's
        engine start path). A program compiled mid-burst lands in some
        request's latency, exactly when the first burst does (seconds
        per program at 8B widths on a v5e: chip_smoke.py prints the
        warm-up's compile time).

        Families: prefill rows over the power-of-two buckets, decode
        windows {1, decode_window}, and — when speculation is on — the
        verify-row buckets; every family crossed with the block-table
        page-bucket ladder when cfg.page_buckets engages (a dispatch's
        table width is a static program key exactly like its row
        count). ``families`` narrows the set for replicas that only
        ever run one side (a P/D prefill replica never decodes; a
        decode replica never prefills — compiling the other side would
        double deploy-time for nothing). Dummy dispatches carry zero
        block tables and zero true_lens, so every write routes to sink
        page 0 and no visible engine state is touched; the donated
        caches round-trip through each program.
        """
        import time as _time
        with self._mesh_scope():
            took = self._warmup_traced(sample_modes, families,
                                       _time.perf_counter())
        self.warm_programs = self.profiler.compiles
        # the one line an engine writes as it starts (a replica's lands in
        # its worker's log)
        print(f"paged_engine: {self.warm_programs} programs warm in "
              f"{took:.1f} s; prefill rows {self._prefill_row_ladder()} "
              f"({'set' if self.cfg.prefill_rows else 'derived'}), page "
              f"buckets {self._page_bucket_ladder()}",
              file=sys.stderr, flush=True)
        return took

    def _warmup_traced(self, sample_modes, families, t0) -> float:
        import time as _time
        cfg = self.cfg
        bs, c = cfg.max_batch_size, cfg.chunk_size
        key, ctr = self._rng_base, np.int32(0)
        modes = [tuple(m) + (False,) * (3 - len(m)) for m in sample_modes]
        buckets = self._page_bucket_ladder()
        for mode in modes:
            for maxp in (buckets if "prefill" in families else ()):
                for rb in self._prefill_row_ladder():
                    tw = _time.perf_counter()
                    toks, _lps, _load, self.caches = self._prefill_rows_fn(
                        rb, mode, maxp)(
                        self.params, self.caches,
                        np.zeros((rb, c), np.int32),
                        self.cache.tables([-1] * rb, maxp, prefill=[]),
                        np.zeros((rb,), np.int32), np.zeros((rb,), np.int32),
                        key, ctr, np.zeros((rb,), np.float32),
                        np.zeros((rb,), np.int32),
                        *self._lora_args(np.zeros((rb,), np.int32)))
                    np.asarray(toks)
                    # book as compile (and mark the key warm) so the first
                    # REAL dispatch after warmup counts as execute time
                    self.profiler.record_compile(
                        _time.perf_counter() - tw, "prefill",
                        (rb, mode, maxp))
            for maxp in (buckets if "decode" in families else ()):
                for w in sorted({1, cfg.decode_window}):
                    tw = _time.perf_counter()
                    out, _lps, _load, self._last, self.caches = \
                        self._decode_window_fn(w, mode, maxp)(
                        self.params, self.caches, np.zeros((bs,), np.int32),
                        self.cache.tables([-1] * bs, maxp),
                        np.zeros((bs,), np.int32), key, ctr,
                        np.zeros((bs,), np.float32),
                        np.zeros((bs,), np.int32),
                        self._last, np.zeros((bs,), np.bool_),
                        *self._lora_args(np.zeros((bs,), np.int32)))
                    np.asarray(out)
                    self.profiler.record_compile(
                        _time.perf_counter() - tw, "decode", (w, mode, maxp))
        if cfg.spec_tokens > 0 and "verify" in families:
            s1 = cfg.spec_tokens + 1
            for maxp in buckets:
                rb = 1
                while True:
                    rb = min(rb, bs)
                    tw = _time.perf_counter()
                    y, _ylp, _load, self.caches = self._verify_fn(
                        rb, s1, maxp)(
                        self.params, self.caches,
                        np.zeros((rb, s1), np.int32),
                        self.cache.tables([-1] * rb, maxp),
                        np.zeros((rb,), np.int32),
                        *self._lora_args(np.zeros((rb,), np.int32)))
                    np.asarray(y)
                    # mark warm like prefill/decode: the first REAL spec
                    # dispatch must book as execute, not compile
                    self.profiler.record_compile(
                        _time.perf_counter() - tw, "verify",
                        (rb, s1, maxp, False))
                    if rb >= bs:
                        break
                    rb <<= 1
        return _time.perf_counter() - t0

    # -- request intake and results -----------------------------------------

    def generate(self, prompts, params=None) -> list[dict]:
        """Blocking batch generation; returns [{text, token_ids,
        prompt_tokens, ttft_s, finish_reason}] in prompt order."""
        if params is None:
            params = SamplingParams()
        plist = params if isinstance(params, list) else \
            [params] * len(prompts)
        reqs = [self.submit(p, sp) for p, sp in zip(prompts, plist)]
        self.run_until_done(reqs)
        return [self._result(r) for r in reqs]

    def submit(self, prompt, params: SamplingParams,
               adapter_slot: int = 0,
               prefix_salt: bytes = b"") -> _Request:
        ids = (self.tokenizer.encode(prompt) if isinstance(prompt, str)
               else list(prompt))
        # keep the prompt (up to the cache capacity) and clamp max_tokens
        # to the remaining room — never silently discard the prompt
        ids = ids[: self.cfg.max_seq_len - 2]
        if not ids:
            raise ValueError("empty prompt")
        if adapter_slot:
            if self.lora is None:
                raise ValueError(
                    "adapter_slot requires "
                    "PagedEngineConfig.max_adapters > 0")
            if not 0 < adapter_slot < self.lora.max_adapters:
                raise ValueError(
                    f"adapter_slot {adapter_slot} outside the slot "
                    f"table [1, {self.lora.max_adapters})")
        capacity = self.cfg.max_seq_len - 1 - len(ids)
        if params.max_tokens > capacity:
            params = dataclasses.replace(params,
                                         max_tokens=max(1, capacity))
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

    def has_work(self) -> bool:
        return bool(self._pending or self._prefilling or self._active
                    or self._inflight)

    def run_until_done(self, reqs: list[_Request]):
        while not all(r.done for r in reqs):
            self.step()
        self._drain()

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

    # -- the spill tier's hooks into the cache (cfg.kv_spill) ---------------

    def _maybe_demote(self, pid: int, h: bytes, slot: Optional[int]):
        """The cache evicts published page ``pid`` (hash ``h``, chain
        ``slot``), whose next owner overwrites the device page: capture
        its KV for the host tier first (kv_cache.IndexLog.demote)."""
        if self.spill.has(h):
            # content already in the tier (promoted or re-computed,
            # then evicted again): a clean eviction — refresh recency,
            # copy nothing
            self.spill.touch(h)
            self.stats["spill_demotions"] += 1
            return
        now = time.monotonic()
        if not self.spill.policy.admit(self.chains, slot, now):
            return      # heat-gated: not worth tier residence — free
        chain = slot if slot is not None else 0
        expired = self.spill.add(h, chain, self._gather_pages(pid), now)
        captured = self.spill.has(h)
        if captured:
            self.stats["spill_demotions"] += 1
            self.stats["spill_pages"] += 1
            self.stats["spill_bytes"] += self.spill.page_nbytes
            if self.chains is not None:
                self.chains.spilled_add(chain)
        self._spill_expired(expired, skip_accounted=not captured)

    def _spill_expired(self, removed, skip_accounted: bool = False):
        """Account tier entries expired under the byte budget (or
        refused entry outright, skip_accounted — never counted in)."""
        for _h, chain in removed:
            if skip_accounted:
                skip_accounted = False
                continue    # the refused page itself: was never added
            self.stats["spill_expired"] += 1
            if self.chains is not None:
                self.chains.spilled_sub(chain)

    def _spill_dropped(self, removed):
        """Account validate-on-promote failures: stale/corrupt tier
        content purged — costs this request a cold prefill, nothing
        else (the module failure model, llm/tiering.py)."""
        for _h, chain in removed:
            self.stats["spill_drops"] += 1
            if self.chains is not None:
                self.chains.spilled_sub(chain)

    def _release(self, req: _Request):
        """A retired request's pages and its slot go back."""
        self.cache.release(req)
        if req.slot >= 0:
            self._free_slots.append(req.slot)
            self._lengths[req.slot] = 0
            req.slot = -1

    # -- engine loop -------------------------------------------------------

    def _phase(self, key: str):
        return phase(self.stats, key, PHASES[key])

    def _launch(self, family: str):
        """The ``ns_<family>_device`` phase of a launch."""
        name, also = LAUNCHES[family]
        return phase(self.stats, f"ns_{family}_device", name, also)

    def step(self):
        """One iteration: admit, launch one prefill dispatch (bounded),
        launch one decode dispatch, then read back everything older than
        the newest launch. No launch waits for a readback; the readbacks
        run one dispatch behind.

        A prefill P(k) is built from host state alone and goes out
        beside the decode D(k-1) that the last step() left on the device.
        The decode D(k) goes out behind both. It continues D(k-1) without
        that one's tokens: the rows' last tokens are on the device, in
        the [B] array D(k-1) left there, and D(k) takes its first tokens
        from it (_decode_window_fn); what the booking of D(k-1) would
        tell the host it reckons at the launch from what D(k-1) allowed
        each row (``allow``): lengths, the block table's width and the
        pages to reserve count booked + in-flight tokens, and a request
        that D(k-1) ends by max_tokens, by the sequence ceiling or by a
        dry pool is no row of D(k) (_launch_decode). A stop the host
        cannot foresee (EOS, stop_token_ids) is seen one dispatch late:
        that row runs one dead dispatch, whose tokens its booking throws
        away as it does the tokens past a stop inside one window. Rows
        that joined since D(k-1) went out (a prompt's first token, which a
        prefill's booking produced; import_prefill) start from the
        host's token: a prompt that ends in P(k) joins the decode batch
        one dispatch later. Then D(k-1) and P(k) are read back and
        booked, in the device's order, beside the programs queued behind
        them, and D(k) stays out. So when a decode is launched at most
        one earlier decode and one prefill are unbooked, at most three
        dispatches are outstanding, and one between two step() calls.
        Behind an unbooked FULL window the decode is one step, and behind
        that step goes the next full window: the step covers the host's
        turn from the window's readback to the next launch, and a prompt
        that arrives while the host waits for the window finds one step
        queued ahead of its prefill, not a second window.

        When P(k) holds the last chunk any prompt waits for, the decode
        after it is a full window: launched beside P(k) it would run
        decode_window steps (and a round of the stream pump's Python)
        without the prompts that end in P(k), so it follows P(k)'s
        booking and carries them. Speculation proposes its drafts from
        the tokens on the host: an engine with spec_tokens > 0 books
        every decode before it launches the next (_launch_decode). A
        step that launched nothing books everything.

        The donated pools order the programs on the device, and a page
        freed on the host can only be written by a program launched
        later; a dead row writes past its request's last valid token,
        never into a page the prefix cache has published. Everything
        falls in one of the eight rtpu.engine.* phases (PHASES): admit,
        {prefill, decode} x {build, device, post}, telemetry; ``device``
        is two spans, a ``.launch`` (LAUNCHES) or the ``.wait`` of a
        blocking readback."""
        with self._phase("ns_admit"):
            self._admit()
        # the mesh scope pins trace-time constrain() resolution for any
        # program a launch compiles below (a no-op off-mesh)
        with self._mesh_scope():
            launched = self._launch_prefill()
            if launched:
                # one prefill at most stays out beside a decode
                self._book_until(1 if self._prompts_wait() else 0, "prefill")
            launched |= self._launch_decode()
            self._book_until(1 if launched else 0)
        with self._phase("ns_telemetry"):
            telemetry.on_step(self)

    def _prompts_wait(self) -> bool:
        """Is any prompt chunk still to be launched?"""
        return bool(self._pending) or any(
            r.prefill_pos < len(r.prompt_ids) for r in self._prefilling)

    def _book_until(self, keep: int, family: Optional[str] = None):
        """Read back and book outstanding dispatches, oldest first (the
        order the device runs them in), until ``keep`` are left (of
        ``family``, where one is given). A booking that another
        readback's wait follows wakes the stream pump: the tokens it put
        on the host would otherwise lie there for the length of that
        wait (_notify_launch)."""
        def left():
            return sum(family in (None, d.family) for d in self._inflight)
        while left() > keep:
            d = self._inflight[0]
            book = (self._book_prefill if d.family == "prefill"
                    else self._book_decode)
            with self._phase(f"ns_{d.family}_device"):
                # block until the outputs are on the host; a readback
                # that raises leaves the dispatch outstanding
                got = [None if x is None else np.asarray(x) for x in d.outs]
            with self._phase(f"ns_{d.family}_post"):
                self._book_ns = time.perf_counter_ns()
                book(*got, **d.host)
                # the device's arrays and their host copies go inside
                # the phase: the phases leave nothing of step() out
                del self._inflight[0], d, got
                if left() > keep:
                    self._notify_launch()

    def _drain(self):
        """Leave nothing outstanding: what ends a blocking call."""
        with self._mesh_scope():
            self._book_until(0)

    def _launched(self, family: str, outs: tuple, **host):
        """A program was just launched: queue its booking, count it as
        running ahead if an earlier one is still out, wake the stream
        pump."""
        if self._inflight:
            self.stats["dispatches_overlapped"] += 1
        self._inflight.append(_Launched(family, outs, host))
        self._rng_ctr += 1
        self._notify_launch()

    def _admit(self):
        with self._lock:
            while self._pending and self._free_slots:
                # held back until the pools cover the whole prompt; then
                # its pages, the cached prefix mapped in (KVCache.admit)
                req = self._pending[0]
                if not self.cache.admit(req, self._free_slots[0]):
                    break
                self._pending.popleft()
                self._free_slots.popleft()
                if self.spill is not None and req.chain_slot > 0:
                    # remember the chain's longest head-rooted hash run —
                    # what proactive re-warm promotes
                    hs = self.cache.prompt_hashes(req)
                    prev = self._chain_runs.get(req.chain_slot)
                    if prev is None or len(hs) > len(prev):
                        self._chain_runs[req.chain_slot] = \
                            list(hs[:self.cfg.max_pages_per_seq])
                self._prefilling.append(req)
                telemetry.on_admit(self, req)
                self.stats["admitted"] += 1
                self.stats["queue_wait_ns"] += int(
                    (req.admit_t - req.submit_t) * 1e9)

    def _launch_prefill(self) -> bool:
        """Pack and launch one prefill dispatch; False when no row can
        be launched yet."""
        if not self._prefilling:
            return False
        cfg = self.cfg
        c, pg = cfg.chunk_size, cfg.page_size
        with self._phase("ns_prefill_build"):
            # pages an outstanding prefill computes are published when
            # it is booked: a request that could then map them in (the
            # rest of an identical-prefix burst) waits for that, and
            # does not compute them again
            unpublished = {
                h for d in self._inflight if d.family == "prefill"
                for req, pos, n in d.host["rows"]
                for h in self.cache.prompt_hashes(req)[
                    pos // pg:(pos + n) // pg]
            } if self.cache.reuses_mid_prefill else ()
            # pack up to self.prefill_rows chunk-rows, queue order; a request
            # with several remaining chunks occupies consecutive rows (every
            # row's K/V is in the pages before any row attends, so later
            # rows see earlier rows' page writes)
            rows: list[tuple] = []              # (req, start, n_tokens)
            for req in self._prefilling:
                # skip ahead over chunks published since the last step (an
                # identical-prefix burst: request 1 computes, the rest map)
                self.cache.reuse(req)
                pos = req.prefill_pos
                if unpublished and pos % c == 0 and \
                        pos < self.cache.reuse_limit(len(req.prompt_ids)) \
                        and self.cache.prompt_hashes(req)[
                            pos // pg] in unpublished:
                    continue
                while pos < len(req.prompt_ids) and \
                        len(rows) < self.prefill_rows:
                    n = self.cache.row_tokens(req, pos)
                    if not self.cache.ensure(req, pos + n):
                        break
                    rows.append((req, pos, n))
                    pos += n
                if len(rows) >= self.prefill_rows:
                    break
            if not rows:
                return False
            # bucket the row count to a rung of the ladder: the jit cache
            # holds its few prefill programs instead of one per packed-row
            # count. Pad rows carry true_len 0, so the kernel routes all
            # their writes to sink page 0 (prefill_paged_rows docstring) —
            # they cost compute but no fresh XLA compile, and a mid-burst
            # compile lands in some request's latency.
            r = len(rows)
            rb = next(b for b in self._prefill_row_ladder() if b >= r)
            # block-table width bucket: widest logical page any row reads
            # or writes this dispatch (prefix + chunk = pos + n tokens)
            ctx_pages = [(pos + n + pg - 1) // pg for _, pos, n in rows]
            W = self._page_bucket(max(ctx_pages))
            chunks = np.zeros((rb, c), np.int32)
            sps = np.zeros((rb,), np.int32)
            tls = np.zeros((rb,), np.int32)
            temps = np.zeros((rb,), np.float32)
            topks = np.zeros((rb,), np.int32)
            lslots = np.zeros((rb,), np.int32)
            for i, (req, pos, n) in enumerate(rows):
                chunks[i, :n] = req.prompt_ids[pos:pos + n]
                sps[i], tls[i] = pos, n
                temps[i] = req.params.temperature
                topks[i] = req.params.top_k
                lslots[i] = req.adapter_slot
                # the next dispatch's rows start where these end: that
                # needs no readback
                req.prefill_pos = pos + n
            mode = self._sampling_mode([q for q, _, _ in rows])
            fn = self._prefill_rows_fn(rb, mode, W)
            tables = self.cache.tables(
                [q.slot for q, _, _ in rows] + [-1] * (rb - r), W,
                prefill=rows)
        with self._launch("prefill"):
            with self.profiler.step("prefill", (rb, mode, W)):
                toks, lps, load, self.caches = fn(
                    self.params, self.caches, chunks, tables, sps, tls,
                    self._rng_base, np.int32(self._rng_ctr), temps, topks,
                    *self._lora_args(lslots))
            self._launched(
                "prefill", (toks, lps, load), rows=rows, rb=rb, W=W,
                ctx_pages=ctx_pages, sps=sps, tls=tls,
                in_bytes=chunks.nbytes + 4 * rb * W + sps.nbytes
                + tls.nbytes + temps.nbytes + topks.nbytes + lslots.nbytes)
        return True

    def _book_prefill(self, toks, lps, load, *, rows, rb, W, ctx_pages,
                      sps, tls, in_bytes):
        """Book a prefill dispatch read back; the keywords are what its
        launch kept of the host's state (_launch_prefill)."""
        r, c, pg = len(rows), self.cfg.chunk_size, self.cfg.page_size
        st = self.stats
        st["prefill_dispatches"] += 1
        st["prefill_rows_live"] += r
        st["prefill_rows_padded"] += rb
        st["prefill_tokens"] += int(tls.sum())
        st["prefill_ctx_pages"] += sum(ctx_pages)
        # causal (query, key) pairs: token q of a row attends the
        # pos cached tokens and the row's first q + 1
        st["prefill_attn_pairs"] += sum(
            n * pos + n * (n + 1) // 2 for _, pos, n in rows)
        steps, masked = live_key_steps(
            sps[:r], tls[:r], c, W, page_size=pg, **self._attn_step(c, W))
        st["prefill_key_steps"] += steps
        st["prefill_key_steps_masked"] += masked
        self._moe_account(load, int(tls.sum()), rb * c)
        self._mesh_account(
            in_bytes,
            toks.nbytes + sum(x.nbytes for x in (lps, load)
                              if x is not None))
        self.cache.booked_prefill(rows)
        for i, (req, pos, n) in enumerate(rows):
            if pos + n >= len(req.prompt_ids):
                # prompt done: the row's in-jit sampled token is the
                # first generated token
                self._first_token(
                    req, int(toks[i]),
                    None if lps is None else float(lps[i]))
        # NOTE: pad positions of the final chunk were written into the
        # sequence's own pages beyond its true length; decode masks
        # positions >= length so they are never attended.

    def _first_token(self, req: _Request, tok: int, lp: Optional[float]):
        """A prompt's last chunk returned: book its first generated
        token and move the request into the decode set (or export it,
        on a disaggregated prefill replica)."""
        req.first_token_ns = req.token_ns = self._book_ns
        req.out_ids.append(tok)
        if lp is not None:
            req.out_logps.append(lp)
        st = self.stats
        st["tokens_out"] += 1
        req.first_token_t = time.perf_counter()
        telemetry.on_first_token(self, req)
        st["first_tokens"] += 1
        st["prefill_span_ns"] += int(
            (req.first_token_t - req.admit_t) * 1e9)
        self._lengths[req.slot] = len(req.prompt_ids)
        self._prefilling.remove(req)
        if getattr(req, "prefill_only", False):
            # disaggregated prefill: export the KV pages + first token
            # instead of decoding here (llm/pd_disagg.py). Under the
            # pool lock: _release mutates _free_slots and the cache,
            # which a concurrent submit/import_prefill (replica
            # threads) also touches — and the export must not observe
            # a cache swap mid-gather. _finish_request stays OUTSIDE
            # it: the span emit can write a pipe, and blocking I/O
            # under the admission lock stalls every replica thread
            # (the GL002 bug class).
            with self._lock:
                req.export_payload = self._export_kv_locked(req, tok)
                self._release(req)
            self._finish_request(req, "export")
            return
        self._active[req.slot] = req
        self._maybe_finish(req, tok)

    @staticmethod
    def _propose_draft(ctx: np.ndarray, n: int, s: int) -> list[int]:
        """Prompt-lookup draft: find the most recent earlier occurrence of
        the context's final n-gram and propose the s tokens that followed
        it (reference role: vLLM's prompt-lookup speculative proposer)."""
        m = len(ctx) - n                   # candidate match positions 0..m-1
        if m <= 0 or s <= 0:
            return []
        tail = ctx[-n:]
        hits = np.flatnonzero(np.all(
            np.stack([ctx[i:m + i] for i in range(n)]) == tail[:, None],
            axis=0))
        if len(hits) == 0:
            return []
        # most recent occurrence that still has a FULL s-token
        # continuation (on constant/periodic runs the newest hit sits at
        # the end of the run with almost nothing after it); fall back to
        # the earliest hit, whose continuation is the longest available
        viable = hits[hits + n + s <= len(ctx)]
        start = int(viable[-1] if len(viable) else hits[0]) + n
        return [int(t) for t in ctx[start:start + s]]

    def _notify_launch(self):
        """Wake whoever waits for new tokens (serving's stream pump, one
        thread whatever the number of streams), called where this
        thread's next act is to wait for the device: with a program just
        launched (_launched), or after a booking that another readback
        follows (_book_until). The pump's Python — detokenising, one
        ring write a stream — then runs beside a program and beside that
        wait. Woken by every booking instead, that Python holds the GIL
        exactly when this thread needs it to launch the next program
        (PERF.md §6, PR 27; with a thread a stream, 64 of them took
        turns at it and a launch that needs 3 ms took 50-80: PR 39). The
        tokens the pump finds at a launch are those of the booking
        before it: of the dispatch two before the one just launched
        when a decode follows a decode, since the readback runs one
        dispatch behind (step())."""
        with self.launched:
            self.launch_gen += 1
            self.launched.notify_all()

    def _moe_account(self, load, live_tokens: int, run_tokens: int,
                     decode: bool = False):
        """Book one dispatch of an MoE config: token-expert assignments
        that belonged to live rows / real prompt tokens (known here) and
        that the program routed (idle rows and padding included: each
        token x top_k x layers), and the sum and the maximum over experts
        of ``load``, the [E] assignment counts the program handed back
        with its tokens. max / (sum / E) over a window says how uneven
        the routing was. ``load`` is None for a dense config."""
        if load is None:
            return
        st = self.stats
        if self._share:
            # behind a share's load: the distinct held experts its layers
            # reached and, under group-limited selection, the tokens whose
            # groups include a held one — each summed over layers and
            # steps (model_module)
            load, tail = load[:self._routing[0]], load[self._routing[0]:]
            here = load[self._held[0]:self._held[1]]
            st["moe_held_hit_decode"] += int(tail[0]) * decode
            st["moe_group_hits"] += int(tail[1]) if len(tail) > 1 else 0
            st["moe_assign_held"] += int(here.sum())
            st["moe_held_load_max"] += int(here.max())
        per_token = self.model.routed_per_token(self.cfg.model)
        st["moe_assign_live"] += live_tokens * per_token
        st["moe_assign_run"] += run_tokens * per_token
        st["moe_expert_load_sum"] += int(load.sum())
        st["moe_expert_load_max"] += int(load.max())

    def _live_pages(self, slots) -> int:
        """KV pages that hold the given slots' tokens: what one decode
        step's attention has to stream."""
        page = self.cfg.page_size
        return int(sum(-(-int(self._lengths[sl]) // page) for sl in slots))

    def _attn_step(self, q_window: int, table_pages: int) -> dict:
        """The model's kernel step ({'q_tile', 'block_keys'}) under a
        window of ``q_window`` queries a row over a table that wide, as
        one of the mesh's head shards runs it."""
        return self.model.attn_step(
            self.cfg.model, q_window, self.cfg.page_size, table_pages,
            1 if self.mesh is None else self.mesh.shape.get("tp", 1))

    def _swept_pages(self, slots, table_pages: int) -> int:
        """KV pages of the key blocks one decode step's kernel call
        sweeps for the given slots over a table that wide: a row's live
        pages rounded up to the call's key block — a partly live block is
        scored whole, its tail under a probability of 0. What a wider
        block costs."""
        step = self._attn_step(1, table_pages)
        ends = np.asarray([self._lengths[sl] for sl in slots], np.int64)
        blocks, _ = live_key_steps(
            np.maximum(ends - 1, 0), np.minimum(ends, 1), 1, table_pages,
            page_size=self.cfg.page_size, **step)
        return blocks * max(1, step["block_keys"] // self.cfg.page_size)

    def _live_window_pages(self, slots) -> int:
        """Window pages one decode step streams for ``slots``: by this
        name tests/benchmark_harness holds benchmarks/flops_window to it."""
        return WindowPages.live_pages(
            self._lengths, slots, self.cfg.page_size, self.window)

    def _spec_step(self) -> bool:
        """One speculative verify dispatch over every active slot. Only
        runs when every slot is greedy (the accept rule reproduces exact
        greedy; sampled rows fall back to the windowed path) and at least
        one slot has a draft. Returns False to fall through. Its phases
        are the decode ones: the verify dispatch is this step's decode.
        Launched and read back here (the drafts are proposed from every
        token so far), with nothing else outstanding: quiet, no prefill
        went out this step, and _launch_decode has booked what the last
        left."""
        cfg = self.cfg
        s, page = cfg.spec_tokens, cfg.page_size
        with self._phase("ns_decode_build"):
            slots = sorted(self._active)
            drafts = {}
            for slot in slots:
                req = self._active[slot]
                ctx = np.asarray(req.prompt_ids + req.out_ids, np.int32)
                drafts[slot] = self._propose_draft(ctx, cfg.spec_ngram, s)
            # every slot must carry a draft: in a spec dispatch a
            # draft-less slot emits exactly ONE token, strictly worse than
            # its share of a decode window. A no-draft round costs the
            # same backed-off cooldown as a failed probe, so
            # non-repetitive text doesn't pay the O(context) n-gram scan
            # on every step.
            if not all(drafts.values()):
                self._spec_cooldown = self._spec_cooldown_len
                self._spec_cooldown_len = min(
                    self._spec_cooldown_len * 2, 256)
                return False
            # bucket the row count to a power of two so the jit cache
            # holds O(log max_batch) verify programs, not one per
            # active-set size; pad rows write only to sink page 0 and are
            # discarded
            r, s1 = len(slots), s + 1
            rb = min(1 << max(r - 1, 0).bit_length(), cfg.max_batch_size)
            # table-width bucket: every row writes positions
            # start..start+s1-1, so the width must cover their pages
            # (beyond-allocation writes then hit the row's zero entries =
            # sink page, never a clamp)
            W = self._page_bucket(max(
                (self._lengths[sl] + s1 - 1) // page + 1 for sl in slots))
            toks = np.zeros((rb, s1), np.int32)
            starts = np.zeros((rb,), np.int32)
            lslots = np.zeros((rb,), np.int32)
            allow: dict[int, int] = {}
            for i, slot in enumerate(slots):
                req = self._active[slot]
                allow[slot] = self._reserve(req, s1)
                toks[i, 0] = req.out_ids[-1]
                toks[i, 1:1 + len(drafts[slot])] = drafts[slot]
                starts[i] = self._lengths[slot]
                lslots[i] = req.adapter_slot
            want_lp = any(self._active[sl].params.logprobs for sl in slots)
            fn = self._verify_fn(rb, s1, W, want_lp)
            tables = self.cache.tables(slots + [-1] * (rb - r), W)
        with self._launch("decode"):
            with self.profiler.step("verify", (rb, s1, W, want_lp)):
                y, ylp, load, self.caches = fn(
                    self.params, self.caches, toks, tables, starts,
                    *self._lora_args(lslots))
            self._notify_launch()
        with self._phase("ns_decode_device"):
            y = np.asarray(y)               # [r, s1]; block: measure
            ylp = None if ylp is None else np.asarray(ylp)
            load = None if load is None else np.asarray(load)
        with self._phase("ns_decode_post"):
            self._book_ns = time.perf_counter_ns()
            self.stats["spec_dispatches"] += 1
            self._moe_account(load, r * s1, rb * s1)
            self._mesh_account(
                toks.nbytes + 4 * rb * W + starts.nbytes + lslots.nbytes,
                y.nbytes + sum(x.nbytes for x in (ylp, load)
                               if x is not None))
            emitted = 0
            for i, slot in enumerate(slots):
                req = self._active[slot]
                d = drafts[slot]
                self.stats["spec_proposed"] += len(d)
                # accept: token j's prediction y[i, j] is the true next
                # token only while every earlier draft matched the
                # model's choice
                def _lp(row, col):
                    return None if ylp is None else float(ylp[row, col])
                out = [(int(y[i, 0]), _lp(i, 0))]
                for j in range(len(d)):
                    if d[j] != out[-1][0]:
                        break
                    out.append((int(y[i, j + 1]), _lp(i, j + 1)))
                    self.stats["spec_accepted"] += 1
                consumed = 0
                req.token_ns = self._book_ns
                for tok, lp in out:
                    if consumed >= allow[slot]:
                        telemetry.on_preempted(self)
                        self._retire(req)
                        break
                    req.out_ids.append(tok)
                    if lp is not None:
                        req.out_logps.append(lp)
                    self._lengths[slot] += 1
                    consumed += 1
                    self.stats["tokens_out"] += 1
                    if self._stop_after(req, tok):
                        self._retire(req)
                        break
                if req.slot >= 0:
                    self.cache.advanced(req, int(self._lengths[slot]))
                emitted += consumed
            # controller: keep speculating only while it beats the window;
            # on fallback, re-probe optimistically after a cooldown that
            # doubles per consecutive failed probe (text that never
            # accepts pays a vanishing probe tax, text that turns
            # repetitive is rediscovered within ~cooldown windows)
            self._spec_gain = 0.5 * self._spec_gain + 0.5 * (emitted / r)
            if self._spec_gain <= self.cfg.decode_window and \
                    self.cfg.decode_window > 1:
                self._spec_cooldown = self._spec_cooldown_len
                self._spec_cooldown_len = min(
                    self._spec_cooldown_len * 2, 256)
                self._spec_gain = float(s + 1)
            else:
                self._spec_cooldown_len = 8
        return True

    def _launch_decode(self) -> bool:
        """Launch one decode dispatch; False when there is no row for
        one. At most one earlier decode is unbooked (step()). The rows
        are the decode set less the requests that decode is known to
        end; a row that continues it starts from the token it left on
        the device and counts the tokens it allowed as there already, the
        others (booked to their last token) start from the host's."""
        cfg = self.cfg
        if cfg.spec_tokens > 0:
            # a draft is proposed from every token so far
            self._book_until(0, "decode")
        if not self._active:
            return False
        bs, page = cfg.max_batch_size, cfg.page_size
        quiet = not (self._prefilling or self._pending)
        if cfg.spec_tokens > 0 and quiet and \
                self._sampling_mode(
                    self._active.values())[:2] == (False, False):
            if self._spec_cooldown > 0:
                self._spec_cooldown -= 1
            elif self._spec_step():
                return True
        with self._phase("ns_decode_build"):
            # full window only when no prompt is waiting: a pending
            # prefill gets interleaved every step, keeping TTFT low under
            # bursts
            w = 1 if not quiet else cfg.decode_window
            ahead = next(
                (d for d in self._inflight if d.family == "decode"), None)
            if ahead is not None and ahead.host["w"] > 1:
                # behind an unbooked full window, one step: it covers
                # the host's turn from that window's readback to the
                # next launch, and a prompt that arrives during the wait
                # is queued behind a step, not behind a second window
                w = 1
            # the rows of THIS dispatch, each with the tokens it has in
            # flight (None: none, its last token is the host's): a
            # request that joins the decode set before it is booked
            # (import_prefill, a prompt's first token) is none of them
            rows: dict[int, tuple] = {}
            for slot, req in self._active.items():
                fly = ahead and ahead.ahead_of(req)
                if fly is None or not self._ends_after(
                        req, fly, ahead.host["w"]):
                    rows[slot] = (req, fly)
            if not rows:
                return False
            tokens = np.zeros((bs,), np.int32)
            fed = np.zeros((bs,), np.bool_)
            lengths = np.zeros((bs,), np.int32)
            temps = np.zeros((bs,), np.float32)
            topks = np.zeros((bs,), np.int32)
            lslots = np.zeros((bs,), np.int32)
            allow: dict[int, int] = {}      # valid tokens per slot
            for slot, (req, fly) in rows.items():
                allow[slot] = self._reserve(req, w, fly or 0)
                if fly is None:
                    tokens[slot] = req.out_ids[-1]
                else:
                    fed[slot] = True
                lengths[slot] = self._lengths[slot]
                temps[slot] = req.params.temperature
                topks[slot] = req.params.top_k
                lslots[slot] = req.adapter_slot
            # table-width bucket: the window writes positions
            # len..len+w-1 per slot, so the width covers every such page
            # (beyond-allocation writes then hit zero entries = sink page,
            # never a clamp)
            W = self._page_bucket(max(
                (lengths[sl] + w - 1) // page + 1 for sl in rows))
            reqs = {slot: req for slot, (req, _) in rows.items()}
            mode = self._sampling_mode(reqs.values())
            fn = self._decode_window_fn(w, mode, W)
            # slots not decoding this step get a zeroed block-table row:
            # their dummy writes go to sink page 0 instead of a live
            # (possibly reused) page
            tables = self.cache.tables(
                [sl if sl in reqs else -1 for sl in range(bs)], W)
        with self._launch("decode"):
            with self.profiler.step("decode", (w, mode, W)):
                out, lps, load, self._last, self.caches = fn(
                    self.params, self.caches, tokens, tables, lengths,
                    self._rng_base, np.int32(self._rng_ctr), temps, topks,
                    self._last, fed, *self._lora_args(lslots))
            self.cache.launched_decode(lengths, reqs)
            self._launched(
                "decode", (out, lps, load), reqs=reqs, allow=allow, w=w,
                fed_rows=int(fed.sum()),
                live_pages=self._live_pages(reqs), table_pages=bs * W,
                swept_pages=self._swept_pages(reqs, W),
                in_bytes=tokens.nbytes + fed.nbytes + 4 * bs * W
                + lengths.nbytes + temps.nbytes + topks.nbytes
                + lslots.nbytes)
            # the next launch starts where this one ends: no booking
            # moves a length
            for slot, n in allow.items():
                self._lengths[slot] += n
        return True

    def _ends_after(self, req: _Request, fly: int, w: int) -> bool:
        """Does the unbooked decode of ``w`` steps that allowed ``req``
        ``fly`` tokens end it, for all the host knows at its launch: cut
        short by a dry pool, or at max_tokens or the sequence ceiling?"""
        return fly < w or self._at_limit(req, len(req.out_ids) + fly)

    def _book_decode(self, out, lps, load, *, reqs, allow, w, fed_rows,
                     live_pages, table_pages, swept_pages, in_bytes):
        """Book a decode dispatch read back ([bs, w] tokens); the
        keywords are what its launch kept of the host's state
        (_launch_decode). A row whose request the booking before this
        one found done is dead: nothing of it is kept."""
        st = self.stats
        live = {slot: req for slot, req in reqs.items() if not req.done}
        st["decode_dispatches"] += 1
        st["decode_live_slots"] += len(live)
        st["decode_dead_rows"] += (len(reqs) - len(live)) * w
        st["decode_rows_fed_on_device"] += fed_rows
        st["decode_live_pages"] += live_pages
        st["decode_table_pages"] += table_pages
        st["decode_swept_pages"] += swept_pages
        st["decode_steps"] += w
        self._moe_account(load, len(live) * w,
                          self.cfg.max_batch_size * w, decode=True)
        self._mesh_account(
            in_bytes,
            out.nbytes + sum(x.nbytes for x in (lps, load)
                             if x is not None))
        for slot, req in live.items():
            # stamped before the tokens are appended: a stream that
            # sees a token finds a stamp no older than its booking
            req.token_ns = self._book_ns
            for j in range(w):
                if j >= allow[slot]:
                    # page pool exhausted mid-window: finish early
                    # rather than wedge (tokens past the allocation
                    # wrote to the sink page and are not trustworthy)
                    telemetry.on_preempted(self)
                    self._retire(req)
                    break
                tok = int(out[slot, j])
                req.out_ids.append(tok)
                if lps is not None:
                    req.out_logps.append(float(lps[slot, j]))
                st["tokens_out"] += 1
                if self._stop_after(req, tok):
                    self._retire(req)
                    break
            if req.slot >= 0:
                self.cache.advanced(req, int(self._lengths[slot]))
        self.cache.booked_decode()

    def _reserve(self, req: _Request, width: int, fly: int = 0) -> int:
        """Pre-allocate pages for up to `width` new tokens and return how
        many of the dispatch's tokens are VALID for this request, which
        has ``fly`` more in an unbooked decode than the host has booked.

        Pages are grabbed only for tokens the request can still emit
        (width, max_tokens remainder, sequence ceiling — whichever is
        least; over-grabbing would starve later slots under pool
        pressure). Device writes past the allocation land on sink page 0
        and those tokens are discarded; if the pool runs dry the request
        keeps only the tokens its allocated pages cover and finishes
        early. Shared by the windowed-decode and speculative paths so
        their page budgeting can never diverge."""
        out = len(req.out_ids) + fly
        total = len(req.prompt_ids) + out
        remaining = max(req.params.max_tokens - out, 1)
        target = min(total + min(width, remaining), self.cfg.max_seq_len)
        if self.cache.ensure(req, target):
            return target - total
        return max(self.cache.held(req) * self.cfg.page_size - total, 0)

    def _at_limit(self, req: _Request, out: int) -> bool:
        """Is a request of ``out`` generated tokens at max_tokens or at
        the sequence ceiling: the stops the host can foresee?"""
        return (out >= req.params.max_tokens
                or len(req.prompt_ids) + out >= self.cfg.max_seq_len - 1)

    def _stop_after(self, req: _Request, tok: int) -> bool:
        """Stop condition evaluated after appending tok to req.out_ids."""
        return (self._at_limit(req, len(req.out_ids))
                or tok == self._eos_id() or tok in req.params.stop_token_ids)

    def _finish_request(self, req: _Request, finish=None):
        """Retire a request: mark done, wake waiters, emit telemetry
        (TTFT/ITL/e2e observations + the request's trace span)."""
        if req.done:
            return
        req.done = True
        req.event.set()
        telemetry.on_finish(self, req, finish)

    def _retire(self, req: _Request):
        self._finish_request(req)
        self._active.pop(req.slot, None)
        if req in self._prefilling:
            self._prefilling.remove(req)
        self._release(req)

    def _maybe_finish(self, req: _Request, tok: int):
        stop = self._stop_after(req, tok)
        if not stop:
            # growing by one token may need one more page
            total = len(req.prompt_ids) + len(req.out_ids)
            if not self.cache.ensure(req, total + 1):
                stop = True  # pool exhausted: finish early rather than wedge
                telemetry.on_preempted(self)
        if stop:
            self._retire(req)

    # -- prefill/decode disaggregation (llm/pd_disagg.py; reference:
    # prefill_decode_disagg.py:64) ----------------------------------------

    def _export_kv_locked(self, req: _Request, first_token: int) -> dict:
        """Gather this request's KV pages to host arrays for transfer to a
        decode replica (the role the KV-connector plays for the reference's
        PD deployments)."""
        pages = self._gather_pages(
            jnp.asarray(np.asarray(req.pages, np.int32)))
        return {"prompt_ids": list(req.prompt_ids),
                "first_token": int(first_token),
                "page_size": self.cfg.page_size,
                # chained content hashes of the FULL prompt pages, in page
                # order: the decode side dedupes payload pages it already
                # holds instead of re-allocating and re-scattering them
                "page_hashes": list(self.cache.prompt_hashes(req)),
                # the chain's seed, so the decode side's request hashes
                # land in the same (tenant-scoped) key space
                "prefix_salt": req.prefix_salt,
                "pages": pages}

    def prefill_export(self, prompt, params: SamplingParams) -> dict:
        """Chunked-prefill `prompt` and return its exported KV payload
        (drives the engine loop until the export is ready)."""
        self._refuse_two_kinds("prefill_export")
        req = self.submit(prompt, params)
        req.prefill_only = True
        req.export_payload = None
        while req.export_payload is None and not req.done:
            self.step()
        self._drain()
        if req.export_payload is None:
            raise RuntimeError("prefill finished without an export "
                               "(prompt rejected?)")
        return req.export_payload

    def import_prefill(self, payload: dict, params: SamplingParams,
                       ) -> _Request:
        """Seed a decode-ready sequence from an exported KV payload:
        allocate slot+pages, scatter the page data into this engine's
        pools, and place the request directly in the decode set."""
        import time
        self._refuse_two_kinds("import_prefill")
        if payload["page_size"] != self.cfg.page_size:
            raise ValueError(
                f"page_size mismatch: payload {payload['page_size']} vs "
                f"engine {self.cfg.page_size}")
        ids = list(payload["prompt_ids"])
        with self._lock:
            req = _Request(self._next_rid, ids, params)
            req.prefix_salt = payload.get("prefix_salt", b"")
            req.submit_t = time.perf_counter()
            req.admit_t = req.submit_t
            telemetry.on_submit(self, req)
            self._next_rid += 1
            if not self._free_slots:
                raise RuntimeError("no free decode slot")
            req.slot = self._free_slots.popleft()
            n_pages = self.cache.pages_for(len(ids) + 1)
            n_in = len(next(iter(payload["pages"][0].values())))
            if n_in != n_pages:
                self._release(req)
                raise ValueError(
                    f"payload covers {n_in} pages but this engine "
                    f"needs {n_pages} for the same prompt")
            # dedupe: payload pages whose content hash this engine already
            # holds are mapped (and pinned) instead of re-scattered — a
            # decode replica serving many same-system-prompt imports keeps
            # one copy of the shared prefix. The chain property means the
            # reusable run is a prefix of the page list. Full pages only;
            # the partial tail page is always private (decode writes into
            # it at position len(ids)).
            hashes = payload.get("page_hashes")
            if hashes is None and self._prefix_on:
                hashes = self.cache.hash_chain(ids, prev=req.prefix_salt)
            # chain property: what is held is a prefix run
            matched = self.cache.index.run(hashes) \
                if self._prefix_on and hashes else []
            if not self.cache.full.claim(req, req.slot, matched,
                                         n_pages):
                self._release(req)
                raise RuntimeError("page pool exhausted importing prefill")
            fresh = list(range(len(matched), n_pages))
            pages = req.pages
            if self._prefix_on:
                # hits/misses track page-level cache efficacy; deduped
                # imports save scatter/transfer, NOT prefill compute (the
                # prefill replica already counted any skipped prefill), so
                # tokens_saved deliberately stays untouched here — fleet
                # sums would otherwise double-count
                self.stats["prefix_hits"] += len(matched)
                nf = len(ids) // self.cfg.page_size  # full prompt pages
                self.stats["prefix_misses"] += nf - len(matched)
                if self.chains is not None and hashes:
                    req.chain_slot = self.chains.slot_for(
                        hashes[0], req.prefix_salt)
                    if matched:
                        self.chains.hit(req.chain_slot, len(matched))
                    if nf > len(matched):
                        self.chains.miss(req.chain_slot,
                                         nf - len(matched))
            if fresh:
                self._scatter_pages([pages[i] for i in fresh],
                                    payload["pages"], fresh)
                if self._prefix_on and hashes:
                    for i in fresh:
                        if i < len(hashes):
                            self.cache.index.publish(pages[i], hashes[i],
                                                     req.chain_slot)
            tok = int(payload["first_token"])
            req.first_token_t = time.perf_counter()
            req.first_token_ns = req.token_ns = int(req.first_token_t * 1e9)
            req.out_ids.append(tok)
            self.stats["tokens_out"] += 1
            req.prefill_pos = len(ids)
            self._lengths[req.slot] = len(ids)
            self._active[req.slot] = req
            self._maybe_finish(req, tok)
        return req

    # A layer's cache is a dict of named pools [P, page, ...] whose names
    # and shapes are the model's (llama: "k" and "v"; mla_moe: "ckv"). Spill,
    # export and import move whole pages of every pool and look at nothing
    # inside them: a payload's "pages" is one {pool name: [n, page, ...]}
    # a layer.

    def _gather_pages(self, idx) -> list[dict]:
        """Host copies of the pages ``idx`` (one id or an array of them)
        of every pool, a dict a layer."""
        return [{name: np.asarray(pool[idx]) for name, pool in layer.items()}
                for layer in self.caches]

    def _scatter_pages(self, pids, payload_pages, sel) -> None:
        """Write rows ``sel`` of a payload's pages into pages ``pids`` of
        the pools, donated and in place."""
        idx = jnp.asarray(np.asarray(pids, np.int32))
        sel = np.asarray(sel)
        for layer, data in zip(self.caches, payload_pages):
            for name in layer:
                layer[name] = self._import_fn(name)(
                    layer[name], idx, jnp.asarray(data[name][sel]))

    def _import_fn(self, pool: str):
        """The donated in-place page scatter into one named pool (cache
        pools are not copied); off a mesh one program serves them all."""
        fns = self._import_fns
        key = pool if self.mesh is not None else None
        if key not in fns:
            scatter = lambda c, idx, data: c.at[idx].set(data)  # noqa: E731
            if self.mesh is None:
                fns[key] = jax.jit(scatter, donate_argnums=(0,))
            else:
                # pinned shardings keep the donated pool usable in place
                # (out == in) and land the host payload replicated-then-
                # scattered without resharding the pool itself
                kv = self._shardings["caches"][0][pool]
                repl = self._shardings["repl"]
                fns[key] = jax.jit(scatter, donate_argnums=(0,),
                                   in_shardings=(kv, repl, repl),
                                   out_shardings=kv)
        return fns[key]

    # -- cluster prefix-cache directory hooks (serve/frontdoor/prefix.py;
    # cross-replica page import extends the import_prefill contract:
    # same chained content hashes, same _import_fn scatter, but the
    # imported pages seed the CACHE — refcount 0, LRU-parked — instead
    # of a decode-ready request) ------------------------------------------

    def hash_prompt(self, prompt, salt: bytes = b"") -> list[bytes]:
        """Chained hashes of the prompt's admission-reusable pages: the
        whole full pages inside the chunk-aligned reuse limit, exactly
        the run admission can serve from cache. ``salt`` must match
        the prefix_salt the request will submit with (tenant-scoped
        chains — KVCache.prompt_hashes). Pure computation — no lock, no
        state."""
        ids = (self.tokenizer.encode(prompt) if isinstance(prompt, str)
               else list(prompt))
        return self.cache.hash_prompt(ids, salt)

    def cached_prefix_len(self, hashes) -> int:
        """How many of `hashes` (a chain run) this engine's cache already
        covers, walking from the head until the first miss."""
        with self._lock:
            return len(self.cache.index.run(hashes))

    def export_prefix(self, hashes) -> Optional[dict]:
        """Gather the cached pages for a chain run of hashes to host
        arrays — the cross-replica analog of _export_kv_locked, keyed by
        content instead of by request. Returns the longest covered
        prefix run (None when even the first page is gone: entries in
        the cluster directory are hints and this engine may have evicted
        since publishing). CALLER must serialize against the stepping
        thread (serving.py's step lock): dispatches donate self.caches,
        so a concurrent step would invalidate the buffers mid-gather."""
        self._refuse_two_kinds("export_prefix")
        with self._lock:
            pids = self.cache.index.run(hashes)
            if not pids:
                return None
            pages = self._gather_pages(
                jnp.asarray(np.asarray(pids, np.int32)))
            self.stats["prefix_exported_pages"] += len(pids)
            if self.chains is not None:
                # peek, never assign: an export targets pages this
                # engine already registered, so the chain (or the
                # overflow sink) exists
                self.chains.exported(self.chains.peek(hashes[0]),
                                     len(pids))
            return {"page_size": self.cfg.page_size,
                    "page_hashes": list(hashes[:len(pids)]),
                    "pages": pages}

    def import_prefix(self, payload: Optional[dict],
                      reserve_pages: Optional[int] = None) -> int:
        """Seed this engine's prefix cache with another replica's
        exported pages: allocate, scatter (donated, in place), publish
        under the payload's chain hashes and park unheld — the next
        admission or mid-prefill reuse admits them like computed pages.
        Imports stop once the pool would drop below `reserve_pages`
        allocatable pages (default one a slot), so a warm import never
        starves active requests. Returns pages imported. CALLER must
        serialize against the stepping thread (as for export_prefix /
        import_prefill: _import_fn donates the cache pools)."""
        if payload is None or not self._prefix_on:
            return 0
        self._refuse_two_kinds("import_prefix")
        if payload["page_size"] != self.cfg.page_size:
            raise ValueError(
                f"page_size mismatch: payload {payload['page_size']} vs "
                f"engine {self.cfg.page_size}")
        with self._lock:
            if reserve_pages is None:
                reserve_pages = self.cfg.max_batch_size
            return self._import_payload_locked(payload,
                                               int(reserve_pages))

    def _import_payload_locked(self, payload: dict, reserve_pages: int,
                               chain: Optional[int] = None) -> int:
        """The take / scatter / park core behind import_prefix
        (cross-replica) and the spill tier's promotes (same payload
        format: a promoted page is bit-identical to a never-evicted one).
        ``chain`` pins the heat attribution (a promote knows its chain
        from the tier entry); None means a cross-replica import's
        accounting: slot from the payload's head hash, imported_pages,
        flight event. Caller holds self._lock, serialized with stepping."""
        hashes = payload["page_hashes"]
        took = self.cache.take_unheld(hashes, reserve_pages)
        if not took:
            return 0
        take_idx, take_pids = map(list, zip(*took))
        self._scatter_pages(take_pids, payload["pages"], take_idx)
        slot = -1
        if chain is not None:
            slot = chain
        elif self.chains is not None:
            # the exporter's chain-head hash carries the tenant salt
            # inside the digest; the salt arg only labels a freshly
            # minted slot, and cross-replica imports are keyed by
            # content alone
            slot = self.chains.slot_for(hashes[0])
        if chain is None and self.chains is not None:
            self.chains.imported(slot, len(take_pids))
            flight.evt(flight.PREFIX_IMPORT, len(take_pids), slot)
        self.cache.park(took, hashes, slot)
        if chain is None:
            self.stats["prefix_imported_pages"] += len(take_pids)
        return len(take_pids)

    # -- spill tier (cfg.kv_spill, llm/tiering.py) -------------------------

    def _promote_for_locked(self, req: _Request, have: int) -> int:
        """Admission-time promote: when the hot cache's longest-prefix
        match ends but the spill tier holds the next consecutive pages
        of the request's chain, scatter them back into HBM BEFORE cold
        prefill. Runs under self._lock on the stepping thread (called
        from _admit). Returns pages promoted; the caller re-matches."""
        page = self.cfg.page_size
        limit = self.cache.reuse_limit(len(req.prompt_ids)) // page
        if have >= limit:
            return 0
        need = self.cache.pages_for(len(req.prompt_ids) + 1)
        if self.cache.index.avail() < need:
            return 0    # admission would stall regardless: no churn
        hashes = self.cache.prompt_hashes(req)
        run = self.spill.covered_run(hashes[have:limit])
        if run <= 0:
            return 0
        want = hashes[have:have + run]
        chain = self.spill.chain_of(want[0])
        payload, dropped = self.spill.payload_for(want, page)
        if dropped:
            self._spill_dropped(dropped)
        if payload is None:
            return 0
        n = self._import_payload_locked(payload, 0, chain=chain)
        if n > 0:
            self.stats["spill_promotions"] += n
            if self.chains is not None:
                self.chains.promoted(chain, n)
        return n

    def maybe_rewarm(self, max_pages: Optional[int] = None) -> int:
        """Proactive re-warm: promote the hottest spilled chain's known
        head run back into HBM while the pool has idle headroom — the
        policy's rewarm gate (SpillPolicy.rewarm_slot). Called by the
        serving layer's engine loop between steps (same serialization
        as import_prefix: the scatter donates the cache pools); safe to
        call any time, a no-op without headroom. Returns pages
        promoted."""
        if self.spill is None or self.chains is None:
            return 0
        with self._lock:
            free_frac = len(self.cache.index.free) / max(
                self.cfg.num_pages - 1, 1)
            slot = self.spill.policy.rewarm_slot(
                self.chains, self.spill.spilled_slots(), free_frac)
            if slot is None:
                return 0
            run = self._chain_runs.get(slot)
            if not run:
                return 0
            index = self.cache.index.hash_to_page
            # the head-rooted usable run: pages already hot pass
            # through (the scatter skips them), tier-resident pages
            # promote, the first page in neither tier ends the run
            want: list[bytes] = []
            for h in run:
                if not (h in index or self.spill.has(h)):
                    break
                want.append(h)
            want = [h for h in want if h not in index]
            if max_pages is not None:
                want = want[:max(int(max_pages), 0)]
            if not want:
                return 0
            payload, dropped = self.spill.payload_for(
                want, self.cfg.page_size)
            if dropped:
                self._spill_dropped(dropped)
            if payload is None:
                return 0
            n = self._import_payload_locked(
                payload, self.cfg.max_batch_size, chain=slot)
            if n > 0:
                self.stats["spill_promotions"] += n
                self.chains.promoted(slot, n)
            return n

    def note_spill_promotion(self, head: bytes, pages: int) -> None:
        """Cross-replica promote accounting (serve/frontdoor/prefix.py):
        pages seeded via a ``spill:`` directory entry's store payload
        count as spill promotions HERE (the tier recovered them for
        this engine) on top of the imported_pages the scatter already
        counted."""
        with self._lock:
            self.stats["spill_promotions"] += int(pages)
            if self.chains is not None:
                self.chains.promoted(self.chains.peek(head), int(pages))

    def note_spill_drops(self, n: int) -> None:
        """Cross-replica validate-on-promote failure accounting: a
        stale/corrupt ``spill:`` entry cost a cold prefill."""
        with self._lock:
            self.stats["spill_drops"] += int(n)

    def spill_teardown(self) -> int:
        """Drop every tier entry — and with them every store segment
        ref — so the host object store drains to exact baseline on
        engine teardown (replica death gets the same result from the
        owner sweep). Returns entries dropped."""
        if self.spill is None:
            return 0
        with self._lock:
            removed = self.spill.clear()
            self._spill_expired(removed)
            return len(removed)

    def drain_directory_delta(self) -> tuple:
        """-> (new_hashes, dropped_hashes) since the last drain, filtered
        against the cache so a publish-then-evict (or the reverse) nets
        out. Only meaningful with ``cache.log.track`` on; called serialized
        with stepping (the serving layer's engine loop), which is also
        what bounds the lists."""
        log = self.cache.log
        if not log.new and not log.dropped:
            return (), ()
        with self._lock:
            return log.drain()

    # -- stats -------------------------------------------------------------

    def profile_summary(self) -> dict:
        """Step-profiler view (util/profiling.py): programs compiled
        (in warm-up and after it) and dispatches per family. Where the
        stepping thread's time goes is in ``stats["ns_*"]`` (PHASES)."""
        return {**self.profiler.summary(),
                # the prefill row budget (set, or derived from the model's
                # routing and the pools) and the programs built under it
                "prefill_rows": self.prefill_rows,
                "prefill_row_ladder": self._prefill_row_ladder(),
                # zero when warm-up covered every shape traffic reached
                "in_window_compiles":
                    self.profiler.compiles - self.warm_programs,
                "dispatches": {
                    "prefill": self.stats["prefill_dispatches"],
                    "decode": self.stats["decode_dispatches"],
                    "spec": self.stats["spec_dispatches"]}}

    def prefix_accounting(self) -> dict:
        """THE accounting source for prefix-cache counters. pool_stats(),
        the telemetry gauges (llm/telemetry.py) and the fleet rollup
        (serve.metrics_summary()["prefix_cache"]) all derive from this
        one snapshot, so the surfaces can never drift from each other —
        tests/test_cache_heat.py asserts the parity."""
        hits = self.stats["prefix_hits"]
        misses = self.stats["prefix_misses"]
        return {
            "hits": hits,
            "misses": misses,
            "evictions": self.stats["prefix_evictions"],
            "tokens_saved": self.stats["prefix_tokens_saved"],
            "imported_pages": self.stats["prefix_imported_pages"],
            "exported_pages": self.stats["prefix_exported_pages"],
            "cached_pages": self.cache.index.parked(),
            "hit_rate": round(hits / (hits + misses), 4)
            if hits + misses else 0.0,
            # spill tier (cfg.kv_spill): cumulative counters + current
            # tier residence — all zero while the tier is off, so the
            # accounting schema is uniform across configurations
            "spill_pages": self.stats["spill_pages"],
            "spill_bytes": self.stats["spill_bytes"],
            "spill_demotions": self.stats["spill_demotions"],
            "spill_promotions": self.stats["spill_promotions"],
            "spill_expired": self.stats["spill_expired"],
            "spill_drops": self.stats["spill_drops"],
            "spill_resident_pages": self.spill.resident_pages()
            if self.spill is not None else 0,
            "spill_resident_bytes": self.spill.resident_bytes
            if self.spill is not None else 0,
        }

    def pool_stats(self) -> dict:
        acct = self.prefix_accounting()
        return {
            # the pools: free, cached and total pages of each kind
            **self.cache.pool_stats(),
            "prefix_hit_rate": acct["hit_rate"],
            "active": len(self._active),
            "prefilling": len(self._prefilling),
            "pending": len(self._pending),
            **self.stats,
        }

    def chain_stats_report(self, top_k: Optional[int] = None) -> dict:
        """Heat-plane snapshot: bounded-table stats, whole-table totals
        (== the matching prefix_accounting() aggregates), and the top-K
        hot chains. Empty dict when the table is disabled."""
        if self.chains is None:
            return {}
        if top_k is None:
            top_k = self.cfg.chain_stats_top_k
        return self.chains.report(top_k)
