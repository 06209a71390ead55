"""Engine telemetry: the serving hot path rendered measurable.

Reference role: vLLM's Stats/StatLogger pipeline (engine-loop iteration
stats feeding Prometheus) and the reference serve deployments' per-request
metrics. Orca/vLLM-class continuous-batching systems are tuned almost
entirely off TTFT / inter-token-latency / KV-utilization telemetry; these
hooks put those series on the head's `/metrics` via the existing
util/metrics.py delta-flush — zero new transport, and a no-op overhead of
a few dict updates per engine step.

Every metric carries an ``engine`` label (``telemetry_kind``, "paged");
gauges additionally carry a ``proc`` (host:pid) label because they are
last-write-wins on the head — without it, replicas would overwrite each
other. When tracing is enabled each request also emits one
``llm.request`` span parented to whatever span submitted it (the serve
replica's task span when the request came through Serve), so a proxy
-> replica -> engine request renders as one stitched tree in
``ray_tpu.timeline()`` — through the OpenAI router too, whose call to
the model deployment is made by a stream's drain thread: that thread
runs in the request's context (serve/controller.py
``_start_stream_channel``), so ``serve.proxy`` -> the router's task ->
the model replica's task -> ``llm.request`` is one trace and
``req.request_id`` is the id the proxy minted. The same context carries
the proxy's arrival stamp, from which ``on_submit`` observes the front
stage ``to_submit`` (serve/metrics.py, "the front path's clock"); under
``llm.request``, with the same ``trace_id`` and
``request_id``, three children end to end: ``llm.queue`` (submit ->
admit), ``llm.prefill`` (admit -> first token) and ``llm.decode`` (first
token -> retire), with ``prompt_tokens``, ``prefix_tokens_saved`` and
``out_tokens`` as arguments. A streamed request has a fourth,
``llm.deliver``, beside ``llm.decode``: first token booked -> the
stream's sink has taken the chunk that carries it (serving's stream
pump, ``LLMServer._pump``). The spans are emitted when the request retires, so
a request that retires before its first chunk was taken (a short
answer, a slow transport) has no ``llm.deliver``.

Metric names (all prefixed ``rtpu_llm_``):
  ttft_seconds           histogram  submit -> first generated token
  inter_token_seconds    histogram  mean gap between generated tokens
  queue_wait_seconds     histogram  submit -> admission into the batch
  e2e_seconds            histogram  submit -> request retired
  batch_occupancy        gauge      active slots / max_batch_size
  kv_utilization         gauge      KV pages in use / pool size
  kv_window_utilization  gauge      the same of the second pool of a
      model with sliding-window layers (its window-layer pages)
  kv_pages_claimed_total / kv_pages_returned_total  counter  pages
      requests claimed / gave back, by ``pool`` (full, window); such a
      model only, as prefix_window_*, prefix_tail_*
  pending_requests       gauge      submitted, not yet admitted
  prefilling_requests    gauge      admitted, prompt not fully prefilled
  decoding_requests      gauge      in the decode set
  tokens_generated_total counter    generated tokens
  requests_total         counter    retired requests, by finish label
  preemptions_total      counter    requests finished early (KV pool dry)
  spec_proposed_total    counter    speculative tokens proposed
  spec_accepted_total    counter    speculative tokens accepted
  dispatches_total       counter    device dispatches, by program family
  dispatches_overlapped_total counter  launches made while another
      dispatch was outstanding (over dispatches_total: how often the
      engine runs ahead of its readbacks)
  decode_rows_fed_on_device_total counter  rows of a decode launched
      behind an unbooked decode whose first token came from the device
  decode_dead_rows_total counter    rows x steps a decode ran for a
      request the booking before it found done (a stop seen one
      dispatch late; over decode steps x max_batch_size: their share)
  decode_live_slots_total counter   slots live, summed over decode
      dispatches (over dispatches_total{family="decode"} x max_batch_size:
      the share of the decode program's rows doing useful work)
  loop_seconds_total     counter    the stepping thread's seconds, by
      ``phase``: admit, prefill_build / _device / _post, decode_build /
      _device / _post, telemetry, loop_other, loop_idle (the ``ns_*`` keys
      of engine.stats, paged_engine.PHASES); they sum to wall time;
      ``*_device`` is a launch OR a blocking readback wait (the launches'
      part is launch_seconds_total of the family, the rest is the wait
      for the device's answer), the others but ``loop_idle`` are host
      time, most of it beside a running dispatch
  launch_seconds_total   counter    seconds inside the jitted calls that
      launch a program, by ``family`` (prefill, decode; a verify dispatch
      is decode's): over dispatches_total, what a launch takes the
      thread — milliseconds when it has the interpreter, tens of them
      when it has to take turns at it
  stream_chunks_total    counter    text chunks the streams' sinks took
      (serving's stream pump: a ring write returned, a queue has it)
  stream_lag_seconds_total counter  booking of a chunk's newest token ->
      its sink has taken the chunk, summed; over
      stream_chunks_total: a chunk's delivery time inside the replica
  stream_write_seconds_total counter  wall seconds the pump spent inside
      its sinks' puts (over the ring: serialise, seal, wake the readers);
      over stream_chunks_total: the part of a chunk's delivery time that
      is the write itself
  stream_cpu_seconds_total counter  CPU seconds of the stream pump, the
      one thread that serves every open stream, detokenisation and the
      sinks' writes included; its rate is the share of one core, so of
      the one interpreter, the streams take
  stream_passes_total    counter    passes of the pump in which a sink
      took at least one chunk; stream_chunks_total over it is the
      chunks a wake-up carries
  stream_deferred_total  counter    puts a sink refused for want of ring
      credit (a slow consumer): the text was kept and went later
  prefix_cache_hits_total      counter  full prompt pages served from cache
  prefix_cache_misses_total    counter  full prompt pages computed by prefill
  prefix_cache_evictions_total counter  cached pages reclaimed under pressure
  prefix_cache_tokens_saved_total counter  prompt tokens whose prefill was
      skipped via cached pages
  prefix_cached_pages    gauge      unreferenced pages retained for reuse
  prefix_cache_hit_rate  gauge      hits / (hits + misses), cumulative
  prefix_cache_imported_pages_total counter  pages seeded from another
      replica's export (cross-replica prefix sharing)
  prefix_cache_exported_pages_total counter  cached pages gathered to host
      for another replica's import

Cache heat plane (llm/chainstats.py) — per-chain series, bounded to the
engine's top-K chains plus the ``__overflow__`` sink so label
cardinality can never follow prompt diversity:
  prefix_chain_hits         gauge  cumulative page hits, per hot chain
  prefix_chain_tokens_saved gauge  prompt tokens skipped, per hot chain
  prefix_chain_resident_pages gauge  pages of the chain now in HBM
  prefix_chain_last_hit_age_s gauge  seconds since the chain last hit
  prefix_chain_tracked      gauge  chains with dedicated slots (rollup)

The prefix gauges and the fleet rollup both read
``engine.prefix_accounting()`` — the single accounting source shared
with ``pool_stats()`` — so surfaces cannot drift apart.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Optional

from ..util.metrics import (LATENCY_BUCKETS, Counter, Gauge, Histogram,
                            cached_metric)


def _hist(name, desc, boundaries=LATENCY_BUCKETS):
    return cached_metric(Histogram, name, desc, boundaries=boundaries,
                         tag_keys=("engine",))


def _gauge(name, desc):
    # gauges carry a per-process label: they are last-write-wins on the
    # head, so two replicas of the same engine kind flushing under one
    # key would mask each other (a saturated replica's kv_utilization
    # hidden by an idle one). Counters/histograms sum deltas and stay
    # engine-keyed.
    return cached_metric(Gauge, name, desc, tag_keys=("engine", "proc"))


_proc_label = ""


def _proc() -> str:
    """host:pid, made once a process: every engine step asks, and
    ``os.getpid()`` is a system call (6 us under gVisor). A forked child
    never inherits the parent's identity: ``_forget_proc`` runs in it."""
    global _proc_label
    if not _proc_label:
        import socket
        _proc_label = f"{socket.gethostname()}:{os.getpid()}"
    return _proc_label


def _forget_proc() -> None:
    global _proc_label
    _proc_label = ""


os.register_at_fork(after_in_child=_forget_proc)


def _counter(name, desc, tag_keys=("engine",)):
    return cached_metric(Counter, name, desc, tag_keys=tag_keys)


def zero_proc_gauges() -> None:
    """Exit-path hook (core/worker.py): zero this process's per-proc
    gauge series before the final flush, so a downscaled replica's last
    values don't pin /metrics and metrics_summary()'s max aggregation
    forever. Best-effort — a SIGKILLed replica skips it."""
    try:
        from ..util import metrics as um
        um.zero_gauges(("proc", _proc()))
    except Exception:
        pass  # lost telemetry on exit is acceptable


def _never_raise(fn):
    """These hooks sit inside the engine step loop and submit path; an
    exception here (e.g. a user metric registered under a colliding
    name) must degrade to lost telemetry, never kill the engine thread
    and strand every in-flight request."""
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        try:
            return fn(*args, **kw)
        except Exception:
            pass  # contract: degrade to lost telemetry
    return wrapped


# --------------------------------------------------------------------- #
# hooks (called by paged_engine.py)
# --------------------------------------------------------------------- #

@_never_raise
def on_submit(engine, req) -> None:
    """Stamp trace/request identity on the request at intake. Runs on the
    submitter's thread (inside the replica's activated task span when the
    request came through Serve), so the engine loop thread can emit the
    request's span later without any contextvar of its own."""
    req.submit_wall = time.time()
    try:
        from ..util import tracing
        if tracing.tracing_enabled():
            req.trace_ctx = tracing.current_context() or \
                (tracing.new_trace_id(), None)
        from ..serve.context import get_request_context, local_ingress_ns
        ctx = get_request_context()
        req.request_id = ctx.request_id
        ingress_ns = local_ingress_ns()
        if ingress_ns and req.submit_t:
            # front stage "to_submit" (serve/metrics.py): the proxy's
            # arrival stamp -> the instant rtpu_llm_ttft_seconds starts
            # at. THE way in, in one number, on one host's clock
            from ..serve.metrics import observe_stage
            req.front = (ctx.app_name, ctx.deployment)
            observe_stage("to_submit",
                          int(req.submit_t * 1e9) - ingress_ns, *req.front)
    except Exception:
        pass  # tracing/request context are optional


@_never_raise
def on_first_chunk(req, lag_ns: int) -> None:
    """A streamed request's first chunk went to its sink `lag_ns` after
    its first token's booking (serving's stream pump): the front stage
    "first_chunk" of a request that came through a proxy of this host."""
    front = getattr(req, "front", None)
    if front:
        from ..serve.metrics import observe_stage
        observe_stage("first_chunk", lag_ns, *front)


@_never_raise
def on_admit(engine, req) -> None:
    req.admit_t = time.perf_counter()


@_never_raise
def on_first_token(engine, req) -> None:
    tags = {"engine": engine.telemetry_kind}
    if req.submit_t:
        _hist("rtpu_llm_ttft_seconds",
              "time to first generated token").observe(
            req.first_token_t - req.submit_t, tags=tags)
        if req.admit_t:
            _hist("rtpu_llm_queue_wait_seconds",
                  "submit to batch admission").observe(
                max(req.admit_t - req.submit_t, 0.0), tags=tags)


@_never_raise
def on_finish(engine, req, finish: Optional[str] = None) -> None:
    now = time.perf_counter()
    if finish is None:
        eos = engine._eos_id()
        if eos is not None and eos in req.out_ids:
            finish = "stop"
        elif len(req.out_ids) >= req.params.max_tokens:
            finish = "length"
        else:
            finish = "other"
    tags = {"engine": engine.telemetry_kind}
    _counter("rtpu_llm_requests_total", "retired requests",
             tag_keys=("engine", "finish")).inc(
        1.0, tags={**tags, "finish": finish})
    if req.submit_t:
        _hist("rtpu_llm_e2e_seconds", "submit to retirement").observe(
            now - req.submit_t, tags=tags)
    n = len(req.out_ids)
    if n > 1 and req.first_token_t:
        _hist("rtpu_llm_inter_token_seconds",
              "mean inter-token gap over the request").observe(
            max(now - req.first_token_t, 0.0) / (n - 1), tags=tags)
    _emit_request_span(req)


@_never_raise
def on_preempted(engine) -> None:
    _counter("rtpu_llm_preemptions_total",
             "requests finished early because the KV page pool ran "
             "dry").inc(1.0, tags={"engine": engine.telemetry_kind})


@_never_raise
def on_step(engine) -> None:
    """Per-step gauges + counter deltas from the engine's stats dict.
    Cheap on purpose: a handful of dict updates under one lock, all
    host-side state (never forces a device transfer)."""
    kind = engine.telemetry_kind
    tags = {"engine": kind}
    gtags = {"engine": kind, "proc": _proc()}
    cfg = engine.cfg
    _gauge("rtpu_llm_batch_occupancy",
           "active decode slots / max_batch_size").set(
        len(engine._active) / max(cfg.max_batch_size, 1), tags=gtags)
    _gauge("rtpu_llm_pending_requests",
           "submitted, not yet admitted").set(
        len(engine._pending), tags=gtags)
    _gauge("rtpu_llm_decoding_requests", "requests in the decode set").set(
        len(engine._active), tags=gtags)
    _gauge("rtpu_llm_prefilling_requests",
           "admitted, prompt not fully prefilled").set(
        len(engine._prefilling), tags=gtags)
    # cached (unreferenced, prefix-reusable) pages are reclaimable on
    # demand: they count as capacity, not utilization — a warm cache
    # must not read as a saturated pool (page 0 is the write sink)
    full, window = engine.cache.index, engine.cache.window
    _gauge("rtpu_llm_kv_utilization",
           "KV pages in use / pool size").set(
        full.live() / max(full.num_pages - 1, 1), tags=gtags)
    if window is not None:
        # a model with sliding-window layers: its second pool, the same
        # way (held pages / pool size; parked prefix pages are capacity)
        _gauge("rtpu_llm_kv_window_utilization",
               "window-layer KV pages in use / window pool size").set(
            window.space.live() / max(window.space.num_pages - 1, 1),
            tags=gtags)
        _gauge("rtpu_llm_prefix_window_cached_pages",
               "unreferenced window-layer pages retained as prefix "
               "tails").set(window.space.parked(), tags=gtags)
    if engine._prefix_on:
        # single accounting source (paged_engine.prefix_accounting):
        # the gauges here, pool_stats() and metrics_summary() must
        # agree by construction, not by parallel bookkeeping
        acct = engine.prefix_accounting()
        _gauge("rtpu_llm_prefix_cached_pages",
               "unreferenced KV pages retained for prefix reuse").set(
            acct["cached_pages"], tags=gtags)
        if acct["hits"] + acct["misses"]:
            _gauge("rtpu_llm_prefix_cache_hit_rate",
                   "prefix cache hits / (hits + misses)").set(
                acct["hit_rate"], tags=gtags)
        if engine.spill is not None:
            # tier-resident gauges: what the host tier holds NOW
            # (same accounting snapshot as the counters above)
            _gauge("rtpu_llm_prefix_spill_resident_pages",
                   "prefix pages resident in the host spill "
                   "tier").set(
                acct["spill_resident_pages"], tags=gtags)
            _gauge("rtpu_llm_prefix_spill_resident_bytes",
                   "bytes resident in the host spill tier").set(
                acct["spill_resident_bytes"], tags=gtags)
    _ship_stat_deltas(engine, engine.stats, tags)
    if engine.chains is not None:
        _ship_chain_stats(engine, gtags)


_STAT_COUNTERS = (
    ("tokens_out", "rtpu_llm_tokens_generated_total",
     "generated tokens", None),
    ("spec_proposed", "rtpu_llm_spec_proposed_total",
     "speculative draft tokens proposed", None),
    ("spec_accepted", "rtpu_llm_spec_accepted_total",
     "speculative draft tokens accepted", None),
    ("prefill_dispatches", "rtpu_llm_dispatches_total",
     "device dispatches by program family", ("family", "prefill")),
    ("decode_dispatches", "rtpu_llm_dispatches_total",
     "device dispatches by program family", ("family", "decode")),
    ("spec_dispatches", "rtpu_llm_dispatches_total",
     "device dispatches by program family", ("family", "verify")),
    ("dispatches_overlapped", "rtpu_llm_dispatches_overlapped_total",
     "launches made while another dispatch was outstanding", None),
    ("decode_rows_fed_on_device", "rtpu_llm_decode_rows_fed_on_device_total",
     "decode rows whose first token came from the device", None),
    ("decode_dead_rows", "rtpu_llm_decode_dead_rows_total",
     "rows x steps a decode ran for a request already done", None),
    ("decode_live_slots", "rtpu_llm_decode_live_slots_total",
     "slots live, summed over decode dispatches", None),
    ("stream_chunks", "rtpu_llm_stream_chunks_total",
     "text chunks the streams' sinks took", None),
    ("stream_passes", "rtpu_llm_stream_passes_total",
     "stream pump passes in which a sink took a chunk", None),
    ("stream_deferred", "rtpu_llm_stream_deferred_total",
     "puts a stream's sink refused for want of credit", None),
    ("prefix_hits", "rtpu_llm_prefix_cache_hits_total",
     "full prompt pages served from the prefix cache", None),
    ("prefix_misses", "rtpu_llm_prefix_cache_misses_total",
     "full prompt pages computed by prefill", None),
    ("prefix_evictions", "rtpu_llm_prefix_cache_evictions_total",
     "cached pages reclaimed under allocation pressure", None),
    ("prefix_tokens_saved", "rtpu_llm_prefix_cache_tokens_saved_total",
     "prompt tokens whose prefill was skipped via cached pages", None),
    ("prefix_imported_pages", "rtpu_llm_prefix_cache_imported_pages_total",
     "pages seeded from another replica's export", None),
    ("prefix_exported_pages", "rtpu_llm_prefix_cache_exported_pages_total",
     "cached pages gathered to host for another replica", None),
    # a model with sliding-window layers (two page pools): pages claimed
    # and handed back a pool kind, and prefix hits the window layers cut
    ("window_pages_claimed", "rtpu_llm_kv_pages_claimed_total",
     "KV pages claimed by requests, by pool kind", ("pool", "window")),
    ("full_pages_claimed", "rtpu_llm_kv_pages_claimed_total",
     "KV pages claimed by requests, by pool kind", ("pool", "full")),
    ("window_pages_returned", "rtpu_llm_kv_pages_returned_total",
     "KV pages requests gave back (window pages: as the window moved "
     "past them), by pool kind", ("pool", "window")),
    ("full_pages_returned", "rtpu_llm_kv_pages_returned_total",
     "KV pages requests gave back (window pages: as the window moved "
     "past them), by pool kind", ("pool", "full")),
    ("window_evictions", "rtpu_llm_prefix_window_evictions_total",
     "cached window-layer pages reclaimed under allocation pressure",
     None),
    ("prefix_tail_cut", "rtpu_llm_prefix_tail_misses_total",
     "admissions whose cached prefix was cut short (cut) or lost for "
     "want of the window layers' pages behind it", ("outcome", "cut")),
    ("prefix_tail_lost", "rtpu_llm_prefix_tail_misses_total",
     "admissions whose cached prefix was cut short (cut) or lost for "
     "want of the window layers' pages behind it", ("outcome", "lost")),
    ("prefix_tail_tokens_lost", "rtpu_llm_prefix_tail_tokens_lost_total",
     "prompt tokens the full layers' cache covered and a missing "
     "window tail had prefilled again", None),
    # spill tier (cfg.kv_spill, llm/tiering.py) — the
    # rtpu_llm_prefix_spill_* family; engine.stats is the single source
    ("spill_pages", "rtpu_llm_prefix_spill_pages_total",
     "evicted prefix pages captured into the host spill tier", None),
    ("spill_bytes", "rtpu_llm_prefix_spill_bytes_total",
     "bytes demoted into the host spill tier", None),
    ("spill_demotions", "rtpu_llm_prefix_spill_demotions_total",
     "eviction-site demote decisions that kept a tier copy "
     "(captures plus clean re-evictions of tier-resident content)",
     None),
    ("spill_promotions", "rtpu_llm_prefix_spill_promotions_total",
     "spilled pages promoted back into HBM (admission-time, re-warm, "
     "or cross-replica via the prefix directory)", None),
    ("spill_expired", "rtpu_llm_prefix_spill_expired_total",
     "tier pages expired under the byte budget or at teardown", None),
    ("spill_drops", "rtpu_llm_prefix_spill_drops_total",
     "validate-on-promote failures: stale/corrupt spill content "
     "dropped, request prefilled cold", None),
    # mesh-parallel engine (cfg.mesh): the zero-involuntary-reshard
    # contract is that reshard_bytes stays 0 while input/output bytes
    # track exactly the declared host arrays (token ids in, tokens out)
    ("mesh_dispatches", "rtpu_llm_mesh_dispatches_total",
     "device dispatches executed under a sharded mesh", None),
    ("mesh_input_bytes", "rtpu_llm_mesh_input_bytes_total",
     "declared host->mesh input bytes (token ids, block tables)", None),
    ("mesh_output_bytes", "rtpu_llm_mesh_output_bytes_total",
     "declared mesh->host output bytes (sampled tokens, logprobs)",
     None),
    ("mesh_reshard_bytes", "rtpu_llm_mesh_reshard_bytes_total",
     "bytes of committed buffers found off their pinned sharding "
     "after a dispatch (must stay 0)", None),
)


# engine.stats keys in nanoseconds, shipped as seconds
_STAT_SECONDS = (
    ("launch_ns_prefill", "rtpu_llm_launch_seconds_total",
     "the engine loop thread's seconds inside launches, by family",
     ("family", "prefill")),
    ("launch_ns_decode", "rtpu_llm_launch_seconds_total",
     "the engine loop thread's seconds inside launches, by family",
     ("family", "decode")),
    ("stream_lag_ns", "rtpu_llm_stream_lag_seconds_total",
     "a chunk's newest token booked -> taken by its sink, summed",
     None),
    ("stream_cpu_ns", "rtpu_llm_stream_cpu_seconds_total",
     "CPU seconds of the stream pump thread", None),
    ("stream_write_ns", "rtpu_llm_stream_write_seconds_total",
     "wall seconds of the stream pump inside its sinks' puts", None),
)


def _ship_stat_deltas(engine, stats: dict, tags: dict) -> None:
    last = getattr(engine, "_telem_shipped", None)
    if last is None:
        last = engine._telem_shipped = {}
    for key, name, desc, label in _STAT_COUNTERS:
        _ship_delta(stats, last, key, name, desc, label, tags)
    for key, name, desc, label in _STAT_SECONDS:
        _ship_delta(stats, last, key, name, desc, label, tags, scale=1e-9)
    # the stepping thread's time by phase (paged_engine.PHASES): every
    # ns_<phase> key, in seconds, under one family
    for key in stats:
        if key.startswith("ns_"):
            _ship_delta(stats, last, key, "rtpu_llm_loop_seconds_total",
                        "the engine loop thread's seconds by phase",
                        ("phase", key[3:]), tags, scale=1e-9)


def _ship_delta(stats, last, key, name, desc, label, tags, scale=1.0):
    cur = stats.get(key)
    if cur is None:
        return
    delta = cur - last.get(key, 0)
    if delta <= 0:
        return
    last[key] = cur
    if label is None:
        _counter(name, desc).inc(delta * scale, tags=tags)
    else:
        _counter(name, desc, tag_keys=("engine", label[0])).inc(
            delta * scale, tags={**tags, label[0]: label[1]})


def _chain_gauge(name, desc):
    # per-chain gauges: the `chain` label values come verbatim from the
    # ChainStatsTable's slot identities (minted once, at most
    # chain_stats_slots of them, plus __overflow__), so the series set
    # stays bounded no matter how diverse client prompts are
    return cached_metric(Gauge, name, desc,
                         tag_keys=("engine", "proc", "chain"))


#: seconds between chain-gauge publishes. The per-chain table updates at
#: O(1) on the hot path; only this snapshot walk is rate-limited.
_CHAIN_SHIP_INTERVAL_S = 2.0


def _ship_chain_stats(engine, gtags: dict) -> None:
    """Publish the engine's top-K hot chains (+ overflow sink) as
    per-chain gauges. Gauge semantics fit: per-chain values are
    last-write-wins snapshots of cumulative table counters, and a
    replica's series zero out with the other proc gauges on exit."""
    now = time.monotonic()
    last = getattr(engine, "_chain_ship_t", 0.0)
    if now - last < _CHAIN_SHIP_INTERVAL_S:
        return
    engine._chain_ship_t = now
    rows = engine.chains.top(engine.cfg.chain_stats_top_k, now)
    for row in rows:
        ctags = {**gtags, "chain": row["chain"]}
        _chain_gauge("rtpu_llm_prefix_chain_hits",
                     "cumulative prefix-cache page hits, per hot "
                     "chain").set(row["hits"], tags=ctags)
        _chain_gauge("rtpu_llm_prefix_chain_tokens_saved",
                     "prompt tokens whose prefill was skipped, per hot "
                     "chain").set(row["tokens_saved"], tags=ctags)
        _chain_gauge("rtpu_llm_prefix_chain_resident_pages",
                     "KV pages of the chain currently in HBM").set(
            row["resident_pages"], tags=ctags)
        age = row["last_hit_age_s"]
        if age is not None:
            _chain_gauge("rtpu_llm_prefix_chain_last_hit_age_s",
                         "seconds since the chain last served a "
                         "hit").set(age, tags=ctags)
    _gauge("rtpu_llm_prefix_chain_tracked",
           "chains holding dedicated heat-table slots").set(
        engine.chains.stats()["tracked"], tags=gtags)


# --------------------------------------------------------------------- #
# multi-LoRA (llm/multilora) — the rtpu_llm_lora_* family
# --------------------------------------------------------------------- #
#   lora_requests_total        counter  adapter-routed requests resolved
#   lora_hits_total            counter  resolves served by a resident slot
#   lora_loads_total           counter  cold slot loads (registry fetch +
#       device scatter)
#   lora_evictions_total       counter  LRU slots reclaimed for a load
#   lora_swaps_total           counter  hot-swaps: a newer version loaded
#       while an older one stayed resident (pinned by in-flight requests)
#   lora_publishes_total       counter  registry publishes, by namespace
#   lora_resident_adapters     gauge    slots currently holding an adapter

def lora_publishes() -> Counter:
    return _counter("rtpu_llm_lora_publishes_total",
                    "adapter versions published to the registry",
                    tag_keys=("namespace",))


_LORA_COUNTERS = (
    ("requests", "rtpu_llm_lora_requests_total",
     "requests resolved to an adapter slot"),
    ("hits", "rtpu_llm_lora_hits_total",
     "adapter resolves served by an already-resident slot"),
    ("loads", "rtpu_llm_lora_loads_total",
     "cold adapter loads into the slot table"),
    ("evictions", "rtpu_llm_lora_evictions_total",
     "resident slots LRU-reclaimed to load another adapter"),
    ("swaps", "rtpu_llm_lora_swaps_total",
     "hot-swaps (newer version loaded beside a pinned older one)"),
)


@_never_raise
def on_lora_stats(manager) -> None:
    """Ship the manager's counter deltas + residency gauge (called on
    every resolve — a handful of dict updates, same budget as
    on_step)."""
    last = getattr(manager, "_telem_shipped", None)
    if last is None:
        last = manager._telem_shipped = {}
    for key, name, desc in _LORA_COUNTERS:
        cur = manager.stats.get(key, 0)
        delta = cur - last.get(key, 0)
        if delta > 0:
            last[key] = cur
            _counter(name, desc).inc(float(delta), tags={"engine": "paged"})
    _gauge("rtpu_llm_lora_resident_adapters",
           "slot-table rows currently holding an adapter").set(
        float(len(manager._resident)),
        tags={"engine": "paged", "proc": _proc()})


def _emit_request_span(req) -> None:
    ctx: Optional[tuple] = getattr(req, "trace_ctx", None)
    if ctx is None:
        return
    try:
        from ..util import tracing
        trace_id, parent_id = ctx
        rec = {"trace_id": trace_id, "span_id": tracing.new_span_id(),
               "parent_id": parent_id, "name": "llm.request",
               "start_s": req.submit_wall,
               "dur_s": max(time.time() - req.submit_wall, 0.0)}
        if getattr(req, "request_id", ""):
            rec["request_id"] = req.request_id
        tracing.record_span(rec)
        # its three children, end to end, from the stamps the request
        # carries: seconds from submit at which it was admitted and got
        # its first token. A stamp never set (retired before admission,
        # an imported prefill) collapses its span to nothing.
        total = rec["dur_s"]
        admit = min(max(req.admit_t - req.submit_t, 0.0), total)
        first = min(max(req.first_token_t - req.submit_t, admit), total)
        args = {"prompt_tokens": len(req.prompt_ids),
                "prefix_tokens_saved": req.prefix_tokens_saved,
                "out_tokens": len(req.out_ids)}
        kids = [("llm.queue", 0.0, admit), ("llm.prefill", admit, first),
                ("llm.decode", first, total)]
        if req.first_chunk_ns:
            # a streamed request: first token booked -> its chunk taken
            # by the transport (the stamps are perf_counter_ns, the
            # engine's clock like submit_t)
            kids.append(("llm.deliver", first, min(max(
                req.first_chunk_ns * 1e-9 - req.submit_t, first), total)))
        for name, t0, t1 in kids:
            tracing.record_span({
                **rec, "span_id": tracing.new_span_id(),
                "parent_id": rec["span_id"], "name": name,
                "start_s": req.submit_wall + t0, "dur_s": t1 - t0,
                "args": args})
    except Exception:
        pass  # span loss must never break retire
