"""serve.llm analog: the engine behind a Serve deployment.

Reference parity: llm/_internal/serve/deployments/llm/llm_server.py:409
(LLMServer — async request intake feeding the engine loop) and :704
(LLMDeployment — the Serve wrapper); router surface matches the OpenAI
completions shape the reference's router exposes.

TPU note (reference analog: LLMConfig -> PG bundles for TP×PP workers,
configs/server_models.py:391-415): the engine's model runs under the current
process's mesh; multi-chip TP serving shards the same jitted programs over a
tp axis — replicas gang-schedule via the deployment's ray_actor_options
TPU resources.
"""
from __future__ import annotations

import dataclasses
import threading
import time as _time
from typing import Optional

from ..util.profiling import phase
from .engine import SamplingParams
from .paged_engine import PHASES, PagedEngineConfig, PagedInferenceEngine


@dataclasses.dataclass
class LLMConfig:
    """(reference: llm/_internal/serve/configs/server_models.py LLMConfig)

    A replica runs ONE engine, built from ``engine`` (default: a paged
    engine over ``llama_tiny``).

    LoRA: a PagedEngineConfig with ``max_adapters > 0`` serves every
    adapter from that one engine — a request carrying ``"lora": "<id>"``
    (or ``model="<model_id>:<id>"``) resolves the adapter's latest
    version in the AdapterRegistry (namespace ``lora_namespace``,
    default the model_id) at admission, rides a resident slot-table
    row, and shares the decode dispatch with every other tenant.
    Hot-swap: a newly published version starts serving within
    cfg.llm_lora_refresh_s, in-flight requests finish on their admitted
    version. Prefix-cache keys are salted per (adapter_id, version), so
    warmed prefixes never cross tenants. With ``max_adapters == 0`` a
    request that names a LoRA is refused."""
    model_id: str = "llama-tiny"
    engine: Optional[PagedEngineConfig] = None
    num_replicas: int = 1
    max_ongoing_requests: int = 64
    tpus_per_replica: float = 0.0
    # registry namespace for batched multi-LoRA (None -> model_id)
    lora_namespace: Optional[str] = None
    # compile every engine program family at replica init, before the
    # replica reports ready (vLLM-style deploy-time graph capture) —
    # keeps the first request burst from paying mid-burst XLA compiles.
    # Sampled + top-k modes are warmed too when True.
    warmup: bool = True
    warmup_sampled: bool = False


class _StreamMeter:
    """One stream's way out of the replica, counted chunk by chunk into
    ``engine.stats`` on the stream's own thread (``completions_stream``):

    - ``stream_chunks``: chunks that carried text and were taken by the
      transport — the generator was resumed after their ``yield`` (the
      replica's drain thread has written its ring, a ``stream_next``
      reply has gone);
    - ``stream_lag_ns``: from the booking of a chunk's newest token
      (``_Request.token_ns``, the stepping thread's stamp) to that
      resumption: the wait for the next launch's wake-up, the thread's
      turn at the interpreter, the detokenisation and the transport's
      write together — what a chunk's delivery takes inside the replica;
    - ``stream_first_chunks`` / ``stream_first_lag_ns``: the same for
      the chunk that carries a request's first token, from that token's
      booking: the part of a client's TTFT between the engine's
      (``rtpu_llm_ttft_seconds``) and the proxy;
    - ``stream_cpu_ns``: the thread's CPU from one chunk's resumption to
      the next, the empty wake-ups between, the detokenisation and the
      transport's write included — what the stream costs the one
      interpreter."""

    __slots__ = ("_stats", "_lock", "_req", "_first", "_cpu", "_thread")

    def __init__(self, stats: dict, lock, req):
        self._stats, self._lock, self._req = stats, lock, req
        self._first = True
        # a thread's CPU clock says nothing of another's, and a
        # stream_next reply may resume the generator on another thread
        # of the actor's pool: the clock is kept with its thread
        self._cpu, self._thread = (_time.thread_time_ns(),
                                   threading.get_ident())

    def taken(self, booked: int) -> None:
        """The transport has taken a chunk whose newest token was booked
        at ``booked`` (``token_ns`` as read when its tokens were seen: a
        token is stamped before it is appended, paged_engine._book_decode,
        so the stamp is no older than the booking of any of them)."""
        now = _time.perf_counter_ns()
        cpu, thread = _time.thread_time_ns(), threading.get_ident()
        spent = cpu - self._cpu if thread == self._thread else 0
        self._cpu, self._thread = cpu, thread
        req, st = self._req, self._stats
        with self._lock:
            st["stream_chunks"] += 1
            st["stream_lag_ns"] += now - booked
            st["stream_cpu_ns"] += spent
            if self._first:
                st["stream_first_chunks"] += 1
                st["stream_first_lag_ns"] += now - req.first_token_ns
        if self._first:
            self._first = False
            req.first_chunk_ns = now


class LLMServer:
    """Deployment callable: background engine thread + request futures
    (reference: llm_server.py:409)."""

    def __init__(self, cfg: LLMConfig, params_ref=None):
        from ..core.usage import record_library_usage
        record_library_usage("llm")

        from ..models import llama
        self.cfg = cfg
        self.engine_cfg = cfg.engine or PagedEngineConfig(
            model=llama.llama_tiny())
        params = None
        if params_ref is not None:
            import ray_tpu
            params = ray_tpu.get(params_ref)
        self.engine = self._build_engine(params)
        self.model_id = cfg.model_id
        self._wake = threading.Event()
        self._stop = False
        self._last_rewarm = 0.0   # spill-tier re-warm cadence (loop)
        self._error: Optional[BaseException] = None
        # serializes engine stepping against cross-replica page
        # import/export (the dispatches donate engine.caches, so a
        # concurrent scatter/gather would read deleted buffers — same
        # contract as pd_disagg's _steplock around import_prefill)
        self._steplock = threading.Lock()
        # the stream threads' adds to engine.stats (_StreamMeter): a
        # read-modify-write a chunk, which two of them must not
        # interleave. The stepping thread never writes those keys and
        # never takes this lock.
        self._stream_lock = threading.Lock()
        # cluster prefix directory (serve/frontdoor/prefix.py). The
        # controller injects this replica's own handle via
        # set_replica_handle; publishing starts then.
        self._prefix_dir = None
        from ..core.config import cfg as rcfg
        if rcfg.serve_prefix_directory and self.engine._prefix_on:
            from ..serve.frontdoor.prefix import PrefixDirectoryClient
            self._prefix_dir = PrefixDirectoryClient(cfg.model_id)
            self.engine.track_page_publish = True
        # batched multi-LoRA (llm/multilora): one engine, many tenants.
        # The manager resolves adapter ids to resident slot-table rows
        # at admission; version pinning, LRU and hot-swap live there.
        self._multilora = None
        if self.engine.lora is not None:
            from .multilora import AdapterRegistry, MultiLoraManager
            self._multilora = MultiLoraManager(
                self.engine,
                AdapterRegistry(cfg.lora_namespace or cfg.model_id))
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        # the stepping thread's CPU clock, which engine_stats() reads
        # from outside: the thread itself never pays for a reading
        self._step_cpu_clock = _time.pthread_getcpuclockid(
            self._thread.ident)

    def _build_engine(self, params):
        eng = PagedInferenceEngine(self.engine_cfg, params)
        if self.cfg.warmup:
            modes = [(False, False)]
            if self.cfg.warmup_sampled:
                modes += [(True, False), (True, True)]
            eng.warmup(sample_modes=tuple(modes))
        return eng

    @staticmethod
    def _lora_id(request: dict) -> Optional[str]:
        lora_id = request.get("lora")
        model = request.get("model", "")
        if not lora_id and ":" in model:
            lora_id = model.split(":", 1)[1]
        return lora_id or None

    def _loop(self):
        # this thread's time outside step() goes to the engine's stats
        # beside step()'s own phases (paged_engine.PHASES), so that the
        # ten sum to the thread's wall time: rtpu.loop.idle is the wait
        # for work and nothing else, rtpu.loop.other the rest (steplock,
        # prefix-directory publish, rewarm).
        eng = self.engine
        st = eng.stats
        other, idle = (
            (key, PHASES[key]) for key in ("ns_loop_other", "ns_loop_idle"))
        try:
            while not self._stop:
                worked = eng.has_work()
                if worked:
                    with phase(st, *other):
                        self._steplock.acquire()
                    try:
                        eng.step()
                    finally:
                        self._steplock.release()
                with phase(st, *other):
                    if self._prefix_dir is not None:
                        # drain newly published/evicted page hashes to
                        # the cluster directory (rate-limited inside;
                        # this IS the stepping thread, per the drain
                        # contract)
                        self._prefix_dir.maybe_publish(eng)
                    if eng.spill is not None:
                        now = _time.monotonic()
                        if now - self._last_rewarm >= 0.25:
                            # proactive promote of the hottest spilled
                            # chain into idle pool headroom; bounded
                            # pages per tick so the scatter never stalls
                            # a step. Under the steplock: the scatter
                            # donates the cache pools (import_prefix
                            # contract).
                            self._last_rewarm = now
                            with self._steplock:
                                eng.maybe_rewarm(max_pages=32)
                if not worked:
                    with phase(st, *other):
                        # nothing follows the last dispatch: its tokens
                        # are sent now
                        eng._notify_launch()
                    with phase(st, *idle):
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
        except BaseException as e:  # noqa: BLE001 — engine died: fail fast
            self._error = e
            # unblock every waiter; completions() re-raises the error, and
            # check_health makes the controller replace this replica
            for req in (list(eng._active.values()) + list(eng._pending)
                        + list(eng._prefilling)):
                req.event.set()

    # -- OpenAI-ish surface ------------------------------------------------

    def _submit(self, request: dict):
        prompt = request.get("prompt", "")
        sp = SamplingParams(
            max_tokens=int(request.get("max_tokens", 64)),
            temperature=float(request.get("temperature", 0.0)),
            top_k=int(request.get("top_k", 0)),
            logprobs=int(request.get("logprobs") or 0),
        )
        eng = self.engine
        # tokenize ONCE: the prefix-directory lookup and submit share
        # the ids (a second encode of a long system prompt would tax
        # exactly the workloads the directory accelerates)
        prompt = (eng.tokenizer.encode(prompt)
                  if isinstance(prompt, str) else list(prompt))
        # base traffic: row 0 of the slot table (a no-op), unsalted
        # prefix-cache keys
        slot, salt = 0, b""
        lora_id = self._lora_id(request)
        if lora_id is not None:
            if self._multilora is None:
                raise ValueError(
                    f"request names LoRA {lora_id!r} but this deployment's "
                    f"engine has no adapter slot table: set "
                    f"PagedEngineConfig.max_adapters > 0")
            # batched multi-LoRA: resolve the adapter's latest version
            # at ADMISSION (in-flight requests stay pinned to it), ride
            # a slot-table row on the shared engine, and salt every
            # prefix-cache key with (adapter_id, version). pin=True
            # holds the slot against eviction across the prefix-import
            # window below — the engine's own in-flight accounting
            # starts only at submit(). Errors stay TYPED: unknown
            # adapter -> ValueError (client error), all slots live ->
            # RuntimeError("overloaded: ...") the proxy turns into a
            # retryable 503, never a bare 500.
            try:
                slot, _version, salt = self._multilora.resolve(
                    lora_id, self._steplock, pin=True)
            except KeyError as e:
                raise ValueError(
                    f"unknown LoRA adapter {lora_id!r} for model "
                    f"{self.model_id!r}: {e}") from e
        try:
            if self._prefix_dir is not None:
                # cluster prefix directory: admission-match a prefix
                # warmed on ANY replica by importing its KV pages before
                # submit — best effort, a miss/failure just means a cold
                # prefill. The hashes carry the tenant's salt: an entry
                # for this (adapter_id, version) can only match its own
                # pages
                self._prefix_dir.maybe_import(eng, self._steplock,
                                              prompt, salt=salt)
            req = eng.submit(prompt, sp, adapter_slot=slot,
                             prefix_salt=salt)
        finally:
            if lora_id is not None:
                self._multilora.unpin(slot)
        self._wake.set()
        return req

    def completions(self, request: dict) -> dict:
        """{"prompt": str, "max_tokens": int, "temperature": float,
        "lora": str, ...} -> completions response."""
        eng, req = self.engine, self._submit(request)
        while not req.event.wait(timeout=1.0):
            if self._error is not None:
                raise RuntimeError("llm engine loop died") from self._error
        if self._error is not None and not req.done:
            raise RuntimeError("llm engine loop died") from self._error
        out = eng._result(req)
        text = out["text"]
        if request.get("echo"):
            # OpenAI echo: the completion text is prompt + generation
            prompt = request.get("prompt", "")
            text = (prompt if isinstance(prompt, str)
                    else eng.tokenizer.decode(list(prompt))) + text
        choice = {
            "text": text,
            # ids beside the text: a prompt may be given as token ids, and
            # decoding is lossy where the tokenizer's vocabulary is not
            # the model's (the default ByteTokenizer)
            "token_ids": out["token_ids"],
            "finish_reason": out["finish_reason"],
            "index": 0,
        }
        if out.get("logprobs") is not None:
            # chosen-token logprobs (top-N alternatives not reported —
            # SamplingParams.logprobs docstring)
            choice["logprobs"] = {
                "tokens": [eng.tokenizer.decode([t])
                           for t in out["token_ids"]],
                "token_logprobs": out["logprobs"],
                "top_logprobs": None,
            }
        return {
            "object": "text_completion",
            "model": self.model_id,
            "choices": [choice],
            "usage": {
                "prompt_tokens": out["prompt_tokens"],
                "completion_tokens": len(out["token_ids"]),
            },
        }

    def completions_stream(self, request: dict):
        """Generator of token-delta dicts while the engine decodes
        (reference: the streaming response path of llm_server.py; pairs
        with handle.options(stream=True) / the SSE proxy path).

        What a token's way out of the replica costs is counted here,
        chunk by chunk, into ``engine.stats`` (``_StreamMeter``): a
        chunk is counted once the transport (the replica's drain thread
        writing its ring, or a ``stream_next`` reply) has taken it and
        resumes the generator."""
        eng, req = self.engine, self._submit(request)
        meter = _StreamMeter(eng.stats, self._stream_lock, req)
        sent = 0
        last_text = ""
        # the engine says when it has launched a dispatch: sleep on
        # that, not on a 50 Hz poll, so that this thread's work runs
        # beside the device's and not in the stepping thread's way
        launched = eng.launched
        while True:
            if self._error is not None and not req.done:
                raise RuntimeError("llm engine loop died") from self._error
            gen = eng.launch_gen
            n = len(req.out_ids)
            if n > sent:
                booked = req.token_ns
                text = eng.tokenizer.decode(list(req.out_ids))
                delta, last_text = text[len(last_text):], text
                sent = n
                if delta:
                    yield {"object": "text_completion.chunk",
                           "model": self.model_id,
                           "choices": [{"text": delta, "index": 0,
                                        "finish_reason": None}]}
                    meter.taken(booked)
            if req.done:
                break
            with launched:
                # the timeout bounds the wait when no dispatch follows
                # (the engine went idle, or its loop died)
                if eng.launch_gen == gen and not req.done:
                    launched.wait(timeout=0.05)
        booked = req.token_ns
        out = eng._result(req)
        tail = out["text"][len(last_text):]
        yield {"object": "text_completion.chunk", "model": self.model_id,
               "choices": [{"text": tail, "index": 0,
                            "finish_reason": out["finish_reason"]}]}
        # the closing chunk is empty unless the last tokens were booked
        # between this thread's look and the retirement
        if tail:
            meter.taken(booked)

    def set_replica_handle(self, handle) -> None:
        """Controller-injected handle to THIS replica's actor: the value
        every prefix-directory entry carries, so peer replicas can call
        export_prefix on the owner."""
        if self._prefix_dir is not None:
            self._prefix_dir.set_replica_handle(handle)

    def export_prefix(self, hashes):
        """Serve a peer replica's cross-replica prefix import: gather
        the cached KV pages for `hashes` (a chain run) to host arrays.
        None when nothing is cached any more — the caller treats the
        directory entry as stale and prefills cold."""
        if not self.engine._prefix_on:
            return None
        with self._steplock:
            return self.engine.export_prefix(list(hashes))

    def engine_stats(self) -> dict:
        """Counter snapshot for ops introspection: the engine's
        stats dict (the stepping thread's ``ns_*`` phase times among
        them) plus the resolved mesh axis sizes (None single-chip).
        ``step_thread_cpu_ns`` is the stepping thread's CPU time so
        far: between two snapshots, its host phases' and launches' wall
        time (``ns_*`` but ``*_device`` and ``ns_loop_idle``, plus
        ``launch_ns_*``) less this is the time the thread stood runnable
        and did not run — the GIL, a lock, the kernel — give or take
        the little CPU a readback's wait takes.
        On a mesh, ``mesh_reshard_bytes`` staying 0 IS the steady-state
        zero-involuntary-reshard invariant — a nonzero value means some
        dispatch committed a buffer off its pinned sharding."""
        import jax

        from ..util.compile_cache import compile_cache_stats
        st = dict(self.engine.stats)
        # the ns_* counters' clock at this snapshot: between two
        # snapshots their deltas sum to this one's
        st["clock_ns"] = _time.perf_counter_ns()
        try:
            st["step_thread_cpu_ns"] = _time.clock_gettime_ns(
                self._step_cpu_clock)
        except OSError:
            pass    # the loop's thread has ended: no clock to read
        mesh = self.engine.mesh
        st["mesh"] = None if mesh is None else {
            k: int(v) for k, v in mesh.shape.items()}
        # what this replica's process really runs on, as JAX reports it:
        # the one way a caller can tell a chip from a silent CPU
        devs = jax.devices()
        st["device"] = {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)}
        st["memory"] = [d.memory_stats() for d in devs]
        st["compile_cache"] = compile_cache_stats()
        st["profile"] = self.engine.profile_summary()
        return st

    def loaded_loras(self) -> list:
        """Resident adapters: the slot table's ``<adapter_id>@<version>``
        pairs (empty without a slot table)."""
        if self._multilora is None:
            return []
        return [f"{aid}@{v}" for aid, v in
                self._multilora.resident().values()]

    def __call__(self, request: dict) -> dict:
        return self.completions(request or {})

    def check_health(self):
        if self._error is not None or not self._thread.is_alive():
            raise RuntimeError("engine loop died") from self._error


def build_llm_deployment(cfg: LLMConfig, params_ref=None):
    """LLMConfig -> a Serve Application (reference:
    build_openai_app / LLMDeployment, llm_server.py:704)."""
    from .. import serve
    dep = serve.deployment(
        LLMServer,
        name=f"llm:{cfg.model_id}",
        num_replicas=cfg.num_replicas,
        max_ongoing_requests=cfg.max_ongoing_requests,
        ray_actor_options=(
            {"num_tpus": cfg.tpus_per_replica}
            if cfg.tpus_per_replica else {}),
    )
    return dep.bind(cfg, params_ref)
