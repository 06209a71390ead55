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

import collections
import dataclasses
import queue
import threading
import time as _time
from typing import Optional

from ..util.profiling import phase
from . import telemetry
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


class _QueueSink:
    """The sink of a stream that is iterated in this process (a
    ``stream_next`` reply, local mode, a test): the pump's items wait in
    a queue for ``TokenStream.__next__``. It never refuses credit."""

    __slots__ = ("items", "shut")

    def __init__(self):
        self.items: queue.SimpleQueue = queue.SimpleQueue()
        self.shut = False

    def put(self, item) -> bool:
        self.items.put(("i", item))
        return True

    def end(self) -> bool:
        self.items.put(("e", None))
        return True

    def fail(self, exc: BaseException) -> bool:
        self.items.put(("x", exc))
        return True

    def closed(self) -> bool:
        return self.shut


class _Open:
    """The pump's record of one open stream: the request, the sink its
    chunks go to, and how far the sink has got."""

    __slots__ = ("req", "sink", "seen", "text", "sent", "booked", "finish",
                 "first", "closing")

    def __init__(self, req, sink):
        self.req, self.sink = req, sink
        self.seen = 0           # tokens of req.out_ids detokenised
        self.text = ""          # the answer so far, detokenised whole
        self.sent = 0           # characters of it the sink has taken
        self.booked = 0         # token_ns of the newest token in text
        self.finish = None      # finish_reason, once the request is done
        self.first = True       # no chunk taken yet
        self.closing = False    # the closing chunk has gone: end() is owed


class TokenStream:
    """What ``LLMServer.completions_stream`` returns: a request already
    submitted, whose chunks the server's one pump thread hands to a
    sink. Two ways to take them:

    - **push**: ``attach(sink)`` — the pump calls ``sink.put(chunk)``,
      ``sink.end()`` after the chunk that carries ``finish_reason``, or
      ``sink.fail(exc)``; each returns False when the sink cannot take
      it now (the pump keeps the text and tries again on a later pass,
      with whatever has come since in the same chunk) and none may
      block; a sink whose ``closed()`` is true is dropped. A sink may
      tell when the write that took its newest item began
      (``wrote_ns``, perf_counter_ns): the front stage ``first_chunk``
      ends there. The serve
      replica attaches a sink over the stream's ring
      (serve/controller.py ``_start_stream_channel``) and starts no
      thread;
    - **pull**: iterate it. The first ``next()`` attaches a queue as the
      sink; ``close()`` (or dropping the object) ends the pump's work
      for it."""

    def __init__(self, server: "LLMServer", req):
        self._server, self._req = server, req
        self._own: Optional[_QueueSink] = None
        self._attached = self._over = False

    def attach(self, sink) -> None:
        if self._attached:
            raise RuntimeError("this stream already has a sink")
        self._attached = True
        self._server._open_stream(_Open(self._req, sink))

    def __iter__(self):
        return self

    def __next__(self):
        if self._over:
            raise StopIteration
        if self._own is None:
            own = _QueueSink()
            self.attach(own)
            self._own = own
        kind, payload = self._own.items.get()
        if kind == "i":
            return payload
        self._over = True
        if kind == "x":
            raise payload
        raise StopIteration

    def close(self) -> None:
        self._over = True
        if self._own is not None:
            self._own.shut = True

    __del__ = close


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
        # streams attached and not yet seen by the pump (TokenStream
        # .attach, any thread -> _pump), and what wakes a pump that has
        # none open
        self._opening: collections.deque = collections.deque()
        self._pump_wake = threading.Event()
        # cluster prefix directory (serve/frontdoor/prefix.py). The
        # controller injects this replica's own handle via
        # set_replica_handle; publishing starts then.
        self._prefix_dir = None
        from ..core.config import cfg as rcfg
        if rcfg.serve_prefix_directory and self.engine._prefix_on:
            from ..serve.frontdoor.prefix import PrefixDirectoryClient
            self._prefix_dir = PrefixDirectoryClient(cfg.model_id)
            self.engine.cache.log.track = True
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
        # the one thread that carries every open stream's tokens out
        self._pump_thread = threading.Thread(
            target=self._pump, daemon=True, name="llm-stream-pump")
        self._pump_thread.start()

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
            # and the pump, which fails every open stream
            eng._notify_launch()

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

    def completions_stream(self, request: dict) -> TokenStream:
        """Submit now, on the caller's thread — a refused request (an
        unknown adapter's ValueError, RuntimeError("overloaded: ...")) is
        raised here and no stream exists — and return the request's
        ``TokenStream`` of token-delta dicts (reference: the streaming
        response path of llm_server.py; pairs with
        handle.options(stream=True) / the SSE proxy path). No thread
        belongs to it: the server's one pump (``_pump``) detokenises and
        delivers for every open stream, and counts what a token's way
        out of the replica costs into ``engine.stats``."""
        return TokenStream(self, self._submit(request))

    # -- the stream pump ---------------------------------------------------

    def _open_stream(self, opened: _Open) -> None:
        self._opening.append(opened)
        self._pump_wake.set()

    def _pump(self):
        """The one thread that serves every open stream. It sleeps on
        ``engine.launched``, so a pass runs where the stepping thread's
        next act is a wait for the device (a program just launched, or a
        booking that another readback follows: paged_engine
        ``_notify_launch``) — beside the device's work, and as ONE
        runnable thread at the interpreter the stepping thread needs for
        its next launch, where a thread a stream made 64 (PERF.md §6,
        PR 39). The tokens a pass finds are those of the last booking.

        Counted into ``engine.stats``, by this thread alone:

        - ``stream_chunks``: chunks that carried text and that their
          sink took (the ring write returned, the queue has it);
        - ``stream_lag_ns``: from the booking of a chunk's newest token
          (``_Request.token_ns``, the stepping thread's stamp) to that
          instant: the wait for the next wake-up, the stream's
          turn in the pass, the detokenisation and the sink's write,
          and every pass a sink without credit held the text back;
        - ``stream_first_chunks`` / ``stream_first_lag_ns``: the same
          for the chunk that carries a request's first token, from that
          token's booking: the part of a client's TTFT between the
          engine's (``rtpu_llm_ttft_seconds``) and the proxy; the
          instant is the request's ``first_chunk_ns`` (``llm.deliver``);
        - ``stream_cpu_ns``: this thread's CPU, read once a pass — what
          all the streams cost the one interpreter;
        - ``stream_passes``: passes in which at least one chunk was
          taken; ``stream_deferred``: puts a sink refused for want of
          credit, the text kept for a later pass;
        - ``stream_write_ns``: the wall time of every ``sink.put`` (over
          the ring: the serialisation, the seal and its wake-ups): the
          part of ``stream_lag_ns`` that is the write itself, beside the
          part that is the wait for a pass."""
        eng = self.engine
        st, launched = eng.stats, eng.launched
        streams: list[_Open] = []
        cpu = _time.thread_time_ns()
        while not self._stop:
            while self._opening:
                streams.append(self._opening.popleft())
            if not streams:
                # no launch is worth waking for
                self._pump_wake.wait(timeout=1.0)
                self._pump_wake.clear()
                continue
            gen = eng.launch_gen
            chunks = st["stream_chunks"]
            streams = [s for s in streams if not self._pump_stream(s)]
            if st["stream_chunks"] > chunks:
                st["stream_passes"] += 1
            now = _time.thread_time_ns()
            st["stream_cpu_ns"] += now - cpu
            cpu = now
            with launched:
                # the timeout bounds the wait when no dispatch follows
                # (the engine went idle, or its loop died)
                if eng.launch_gen == gen and not self._opening:
                    launched.wait(timeout=0.05)

    def _pump_stream(self, s: _Open) -> bool:
        """One stream's turn in a pass; True once the pump is done with
        it. An error of its own (a sink's, the tokenizer's) ends that
        stream alone."""
        try:
            return self._deliver(s)
        except Exception as e:  # noqa: BLE001 — shipped to the consumer
            try:
                s.sink.fail(e)
            except Exception:  # noqa: BLE001 — nothing left to tell
                pass
            return True

    def _deliver(self, s: _Open) -> bool:
        eng, req, sink = self.engine, s.req, s.sink
        if sink.closed():
            return True         # the consumer cancelled
        if s.finish is None:
            # done is read before the tokens: a done request's are all in
            if req.done:
                out = eng._result(req)
                s.booked, s.seen = req.token_ns, len(req.out_ids)
                s.text, s.finish = out["text"], out["finish_reason"]
            elif self._error is not None:
                err = RuntimeError("llm engine loop died")
                err.__cause__ = self._error
                return sink.fail(err) or sink.closed()
            elif len(req.out_ids) > s.seen:
                ids = list(req.out_ids)
                # a token is stamped before it is appended
                # (paged_engine._book_decode): read after the ids, the
                # stamp is no older than the booking of any of them
                s.booked, s.seen = req.token_ns, len(ids)
                s.text = eng.tokenizer.decode(ids)
        delta = s.text[s.sent:]
        if not s.closing and (delta or s.finish is not None):
            t0 = _time.perf_counter_ns()
            took = sink.put({
                "object": "text_completion.chunk", "model": self.model_id,
                "choices": [{"text": delta, "index": 0,
                             "finish_reason": s.finish}]})
            now = _time.perf_counter_ns()
            eng.stats["stream_write_ns"] += now - t0
            if not took:
                if sink.closed():
                    return True
                # no credit: the text is kept, and goes with what the
                # next passes add in one longer chunk
                eng.stats["stream_deferred"] += 1
                return False
            if delta:
                self._taken(s, now)
            s.sent = len(s.text)
            s.closing = s.finish is not None
        return s.closing and (sink.end() or sink.closed())

    def _taken(self, s: _Open, now: int) -> None:
        """A sink has taken a chunk that carried text; its put returned
        at `now`."""
        st = self.engine.stats
        st["stream_chunks"] += 1
        st["stream_lag_ns"] += now - s.booked
        if s.first:
            s.first = False
            st["stream_first_chunks"] += 1
            st["stream_first_lag_ns"] += now - s.req.first_token_ns
            s.req.first_chunk_ns = now
            # the front stage ends where the ring's hop begins: at the
            # write's stamp, so that a write lies in one stage alone
            telemetry.on_first_chunk(s.req, (
                getattr(s.sink, "wrote_ns", 0) or now)
                - s.req.first_token_ns)

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
        if not self._pump_thread.is_alive():
            raise RuntimeError("stream pump died")


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
