"""DeploymentHandle: the client-side router.

Reference parity: serve/handle.py:639 (DeploymentHandle.remote :715 ->
DeploymentResponse), _private/router.py:365 (AsyncioRouter.assign_request
:676) and request_router/pow_2_router.py:27 (power-of-two-choices).

The routing state of a deployment is ONE object a process (``_Router``):
the cached replica set, the in-flight counts of what this process sent
over it, and the one long-poll listener that keeps it fresh. Every handle
on the deployment — ``options()``, ``handle.method``, an unpickled copy —
is a view of that object and keeps only what selects a call (method,
stream, model id, pinned replica), so a handle made per request costs no
controller round trip and no thread. Routing picks the lighter of two
random replicas by those shared counts; the set is refreshed when its
version changes or a replica dies mid-call (retried once on a fresh set).

Prefix affinity: LLM-style requests (a dict carrying ``prompt``, or an
explicit ``session_id``) rendezvous-hash onto a stable replica so repeated
prefixes — system prompts, multi-turn sessions — land where the paged
engine's prefix cache already holds their KV pages (paged_engine.py
enable_prefix_caching). The affinity choice yields to least-loaded when
the preferred replica is clearly busier than the lightest one, so a hot
prefix cannot hotspot a replica into queueing.
"""
from __future__ import annotations

import random
import threading
import time
import weakref
from collections import deque
from typing import Any, Optional

from ..core.config import cfg as _cfg
from ..core import flight as _fl

# affinity yields to load: the preferred replica is skipped when it has
# this many more in-flight requests (from this process) than the lightest
# replica — a cache hit saves prefill, not a queueing delay
_AFFINITY_SLACK = 4


class DeploymentResponse:
    """Future for one request (reference: handle.py DeploymentResponse).
    `.result()` blocks; `await` works inside async actors; passing a
    response to another .remote() passes the underlying ObjectRef so the
    payload never bounces through the caller.

    `.result()` retries once on a fresh replica set when the chosen replica
    died (scale-down or crash race against the handle's cached set)."""

    def __init__(self, ref, on_done, retry=None):
        self._ref = ref
        self._done = False
        self._on_done = on_done
        self._retry = retry

    def result(self, timeout_s: Optional[float] = None) -> Any:
        import ray_tpu
        from ..exceptions import ActorDiedError, WorkerCrashedError
        try:
            try:
                return ray_tpu.get(self._ref, timeout=timeout_s)
            except (ActorDiedError, WorkerCrashedError) as e:
                if self._retry is None:
                    raise
                # break the exception->traceback->frame cycle NOW: the
                # traceback's get() frames pin the dead replica's error
                # ref until a gc pass happens to run, which would hold
                # the store above baseline long after a chaos kill is
                # retried successfully
                e.__traceback__ = None
                self._ref = self._retry()
                return ray_tpu.get(self._ref, timeout=timeout_s)
        finally:
            self._settle()

    def _settle(self):
        if not self._done:
            self._done = True
            self._on_done()

    # a response dropped without result() must not stay counted in the
    # router its deployment's other handles route by
    __del__ = _settle

    def _to_object_ref(self):
        return self._ref

    def __await__(self):
        def gen():
            try:
                out = yield from self._ref.__await__()
                return out
            finally:
                self._settle()
        return gen()


# What a response generator counts for its stream — items, and on the
# ring transport each item's lag from its write stamp to the read — it
# keeps in plain ints and adds to the series ONCE, when the stream settles,
# is cancelled or fails (serve/metrics.py: rtpu_serve_stream_items_total,
# rtpu_serve_chunk_*_total stage "hop"): a chunk's path takes no lock. A
# generator that is dropped unsettled leaves its totals in `_orphans` — a
# finalizer may run inside any lock, the metric registry's among them — and
# the next stream of the process to open or settle adds them.
_orphans: deque = deque()

# `ns`: when the newest ring read on this thread returned. A replica's
# drain thread zeroes it before it pulls its generator: not zero after the
# pull means the item came off a handle's stream, then (controller.py
# ``_start_stream_channel``, stages "first_relay" / "relay")
took = threading.local()


def _add_totals(*rows) -> None:
    """Add to the series the totals — (tags, items, hop_ns, hops) — of
    the streams given, which are over, and of whatever was orphaned."""
    rows = list(rows)
    while _orphans:
        try:
            rows.append(_orphans.popleft())
        except IndexError:      # another thread took the last
            break
    try:
        from . import metrics as sm
        for tags, items, hop_ns, hops in rows:
            if items:
                sm.stream_items().inc(float(items), tags=tags)
            sm.add_chunks("hop", hop_ns, hops, tags["app"],
                          tags["deployment"])
    except Exception:
        pass  # telemetry must never fail a stream


class ChannelResponseGenerator:
    """Iterator over a streaming response served by the STATIC DECODE
    PLAN: the replica seals the stream's items into a ring channel
    (dag/channel.py) — a drain thread that pulls the deployment's
    generator, or the deployment's own thread where its return value
    pushes (controller.py ``_start_stream_channel``) — and this end
    reads items straight out of shm —
    zero control-plane dispatches per item in steady state (the only
    actor calls are the setup and, when the stream goes quiet for a long
    time, a liveness probe so a dead replica raises instead of hanging).
    Falls out of DeploymentHandle.remote() when the replica shares the
    caller's object store and cfg.serve_static_decode_plan is on."""

    # probe the replica after this many idle 0.5s wait-slices in a row
    # (a healthy but slow decode costs at most one probe dispatch per
    # ~30s of silence — still amortized-zero)
    _PROBE_IDLE_SLICES = 60

    def __init__(self, replica, chan: dict, on_done, tags: dict,
                 staged: bool = False):
        from ..core import runtime as rt_mod
        from ..core.ids import ObjectID
        from ..dag.channel import RingReader
        rt = rt_mod.get_runtime_if_exists()
        self._on_done = on_done
        self._done = False
        self._replica = replica
        self._reader = RingReader(rt.store, chan["base"],
                                  ObjectID(chan["stop"]),
                                  int(chan["ring"]))
        self._tags = {**tags, "transport": "chan"}
        self._idle = 0
        # the stream belongs to a proxied request of this host: its
        # items' lags are front stages (serve/metrics.py)
        self._staged = staged
        self._items = self._hops = self._hop_ns = 0
        #: perf_counter_ns() as the newest read returned
        self.take_ns = 0

    def __iter__(self):
        return self

    def _probe(self):
        self._idle += 1
        if self._idle % self._PROBE_IDLE_SLICES:
            return
        import ray_tpu
        try:
            from . import metrics as sm
            sm.stream_dispatches().inc(1.0, tags=self._tags)
        except Exception:
            pass  # telemetry must never fail a stream
        ray_tpu.get(self._replica.stats.remote(), timeout=30)  # liveness

    def __next__(self):
        from ..dag.channel import ChannelClosed
        if self._done:
            raise StopIteration
        try:
            msg = self._reader.read(on_idle=self._probe)
        except ChannelClosed:
            self._reader.retire()
            self._settle()
            raise StopIteration from None
        take = self.take_ns = took.ns = time.perf_counter_ns()
        self._idle = 0
        # (kind, payload, write_ns); a writer of before the stamp sent two
        kind, payload = msg[0], msg[1]
        if kind == "i":
            self._items += 1
            if self._staged and len(msg) > 2:
                lag = take - msg[2]
                self._hop_ns += lag
                self._hops += 1
                if self._hops == 1:
                    from . import metrics as sm
                    sm.observe_stage("first_hop", lag, self._tags["app"],
                                     self._tags["deployment"])
            return payload
        self._reader.retire()  # sweep the trailing ack ring (leak-free)
        self._settle()
        if kind == "x":
            raise payload
        raise StopIteration

    def _settle(self, finalizer: bool = False):
        if not self._done:
            self._done = True
            row = (self._tags, self._items, self._hop_ns, self._hops)
            if finalizer:
                _orphans.append(row)
            else:
                _add_totals(row)
            if self._on_done:
                self._on_done()
                self._on_done = None

    def __del__(self):
        self._settle(finalizer=True)

    def cancel(self):
        if self._done:
            return
        # sealing the stop flag is the whole cancellation: whoever
        # writes this ring in the replica (the stream's drain thread, or
        # the deployment's pushing thread) observes it at its next write
        # and sweeps the channel — no actor call, zero dispatches
        self._reader.close()
        self._settle()


class DeploymentResponseGenerator:
    """Iterator over a streaming deployment response (reference:
    handle.py DeploymentResponseGenerator). Pulls batched chunks from the
    replica-retained generator via stream_next — the fallback transport
    when the static decode plan can't engage (no shared store, or
    cfg.serve_static_decode_plan off)."""

    def __init__(self, replica, sid: int, on_done, tags=None):
        self._replica = replica
        self._sid = sid
        self._on_done = on_done
        self._tags = {"app": "", "deployment": "", **(tags or {}),
                      "transport": "poll"}
        self._buf: deque = deque()
        self._done = False
        self._items = 0
        #: perf_counter_ns() as the newest stream_next returned
        self.take_ns = 0

    def __iter__(self):
        return self

    def __next__(self):
        import ray_tpu
        while not self._buf:
            if self._done:
                raise StopIteration
            try:
                items, done = ray_tpu.get(
                    self._replica.stream_next.remote(self._sid))
            except BaseException:
                self._settle()  # failed: the replica has dropped it
                raise
            self.take_ns = time.perf_counter_ns()
            try:
                from . import metrics as sm
                sm.stream_dispatches().inc(1.0, tags=self._tags)
            except Exception:
                pass  # telemetry must never fail a stream
            self._items += len(items)
            self._buf.extend(items)
            if done:
                self._settle()
        return self._buf.popleft()

    def _settle(self, finalizer: bool = False):
        self._done = True
        if self._on_done:
            row = (self._tags, self._items, 0, 0)
            if finalizer:
                _orphans.append(row)
            else:
                _add_totals(row)
            self._on_done()
            self._on_done = None

    def __del__(self):
        self._settle(finalizer=True)

    def cancel(self):
        import ray_tpu
        if not self._done:
            try:
                ray_tpu.get(self._replica.stream_cancel.remote(self._sid))
            except Exception:
                pass  # replica died; stream is gone either way
            self._settle()


# Bumped by serve.shutdown() (api.py): a listener thread started under an
# earlier value ends at its next turn instead of backing off and retrying
# against whatever controller the process starts next — where each stale
# thread parked one long-poll object in the NEW cluster's store (the
# objects tests/test_serve_frontdoor.py's drain check allows one of). It
# is part of the registry's key too, so a handle made after a shutdown
# never finds the router (and the replicas) of the serve instance before.
_serve_epoch = 0


def end_listeners() -> None:
    global _serve_epoch
    _serve_epoch += 1


class _ReplicaSet:
    """One version of a deployment's replica set and the in-flight count
    of what this process sent to each member. A change of the set is a NEW
    object installed by one assignment (``_Router.rs``): a routing thread
    that read ``rs`` once holds replicas, version and counts that belong
    together, and a response that settles after a change counts down on
    the set it was counted up on."""

    __slots__ = ("version", "replicas", "inflight")

    def __init__(self, version: int, replicas: list):
        self.version = version
        self.replicas = replicas
        self.inflight = [0] * len(replicas)  # guarded by: _Router.lock


class _Router:
    """What ``remote()`` routes by, once a (controller, app, deployment)
    in this process; handles are views of it (``_router_for``)."""

    def __init__(self, ctrl, app: str, deployment: str):
        self.ctrl = ctrl
        self.app = app
        self.deployment = deployment
        self.rs = _ReplicaSet(-1, [])
        # monotonic time the newest answer was asked for (a long-poll's:
        # returned); 0.0 until the first
        self.last_refresh = 0.0
        # re-entrant: a response dropped unsettled counts down from its
        # finalizer, which may run inside this thread's own hold
        self.lock = threading.RLock()
        self._fetch_lock = threading.Lock()
        self._listener_started = False  # guarded by: self.lock

    def install(self, version: int, replicas: list, asked: float) -> None:
        with self.lock:
            if version != self.rs.version:
                self.rs = _ReplicaSet(version, replicas)
            self.last_refresh = max(self.last_refresh, asked)

    def _why_fetch(self, force: bool, called: float) -> Optional[str]:
        """Why a caller that arrived at `called` has to fetch; None when
        what is installed will do."""
        last = self.last_refresh
        if not last:
            return "cold"
        if force or not self.rs.replicas:
            # satisfied by an answer that was asked for after this call
            return None if last > called else "forced"
        if called - last >= _cfg.serve_replica_poll_s:
            return "ttl"
        return None

    def refresh(self, force: bool = False) -> None:
        """Fetch the replica set unless it is fresh. One fetch at a time:
        the threads that arrive at a cold router together wait for the
        first one's answer and find it fresh."""
        import ray_tpu
        called = time.monotonic()
        if self._why_fetch(force, called) is None:
            return
        with self._fetch_lock:
            why = self._why_fetch(force, called)
            if why is None:
                return
            asked = time.monotonic()
            version, replicas = ray_tpu.get(self.ctrl.get_replicas.remote(
                self.app, self.deployment))
            self.install(version, replicas, asked)
        try:
            from . import metrics as sm
            sm.handle_refreshes().inc(1.0, tags={
                "app": self.app, "deployment": self.deployment, "why": why})
        except Exception:
            pass  # telemetry must never fail a request
        if why == "cold":
            _publish_routers(self.app, self.deployment)

    def ensure_listener(self) -> None:
        """Long-poll push of replica-set changes (reference:
        _private/long_poll.py LongPollClient): one daemon thread parks in
        the controller's listen_for_change, so scale-ups/downs reach every
        handle on this deployment promptly instead of on the next TTL
        poll, and steady-state traffic costs the controller one parked
        waiter a process, not one a handle. The thread holds only a
        WEAKREF to the router and exits when the last handle is gone."""
        with self.lock:
            if self._listener_started:
                return
            self._listener_started = True
        threading.Thread(target=_listen_loop_weak,
                         args=(weakref.ref(self), self.app, self.deployment),
                         daemon=True,
                         name=f"serve-lp-{self.deployment}").start()


_routers: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
_routers_lock = threading.Lock()


def _router_for(ctrl, app: str, deployment: str) -> _Router:
    """The process's router for a deployment, made at the first handle on
    it. Weak: it goes with its last handle (and in-flight response)."""
    key = (_serve_epoch, getattr(ctrl, "_actor_id", None), app, deployment)
    with _routers_lock:
        router = _routers.get(key)
        if router is None:
            router = _routers[key] = _Router(ctrl, app, deployment)
    return router


def _publish_routers(app: str, deployment: str) -> None:
    """Set the live-routers gauge: after a router's first fetch (one that
    never routed is not counted — the controller makes and pickles away a
    handle for every bound child) and where its listener ends. Never from
    a finalizer: those run inside any lock."""
    try:
        from . import metrics as sm
        with _routers_lock:
            n = sum(1 for r in _routers.values()
                    if r.app == app and r.deployment == deployment)
        sm.handle_routers().set(float(n), tags={
            "app": app, "deployment": deployment, "proc": _proc()})
    except Exception:
        pass  # telemetry must never fail a request


def _proc() -> str:
    """host:pid, the label the head's worker-death sweep zeroes a dead
    process's gauges by (llm/telemetry.py has the same; importing it here
    would bring jax into the proxy)."""
    import os

    from .context import host_name
    return f"{host_name()}:{os.getpid()}"


def _listen_loop_weak(router_ref, app: str, deployment: str):
    """Body of a router's long-poll listener thread. Takes a weakref so an
    abandoned router (and this thread) can die; between polls only ids are
    kept live. Ends when serve is shut down in this process: the router
    starts a new one at its next request (`ensure_listener`)."""
    try:
        _listen_loop(router_ref)
    finally:
        _publish_routers(app, deployment)


def _listen_loop(router_ref):
    import ray_tpu
    failures = 0
    epoch = _serve_epoch
    while True:
        r = router_ref()
        if r is None:
            return
        if epoch != _serve_epoch:
            r._listener_started = False
            return
        ctrl, app, dep, known = r.ctrl, r.app, r.deployment, r.rs.version
        del r  # don't pin the router across the (long) poll
        try:
            version, replicas = ray_tpu.get(
                ctrl.listen_for_change.remote(app, dep, known),
                timeout=45.0)
            failures = 0
        except Exception:
            # controller busy/restarting or deployment deleted; back off
            # and give up after repeated failures (the TTL path in
            # refresh still keeps the router usable)
            failures += 1
            r = router_ref()
            if failures >= 5 or r is None:
                if r is not None:
                    r._listener_started = False
                return
            del r
            time.sleep(min(2.0 ** failures, 10.0))
            continue
        r = router_ref()
        if r is None:
            return
        # the poll's answer is the state as it returns, not as asked
        r.install(version, replicas, time.monotonic())
        del r


class DeploymentHandle:
    def __init__(self, deployment: str, app: str, controller,
                 method: str = "__call__", stream: bool = False,
                 multiplexed_model_id: str = "",
                 replica_index: Optional[int] = None,
                 _router: Optional[_Router] = None):
        self.deployment_name = deployment
        self.app_name = app
        self._ctrl = controller
        self._method = method
        self._stream = stream
        self._model_id = multiplexed_model_id
        self._replica_index = replica_index
        self._router = _router or _router_for(controller, app, deployment)

    # handles pickle into replicas/tasks and find (or make) the receiving
    # process's router there
    def __reduce__(self):
        return (DeploymentHandle,
                (self.deployment_name, self.app_name, self._ctrl,
                 self._method, self._stream, self._model_id,
                 self._replica_index))

    def options(self, method_name: Optional[str] = None,
                stream: Optional[bool] = None,
                multiplexed_model_id: Optional[str] = None,
                replica_index: Optional[int] = None,
                **_ignored) -> "DeploymentHandle":
        return DeploymentHandle(
            self.deployment_name, self.app_name, self._ctrl,
            method_name or self._method,
            self._stream if stream is None else stream,
            self._model_id if multiplexed_model_id is None
            else multiplexed_model_id,
            self._replica_index if replica_index is None
            else replica_index, _router=self._router)

    def __getattr__(self, name: str) -> "DeploymentHandle":
        if name.startswith("_"):
            raise AttributeError(name)
        return self.options(method_name=name)

    # -- routing ----------------------------------------------------------

    def num_replicas(self) -> int:
        """Live replica count (fresh poll) — lets index-pinned callers
        (see ``options(replica_index=...)``) size their routing modulus
        to the deployment's actual width."""
        self._router.refresh(force=True)
        return len(self._router.rs.replicas)

    @staticmethod
    def _affinity_key(args: tuple, kwargs: dict) -> Optional[str]:
        """Prefix-affinity routing key for LLM-style calls: an explicit
        ``session_id`` (kwarg or request field) wins; otherwise the head
        of the request dict's prompt — the first N tokens/chars, which is
        exactly the region the paged engine's prefix cache can reuse.
        Non-LLM calls (no dict request, no session) return None and keep
        pure least-loaded routing."""
        req = args[0] if args and isinstance(args[0], dict) else None
        sid = kwargs.get("session_id") or (
            req.get("session_id") if req else None)
        if sid:
            return f"sid:{sid}"
        if req is None:
            return None
        prompt = req.get("prompt")
        if isinstance(prompt, str) and prompt:
            return "tok:" + prompt[:256]
        if isinstance(prompt, (list, tuple)) and prompt:
            return "tok:" + ",".join(map(str, prompt[:64]))
        return None

    def _pick(self, rs: _ReplicaSet, affinity: Optional[str] = None) -> int:
        """Power-of-two-choices over the process's in-flight counts
        (reference: pow_2_router.py:27). With a multiplexed model id,
        rendezvous hashing over stable replica (actor) ids instead: same
        model → same replica while it lives, so its weights stay
        cache-hot (multiplex.py routing note). An affinity key (shared
        prompt prefix / session) rendezvous-hashes the same way — same
        prefix → same replica → warm prefix cache — but yields to the
        least-loaded replica when the preferred one is clearly busier.
        Operates on the caller's SNAPSHOT of the replica set — the
        listener thread may install another concurrently."""
        replicas, loads = rs.replicas, rs.inflight
        n = len(replicas)
        if n == 1:
            return 0
        import hashlib

        def rendezvous(key):
            def score(i):
                rid = replicas[i]._actor_id.hex()
                return hashlib.md5(f"{key}:{rid}".encode()).digest()
            return max(range(n), key=score)

        if self._model_id:
            return rendezvous(self._model_id)
        if affinity is not None:
            pref = rendezvous(affinity)
            if loads[pref] <= min(loads) + _AFFINITY_SLACK:
                return pref
            return loads.index(min(loads))
        i, j = random.sample(range(n), 2)
        return i if loads[i] <= loads[j] else j

    @staticmethod
    def _make_chan_spec():
        """Channel spec for the static decode plan, or None when it
        can't engage from this process (flag off, no shm store — local
        mode — or this caller sits on an own-store node and can't share
        a store with a head-store replica)."""
        if not _cfg.serve_static_decode_plan:
            return None
        from ..core import runtime as rt_mod
        rt = rt_mod.get_runtime_if_exists()
        if getattr(rt, "store", None) is None or \
                getattr(rt, "own_store", False):
            return None
        import os
        return {"base": os.urandom(16), "stop": os.urandom(16),
                "ring": max(2, _cfg.serve_stream_ring)}

    def remote(self, *args, **kwargs) -> DeploymentResponse:
        import ray_tpu
        t0 = time.perf_counter()
        router = self._router
        router.refresh()
        router.ensure_listener()
        deadline = time.monotonic() + 30.0
        while not router.rs.replicas:
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"no replicas for {self.deployment_name!r}")
            time.sleep(0.05)
            router.refresh(force=True)
        args = tuple(a._to_object_ref() if isinstance(a, DeploymentResponse)
                     else a for a in args)
        kwargs = {k: (v._to_object_ref()
                      if isinstance(v, DeploymentResponse) else v)
                  for k, v in kwargs.items()}
        rs = router.rs  # snapshot: the listener may install another set
        if self._replica_index is not None:
            # pinned routing (PD channel pairing): the caller addresses a
            # specific replica by stable index, modulo the live count so a
            # scale-down degrades to wraparound instead of erroring
            idx = self._replica_index % len(rs.replicas)
        else:
            idx = self._pick(rs, self._affinity_key(args, kwargs))
        replica = rs.replicas[idx]
        with router.lock:
            rs.inflight[idx] += 1
        _fl.evt(_fl.SRV_DISPATCH, idx, int(self._stream))

        def done():
            with router.lock:
                rs.inflight[idx] -= 1

        from .context import get_request_context, local_ingress_ns
        ctx = get_request_context()
        routed_ns = time.perf_counter_ns()
        try:
            from . import metrics as sm
            tags = {"app": self.app_name,
                    "deployment": self.deployment_name}
            sm.handle_requests().inc(1.0, tags=tags)
            sm.router_wait().observe(routed_ns * 1e-9 - t0, tags=tags)
        except Exception:
            pass  # telemetry must never fail a request

        # the request's id and arrival stamp ride every hop as the proxy
        # set them; a call outside a request sends "" and 0
        context = {"app_name": self.app_name,
                   "deployment": self.deployment_name,
                   "multiplexed_model_id": self._model_id,
                   "request_id": ctx.request_id,
                   "ingress_ns": ctx.ingress_ns,
                   "ingress_host": ctx.ingress_host}

        if self._stream:
            import ray_tpu
            tags = {"app": self.app_name, "deployment": self.deployment_name}
            # front stages are a proxied request's, on this host's clock
            staged = bool(local_ingress_ns())
            _add_totals()       # of streams dropped unsettled, if any
            chan = self._make_chan_spec()
            try:
                resp = ray_tpu.get(replica.handle_request_streaming.remote(
                    self._method, args, kwargs, context, chan))
            except BaseException:
                done()  # no stream was opened: nothing else settles it
                raise
            try:
                from . import metrics as sm
                sm.stream_dispatches().inc(1.0, tags={
                    **tags, "transport": "chan" if isinstance(resp, dict)
                    else "poll"})
            except Exception:
                pass  # telemetry must never fail a request
            if isinstance(resp, dict) and resp.get("chan") is not None:
                # static decode plan engaged: items arrive over the ring
                # channel, no per-chunk actor calls
                _fl.evt(_fl.SRV_STREAM_START, int(resp["chan"]), 1)
                gen = ChannelResponseGenerator(replica, chan, done, tags,
                                               staged)
            else:
                _fl.evt(_fl.SRV_STREAM_START, int(resp), 0)
                gen = DeploymentResponseGenerator(replica, resp, done, tags)
            if staged:
                # the actor round trip that opens a stream, which
                # router_wait leaves out
                sm.observe_stage("open", time.perf_counter_ns() - routed_ns,
                                 self.app_name, self.deployment_name)
            return gen

        def retry():
            router.refresh(force=True)
            fresh = router.rs
            if not fresh.replicas:
                raise RuntimeError(
                    f"no replicas for {self.deployment_name!r}")
            r = fresh.replicas[self._pick(fresh)]
            return r.handle_request.remote(self._method, args, kwargs,
                                           context)

        ref = replica.handle_request.remote(self._method, args, kwargs,
                                            context)
        return DeploymentResponse(ref, done, retry)
