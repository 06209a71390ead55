"""Head-process runtime: object directory, scheduler, worker pool, actors.

This module is the TPU-build's merged equivalent of three reference
components, collapsed into the driver process because a TPU host runs one
framework instance per node and cross-node control travels over the same
socket fabric either way:

  - GCS (global control plane): node/actor/PG/job tables, named actors —
    reference: src/ray/gcs/gcs_server/gcs_server.h:91, gcs_actor_manager.h:352,
    gcs_placement_group_mgr.h:232.
  - Raylet (per-node scheduler + worker pool): lease/dispatch of tasks onto
    workers, dependency management, resource accounting — reference:
    src/ray/raylet/node_manager.h:124, local_task_manager.h:60,
    scheduling/cluster_task_manager.h:44, worker_pool.h:283.
  - Core-worker ownership bookkeeping: object directory with lineage for
    reconstruction — reference: src/ray/core_worker/task_manager.h:175,
    reference_count.h:73, object_recovery_manager.h:43.

Transport: `multiprocessing.connection` unix sockets (control plane) +
the node-shared mmap object store (data plane, core/object_store.py).
Scheduling policy is hybrid pack-then-spread like the reference's
HybridSchedulingPolicy (scheduling/policy/hybrid_scheduling_policy.h:50):
prefer the head/local node until utilization passes a threshold, then pick
the least-utilized feasible node; SPREAD strategy round-robins.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import threading
import time
import traceback
from collections import OrderedDict, deque
from multiprocessing.connection import Connection, Listener
from typing import Any, Optional

import cloudpickle

from .. import exceptions as exc
from . import flight
from . import stacks
from .directory import DirectoryService
from .ids import ActorID, JobID, NodeID, ObjectID, PlacementGroupID, TaskID
from .object_store import GetTimeoutError as StoreTimeout
from .object_store import ObjectStoreFullError, SharedObjectStore, SpillStore
from .ref import ObjectRef
from .protocol import (PROTOCOL_VERSION, ProtocolMismatchError,
                       check_peer_version)
from .task_spec import ActorSpec, TaskSpec

# directory states
PENDING, READY, FAILED, SPILLED = 0, 1, 2, 3

_runtime: Optional["Runtime"] = None
_runtime_lock = threading.Lock()


def get_runtime_if_exists() -> Optional["Runtime"]:
    return _runtime


def set_runtime(rt) -> None:
    global _runtime
    _runtime = rt


class NodeInfo:
    def __init__(self, node_id: NodeID, resources: dict[str, float],
                 labels: dict[str, str] | None = None, name: str = ""):
        self.node_id = node_id
        self.resources_total = dict(resources)
        self.resources_avail = dict(resources)
        self.labels = labels or {}
        self.name = name
        self.alive = True
        self.workers: set[str] = set()
        # set for agent-backed nodes (a node_agent process joined over TCP);
        # worker spawn/kill on this node routes through the agent
        self.agent: Optional["_AgentHandle"] = None
        # cross-node data plane (object_transfer.py): the node's data-server
        # address, and whether it runs its OWN store (no shared /dev/shm —
        # objects move via fetch, RPC replies via the control conn)
        self.data_addr: Optional[str] = None
        self.own_store = False
        # allow one worker per CPU plus headroom for zero-cpu tasks
        self.max_workers = int(resources.get("CPU", 1)) + 4
        # chip ids of this host not yet owned by a TPU worker (a chip
        # belongs to one process): _spawn_worker_locked hands them out,
        # the worker's death returns them
        self.free_chips = list(range(int(resources.get("TPU", 0))))

    def utilization(self) -> float:
        tot = self.resources_total.get("CPU", 0)
        if tot <= 0:
            return 1.0
        return 1.0 - self.resources_avail.get("CPU", 0) / tot


class WorkerInfo:
    def __init__(self, wid: str, node_id: NodeID, proc, tpu: bool,
                 chips: tuple = ()):
        self.wid = wid
        self.node_id = node_id
        self.proc = proc
        self.tpu = tpu
        self.chips = chips               # host chip ids this process owns
        self.conn: Optional[Connection] = None
        self.send_lock = threading.Lock()
        self.state = "starting"          # starting|idle|busy|actor|dead
        self.current: Optional[TaskSpec] = None
        # pipelined (spec, nonce) already SENT to the worker behind
        # `current` (reference analog: lease reuse / owned-task pipelining
        # on the direct task transport). The worker's single-thread
        # executor runs them FIFO; the head promotes on each done message.
        # Steals name the per-dispatch nonce, not the task id, so a stale
        # steal can never skip a later re-dispatch of the same task.
        self.queued: deque = deque()
        self.send_seq = 0
        self.funcs: set[str] = set()
        # runtime-env dedication: a worker that applied env E only runs
        # env-E work (reference worker_pool.h matching semantics)
        self.env_hash: Optional[str] = None
        self.actor_id: Optional[ActorID] = None
        self.holding: dict[str, float] = {}   # node resources acquired
        self.holding_bundle: tuple | None = None  # (pg_id, idx, res)
        self.blocked = False

    def send(self, msg) -> bool:
        c = self.conn
        if c is None or self.state == "dead":
            return False
        try:
            with self.send_lock:
                c.send(msg)
            return True
        except (OSError, ValueError, BrokenPipeError):
            return False


def host_ip() -> str:
    """Best-effort externally-dialable IP of this host (connected-UDP-socket
    trick; gethostbyname(hostname) commonly resolves to loopback)."""
    import socket
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("10.255.255.255", 1))  # no packets sent
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"


def build_worker_env(*, store_path: str, head_addr: str, head_family: str,
                     authkey_hex: str, wid: str, node_id_hex: str,
                     tpu: bool, spill_dir: str = "",
                     own_store: bool = False,
                     chips: tuple = ()) -> dict:
    """Environment for a `python -m ray_tpu.core.worker` process — the ONE
    definition shared by the head's local pool and node agents, so worker
    behavior cannot drift by host."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in sys.path if p] + [env.get("PYTHONPATH", "")])
    if not tpu:
        # a worker without a TPU resource must never take the chip from
        # the one that was granted it (one process per chip)
        env["JAX_PLATFORMS"] = "cpu"
    elif chips:
        # a share of the host's chips: libtpu's recipe for several
        # processes on one host, each seeing only its own devices
        # (jax.devices() is then this worker's grant, nothing else)
        env["TPU_VISIBLE_CHIPS"] = ",".join(map(str, chips))
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = f"1,{len(chips)},1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    from ..util.compile_cache import CACHE_ENV, compile_cache_dir
    env[CACHE_ENV] = compile_cache_dir()
    # programmatic cfg.override()s made in the driver ship as RTPU_* env
    # to workers SPAWNED AFTER the override (the reference ships RAY_*
    # system config the same way). Already-running workers keep their
    # values — protocols that cross processes must compose with mixed
    # settings (e.g. collective payloads declare inline vs store-backed
    # per contribution)
    from .config import cfg as _cfg
    for name, val in _cfg.overrides_for_env().items():
        env[name] = val
    env["RTPU_STORE_PATH"] = store_path
    if spill_dir:
        env["RTPU_SPILL_DIR"] = spill_dir
    if own_store:
        # node-local store: object misses resolve via locate+fetch and RPC
        # replies arrive over the control conn (object_transfer.py)
        env["RTPU_OWN_STORE"] = "1"
    env["RTPU_HEAD_ADDR"] = head_addr
    if head_family != "AF_UNIX":
        env["RTPU_HEAD_FAMILY"] = head_family
    env["RTPU_AUTHKEY"] = authkey_hex
    env["RTPU_WORKER_ID"] = wid
    env["RTPU_NODE_ID"] = node_id_hex
    return env


class _AgentHandle:
    """Head-side handle on a node_agent control connection (the raylet-client
    analog, reference: raylet_client/raylet_client.h — here the head asks the
    agent to fork/kill workers instead of leasing from a local pool)."""

    def __init__(self, conn: Connection):
        self.conn = conn
        self.send_lock = threading.Lock()

    def send(self, msg) -> bool:
        try:
            with self.send_lock:
                self.conn.send(msg)
            return True
        except (OSError, ValueError, BrokenPipeError):
            return False


class _RemoteProc:
    """Process handle for a worker living under a node agent: mirrors the
    subprocess.Popen surface the runtime uses (pid/kill/terminate/wait/poll),
    delegating kills to the agent and completing on agent exit reports."""

    def __init__(self, agent: _AgentHandle, wid: str):
        self._agent = agent
        self._wid = wid
        self.pid: int | None = None
        self.returncode: int | None = None
        self._exited = threading.Event()

    def kill(self):
        self._agent.send({"t": "kill_worker", "wid": self._wid})

    terminate = kill

    def wait(self, timeout: float | None = None):
        if not self._exited.wait(timeout):
            raise subprocess.TimeoutExpired(f"agent-worker {self._wid}",
                                            timeout)
        return self.returncode

    def poll(self):
        return self.returncode

    def mark_exited(self, rc: int | None):
        self.returncode = rc if rc is not None else -1
        self._exited.set()


class _ExternalProc:
    """Proc shim for driver clients: the head supervises but never owns the
    process — kill/wait are no-ops beyond state tracking."""

    def __init__(self, pid: int):
        self.pid = pid

    def kill(self):
        pass

    def wait(self, timeout: float | None = None):
        return 0

    def poll(self):
        return None


class DirEntry:
    # `locations` (node-id hexes known to hold a copy) stays None on
    # single-host clusters (object_transfer.py data plane)
    __slots__ = ("state", "lineage", "error_brief", "locations")

    def __init__(self, state=PENDING, lineage: TaskSpec | None = None):
        self.state = state
        self.lineage = lineage
        self.error_brief: str | None = None
        self.locations: set[str] | None = None

    def add_location(self, node_hex: str) -> None:
        if self.locations is None:
            self.locations = set()
        self.locations.add(node_hex)


class ActorInfo:
    def __init__(self, spec: ActorSpec):
        self.spec = spec
        self.state = "pending"           # pending|alive|restarting|dead
        self.wid: Optional[str] = None
        self.restarts_left = spec.max_restarts
        self.queue: deque[TaskSpec] = deque()
        self.running: dict[TaskID, TaskSpec] = {}
        self.seq = 0
        self.death_cause: Optional[str] = None


class BundleState:
    def __init__(self, index: int, resources: dict[str, float]):
        self.index = index
        self.resources = dict(resources)
        self.avail = dict(resources)
        self.node_id: Optional[NodeID] = None


class PlacementGroupState:
    def __init__(self, pg_id: PlacementGroupID, bundles: list[dict[str, float]],
                 strategy: str, name: str = "",
                 same_label: str | None = None,
                 bundle_selectors: list[dict | None] | None = None):
        self.pg_id = pg_id
        self.bundles = [BundleState(i, b) for i, b in enumerate(bundles)]
        self.strategy = strategy
        self.name = name
        # same_label: every bundle must land on nodes sharing ONE value of
        # this node-label key — how whole TPU slices (ICI domains) are
        # gang-reserved (reference encodes this as TPU-{pod}-head resources,
        # _private/accelerators/tpu.py:110).
        self.same_label = same_label
        # per-bundle exact-match node label requirements (or None)
        self.bundle_selectors = list(bundle_selectors or [])
        self.state = "pending"           # pending|created|removed
        self.ready_event = threading.Event()


def _placement_key(spec) -> tuple:
    """Everything node selection + worker acquisition depend on. Two specs
    with equal keys place identically against identical cluster state."""
    from .runtime_env import env_hash
    sel = getattr(spec, "label_selector", None)
    return (tuple(sorted(spec.resources.items())), spec.pg_id,
            spec.pg_bundle_index, spec.node_affinity,
            spec.node_affinity_soft, spec.scheduling_strategy,
            tuple(sorted(sel.items())) if sel else None,
            env_hash(spec.runtime_env))


class _PendingQueues:
    """Pending tasks bucketed by placement signature (reference analog:
    the cluster task manager's per-shape dispatch queues,
    cluster_task_manager.h:72). A scheduling pass probes one head per
    bucket instead of rescanning every pending task, so a burst of N
    same-shape submissions costs O(N) total scheduling work, not O(N^2).
    Iteration order is bucket insertion order (FIFO within a bucket)."""

    __slots__ = ("buckets",)

    def __init__(self):
        self.buckets: dict[tuple, deque] = {}

    def append(self, spec) -> None:
        self.buckets.setdefault(_placement_key(spec),
                                deque()).append(spec)

    def remove(self, spec) -> None:
        key = _placement_key(spec)
        dq = self.buckets.get(key)
        if dq is None:
            raise ValueError(f"{spec!r} not pending")
        dq.remove(spec)  # raises ValueError if absent, like deque
        if not dq:
            del self.buckets[key]

    def __len__(self) -> int:
        return sum(len(dq) for dq in self.buckets.values())

    def __bool__(self) -> bool:
        return any(self.buckets.values())

    def __iter__(self):
        for dq in list(self.buckets.values()):
            yield from list(dq)


class Runtime:
    """The head runtime. Exactly one per driver process."""

    def __init__(self, resources: dict[str, float],
                 object_store_memory: int | None = None,
                 session_dir: str | None = None,
                 head_labels: dict[str, str] | None = None,
                 enable_remote_nodes: bool = False,
                 log_to_driver: bool = True):
        from .config import cfg
        if object_store_memory is None:
            object_store_memory = cfg.object_store_memory
        self.job_id = JobID.from_random()
        sid = self.job_id.hex()[:8]
        self.session_dir = session_dir or f"/tmp/ray_tpu/session_{sid}"
        os.makedirs(self.session_dir, exist_ok=True)
        self.store_path = f"/dev/shm/ray_tpu_{sid}"
        self.store = SharedObjectStore(
            self.store_path, capacity=object_store_memory, create=True)
        self.spill = SpillStore(os.path.join(self.session_dir, "spill"))

        self.lock = threading.RLock()
        self.cv = threading.Condition(self.lock)

        # graftlint GL001 enforces the annotations: every touch of these
        # outside `with self.lock` (or a *_locked method) is a finding
        self.directory: dict[ObjectID, DirEntry] = {}  # guarded by: self.lock
        # distributed refcounting (reference_count.h:73 analog):
        # which processes hold >=1 live ObjectRef, serialized-copy pins
        # (may go negative when a receiver's add outruns the sender's pin —
        # per-connection FIFO makes that transient), driver-local counts,
        # and driver-side store pins from ray.put
        self.interest: dict[ObjectID, set[str]] = {}  # guarded by: self.lock
        self.xfer_pins: dict[ObjectID, int] = {}  # guarded by: self.lock
        # standing programmatic demand floor (autoscaler/sdk.py
        # request_resources); the autoscaler plans these every tick
        self.resource_requests: list[dict] = []
        self._local_refs: dict[ObjectID, int] = {}  # guarded by: self.lock
        self._pinned: set[ObjectID] = set()  # guarded by: self.lock
        # containment edges: outer stored object -> refs pickled inside it
        # (the outer holds interest in its inners until the outer is freed)
        self.contained: dict[ObjectID, list[ObjectID]] = {}  # guarded by: self.lock
        self.func_registry: dict[str, bytes] = {}
        # runtime-env blobs (working_dir / py_modules zips), hash-addressed
        # (reference analog: the GCS KV store runtime-env uploads)
        self.renv_registry: dict[str, bytes] = {}
        self.nodes: dict[NodeID, NodeInfo] = {}
        self.workers: dict[str, WorkerInfo] = {}  # guarded by: self.lock
        self.actors: dict[ActorID, ActorInfo] = {}
        self.named_actors: dict[str, ActorID] = {}
        # dead actors' ready oids that died UNOBSERVED (no ref held, so
        # no error object was stored — storing one per dead actor leaks
        # forever); a late __ray_ready__ ref materializes the error from
        # here. guarded by: self.lock
        self._ready_failed: dict[ObjectID, str] = {}
        # ALIVE actors' ready oids (init completed): nothing is sealed
        # under a ready oid up front — one object per actor that nobody
        # reads would leak — so a __ray_ready__ ref materializes the
        # "ok" payload lazily at ref-add, and its refcount frees it.
        # guarded by: self.lock
        self._ready_ok: set[ObjectID] = set()
        self.pgs: dict[PlacementGroupID, PlacementGroupState] = {}
        self.pending = _PendingQueues()  # guarded by: self.lock
        self._sweeping_failed_deps = False
        self._abandoned_rpcs: set[ObjectID] = set()
        # timeline events, bounded so a long-lived driver doesn't grow
        # without limit
        self.events: deque[dict] = deque(maxlen=cfg.timeline_events_max)
        # per-task state records for the state API (reference analog: the
        # GCS task-event store, gcs_task_manager.h:94); bounded FIFO
        self.task_records: "OrderedDict" = OrderedDict()
        self.task_records_max = cfg.task_records_max
        # optional task-event export stream (reference: the export-events
        # schemas + task-event files the dashboard consumes)
        self._event_file = None
        if cfg.event_export_enabled:
            self._event_file = open(
                os.path.join(self.session_dir, "events.jsonl"), "a",
                buffering=1)
        self.counters = {"tasks_submitted": 0, "tasks_finished": 0,
                         "tasks_failed": 0, "tasks_retried": 0,
                         "actors_created": 0}
        self._shutdown = False
        self._worker_seq = 0
        self._spread_rr = 0
        # open per-worker message batch for the current scheduling pass
        # (see _schedule_locked); None outside a pass
        self._send_buf: dict | None = None
        # deferred-scheduling state (control-plane fast path): while
        # _defer_sched > 0, _schedule_locked only records that a pass is
        # wanted — a client batch frame or a submit burst then pays ONE
        # pass (and one batched frame per worker) instead of one per
        # message. _sched_evt wakes the scheduler pump for deferred
        # in-process submits.
        self._defer_sched = 0
        self._sched_wanted = False
        self._last_submit_ts = 0.0
        self._burst_window = (cfg.submit_burst_window_us / 1e6
                              if cfg.control_batching else 0.0)
        # in-process driver submit fast path (the v2 "submit carries the
        # submitter's interest" protocol applied to the LOCAL driver):
        # .remote() appends the spec here and marks its return oids
        # presumed; the scheduler pump registers interest and admits a
        # whole burst under ONE lock acquisition + ONE scheduling pass,
        # mirroring _handle_batch for remote clients. The driver thread
        # itself never touches the runtime lock on the submit hot path.
        self._submit_q: deque = deque()
        self._submitq_on = bool(cfg.driver_submit_queue)
        # live driver-ref counts for oids whose spec is still queued;
        # migrated into _local_refs when the pump admits the spec
        self._presumed: dict[ObjectID, int] = {}  # guarded by: self._presumed_lock
        # oids whose every presumed ref died before the pump saw the
        # spec: the pump must NOT register driver interest for them
        self._dropped_early: set[ObjectID] = set()  # guarded by: self._presumed_lock
        self._presumed_lock = threading.Lock()
        # serializes queue drains so specs admit in FIFO order even when
        # cancel() drains concurrently with the pump
        self._submitq_drain_lock = threading.Lock()
        # flight-recorder cluster collection: nonce -> {"snap"}
        # answered by the flight_ring handler as worker replies land
        self._flight_pulls: dict[bytes, dict] = {}
        self._flight_evt = threading.Event()
        # live-stack cluster collection (stall doctor, core/stacks.py):
        # same nonce protocol over the new stack_dump/stack_reply frames
        self._stack_pulls: dict[bytes, dict] = {}
        self._stack_evt = threading.Event()
        # stuck-task watchdog: per-task-name runtime EWMAs (updated on
        # every successful done) + scan/flag health counters; cycle keys
        # already reported (one DEADLOCK flight event per incident, not
        # per hang_report poll)
        self._seen_cycles: set = set()  # guarded by: self.lock
        self._task_ewma: dict[str, float] = {}  # guarded by: self.lock
        self._watchdog = {"enabled": bool(cfg.stall_watchdog), "scans": 0,
                          "flagged_total": 0, "stuck_running": 0,
                          "last_scan": 0.0}
        # cluster-shared directory service (core/directory.py, protocol
        # v7): named hint maps behind dir_update/dir_query frames. NOT
        # self.directory — that is the object directory below.
        self.dirs = DirectoryService()
        # store-path rpc replies in flight per peer: a reply written to
        # the shared store has NO directory entry (the peer reads and
        # deletes it directly), so a peer killed between sending the
        # rpc and reading the reply would leak the object forever —
        # _on_worker_death reclaims these. Pruned lazily on write.
        self._rpc_reply_pins: dict[str, set] = {}  # guarded by: self.lock
        flight.set_proc_name("head")
        self._sched_evt = threading.Event()
        threading.Thread(target=self._sched_pump_loop, daemon=True,
                         name="rtpu-sched-pump").start()
        # merged user-defined metrics (util/metrics.py):
        # name -> {kind, desc, series: {tag-tuple: value}}
        self.user_metrics: dict[str, dict] = {}
        import concurrent.futures
        # worker->head rpc handlers (blocking calls like pg_wait run here)
        # 32 threads: pg_wait parks here for up to its full timeout, and a
        # gang of waiters must not starve cheap rpcs behind it
        self._rpc_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=cfg.rpc_pool_workers, thread_name_prefix="rtpu-rpc")
        import queue
        self._drop_q: "queue.SimpleQueue" = queue.SimpleQueue()
        threading.Thread(target=self._drop_loop, daemon=True,
                         name="rtpu-ref-drops").start()

        # head node
        self.head_node = NodeInfo(NodeID.from_random(), resources,
                                  head_labels, name="head")
        self.nodes[self.head_node.node_id] = self.head_node

        # control-plane listeners: AF_UNIX for local workers, TCP for node
        # agents / remote workers (reference analog: the gRPC services every
        # raylet/worker dials, rpc/grpc_server.h:88 — one authkeyed
        # connection-oriented channel here)
        addr = os.path.join(self.session_dir, "head.sock")
        # a stable cluster authkey (RTPU_CLUSTER_AUTHKEY hex) + fixed
        # cfg.head_tcp_port let agents and drivers re-dial a RESTARTED
        # head at the same address — the role Redis's fixed address plays
        # for reference GCS failover (redis_store_client.h:111)
        ak_env = os.environ.get("RTPU_CLUSTER_AUTHKEY")
        self._authkey = bytes.fromhex(ak_env) if ak_env else os.urandom(16)
        self.listener = Listener(addr, "AF_UNIX", authkey=self._authkey)
        self.listener_addr = addr
        # loopback unless the user opts into remote nodes: the channel is
        # authkey-HMAC-gated but carries pickles, so it must not face the
        # network by default
        self._tcp_host = "0.0.0.0" if enable_remote_nodes else "127.0.0.1"
        self.tcp_listener = Listener(
            (self._tcp_host, cfg.head_tcp_port), "AF_INET",
            authkey=self._authkey)
        self.tcp_port = self.tcp_listener.address[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, args=(self.listener,),
            daemon=True, name="rtpu-accept")
        self._accept_thread.start()
        self._tcp_accept_thread = threading.Thread(
            target=self._accept_loop, args=(self.tcp_listener,),
            daemon=True, name="rtpu-accept-tcp")
        self._tcp_accept_thread.start()

        # cluster file: everything a driver client / node agent / job needs
        # to dial this cluster (reference analog: the GCS address + redis
        # password a reference driver resolves from --address; here a
        # 0600 json since the authkey is a credential)
        from .job_manager import JobManager
        self.cluster_file = os.path.join(self.session_dir, "cluster.json")
        cf = {"unix_addr": addr, "tcp_host": self._tcp_host,
              "tcp_port": self.tcp_port, "authkey": self._authkey.hex(),
              "store_path": self.store_path, "spill_dir": self.spill.dir,
              "session_dir": self.session_dir, "pid": os.getpid()}
        fd = os.open(self.cluster_file,
                     os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
        with os.fdopen(fd, "w") as f:
            json.dump(cf, f)
        from .config import cfg as _cfg
        from .gcs_store import GcsStore, start_snapshot_loop
        from .pubsub import Publisher
        self.pubsub = Publisher()
        # durable metadata (redis_store_client.h analog): internal KV +
        # restorable head-state snapshots
        self.kv = GcsStore(os.path.join(self.session_dir, "gcs.sqlite"))
        self._snapshot_stop = None
        if _cfg.gcs_snapshot_period_s > 0:
            self._snapshot_stop = start_snapshot_loop(
                self, _cfg.gcs_snapshot_period_s)
        # OOM protection (memory_monitor.h:52 analog); runs only when the
        # refresh period is non-zero
        from .memory_monitor import MemoryMonitor
        self.memory_monitor = MemoryMonitor(self).start()
        self.jobs = JobManager(self.session_dir, self.cluster_file)
        self.jobs.on_status = lambda job_id, status: self.pubsub.publish(
            "jobs", {"job_id": job_id, "status": status})
        self._driver_seq = 0

        # worker stdout/stderr -> the driver console (reference:
        # log_to_driver / the log monitor tailing worker files)
        if log_to_driver:
            threading.Thread(target=self._log_tail_loop, daemon=True,
                             name="rtpu-logtail").start()

        # agent liveness: heartbeats guard against HUNG agents (conn EOF
        # already covers dead processes) — gcs_health_check_manager.h:45
        threading.Thread(target=self._health_check_loop, daemon=True,
                         name="rtpu-healthcheck").start()
        threading.Thread(target=self._pipeline_rebalance_loop, daemon=True,
                         name="rtpu-rebalance").start()
        threading.Thread(target=self._stall_watchdog_loop, daemon=True,
                         name="rtpu-stall-watchdog").start()
        # metrics plane (ray_tpu/obs): TSDB scraper + SLO engine. Rides
        # the merged user-metric store — no new wire frames; remote
        # drivers query it over metrics_history/slo_report/obs_signals
        # in _RPC_METHODS
        self.obs = None
        if cfg.tsdb_enable:
            from ..obs.scraper import MetricsScraper
            self.obs = MetricsScraper(self).start()

        # cross-node data plane: serve this node's store to pullers
        # (object_manager.h:119 Push/Pull analog; object_transfer.py)
        from .object_transfer import ObjectDataServer
        self.data_server = ObjectDataServer(
            self.store, self.spill,
            host=("0.0.0.0" if enable_remote_nodes else "127.0.0.1"))
        if enable_remote_nodes:
            self.head_node.data_addr = (
                f"{host_ip()}:{self.data_server.address.rsplit(':', 1)[1]}")
        else:
            self.head_node.data_addr = self.data_server.address

        # prestart the worker pool so first tasks don't pay process cold-start
        # (reference: worker_pool.h:283 PrestartWorkers / idle pool)
        with self.lock:
            n_prestart = min(int(resources.get("CPU", 1)),
                             cfg.worker_prestart)
            for _ in range(n_prestart):
                self._spawn_worker_locked(self.head_node)

    # ------------------------------------------------------------------ #
    # connection plumbing
    # ------------------------------------------------------------------ #

    def _log_tail_loop(self):
        """Follow head-pool worker logs, echoing new output with a
        (worker) prefix (reference: the log monitor pushing worker
        stdout/stderr to the driver). shutdown() runs one final scan so
        late prints aren't dropped."""
        self._logtail_state = ({}, {})  # offsets, partial-line carries
        while not self._shutdown:
            time.sleep(0.5)
            self._log_tail_scan()

    def _log_tail_scan(self):
        import glob
        offsets, carries = self._logtail_state
        for path in glob.glob(os.path.join(self.session_dir,
                                           "worker-*.log")):
            try:
                size = os.path.getsize(path)
                seen = offsets.get(path, 0)
                if size <= seen:
                    continue
                with open(path, "rb") as f:
                    f.seek(seen)
                    chunk = f.read(size - seen)
                offsets[path] = size
                # emit only COMPLETE lines: carry the trailing partial so
                # split lines / bisected UTF-8 chars are never printed
                data = carries.get(path, b"") + chunk
                head, nl, tail = data.rpartition(b"\n")
                carries[path] = tail
                if not nl:
                    continue
                wid = os.path.basename(path)[len("worker-"):-len(".log")]
                for line in head.decode(errors="replace").splitlines():
                    if line.strip():
                        print(f"({wid}) {line}", flush=True)
            except OSError:
                continue

    def _health_check_loop(self):
        from .config import cfg
        period = cfg.health_check_period_ms / 1000.0
        timeout = cfg.health_check_timeout_s
        if period <= 0:
            return
        while not self._shutdown:
            time.sleep(period)
            now = time.monotonic()
            with self.lock:
                stale = [n for n in self.nodes.values()
                         if n.agent is not None and n.alive
                         and getattr(n, "last_heartbeat", None) is not None
                         and now - n.last_heartbeat > timeout]
            self._reap_idle_workers()
            for n in stale:
                # declare the node dead DIRECTLY: closing the conn would
                # not wake the agent loop's blocked read (Linux read()
                # survives a concurrent close), so run the removal here —
                # the loop's eventual EOF cleanup double-calls remove_node,
                # which no-ops on a dead node
                with self.lock:
                    for wid in list(n.workers):
                        w = self.workers.get(wid)
                        if w is not None and isinstance(w.proc,
                                                        _RemoteProc):
                            w.proc.mark_exited(-1)
                try:
                    self.remove_node(n.node_id)
                except Exception:
                    pass  # agent-loop EOF path already removed it
                try:
                    n.agent.conn.close()
                except Exception:
                    pass  # already closed

    def _pipeline_rebalance_loop(self):
        """Periodic work-stealing fallback (own timer — NOT coupled to the
        health-check flag): the done->idle steal trigger misses the case
        where the last other-worker done fires before a pipeline gets
        stuck behind a slow task, or fires inside the 50ms slow gate —
        with no further events, nothing would ever steal the straggler."""
        from .config import cfg
        if cfg.worker_pipeline_depth <= 0:
            return
        while not self._shutdown:
            time.sleep(0.1)
            try:
                with self.lock:
                    if any(w.state == "idle"
                           for w in self.workers.values()):
                        self._rebalance_pipelines_locked()
            except Exception:
                pass  # never let bookkeeping kill the timer

    def _reap_idle_workers(self):
        """Idle workers beyond the prestart floor exit after
        worker_idle_timeout_s (worker_pool.h idle-eviction analog);
        runtime-env-dedicated workers reap the same way."""
        from .config import cfg
        timeout = cfg.worker_idle_timeout_s
        if timeout <= 0:
            return
        now = time.monotonic()
        with self.lock:
            head_id = self.head_node.node_id
            floor = min(int(self.head_node.resources_total.get("CPU", 1)),
                        cfg.worker_prestart)
            # head-pool scope only: agent nodes manage their own workers
            head_workers = [w for w in self.workers.values()
                            if w.node_id == head_id]
            idle = [w for w in head_workers
                    if w.state == "idle" and w.conn is not None
                    and now - getattr(w, "idle_since", now) > timeout]
            n_idle = sum(1 for w in head_workers if w.state == "idle")
            victims = idle[:max(0, n_idle - floor)]
            for w in victims:
                w.send({"t": "exit"})
                self._on_worker_death_locked_prep(w)

    def _accept_loop(self, listener):
        while not self._shutdown:
            try:
                conn = listener.accept()
            except (OSError, EOFError):
                return
            threading.Thread(target=self._recv_loop, args=(conn,),
                             daemon=True, name="rtpu-recv").start()

    @property
    def head_address(self) -> str:
        """TCP address a node agent dials
        (`ray_tpu.core.node_agent --head <this>`). With the default
        loopback bind this is only dialable from this host; pass
        init(enable_remote_nodes=True) for other hosts."""
        if self._tcp_host != "0.0.0.0":
            return f"{self._tcp_host}:{self.tcp_port}"
        return f"{host_ip()}:{self.tcp_port}"

    def _recv_loop(self, conn: Connection):
        wid = None
        try:
            msg = conn.recv()
            if msg.get("t") in ("register", "register_node",
                                "register_driver"):
                who = {"register": "worker",
                       "register_node": "node agent",
                       "register_driver": "driver client"}[msg["t"]]
                try:
                    check_peer_version(msg.get("pv"), who)
                except ProtocolMismatchError as e:
                    # structured refusal: agents/drivers raise it to the
                    # user from their registration-reply check
                    try:
                        conn.send({"t": "rejected", "error": str(e)})
                    except Exception:
                        pass  # peer hung up before reading the refusal
                    conn.close()
                    return
            if msg.get("t") == "register_node":
                self._agent_loop(conn, msg)
                return
            if msg.get("t") == "register_driver":
                # a driver client (reference analog: ray.init(address=...)
                # attaching a driver core worker to a running cluster /
                # the util/client proxy role). It speaks the full worker
                # protocol but never executes tasks: it lives outside every
                # node's worker pool so the scheduler cannot pick it.
                with self.lock:
                    self._driver_seq += 1
                    wid = f"driver-{self._driver_seq:04d}"
                    w = WorkerInfo(wid, self.head_node.node_id,
                                   _ExternalProc(int(msg.get("pid", 0))),
                                   tpu=False)
                    w.state = "driver"
                    w.conn = conn
                    self.workers[wid] = w
                with w.send_lock:
                    # session_dir + resumed_from let a reconnecting driver
                    # verify this head is ITS cluster (same session, or a
                    # restart resumed from its session) before attaching —
                    # auto-resolve must never hijack onto an unrelated
                    # local cluster (client.py _reconnect)
                    conn.send({"t": "registered_driver", "wid": wid,
                               "store_path": self.store_path,
                               "spill_dir": self.spill.dir,
                               "job_id": self.job_id.hex(),
                               "session_dir": self.session_dir,
                               "resumed_from": getattr(
                                   self, "resumed_from", None),
                               "pv": PROTOCOL_VERSION})
                while True:
                    m = conn.recv()
                    try:
                        self._handle_msg(wid, m)
                    except Exception:
                        traceback.print_exc()
            if msg.get("t") != "register":
                conn.close()
                return
            wid = msg["wid"]
            with self.lock:
                w = self.workers.get(wid)
                if w is None or w.state == "dead":
                    conn.close()
                    return
                w.conn = conn
                pending_spec = getattr(w, "pending_spec", None)
                pending_actor = getattr(w, "pending_actor", None)
                if pending_spec is not None:
                    w.pending_spec = None
                    self._dispatch_locked(w, pending_spec)
                elif pending_actor is not None:
                    w.pending_actor = None
                    self._dispatch_actor_locked(w, pending_actor)
                elif w.state == "starting":
                    w.state = "idle"
                self._schedule_locked()
            while True:
                msg = conn.recv()
                try:
                    self._handle_msg(wid, msg)
                except Exception:
                    # a bad application-level request must not tear down a
                    # healthy worker's control connection
                    traceback.print_exc()
        except (EOFError, OSError):
            pass
        except Exception:
            traceback.print_exc()
        finally:
            if wid is not None:
                self._on_worker_death(wid)

    def _sched_pump_loop(self):
        """Admits queued driver submits (one lock hold + one scheduling
        pass per accumulated batch — see _drain_submit_q) and runs the
        scheduling passes that deferred burst submissions request; a
        burst's per-worker dispatches coalesce into one frame each."""
        while True:
            self._sched_evt.wait()
            self._sched_evt.clear()
            if self._shutdown:
                return
            try:
                self._drain_submit_q()
                with self.lock:
                    self._schedule_locked()
            except Exception:
                if self._shutdown:
                    return
                traceback.print_exc()

    def _handle_batch(self, wid: str, msgs: list):
        """A client batch frame: one scheduler-lock acquisition serves
        every contained message (in order), and all the scheduling passes
        they request collapse into ONE at the end — whose per-worker task
        dispatches ride one batched frame each (_send_buf). A bad message
        must not poison the rest, same contract as the recv loop."""
        flight.evt(flight.BATCH_RECV, len(msgs))
        with self.lock:
            opened = self._send_buf is None
            if opened:
                self._send_buf = {}
            self._defer_sched += 1
            try:
                for m in msgs:
                    try:
                        self._handle_msg(wid, m)
                    except Exception:
                        traceback.print_exc()
            finally:
                self._defer_sched -= 1
                try:
                    if self._sched_wanted and not self._defer_sched:
                        self._sched_wanted = False
                        self._schedule_locked()  # rides the open send buf
                finally:
                    # restore + flush even if the pass raises: leaking an
                    # open _send_buf would silently black-hole every
                    # future worker dispatch
                    if opened:
                        buf, self._send_buf = self._send_buf, None
                        self._flush_wsend_buf(buf)

    def _handle_msg(self, wid: str, msg: dict):
        t = msg["t"]
        if t == "batch":
            self._handle_batch(wid, msg["msgs"])
        elif t == "done":
            if "span" in msg:
                self.record_trace_span(msg["span"])
            self._on_task_done(wid, msg)
        elif t == "trace_span":
            self.record_trace_span(msg["span"])
        elif t == "flight_ring":
            # A worker's answer to flight_pull. The monotonic-clock
            # offset is estimated through the WALL clock as a bridge:
            # the snapshot samples (mono, wall) together, we sample our
            # own pair at receipt, and offset = (their mono - their
            # wall) - (our mono - our wall). Unlike the request/reply
            # midpoint this is immune to transport latency (an 8ms
            # queueing delay on a loaded box would otherwise skew the
            # midpoint by 4ms and reorder same-host seal->wake edges);
            # it is exact whenever wall clocks agree — always on one
            # host, NTP-close across hosts. Sub-millisecond residue is
            # clamped to zero so shared-clock processes stitch exactly.
            rec = self._flight_pulls.get(msg["nonce"])
            if rec is not None:
                snap = msg["snap"]
                mono, wall = time.monotonic_ns(), time.time_ns()
                off = ((snap.get("mono_ns", 0) - snap.get("wall_ns", 0))
                       - (mono - wall))
                if abs(off) < 1_000_000:
                    off = 0
                snap["offset_ns"] = off
                rec["snap"] = snap
                self._flight_evt.set()
        elif t == "stack_reply":
            # a worker's/driver's answer to stack_dump (stall doctor);
            # wait-beacon durations are already relative in the snapshot,
            # so no clock stitching is needed here
            rec = self._stack_pulls.get(msg["nonce"])
            if rec is not None:
                rec["snap"] = msg["snap"]
                self._stack_evt.set()
        elif t == "actor_ready":
            self._on_actor_ready(wid, msg)
        elif t == "submit":
            with self.lock:
                # v2 protocol: the submit itself registers the submitter's
                # interest in every return (the client sends no per-task
                # ref_add — half the client writes on a burst). Interest
                # lands BEFORE the task can run, same guarantee as before.
                for oid in msg["spec"].return_ids:
                    self._ref_add_locked(oid, wid, False)
                self._submit_locked(msg["spec"])
        elif t == "func_def":
            with self.lock:
                self.func_registry.setdefault(msg["fid"], msg["blob"])
        elif t == "renv_def":
            with self.lock:
                self.renv_registry.setdefault(msg["hash"], msg["blob"])
        elif t == "put":
            with self.lock:
                e = self.directory[msg["oid"]] = DirEntry(READY)
                w = self.workers.get(wid)
                loc = self._own_store_loc_locked(w)
                if loc is not None:
                    e.add_location(loc)
        elif t == "object_copied":
            # a puller holds a copy now (object_transfer): free fanout and
            # locate() must know (reference: object-directory location add)
            with self.lock:
                e = self.directory.get(ObjectID(msg["oid"]))
                w = self.workers.get(wid)
                loc = self._own_store_loc_locked(w)
                if e is not None and loc is not None:
                    e.add_location(loc)
        elif t == "put_spilled":
            with self.lock:
                oid = ObjectID(msg["oid"])
                e = self.directory.get(oid)
                if e is None:
                    e = self.directory[oid] = DirEntry(SPILLED)
                else:
                    e.state = SPILLED  # keep lineage for later recovery
                loc = self._own_store_loc_locked(self.workers.get(wid))
                if loc is not None:
                    e.add_location(loc)  # spill lives on that node's disk
        elif t == "contained":
            with self.lock:
                self._register_contained_locked(
                    ObjectID(msg["oid"]),
                    [ObjectID(b) for b in msg["inner"]])
        elif t == "ref_add":
            with self.lock:
                self._ref_add_locked(ObjectID(msg["oid"]), wid,
                                     msg.get("transfer", False))
        elif t == "ref_drop":
            with self.lock:
                self._ref_drop_locked(ObjectID(msg["oid"]), wid)
        elif t == "ref_drops":
            # batched 1->0 drops from a client's drop thread: one lock
            # acquire + one message for a burst of dying refs
            with self.lock:
                for ob in msg["oids"]:
                    self._ref_drop_locked(ObjectID(ob), wid)
        elif t == "ref_xfer":
            with self.lock:
                oid = ObjectID(msg["oid"])
                self.xfer_pins[oid] = self.xfer_pins.get(oid, 0) + 1
        elif t == "create_actor":
            with self.lock:
                self._create_actor_locked(msg["spec"])
        elif t == "actor_call":
            with self.lock:
                # v2: actor_call implies submitter interest (see "submit");
                # route directly rather than via submit_actor_task_spec so
                # no head-side ObjectRefs are minted just to be GC'd
                for oid in msg["spec"].return_ids:
                    self._ref_add_locked(oid, wid, False)
                self._submit_actor_task_locked(msg["spec"])
        elif t == "kill_actor":
            self.kill_actor(ActorID(msg["actor_id"]), msg.get("no_restart", True))
        elif t == "ensure":
            with self.lock:
                for ob in msg["oids"]:
                    self._ensure_available_locked(ObjectID(ob))
                self._schedule_locked()
        elif t == "user_metrics":
            self.merge_user_metrics(msg["rows"])
        elif t == "blocked":
            with self.lock:
                w = self.workers.get(wid)
                # zero-resource tasks hold nothing but must STILL mark
                # blocked: the flag is what excludes this worker from
                # pipelining and what triggers the queue steal — without
                # it a zero-cpu task waiting on work queued behind itself
                # deadlocks (release/reacquire are no-ops on {} holdings)
                if w and not w.blocked:
                    w.blocked = True
                    # a blocked task may be waiting on work queued behind
                    # it — steal the pipeline back before releasing
                    self._steal_queued_locked(w)
                    self._release_to_node(w)
                    self._schedule_locked()
        elif t == "unblocked":
            with self.lock:
                w = self.workers.get(wid)
                if w and w.blocked:
                    w.blocked = False
                    self._reacquire_from_node(w)
        elif t == "cancel":
            self.cancel(ObjectRef(ObjectID(msg["oid"])),
                        force=msg.get("force", False))
        elif t == "device_fetch":
            # device-object payload request (experimental/device_objects):
            # route to the owner process; serving may serialize a large
            # array, so keep it off this recv loop
            self._rpc_pool.submit(self.device_fetch, msg["owner"],
                                  msg["key"], msg["reply_oid"], wid)
        elif t == "device_payload":
            # owner's answer to a device_fetch: deliver to the requester
            self._deliver_payload(msg.get("requester", "driver"),
                                  msg["reply_oid"], msg["payload"])
        elif t == "rpc":
            # Handled off-thread: rpcs like pg_wait block, and this recv loop
            # must keep draining the worker's other messages. A shared pool
            # replaces the former thread-per-rpc spawn (hot-path cost).
            self._rpc_pool.submit(self._handle_worker_rpc, msg, wid)
        elif t == "dir_update":
            # shared-directory merge (core/directory.py): cheap dict ops,
            # handled inline; publishes are owner-stamped with the sending
            # connection so _on_worker_death can sweep them
            self.dirs.merge(msg["d"], msg.get("put"), msg.get("drop"),
                            owner=wid)
        elif t == "dir_query":
            # answered INLINE on this recv thread (not the rpc pool): a
            # pure dict read under the directory's own short lock, and
            # admission-time prefix lookups sit on the serve hot path
            try:
                payload = ("ok", self.dirs.lookup(msg["d"],
                                                  msg.get("keys")))
            except Exception as e:  # noqa: BLE001 — reply with any failure
                payload = ("err", e)
            self._reply_rpc(wid, ObjectID(msg["reply_oid"]), payload)
        elif t == "rpc_abandon":
            # Worker timed out waiting for a reply. Mark abandoned FIRST,
            # then reclaim if already written — this order closes the race
            # with the rpc thread's put-then-check (one side always sees the
            # other's write).
            oid = ObjectID(msg["reply_oid"])
            with self.lock:
                self._abandoned_rpcs.add(oid)
            if self.store.contains(oid):
                with self.lock:
                    self._abandoned_rpcs.discard(oid)
                self.store.delete(oid)

    def _agent_loop(self, conn: Connection, msg: dict):
        """Serve one node agent for its lifetime (reference analog: the
        node-membership half of GcsNodeManager, gcs_node_manager.h:49 —
        register on connect, dead on disconnect)."""
        agent = _AgentHandle(conn)
        node = NodeInfo(NodeID.from_random(), msg["resources"],
                        msg.get("labels"), name=msg.get("name", "agent"))
        node.agent = agent
        node.data_addr = msg.get("data_addr")
        node.own_store = bool(msg.get("own_store"))
        # reply BEFORE the node becomes schedulable: otherwise a pending
        # task could push a spawn_worker ahead of this reply and the agent's
        # registration recv would read the wrong message. The agent already
        # holds the authkey (it authenticated with it) — never echo it.
        agent.send({"t": "registered", "node_id": node.node_id.hex(),
                    "store_path": self.store_path,
                    "spill_dir": self.spill.dir,
                    "tcp_port": self.tcp_port, "pv": PROTOCOL_VERSION})
        with self.lock:
            self.nodes[node.node_id] = node
            self._retry_pending_pgs_locked()
            self._schedule_locked()
        self.pubsub.publish("nodes", {"node_id": node.node_id.hex(),
                                      "event": "added", "name": node.name})
        node.last_heartbeat = time.monotonic()
        try:
            while True:
                m = conn.recv()
                t = m.get("t")
                if t == "heartbeat":
                    node.last_heartbeat = time.monotonic()
                elif t == "worker_spawned":
                    with self.lock:
                        w = self.workers.get(m["wid"])
                        if w is not None and isinstance(w.proc, _RemoteProc):
                            w.proc.pid = m["pid"]
                elif t == "worker_exit":
                    with self.lock:
                        w = self.workers.get(m["wid"])
                        if w is not None and isinstance(w.proc,
                                                        _RemoteProc):
                            w.proc.mark_exited(m.get("rc"))
                    self._on_worker_death(m["wid"])
                elif t == "deregister":
                    break
        except (EOFError, OSError):
            pass
        finally:
            try:
                conn.close()
            except Exception:
                pass  # already closed
            # complete every orphaned remote proc first so remove_node's
            # per-worker proc.wait() returns immediately instead of timing
            # out sequentially
            with self.lock:
                for wid in list(node.workers):
                    w = self.workers.get(wid)
                    if w is not None and isinstance(w.proc, _RemoteProc):
                        w.proc.mark_exited(-1)
            try:
                self.remove_node(node.node_id)
            except Exception:
                pass  # double remove_node is a benign no-op

    # Worker→head request/reply: the reply value is written into the shared
    # store at a worker-chosen oid (reference analog: the CoreWorkerService /
    # GCS RPCs workers issue for name resolution and cluster state,
    # gcs_client/accessor.h — here the shm store doubles as the reply channel).
    _RPC_METHODS = ("get_actor_by_name", "cluster_resources",
                    "available_resources", "node_table", "pg_wait",
                    "create_placement_group_rpc", "remove_placement_group_rpc",
                    "timeline", "flight_timeline", "flight_stats",
                    "stack_report", "hang_report",
                    "state_list", "state_summary",
                    "memory_summary", "autoscaler_status",
                    "user_metrics_dump", "pubsub_poll",
                    "metrics_history", "metrics_names", "slo_report",
                    "obs_signals", "cache_report",
                    "kv_put", "kv_get", "kv_del", "kv_keys", "locate",
                    "locate_many", "request_resources_rpc",
                    "job_submit", "job_list", "job_status", "job_logs",
                    "job_stop")

    def locate(self, oid_bytes: bytes) -> list[str]:
        """Data-server addresses of nodes holding the object (ownership
        object directory analog, ownership_object_directory.h). The head's
        own store/spill is always checked — errors and driver puts live
        there."""
        oid = ObjectID(oid_bytes)
        out = []
        with self.lock:
            e = self.directory.get(oid)
            locs = set(e.locations or ()) if e is not None else set()
            if self.store.contains(oid) or self.spill.contains(oid):
                locs.add(self.head_node.node_id.hex())
            for n in self.nodes.values():
                if n.alive and n.node_id.hex() in locs and n.data_addr:
                    out.append(n.data_addr)
        return out

    def request_resources_rpc(self, bundles: list[dict]) -> None:
        """Replace the standing programmatic demand floor
        (autoscaler/sdk.py request_resources from a remote driver)."""
        with self.lock:
            self.resource_requests = [dict(b) for b in bundles]

    def locate_many(self, oids: list[bytes]) -> list[bool]:
        """Settled-ness (a result exists anywhere — any store, spill, or
        live holder node — or the task terminally FAILED) for a batch of
        objects in ONE round-trip: the saturated max_pending_calls prune
        asks about every pending result at once (actor.py
        _admit_pending) instead of one locate RPC per ref. FAILED counts
        as settled — an errored call is not in flight (runtime.wait's
        'errors count as ready' rule). Store/spill probes (shm lookup +
        file stat each) run OUTSIDE the head lock, same reasoning as
        state.memory_summary."""
        undecided: list[tuple[int, ObjectID]] = []
        out = [False] * len(oids)
        with self.lock:
            alive = {n.node_id.hex() for n in self.nodes.values()
                     if n.alive}
            for i, ob in enumerate(oids):
                oid = ObjectID(ob)
                e = self.directory.get(oid)
                if e is not None and e.state == FAILED:
                    out[i] = True
                    continue
                locs = set(e.locations or ()) if e is not None else set()
                if locs & alive:
                    out[i] = True
                else:
                    undecided.append((i, oid))
        for i, oid in undecided:
            out[i] = self.store.contains(oid) or self.spill.contains(oid)
        return out

    # internal KV (gcs_kv_manager.h / ray.experimental.internal_kv analog);
    # user namespace is prefixed so snapshots can't be clobbered
    def kv_put(self, key: str, value: bytes) -> None:
        self.kv.put("user", key, value)

    def kv_get(self, key: str):
        return self.kv.get("user", key)

    def kv_del(self, key: str) -> bool:
        return self.kv.delete("user", key)

    def kv_keys(self) -> list[str]:
        return self.kv.keys("user")

    def _own_store_loc_locked(self, w) -> str | None:
        """Node hex for location tracking — ONLY own-store nodes:
        shared-store copies live in the head store the directory already
        checks directly, and recording them would make eviction look like
        a live remote copy (blocking lineage reconstruction)."""
        if w is None:
            return None
        n = self.nodes.get(w.node_id)
        if n is not None and n.own_store:
            return n.node_id.hex()
        return None

    def _deliver_payload(self, requester: str, reply_oid: bytes,
                         payload) -> None:
        """Hand an out-of-band reply to a requester: the head store for
        the driver and shared-store workers, the control conn for
        own-store workers (who cannot see the head store)."""
        if requester != "driver":
            with self.lock:
                w = self.workers.get(requester)
                n = self.nodes.get(w.node_id) if w is not None else None
            if n is not None and n.own_store:
                if w.send({"t": "rpc_reply", "reply_oid": reply_oid,
                           "payload": payload}):
                    return
        try:
            self.store.put(ObjectID(reply_oid), payload)
        except Exception:
            pass  # store full/closing: requester times out

    def device_fetch(self, owner: str, key: str, reply_oid: bytes,
                     requester: str = "driver") -> None:
        """Route a device-object fetch to its owner process
        (experimental/device_objects.py; RDT transfer-request analog).
        The payload travels owner -> head -> requester over the control
        conns, so it works across per-node stores."""
        if owner == "driver":
            from ..experimental.device_objects import _fetch_payload
            self._deliver_payload(requester, reply_oid, _fetch_payload(key))
            return
        with self.lock:
            w = self.workers.get(owner)
        if w is None or w.state == "dead" or not w.send(
                {"t": "device_get", "key": key, "reply_oid": reply_oid,
                 "requester": requester}):
            self._deliver_payload(requester, reply_oid,
                                  ("err", f"device-object owner {owner} "
                                          f"is gone"))

    def state_list(self, kind, limit=1000, filters=None):
        """State-API rows for workers/driver clients (util/state/api.py)."""
        from .. import state as state_api
        fn = getattr(state_api, f"list_{kind}", None)
        if fn is None:
            raise ValueError(f"unknown state kind {kind!r}")
        import inspect
        params = inspect.signature(fn).parameters
        kwargs = {}
        if "limit" in params:
            kwargs["limit"] = limit
        if "filters" in params and filters:
            kwargs["filters"] = filters
        return fn(**kwargs)

    def state_summary(self):
        from .. import state as state_api
        return state_api.summary()

    def memory_summary(self, limit: int = 1000):
        from .. import state as state_api
        return state_api.memory_summary(limit)

    def autoscaler_status(self):
        from .. import state as state_api
        return state_api.autoscaler_status()

    def pubsub_poll(self, channel, cursor=0, timeout_s=20.0):
        # runs on the rpc pool (long-poll parks a pool thread, like pg_wait)
        return self.pubsub.poll(channel, cursor, timeout_s)

    def _reply_via_conn(self, wid: str | None) -> bool:
        """Workers on own-store nodes can't see the head store; their RPC
        replies ride the control connection instead."""
        if wid is None:
            return False
        with self.lock:
            w = self.workers.get(wid)
            if w is None:
                return False
            n = self.nodes.get(w.node_id)
            return n is not None and n.own_store

    def _reply_rpc(self, wid: str | None, oid: ObjectID, payload) -> None:
        """Deliver one rpc-style reply: over the control connection for
        own-store peers, into the shared store otherwise (with the
        abandon-race reclaim). Shared by the rpc pool and the inline
        dir_query handler."""
        if self._reply_via_conn(wid):
            with self.lock:
                w = self.workers.get(wid)
            if w is not None:
                # outside the lock: w.send pickles + writes the pipe
                # under its own per-worker send_lock
                w.send({"t": "rpc_reply", "reply_oid": oid.binary(),
                        "payload": payload})
            return
        self.store.put(oid, payload)
        # No directory entry: the worker polls the store directly and deletes
        # the reply once read. If the worker already gave up, reclaim now.
        with self.lock:
            abandoned = oid in self._abandoned_rpcs
            self._abandoned_rpcs.discard(oid)
            if not abandoned and wid is not None:
                pend = self._rpc_reply_pins.setdefault(wid, set())
                # lazy prune: replies the peer already consumed (and
                # deleted) fall out here, keeping the set at the number
                # of genuinely in-flight replies
                pend.difference_update(
                    [o for o in pend if not self.store.contains(o)])
                pend.add(oid)
        if abandoned:
            self.store.delete(oid)

    def _handle_worker_rpc(self, msg: dict, wid: str | None = None):
        oid = ObjectID(msg["reply_oid"])
        try:
            m = msg["m"]
            if m not in self._RPC_METHODS:
                raise ValueError(f"unknown rpc {m!r}")
            result = getattr(self, m)(*msg.get("args", ()))
            self._reply_rpc(wid, oid, ("ok", result))
        except BaseException as e:  # noqa: BLE001 — reply with any failure
            try:
                self._reply_rpc(wid, oid, ("err", e))
            except BaseException:  # unpicklable exception/result
                self._reply_rpc(wid, oid, ("err", RuntimeError(
                    f"rpc {msg.get('m')} failed with unpicklable error: "
                    f"{type(e).__name__}: {e!r}")))

    # job-table RPCs (gcs_job_manager.h:52 / job_manager.py:60 analog)
    def job_submit(self, entrypoint, env=None, working_dir_zip=None,
                   metadata=None, job_id=None):
        return self.jobs.submit(entrypoint, env, working_dir_zip,
                                metadata, job_id)

    def job_list(self):
        return self.jobs.list()

    def job_status(self, job_id):
        return self.jobs.status(job_id)

    def job_logs(self, job_id, tail_bytes=1 << 20, offset=None):
        return self.jobs.logs(job_id, tail_bytes, offset)

    def job_stop(self, job_id):
        return self.jobs.stop(job_id)

    def create_placement_group_rpc(self, bundles, strategy, name="",
                                   same_label=None, bundle_selectors=None):
        pg = self.create_placement_group(
            bundles, strategy, name,
            same_label=same_label, bundle_selectors=bundle_selectors)
        return (pg.pg_id, [dict(b.resources) for b in pg.bundles])

    def remove_placement_group_rpc(self, pg_id):
        self.remove_placement_group(pg_id)
        return None

    def pg_wait(self, pg_id, timeout: float = 30.0) -> bool:
        with self.lock:
            pg = self.pgs.get(pg_id)
        if pg is None:
            raise ValueError(f"no placement group {pg_id}")
        # removal sets ready_event to wake waiters; only 'created' is ready
        ok = pg.ready_event.wait(timeout=timeout)
        return ok and pg.state == "created"

    # ------------------------------------------------------------------ #
    # worker pool (reference: raylet/worker_pool.h:283)
    # ------------------------------------------------------------------ #

    @staticmethod
    def _take_chips(node: NodeInfo, tpus: float) -> tuple:
        """Chip ids for a worker granted `tpus` chips of `node`: a worker
        that shares the host with other TPU workers is pinned to its own
        chip, or its own aligned pair (the sub-host shapes libtpu can
        bound); one granted the whole host — or a share that cannot be
        bounded — sees every chip, as it would without this."""
        n = math.ceil(tpus)
        total = int(node.resources_total.get("TPU", 0))
        free = node.free_chips
        chips = ()
        if n == 1 and total > 1 and free:
            chips = (free[0],)
        elif n == 2 and total > 2:
            chips = next(((c, c + 1) for c in free
                          if c % 2 == 0 and c + 1 in free), ())
        node.free_chips = [c for c in free if c not in chips]
        return chips

    def _spawn_worker_locked(self, node: NodeInfo,
                             tpus: float = 0) -> WorkerInfo:
        self._worker_seq += 1
        wid = f"w{self._worker_seq:05d}"
        tpu = tpus > 0
        chips = self._take_chips(node, tpus)
        if node.agent is not None:
            # agent-backed node: the agent forks the worker on its host and
            # reports pid/exit back over its control connection
            w = WorkerInfo(wid, node.node_id,
                           _RemoteProc(node.agent, wid), tpu, chips)
            w.pending_spec = None
            w.pending_actor = None
            self.workers[wid] = w
            node.workers.add(wid)
            node.agent.send({
                "t": "spawn_worker", "wid": wid, "tpu": tpu,
                "chips": chips, "node_id": node.node_id.hex()})
            return w
        env = build_worker_env(
            store_path=self.store_path, head_addr=self.listener_addr,
            head_family="AF_UNIX", authkey_hex=self._authkey.hex(),
            wid=wid, node_id_hex=node.node_id.hex(), tpu=tpu,
            spill_dir=self.spill.dir, chips=chips)
        log = open(os.path.join(self.session_dir, f"worker-{wid}.log"), "wb")
        # fork under the runtime lock is deliberate: wid allocation and
        # the workers-table insert must be atomic with the scheduling
        # pass that decided to spawn (dropping the lock here would let a
        # concurrent pass double-assign the bundle). The local-process
        # path only runs on the head node — agent-backed nodes (the
        # scale path) take the non-blocking send branch above.
        proc = subprocess.Popen(  # graftlint: disable=GL012,GL013
            [sys.executable, "-m", "ray_tpu.core.worker"],
            env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True)
        w = WorkerInfo(wid, node.node_id, proc, tpu, chips)
        w.pending_spec = None
        w.pending_actor = None
        self.workers[wid] = w
        node.workers.add(wid)
        # watchdog: a worker that dies before (or without) connecting would
        # otherwise never trigger the recv-loop EOF path
        threading.Thread(target=self._watch_proc, args=(w,),
                         daemon=True, name=f"rtpu-watch-{wid}").start()
        return w

    def _watch_proc(self, w: WorkerInfo):
        try:
            w.proc.wait()
        except Exception:
            pass  # reaped elsewhere; death path runs below
        self._on_worker_death(w.wid)

    def _returns_complete_locked(self, spec) -> bool:
        """All of a task's returns already produced (sealed in shm OR
        spilled to disk, and not failed) — the call completed even if its
        done message never arrived."""
        if not spec.return_ids:
            return False
        for oid in spec.return_ids:
            e = self.directory.get(oid)
            if e is not None and e.state == FAILED:
                return False
            if e is not None and e.state == SPILLED:
                continue
            if not self.store.contains(oid):
                return False
        return True

    def _on_worker_death(self, wid: str):
        with self.lock:
            if self._shutdown:
                # shutdown() already tears every worker down; running the
                # death path now would race the closing object store
                return
            w = self.workers.get(wid)
            if w is None or w.state == "dead":
                return
            w.state = "dead"
            # reclaim store state the dead process can no longer release:
            # unsealed creates (it died mid-put) and leaked read pins
            try:
                self.store.reclaim_pid(w.proc.pid)
            except Exception:
                pass  # store closing; pins die with it
            # zero the dead process's per-proc gauge series (host:pid
            # label, llm/telemetry.py): gauges are last-write-wins with
            # no owner left to update them, so a killed replica's last
            # kv_utilization/occupancy would pin /metrics forever. A
            # same-pid collision from another host self-heals on that
            # process's next ~2s flush tick.
            try:
                suffix = f":{w.proc.pid}"
                for rec in self.user_metrics.values():
                    if rec.get("kind") != "gauge":
                        continue
                    for key, val in rec["series"].items():
                        if val and any(k == "proc"
                                       and str(v).endswith(suffix)
                                       for k, v in key):
                            rec["series"][key] = 0.0
            except Exception:
                pass  # gauge cleanup must never block reaping
            # and its refcount interest (it will never send ref_drop)
            for oid in [o for o, s in self.interest.items() if wid in s]:
                self._ref_drop_locked(oid, wid)
            # store-path rpc replies it will never read (a peer killed
            # between sending an rpc/dir_query and consuming the reply)
            for oid in self._rpc_reply_pins.pop(wid, ()):
                try:
                    if self.store.contains(oid):
                        self.store.delete(oid)
                except Exception:
                    pass  # store closing; the reply dies with it
            node = self.nodes.get(w.node_id)
            if node:
                node.workers.discard(wid)
                node.free_chips = sorted({*node.free_chips, *w.chips})
            if not w.blocked:
                self._release_to_node(w)
            # pipelined-but-not-started tasks just go back to pending
            if w.queued:
                for s, _n in w.queued:
                    self.pending.append(s)
                w.queued.clear()
            # running normal task?
            spec = w.current
            if spec is not None and not spec.is_actor_task:
                if self._returns_complete_locked(spec):
                    # results all sealed: the task completed, only its done
                    # message lost the race with the death — don't clobber
                    self.counters["tasks_finished"] += 1
                    self._record_task_locked(spec, "FINISHED",
                                             finished_at=time.time())
                    for oid in spec.return_ids:
                        e = self.directory.get(oid)
                        if e is not None and e.state == PENDING:
                            e.state = READY
                        self._maybe_free_locked(oid)
                    self._drop_task_dep_interest_locked(spec)
                else:
                    self._handle_failed_task_locked(
                        spec, exc.WorkerCrashedError(
                            f"worker {wid} died while running {spec.name}"))
            # actor hosted here?
            if w.actor_id is not None:
                self._on_actor_worker_death_locked(w.actor_id, wid)
            self._schedule_locked()
            self.cv.notify_all()
        # outside self.lock (own short lock): a dead publisher's shared-
        # directory hints are swept so stale entries die with the worker
        # instead of lingering until every reader validates them
        try:
            self.dirs.sweep_owner(wid)
        except Exception:
            pass  # hint cleanup must never block reaping
        try:
            w.proc.wait(timeout=1)
        except Exception:
            pass  # slow exit; the OS reaps the zombie

    def _release_to_node(self, w: WorkerInfo):
        node = self.nodes.get(w.node_id)
        if node and node.alive and w.holding:
            for k, v in w.holding.items():
                node.resources_avail[k] = node.resources_avail.get(k, 0) + v
        if w.holding_bundle:
            pg_id, idx, res = w.holding_bundle
            pg = self.pgs.get(pg_id)
            if pg and pg.state == "created":
                b = pg.bundles[idx]
                for k, v in res.items():
                    b.avail[k] = b.avail.get(k, 0) + v

    def _reacquire_from_node(self, w: WorkerInfo):
        node = self.nodes.get(w.node_id)
        if node and node.alive and w.holding:
            for k, v in w.holding.items():
                node.resources_avail[k] = node.resources_avail.get(k, 0) - v
        if w.holding_bundle:
            pg_id, idx, res = w.holding_bundle
            pg = self.pgs.get(pg_id)
            if pg and pg.state == "created":
                b = pg.bundles[idx]
                for k, v in res.items():
                    b.avail[k] = b.avail.get(k, 0) - v

    # ------------------------------------------------------------------ #
    # object directory + lineage (reference: reference_count.h:73,
    # object_recovery_manager.h:43)
    # ------------------------------------------------------------------ #

    def put(self, value: Any, pin: bool = True) -> ObjectRef:
        oid = ObjectID.from_random()
        ref = self.put_at(oid, value)
        if pin:
            with self.lock:
                e = self.directory.get(oid)
                if e is not None and e.state == READY and \
                        oid not in self._pinned:
                    # store-level pin so LRU eviction never drops a live
                    # ray.put (released when the refcount frees the object)
                    if self.store.get_raw(oid, timeout_ms=0) is not None:
                        self._pinned.add(oid)
        return ref

    def expect(self, oid: ObjectID) -> None:
        """No-op: deferred oids need no pre-registration in the shared-store
        runtimes (get() already blocks). LocalModeRuntime overrides."""

    def put_at(self, oid: ObjectID, value: Any,
               is_exception: bool = False) -> ObjectRef:
        """Write `value` under a pre-allocated id (deferred-resolution refs).
        Objects the shm store can't hold spill to disk. Refs pickled inside
        `value` become containment edges so they outlive one transfer."""
        from .ref import capture_serialized_refs
        with capture_serialized_refs() as inner_ids:
            spilled = self.store.put_or_spill(oid, value, is_exception,
                                              self.spill)
        state = SPILLED if spilled else READY
        with self.lock:
            self.directory[oid] = DirEntry(state)
            if inner_ids:
                self._register_contained_locked(oid, inner_ids)
        return ObjectRef(oid)

    def _register_contained_locked(self, outer: ObjectID,
                                   inner_ids: list[ObjectID]):
        holder = f"obj:{outer.hex()}"
        self.contained.setdefault(outer, []).extend(inner_ids)
        for inner in inner_ids:
            self.interest.setdefault(inner, set()).add(holder)

    # -- refcounting (reference: reference_count.h:73) ---------------------

    def ref_created(self, oid: ObjectID, from_transfer: bool):
        if not from_transfer:
            # submit fast path: refs of a still-queued spec count under
            # the (cheap, uncontended) presumed lock; the pump migrates
            # the count and registers interest when it admits the spec
            with self._presumed_lock:
                c = self._presumed.get(oid)
                if c is not None:
                    self._presumed[oid] = c + 1
                    return
        with self.lock:
            c = self._local_refs.get(oid, 0)
            self._local_refs[oid] = c + 1
            if c == 0 or from_transfer:
                self._ref_add_locked(oid, "driver", from_transfer)

    def ref_deleted(self, oid: ObjectID):
        # __del__ context: must not mutate interest/directory synchronously
        # (a GC pass can fire inside code iterating those dicts on this
        # very thread); enqueue and let the drop thread do the bookkeeping
        self._drop_q.put(oid)

    def _drop_loop(self):
        while True:
            oid = self._drop_q.get()
            if oid is None:
                return
            try:
                # presumed drops settle under the presumed lock ALONE
                # (never nested inside self.lock here — the pump nests the
                # other way around); a ref created before the pump admits
                # its spec and dropped after is attributed here by oid,
                # which can transiently mis-attribute when the same oid
                # also has transfer-created refs — worst case a leaked
                # interest entry, never a premature free
                handled = False
                with self._presumed_lock:
                    c = self._presumed.get(oid)
                    if c is not None:
                        handled = True
                        if c <= 1:
                            self._presumed.pop(oid, None)
                            # every local ref died before the pump saw
                            # the spec: interest must never be registered
                            self._dropped_early.add(oid)
                        else:
                            self._presumed[oid] = c - 1
                if handled:
                    continue
                with self.lock:
                    c = self._local_refs.get(oid, 0) - 1
                    if c <= 0:
                        self._local_refs.pop(oid, None)
                        self._ref_drop_locked(oid, "driver")
                    else:
                        self._local_refs[oid] = c
            except Exception:
                traceback.print_exc()

    def ref_serialized(self, oid: ObjectID):
        with self.lock:
            self.xfer_pins[oid] = self.xfer_pins.get(oid, 0) + 1

    def _ref_add_locked(self, oid: ObjectID, holder: str,
                        from_transfer: bool):
        self.interest.setdefault(oid, set()).add(holder)
        if oid in self._ready_ok and not self.store.contains(oid):
            # an ALIVE actor's ready oid gains an observer: seal the
            # "ok" payload now (nothing is stored up front — see
            # _ready_ok) so ray.get(h.__ray_ready__()) resolves; this
            # ref's refcount frees it, and a later re-observation
            # re-materializes
            try:
                self.store.put(oid, True)
            except Exception:
                pass  # store full: get() falls back to ensure/locate
            else:
                if oid not in self.directory:
                    self.directory[oid] = DirEntry(READY)
        # not popped: the entry persists (one small dict slot per dead
        # actor) so every FUTURE ref — including one deserialized after
        # the first observer's error object was freed — re-materializes
        brief = self._ready_failed.get(oid)
        if brief is not None and not self.store.contains(oid):
            # a dead actor's payload-less ready oid gains its first
            # observer: materialize the death error now so get() raises
            # it instead of spinning on a missing object; this ref's
            # refcount frees it like any task result. (Scoped to ready
            # oids via the registry — a generic FAILED entry may hold
            # its real, differently-typed error on a remote store.)
            self._store_error(oid, exc.ActorDiedError(brief))
            if oid not in self.directory:
                # the original entry may have been freed by an earlier
                # ready ref's drop; without one, _maybe_free could
                # never reclaim the error object we just stored
                self.directory[oid] = DirEntry(FAILED)
        if from_transfer:
            # clamp at 0: deserializations of refs embedded in STORED
            # objects carry no pin (containment edges protect those), and
            # a pin must never be cancelled by an unrelated deserialize
            n = self.xfer_pins.get(oid, 0) - 1
            if n <= 0:
                self.xfer_pins.pop(oid, None)
            else:
                self.xfer_pins[oid] = n

    def _ref_drop_locked(self, oid: ObjectID, holder: str):
        s = self.interest.get(oid)
        if s is not None:
            s.discard(holder)
            if not s:
                self.interest.pop(oid, None)
        self._maybe_free_locked(oid)

    def _maybe_free_locked(self, oid: ObjectID):
        """Free payload + metadata once the object is unreachable: no
        process holds a ref, no serialized copy is in flight, and no task
        is about to produce it."""
        if oid in self.interest or self.xfer_pins.get(oid, 0) > 0:
            return
        e = self.directory.get(oid)
        if e is None or e.state == PENDING:
            return
        e_locs = e.locations
        self.directory.pop(oid, None)
        # copies on own-store nodes are freed by their agents (the head
        # can't reach those stores); reference: FreeObjects fanout
        if e_locs:
            for n in self.nodes.values():
                if (n.agent is not None and n.own_store
                        and n.node_id.hex() in e_locs):
                    n.agent.send({"t": "free_objects",
                                  "oids": [oid.binary()]})
        if oid in self._pinned:
            self._pinned.discard(oid)
            try:
                self.store.release(oid)
            except Exception:
                pass  # store closing; the pin dies with it
        try:
            self.store.delete(oid)
        except Exception:
            pass  # already evicted
        self.spill.delete(oid)
        self.xfer_pins.pop(oid, None)
        # the freed outer no longer keeps its inners alive
        holder = f"obj:{oid.hex()}"
        for inner in self.contained.pop(oid, []):
            self._ref_drop_locked(inner, holder)

    def _store_error(self, oid: ObjectID, err: BaseException):
        try:
            self.store.delete(oid)
            self.store.put(oid, err, is_exception=True)
        except Exception:
            pass  # store full/closing; directory marks FAILED

    def _ensure_available_locked(self, oid: ObjectID):
        """If `oid` was evicted, restore it from spill or resubmit its
        producing task (lineage)."""
        e = self.directory.get(oid)
        if e is not None and e.state == SPILLED:
            # spilled objects are served from disk: the head reads the file
            # directly and workers fall back to the shared spill directory
            # (restoring into the store here would do multi-GB IO under the
            # runtime lock and lose spill-awareness on later eviction)
            return
        if e is None or e.state != READY or self.store.contains(oid):
            return
        if e.locations:
            # a live copy on another node satisfies consumers via the
            # transfer service — reconstruction would DOUBLE-RUN the
            # producer (wrong for side-effecting tasks)
            alive = {n.node_id.hex() for n in self.nodes.values()
                     if n.alive}
            live_copies = e.locations & alive
            if live_copies:
                return
            e.locations = None  # every holder died: fall through to lineage
        if e.lineage is None:
            self._store_error(oid, exc.ObjectLostError(
                f"object {oid} was evicted and has no lineage "
                "(ray_tpu.put objects are not reconstructable)"))
            e.state = FAILED
            self._sweep_failed_deps_locked()
            return
        e.state = PENDING
        spec = e.lineage
        # all sibling returns become pending again
        for rid in spec.return_ids:
            ent = self.directory.get(rid)
            if ent is not None:
                ent.state = PENDING
        self.pending.append(spec)

    # ------------------------------------------------------------------ #
    # task submission + scheduling (reference: cluster_task_manager.h:72,
    # hybrid_scheduling_policy.h:50, local_task_manager.h:60)
    # ------------------------------------------------------------------ #

    def register_renv(self, h: str, blob: bytes):
        with self.lock:
            self.renv_registry.setdefault(h, blob)

    def register_function(self, fid: str, blob: bytes):
        with self.lock:
            self.func_registry.setdefault(fid, blob)

    def _queue_submit(self, kind: str, spec: TaskSpec) -> list[ObjectRef]:
        """Driver submit fast path: mark the return oids presumed (their
        ObjectRefs count under the presumed lock, not the runtime lock),
        queue the spec, and wake the pump. Interest lands when the pump
        admits the spec — BEFORE the task can run, the same guarantee
        the v2 submit message gives remote clients."""
        with self._presumed_lock:
            for o in spec.return_ids:
                self._presumed.setdefault(o, 0)
        refs = [ObjectRef(o) for o in spec.return_ids]
        self._submit_q.append((kind, spec))
        self._sched_evt.set()
        return refs

    def _drain_submit_q(self):
        """Admit every queued driver spec: one lock acquisition and one
        deferred scheduling pass per batch (same shape as _handle_batch
        for remote clients). Single drainer at a time so specs admit in
        queue order (actor-call ordering depends on it)."""
        with self._submitq_drain_lock:
            while self._submit_q:
                batch = []
                # bounded batches: the first specs of a burst dispatch
                # after a short admission pass (workers start while the
                # rest of the burst admits), and done-processing recv
                # threads never stall behind one long lock hold
                while self._submit_q and len(batch) < 128:
                    try:
                        batch.append(self._submit_q.popleft())
                    except IndexError:
                        break
                if not batch:
                    return
                with self.lock:
                    opened = self._send_buf is None
                    if opened:
                        self._send_buf = {}
                    self._defer_sched += 1
                    try:
                        for kind, spec in batch:
                            try:
                                self._admit_driver_spec_locked(kind, spec)
                            except Exception:
                                traceback.print_exc()
                    finally:
                        self._defer_sched -= 1
                        try:
                            if self._sched_wanted and not self._defer_sched:
                                self._sched_wanted = False
                                self._schedule_locked()
                        finally:
                            if opened:
                                buf, self._send_buf = self._send_buf, None
                                self._flush_wsend_buf(buf)

    def _admit_driver_spec_locked(self, kind: str, spec: TaskSpec):
        # migrate presumed ref counts into the lock-guarded table and
        # register the driver's interest — exactly what _handle_msg
        # "submit" does for a remote client's return oids
        with self._presumed_lock:
            settled = []
            for oid in spec.return_ids:
                cnt = self._presumed.pop(oid, None)
                early = oid in self._dropped_early
                self._dropped_early.discard(oid)
                settled.append((oid, cnt, early))
        for oid, cnt, early in settled:
            if early:
                continue  # every ref died pre-admission: no interest
            if cnt:
                self._local_refs[oid] = self._local_refs.get(oid, 0) + cnt
            self._ref_add_locked(oid, "driver", False)
        if kind == "actor":
            self._submit_actor_task_locked(spec)
        else:
            self._submit_locked(spec)

    def submit_task(self, spec: TaskSpec) -> list[ObjectRef]:
        if self._submitq_on and not self._shutdown:
            return self._queue_submit("task", spec)
        with self.lock:
            # interest BEFORE the task can run: a fast task finishing
            # between submit and ref construction must not see an
            # unreferenced result and free it
            refs = [ObjectRef(o) for o in spec.return_ids]
            bw = self._burst_window
            if bw > 0.0:
                now = time.monotonic()
                burst = now - self._last_submit_ts < bw
                self._last_submit_ts = now
                if burst:
                    # burst submission (in-process driver): defer the
                    # scheduling pass to the pump so one pass — and one
                    # batched pipe frame per worker — serves the whole
                    # burst. An isolated submit (interval >= the window,
                    # i.e. anything with a round-trip in between) still
                    # schedules inline with zero added latency.
                    self._defer_sched += 1
                    try:
                        self._submit_locked(spec)
                    finally:
                        self._defer_sched -= 1
                    if self._sched_wanted and not self._defer_sched:
                        self._sched_wanted = False
                        self._sched_evt.set()
                    return refs
            self._submit_locked(spec)
        return refs

    def _record_task_locked(self, spec, state: str, **extra):
        # every transition hits the flight ring, even ones whose state-
        # API record was FIFO-evicted — the recorder is the always-on
        # view of task flow, the records dict is the bounded query view
        flight.evt(flight.TASK_STATE, flight.lo48(spec.task_id),
                   flight.TASK_STATES.get(state, -1))
        rec = self.task_records.get(spec.task_id)
        if rec is None:
            if state != "PENDING":
                # record was FIFO-evicted: don't resurrect it with a bogus
                # submitted_at — honest absence beats wrong timestamps
                return
            rec = {"task_id": spec.task_id.hex(), "name": spec.name,
                   "state": state, "is_actor_task": spec.is_actor_task,
                   "submitted_at": time.time()}
            self.task_records[spec.task_id] = rec
            while len(self.task_records) > self.task_records_max:
                self.task_records.popitem(last=False)
        rec["state"] = state
        if state in ("RUNNING", "RETRYING") and rec.get("stuck"):
            # a fresh attempt starts clean: without this, a retried task
            # is falsely listed stuck the moment it re-enters RUNNING
            # (stale flag + stale stack from the previous attempt), and
            # a retry that genuinely wedges later could never be
            # re-flagged with a fresh stack
            for k in ("stuck", "stuck_at", "threshold_s", "ewma_s",
                      "stack"):
                rec.pop(k, None)
        rec.update(extra)
        if self._event_file is not None:
            try:
                self._event_file.write(json.dumps(
                    {"ts": time.time(), "task_id": rec["task_id"],
                     "name": rec["name"], "state": state, **{
                         k: v for k, v in extra.items()
                         if isinstance(v, (int, float, str))}}) + "\n")
            except (OSError, ValueError):
                self._event_file = None  # disk gone: stop exporting

    def _submit_locked(self, spec: TaskSpec):
        self.counters["tasks_submitted"] += 1
        self._record_task_locked(spec, "PENDING")
        for oid in spec.return_ids:
            self.directory[oid] = DirEntry(PENDING, lineage=spec)
        # the task holds interest in its args until it terminally completes
        # (covers re-deserialization on retries; the submitter may drop its
        # refs right after submit)
        holder = f"task:{spec.task_id.hex()}"
        for d in spec.dep_oids:
            self.interest.setdefault(d, set()).add(holder)
        if spec.is_actor_task:
            self._route_actor_task_locked(spec)
        elif spec.dep_oids and self._deps_state_locked(spec) == "failed":
            # dep already failed at submit: fail fast — a blocked bucket
            # head would otherwise hide this task from the next pass
            self._handle_failed_task_locked(
                spec, self._collect_dep_error_locked(spec), retryable=False)
        else:
            self.pending.append(spec)
            self._schedule_locked()

    def _feasible(self, node: NodeInfo, res: dict[str, float]) -> bool:
        return node.alive and all(
            node.resources_total.get(k, 0) >= v for k, v in res.items())

    def _has_avail(self, node: NodeInfo, res: dict[str, float]) -> bool:
        return node.alive and all(
            node.resources_avail.get(k, 0) >= v - 1e-9 for k, v in res.items())

    @staticmethod
    def _labels_ok(node: NodeInfo, spec) -> bool:
        sel = getattr(spec, "label_selector", None)
        if not sel:
            return True
        return all(node.labels.get(k) == v for k, v in sel.items())

    def _pick_node_locked(self, spec) -> Optional[NodeInfo]:
        res = spec.resources
        if spec.pg_id is not None:
            pg = self.pgs.get(spec.pg_id)
            if pg is None or pg.state != "created":
                return None
            idxs = ([spec.pg_bundle_index] if spec.pg_bundle_index >= 0
                    else range(len(pg.bundles)))
            for i in idxs:
                b = pg.bundles[i]
                node = self.nodes.get(b.node_id)
                if node is None or not node.alive \
                        or not self._labels_ok(node, spec):
                    continue
                if all(b.avail.get(k, 0) >= v - 1e-9 for k, v in res.items()):
                    return node
            return None
        if spec.node_affinity is not None:
            node = self.nodes.get(NodeID(spec.node_affinity))
            if node and self._has_avail(node, res) \
                    and self._labels_ok(node, spec):
                return node
            if spec.node_affinity_soft:
                pass  # fall through to normal policy
            else:
                return None
        alive = [n for n in self.nodes.values()
                 if n.alive and self._labels_ok(n, spec)]
        if not alive:
            return None
        if spec.scheduling_strategy == "SPREAD":
            order = alive[self._spread_rr % len(alive):] + \
                alive[:self._spread_rr % len(alive)]
            for n in order:
                if self._has_avail(n, res):
                    self._spread_rr += 1
                    return n
            return None
        # hybrid: pack onto head/local until 50% utilized, then least-utilized
        head = self.head_node
        from .config import cfg as _cfg
        if self._labels_ok(head, spec) and self._has_avail(head, res) and \
                head.utilization() < _cfg.scheduler_spread_threshold:
            return head
        best, best_u = None, 2.0
        for n in alive:
            if self._has_avail(n, res) and n.utilization() < best_u:
                best, best_u = n, n.utilization()
        return best

    def _deps_state_locked(self, spec) -> str:
        """-> 'ready' | 'wait' | 'failed'."""
        for d in spec.dep_oids:
            e = self.directory.get(d)
            if e is not None and e.state == FAILED:
                return "failed"
            if e is not None and e.state == SPILLED:
                # satisfiable from disk: workers fall back to the shared
                # spill directory when the store misses
                continue
            if not self.store.contains(d):
                if e is not None and e.state == READY and e.locations and \
                        any(n.alive and n.node_id.hex() in e.locations
                            for n in self.nodes.values()):
                    # a live copy on another node; the executing worker
                    # pulls it via the transfer service (every worker can
                    # fetch — see worker._try_fetch)
                    continue
                if e is not None and e.state == READY:
                    self._ensure_available_locked(d)  # evicted → reconstruct
                return "wait"
        return "ready"

    def _schedule_locked(self):
        """Drain what's dispatchable. Per-shape bucket queues make this
        O(buckets + dispatched + dep-waiters) per pass: once a bucket's
        head can't place, the rest of that bucket can't either (identical
        placement signature, and capacity only shrinks as the pass
        dispatches), so the bucket is skipped whole. Dep-waiting tasks are
        set aside per pass so a blocked head never hides a ready task
        behind it. All control messages to one worker during the pass
        coalesce into ONE pipe write (a burst refilling a 4-deep pipeline
        costs one syscall, not four)."""
        if self._shutdown:
            return
        if self._defer_sched:
            # inside a batch frame / deferred submit: one pass at the end
            # serves every request made during it
            self._sched_wanted = True
            return
        if self._send_buf is None:
            self._send_buf = {}
            try:
                self._schedule_pass_locked()
            finally:
                buf, self._send_buf = self._send_buf, None
                self._flush_wsend_buf(buf)
            return
        self._schedule_pass_locked()

    def _flush_wsend_buf(self, buf: dict) -> None:
        """Ship the per-worker message batches accumulated by _wsend —
        one pipe write per worker per pass/batch."""
        dead = []
        for w, msgs in buf.items():
            msg = (msgs[0] if len(msgs) == 1
                   else {"t": "batch", "msgs": msgs})
            if not w.send(msg):
                dead.append(w.wid)
        for wid in dead:
            self._on_worker_death(wid)

    def _wsend(self, w: WorkerInfo, msg) -> bool:
        """Send to a worker, coalescing into the current scheduling
        pass's per-worker batch when one is open."""
        buf = self._send_buf
        if buf is not None:
            buf.setdefault(w, []).append(msg)
            return True  # delivery failures surface at flush
        return w.send(msg)

    def _dispatch_possible_locked(self) -> bool:
        """Cheap saturation check before walking every bucket: can ANY
        pending plain task possibly dispatch this pass? True when a
        zero-resource shape is pending (always placeable), a PLACEMENT
        GROUP task is pending (bundles hold their own reserved capacity,
        invisible in node.resources_avail — gating on node availability
        would deadlock a PG that reserved a whole node), a node has any
        free resource, or a busy worker has an open pipeline slot.
        O(nodes + workers) instead of a full pass with per-task dep
        checks — what a burst of submits pays per task once the pool is
        saturated. Conservative by construction: a true here only means
        the full pass runs (possibly finding nothing). Accepted
        semantics: reconstruction of an evicted dep kicks at the next
        capacity-freeing event rather than instantly — while saturated
        the regenerating task could not run anyway (failed-dep
        propagation is unaffected: submit fail-fast plus the
        failure-event sweep run outside the pass)."""
        from .config import cfg as _cfg
        for key in self.pending.buckets:
            if not key[0] or key[1] is not None:
                return True
        for n in self.nodes.values():
            if n.alive and any(v > 1e-9 for v in n.resources_avail.values()):
                return True
        depth = _cfg.worker_pipeline_depth
        if depth > 0:
            for w in self.workers.values():
                if (w.state == "busy" and not w.blocked
                        and w.conn is not None and w.actor_id is None
                        and len(w.queued) < depth):
                    return True
        return False

    def _schedule_pass_locked(self):
        if self.pending.buckets and not self._dispatch_possible_locked():
            return
        flight.evt(flight.SCHED_BEGIN)
        try:
            self._schedule_pass_body_locked()
        finally:
            flight.evt(flight.SCHED_END)

    def _schedule_pass_body_locked(self):
        for key in list(self.pending.buckets):
            dq = self.pending.buckets.get(key)
            if not dq:
                continue
            dep_wait: list = []
            while dq:
                spec = dq.popleft()
                deps = self._deps_state_locked(spec)
                if deps == "failed":
                    err = self._collect_dep_error_locked(spec)
                    self._handle_failed_task_locked(spec, err,
                                                    retryable=False)
                    continue
                if deps == "wait":
                    dep_wait.append(spec)
                    continue
                node = self._pick_node_locked(spec)
                w = None if node is None else \
                    self._acquire_worker_locked(node, spec)
                if w is None:
                    if self._pipeline_dispatch_locked(spec):
                        continue
                    # same signature ⇒ the rest of the bucket can't place
                    # either this pass; stop (tasks behind the head are
                    # NOT rescanned — failed-dependency propagation is
                    # event-driven via _sweep_failed_deps_locked, so a
                    # blocked head can't hide a doomed task)
                    dq.appendleft(spec)
                    break
                self._dispatch_locked(w, spec)
            # the failure sweep (run from _handle_failed_task_locked above)
            # may have emptied-and-removed THIS bucket mid-pass: only touch
            # the dict entry if it is still our deque, and re-route
            # dep-waiters through append() otherwise so they land in a
            # live bucket instead of an orphaned one
            if dep_wait:
                if self.pending.buckets.get(key) is dq:
                    dq.extend(dep_wait)
                else:
                    for s in dep_wait:
                        self.pending.append(s)
            if not dq and self.pending.buckets.get(key) is dq:
                del self.pending.buckets[key]

    def _sweep_failed_deps_locked(self):
        """Fail every pending task whose dependency just failed. Called on
        failure EVENTS (object marked FAILED), not per scheduling pass —
        keeping the hot path O(buckets) while failures still propagate
        promptly past placement-blocked bucket heads. Iterates to a
        fixpoint (a failed task's returns can doom further dependents);
        the guard flattens the recursion through
        _handle_failed_task_locked."""
        if self._sweeping_failed_deps:
            return
        self._sweeping_failed_deps = True
        try:
            while True:
                doomed = [
                    spec for spec in self.pending
                    if spec.dep_oids
                    and self._deps_state_locked(spec) == "failed"]
                if not doomed:
                    return
                for spec in doomed:
                    try:
                        self.pending.remove(spec)
                    except ValueError:
                        continue
                    err = self._collect_dep_error_locked(spec)
                    self._handle_failed_task_locked(spec, err,
                                                    retryable=False)
        finally:
            self._sweeping_failed_deps = False

    def _acquire_worker_locked(self, node: NodeInfo, spec) -> Optional[WorkerInfo]:
        from .runtime_env import env_hash as _env_hash
        want_env = _env_hash(getattr(spec, "runtime_env", None))
        tpus = spec.resources.get("TPU", 0)
        for wid in node.workers:
            w = self.workers[wid]
            # a TPU worker pinned to its own chips only takes work that
            # was granted as many
            if w.state == "idle" and w.conn is not None and \
                    w.tpu == (tpus > 0) and \
                    len(w.chips) in (0, math.ceil(tpus)) and \
                    w.env_hash == want_env:
                self._mark_busy(w, node, spec)
                return w
        # blocked workers don't count against the cap: their CPU is
        # released and the task that blocked them may be waiting on
        # exactly the child task this spawn would run (reference: the
        # worker pool starts a replacement when a worker blocks in
        # ray.get, so nested task trees can't wedge the pool)
        live = sum(1 for wid in node.workers
                   if self.workers[wid].state != "dead"
                   and not self.workers[wid].blocked)
        if live >= node.max_workers:
            # pool full of idle workers dedicated to OTHER runtime envs?
            # reap one so this env can make progress (reference: the worker
            # pool kills idle dedicated workers under starvation)
            victim = next(
                (self.workers[wid] for wid in node.workers
                 if self.workers[wid].state == "idle"
                 and self.workers[wid].env_hash != want_env), None)
            if victim is None:
                return None
            victim.send({"t": "exit"})
            self._on_worker_death_locked_prep(victim)
            live -= 1
        if live < node.max_workers:
            w = self._spawn_worker_locked(
                node, tpus=spec.resources.get("TPU", 0))
            # not yet connected; dispatch happens when it registers
            self._mark_busy(w, node, spec, dispatch_later=True)
            return w
        return None

    def _on_worker_death_locked_prep(self, w: WorkerInfo):
        """Mark an intentionally-reaped worker dead under the lock (the
        recv-loop EOF will find state=='dead' and no-op)."""
        w.state = "dead"
        for oid in [o for o, s in self.interest.items() if w.wid in s]:
            self._ref_drop_locked(oid, w.wid)
        node = self.nodes.get(w.node_id)
        if node:
            node.workers.discard(w.wid)
            node.free_chips = sorted({*node.free_chips, *w.chips})

    def _mark_busy(self, w: WorkerInfo, node: NodeInfo, spec,
                   dispatch_later: bool = False):
        w.state = "busy" if not dispatch_later else w.state
        res = spec.resources
        if spec.pg_id is not None:
            pg = self.pgs[spec.pg_id]
            idxs = ([spec.pg_bundle_index] if spec.pg_bundle_index >= 0
                    else range(len(pg.bundles)))
            for i in idxs:
                b = pg.bundles[i]
                if b.node_id == node.node_id and all(
                        b.avail.get(k, 0) >= v - 1e-9 for k, v in res.items()):
                    for k, v in res.items():
                        b.avail[k] -= v
                    w.holding_bundle = (spec.pg_id, i, dict(res))
                    break
        else:
            for k, v in res.items():
                node.resources_avail[k] = node.resources_avail.get(k, 0) - v
            w.holding = dict(res)

    def _dispatch_locked(self, w: WorkerInfo, spec):
        w.current = spec
        if w.conn is None:
            # newly spawned; stash the task — dispatched on register
            w.state = "starting"
            w.pending_spec = spec
            return
        w.state = "busy"
        w.current_started = time.monotonic()
        if spec.runtime_env and w.env_hash is None:
            self._ship_renv_locked(w, spec.runtime_env)
        self._ship_function_locked(w, spec.func_id)
        self._record_task_locked(spec, "RUNNING", worker=w.wid,
                                 node=w.node_id.hex(),
                                 started_at=time.time())
        self.events.append({"name": spec.name, "cat": "task", "ph": "B",
                            "pid": w.wid, "ts": time.time() * 1e6,
                            "tid": spec.task_id.hex()[:8]})
        if not self._wsend(w, {"t": "task", "spec": spec}):
            self._on_worker_death(w.wid)

    def _pipeline_dispatch_locked(self, spec) -> bool:
        """Queue a same-shape plain task behind a busy worker's current
        task (reference analog: worker-lease reuse on the direct task
        transport — the done->dispatch round-trip leaves the worker's
        critical path because the next task message is already in its
        pipe). The queued task reuses the running task's resource lease,
        so nothing extra is charged; eligibility is strict: identical
        resource shape, same runtime env, no placement constraints."""
        from .config import cfg as _cfg
        depth = _cfg.worker_pipeline_depth
        if depth <= 0 or spec.pg_id is not None \
                or spec.node_affinity is not None \
                or spec.scheduling_strategy == "SPREAD":
            return False
        env_hash = (spec.runtime_env or {}).get("hash")
        best = None
        for w in self.workers.values():
            if (w.state == "busy" and not w.blocked and w.conn is not None
                    and w.actor_id is None and w.current is not None
                    and not w.current.is_actor_task
                    and len(w.queued) < depth
                    and w.current.resources == spec.resources
                    and w.env_hash == env_hash
                    and self._labels_ok(self.nodes[w.node_id], spec)
                    and (best is None or len(w.queued) < len(best.queued))):
                best = w
        if best is None:
            return False
        self._ship_function_locked(best, spec.func_id)
        nonce = f"{best.wid}:{best.send_seq}"
        best.send_seq += 1
        # only reachable from inside a scheduling pass, so _wsend always
        # buffers here: a pipe failure surfaces at the pass flush, which
        # requeues best.queued via _on_worker_death
        self._wsend(best, {"t": "task", "spec": spec, "n": nonce})
        best.queued.append((spec, nonce))
        return True

    def _promote_queued_locked(self, w: WorkerInfo):
        """The previous task's done message means the worker is already
        executing the head of its queue: transfer the lease head-side."""
        nxt, _nonce = w.queued.popleft()
        w.current = nxt
        w.state = "busy"
        w.current_started = time.monotonic()
        self._record_task_locked(nxt, "RUNNING", worker=w.wid,
                                 node=w.node_id.hex(),
                                 started_at=time.time())
        self.events.append({"name": nxt.name, "cat": "task", "ph": "B",
                            "pid": w.wid, "ts": time.time() * 1e6,
                            "tid": nxt.task_id.hex()[:8]})

    def _steal_queued_locked(self, w: WorkerInfo):
        """Pull pipelined tasks back from a worker (it blocked or is
        wanted for other work): the worker is told to skip them and the
        specs re-enter the pending queues. Prevents the deadlock where a
        blocked task waits on a result only its own queued successor
        would produce."""
        if not w.queued:
            return
        stolen = list(w.queued)
        w.queued.clear()
        w.send({"t": "steal", "nonces": [n for _, n in stolen]})
        for s, _ in stolen:
            self.pending.append(s)

    def merge_user_metrics(self, rows: list) -> None:
        """Fold user-metric deltas from any process into the head store
        (util/metrics.py; counters/histogram buckets SUM, gauges
        last-write-wins)."""
        with self.lock:
            store = self.user_metrics
            for kind, name, desc, key, value, add in rows:
                rec = store.setdefault(
                    name, {"kind": kind, "desc": desc, "series": {}})
                if add:
                    rec["series"][key] = rec["series"].get(key, 0.0) + value
                else:
                    rec["series"][key] = value

    def user_metrics_dump(self) -> dict:
        """RPC: the merged user-metric store (remote drivers render their
        own Prometheus text from it)."""
        with self.lock:
            return {n: {"kind": r["kind"], "desc": r["desc"],
                        "series": dict(r["series"])}
                    for n, r in self.user_metrics.items()}

    # -- metrics plane (ray_tpu/obs): TSDB history + SLOs + signals ---- #

    def _obs(self):
        if self.obs is None:
            raise RuntimeError(
                "metrics TSDB disabled (cfg.tsdb_enable=0); history/SLO "
                "queries need the head scraper")
        return self.obs

    def metrics_history(self, name: str, tags=None, window_s=None,
                        quantiles=None, group_by=None) -> dict:
        """RPC: range-query the head TSDB. With ``quantiles``, also fold
        the matching histogram bucket series into windowed quantile
        values (state.metrics_history / cli top / dashboard). With
        ``group_by`` (label names), additionally return per-group
        rate/quantile aggregates under "groups" — one round-trip serves
        a whole `cli top` column instead of one RPC per deployment."""
        obs = self._obs()
        tags = dict(tags) if tags else None
        out = {
            "name": name,
            "kind": obs.tsdb.kind_of(name),
            "series": obs.tsdb.query(name, tags, window_s),
            "scrape_s": obs.tsdb.scrape_s,
        }
        qs = tuple(float(q) for q in quantiles) if quantiles else None
        if qs:
            out["quantiles"] = dict(zip(
                (str(q) for q in qs),
                obs.tsdb.histogram_quantiles(name, tags, window_s, qs)))
        if out["kind"] == "counter":
            out["rate_per_s"] = obs.tsdb.rate(name, tags, window_s)
        if group_by:
            gb = tuple(group_by)
            keys: list[dict] = []
            for s in out["series"]:
                key = dict(s["key"])
                # only labels the series actually carries: a "" filler
                # could never subset-match back into the TSDB
                gk = {k: key[k] for k in gb if k in key}
                if gk not in keys:
                    keys.append(gk)
            rows = []
            for gk in keys:
                # group aggregates honor the caller's tags filter too
                qtags = {**tags, **gk} if tags else (gk or None)
                row: dict = {"key": gk}
                if qs:
                    row["quantiles"] = dict(zip(
                        (str(q) for q in qs),
                        obs.tsdb.histogram_quantiles(
                            name, qtags, window_s, qs)))
                if out["kind"] == "counter":
                    row["rate_per_s"] = obs.tsdb.rate(name, qtags,
                                                      window_s)
                rows.append(row)
            out["groups"] = rows
        return out

    def metrics_names(self) -> list[str]:
        return self._obs().tsdb.names()

    def slo_report(self) -> dict:
        """RPC: the SLO engine's latest evaluation + TSDB health."""
        obs = self._obs()
        rep = dict(obs.engine.report())
        rep["tsdb"] = obs.stats()
        return rep

    def obs_signals(self, app: str, deployment: str) -> dict:
        """RPC: the autoscaler's composed scale-out signals for one
        deployment (serve controller, once per scrape period)."""
        from ..obs.scraper import autoscale_signals
        obs = self._obs()
        return autoscale_signals(obs.tsdb, obs.engine, app, deployment)

    def cache_report(self, top_k: int = 10) -> dict:
        """RPC: the cluster-wide prefix-cache heat map (cache heat
        plane). Folds three independent sources — the replicas'
        ``heat:*`` directory summaries (per-replica pools + hot
        chains), the merged metric store's ``rtpu_llm_prefix_cache_*``
        aggregates, and the per-chain ``rtpu_llm_prefix_chain_*``
        gauges — so it works whether or not the TSDB scraper is on
        (trend is attached only when it is). When the tiered KV-cache
        ran anywhere, a ``spill`` section carries the fleet's
        ``rtpu_llm_prefix_spill_*`` lifecycle totals and residency,
        and each replica row counts its directory's ``spill:``
        store-backed entries."""
        now = time.time()
        top_k = max(int(top_k), 1)
        # -- per-replica heat summaries from the shared directories ---- #
        replicas: list[dict] = []
        dir_sizes = self.dirs.stats()["directories"]
        for name in sorted(dir_sizes):
            if not name.startswith("serve:prefix:"):
                continue
            heats = self.dirs.lookup_prefix(name, "heat:")
            # spill: entries (tiered KV-cache, llm/tiering.py) share
            # the directory but are store-backed pages, not live ones
            n_spill = len(self.dirs.lookup_prefix(name, "spill:"))
            for _k, v in sorted(heats.items()):
                row = dict(v)
                ts = row.pop("ts", None)
                row["age_s"] = round(now - ts, 1) if ts else None
                row["directory_pages"] = \
                    dir_sizes[name] - len(heats) - n_spill
                row["directory_spilled"] = n_spill
                replicas.append(row)
        # -- fleet totals from the merged counter store ---------------- #
        def _total(metric: str) -> float:
            rec = self.user_metrics.get(metric)
            return sum(rec["series"].values()) if rec else 0.0
        with self.lock:
            totals = {k: _total(f"rtpu_llm_prefix_cache_{k}_total")
                      for k in ("hits", "misses", "evictions",
                                "tokens_saved", "imported_pages",
                                "exported_pages")}
            seen = totals["hits"] + totals["misses"]
            totals["hit_rate"] = round(totals["hits"] / seen, 4) \
                if seen else 0.0
            spill_totals = {
                k: _total(f"rtpu_llm_prefix_spill_{k}_total")
                for k in ("pages", "bytes", "demotions", "promotions",
                          "expired", "drops")}
            spill_totals["resident_pages"] = _total(
                "rtpu_llm_prefix_spill_resident_pages")
            spill_totals["resident_bytes"] = _total(
                "rtpu_llm_prefix_spill_resident_bytes")
            # -- cluster chain fold: sum per-chain gauges across procs - #
            chains: dict[str, dict] = {}
            for metric, field, fold in (
                    ("rtpu_llm_prefix_chain_hits", "hits", "sum"),
                    ("rtpu_llm_prefix_chain_tokens_saved",
                     "tokens_saved", "sum"),
                    ("rtpu_llm_prefix_chain_resident_pages",
                     "resident_pages", "sum"),
                    ("rtpu_llm_prefix_chain_last_hit_age_s",
                     "last_hit_age_s", "min")):
                rec = self.user_metrics.get(metric)
                for key, val in (rec["series"] if rec else {}).items():
                    labels = dict(key)
                    chain = labels.get("chain", "")
                    row = chains.setdefault(
                        chain, {"chain": chain, "replicas": 0})
                    if fold == "sum":
                        row[field] = row.get(field, 0) + val
                    else:
                        row[field] = min(row.get(field, val), val)
                    if metric.endswith("_hits"):
                        row["replicas"] += 1
        chain_rows = sorted(chains.values(),
                            key=lambda r: -r.get("hits", 0))[:top_k]
        # -- per-tenant warmth + pool rollup from replica summaries ---- #
        tenants: dict[str, dict] = {}
        pages = {"free": 0, "cached": 0, "total": 0,
                 "reclaimable_bytes": 0,
                 "spilled": 0, "spilled_bytes": 0}
        for rep in replicas:
            pool = rep.get("pool") or {}
            pages["free"] += pool.get("free_pages", 0)
            pages["cached"] += pool.get("cached_pages", 0)
            pages["total"] += pool.get("total_pages", 0)
            pages["reclaimable_bytes"] += pool.get("reclaimable_bytes", 0)
            pages["spilled"] += pool.get("spilled_pages", 0)
            pages["spilled_bytes"] += pool.get("spilled_bytes", 0)
            for c in rep.get("chains") or ():
                t = tenants.setdefault(
                    c.get("tenant", ""), {"hits": 0, "tokens_saved": 0,
                                          "resident_bytes": 0})
                t["hits"] += c.get("hits", 0)
                t["tokens_saved"] += c.get("tokens_saved", 0)
                t["resident_bytes"] += c.get("resident_bytes", 0)
        out = {"generated_at": now, "totals": totals,
               "chains": chain_rows, "replicas": replicas,
               "pages": pages, "tenants": tenants}
        if any(spill_totals.values()) or pages["spilled"]:
            out["spill"] = spill_totals
        # -- recent trend, only when the TSDB scraper is running ------- #
        if self.obs is not None:
            try:
                hr = self.obs.tsdb.rate(
                    "rtpu_llm_prefix_cache_hits_total", None, 300.0)
                mr = self.obs.tsdb.rate(
                    "rtpu_llm_prefix_cache_misses_total", None, 300.0)
                out["trend"] = {
                    "window_s": 300.0,
                    "hits_per_s": round(hr, 3),
                    "misses_per_s": round(mr, 3),
                    "hit_rate": round(hr / (hr + mr), 4)
                    if hr + mr else None,
                }
            except Exception:
                pass  # trend is garnish; the report stands without it
        return out

    def _rebalance_pipelines_locked(self):
        """A worker just went idle with nothing pending: if another worker
        has pipelined tasks stuck behind a slower one, steal that queue
        back so the idle capacity absorbs it (work stealing keeps deep
        pipelines safe under skewed task durations — even a single queued
        straggler moves, else it waits out the whole task ahead of it)."""
        if self.pending:
            return  # the scheduler will feed the idle worker anyway
        # only steal from behind a task that is demonstrably SLOW: during
        # a fast-draining burst workers dip idle between submissions, and
        # stealing then just churns messages (tasks would finish sooner
        # where they are)
        now = time.monotonic()
        victim = None
        for w in self.workers.values():
            if len(w.queued) >= 1 \
                    and now - getattr(w, "current_started", 0.0) > 0.05 \
                    and (victim is None
                         or len(w.queued) > len(victim.queued)):
                victim = w
        if victim is not None:
            self._steal_queued_locked(victim)
            self._schedule_locked()

    def _ship_renv_locked(self, w: WorkerInfo, renv_spec: dict):
        """Dedicate `w` to this runtime env: ship the env spec + its blobs
        once; the worker applies them process-wide before the task runs
        (messages are ordered on the connection)."""
        hashes = list(renv_spec.get("py_modules", []))
        if renv_spec.get("working_dir"):
            hashes.append(renv_spec["working_dir"])
        blobs = {h: self.renv_registry[h] for h in hashes
                 if h in self.renv_registry}
        missing = [h for h in hashes if h not in blobs]
        if missing:
            # blob lost (e.g. head restarted): fail loudly at dispatch
            self._wsend(w, {"t": "renv", "spec": renv_spec,
                            "blobs": blobs, "missing": missing})
        else:
            self._wsend(w, {"t": "renv", "spec": renv_spec,
                            "blobs": blobs})
        w.env_hash = renv_spec["hash"]

    def _ship_function_locked(self, w: WorkerInfo, fid: str):
        if fid and fid not in w.funcs:
            blob = self.func_registry.get(fid)
            if blob is not None:
                self._wsend(w, {"t": "func", "fid": fid, "blob": blob})
                w.funcs.add(fid)

    def _collect_dep_error_locked(self, spec) -> BaseException:
        for d in spec.dep_oids:
            e = self.directory.get(d)
            if e is not None and e.state == FAILED:
                try:
                    return self.store.get(d, timeout_ms=0)
                except StoreTimeout:
                    pass
                except BaseException as caught:  # the stored exception
                    return caught
        return exc.RayError(f"dependency of {spec.name} failed")

    def _handle_failed_task_locked(self, spec, err: BaseException,
                                   retryable: bool = True):
        if retryable and spec.retries_left > 0:
            from .config import cfg as _cfg
            spec.retries_left -= 1
            self.counters["tasks_retried"] += 1
            self._record_task_locked(spec, "RETRYING", error=repr(err))
            delay = _cfg.task_retry_delay_ms / 1000.0
            if delay > 0 and not spec.is_actor_task:
                # backoff off-lock; resubmission re-enters under it
                def _later(s=spec):
                    time.sleep(delay)
                    with self.lock:
                        if not self._shutdown:
                            self.pending.append(s)
                            self._schedule_locked()
                threading.Thread(target=_later, daemon=True).start()
            elif spec.is_actor_task:
                self._route_actor_task_locked(spec)
            else:
                self.pending.append(spec)
            return
        self.counters["tasks_failed"] += 1
        self._record_task_locked(spec, "FAILED", finished_at=time.time(),
                                 error=repr(err))
        for oid in spec.return_ids:
            self._store_error(oid, err)
            e = self.directory.get(oid)
            if e is not None:
                e.state = FAILED
                e.error_brief = repr(err)
            self._maybe_free_locked(oid)
        self._drop_task_dep_interest_locked(spec)
        self._sweep_failed_deps_locked()   # cascade to pending dependents
        self.cv.notify_all()

    def _drop_task_dep_interest_locked(self, spec):
        holder = f"task:{spec.task_id.hex()}"
        for d in spec.dep_oids:
            self._ref_drop_locked(d, holder)

    def _on_task_done(self, wid: str, msg: dict):
        with self.lock:
            w = self.workers.get(wid)
            if w is None:
                return
            task_id = msg["task_id"]
            spec = None
            if w.actor_id is not None:
                # actor method completion: resources stay held by the actor
                a = self.actors.get(w.actor_id)
                if a is not None:
                    spec = a.running.pop(task_id, None)
            else:
                spec = w.current
                if spec is not None and spec.task_id != task_id:
                    # stale done: a pipelined dispatch was stolen AFTER the
                    # worker had already started it (the steal lost the
                    # race with the predecessor's in-flight done). The
                    # worker is now executing `spec`; its real done is
                    # still coming — record nothing, release nothing.
                    self.events.append(
                        {"name": msg.get("name", "task"), "cat": "task",
                         "ph": "E", "pid": wid, "ts": time.time() * 1e6,
                         "tid": task_id.hex()[:8]})
                    self.cv.notify_all()
                    return
                w.current = None
                if w.queued and not w.blocked:
                    # lease transfers to the already-sent next task; the
                    # worker is executing it as this message is handled
                    self._promote_queued_locked(w)
                else:
                    if w.blocked:
                        w.blocked = False
                    else:
                        self._release_to_node(w)
                    w.holding = {}
                    w.holding_bundle = None
                    w.state = "idle"
                    w.idle_since = time.monotonic()
                    self._rebalance_pipelines_locked()
            self.events.append({"name": msg.get("name", "task"), "cat": "task",
                                "ph": "E", "pid": wid, "ts": time.time() * 1e6,
                                "tid": task_id.hex()[:8]})
            if spec is not None and spec.task_id == task_id:
                if msg["ok"]:
                    self.counters["tasks_finished"] += 1
                    # per-task-name runtime EWMA: the stuck-task
                    # watchdog's notion of "typical" (bounded dict —
                    # oldest name evicted, matching task_records FIFO)
                    dur = msg.get("dur")
                    if isinstance(dur, (int, float)):
                        prev = self._task_ewma.get(spec.name)
                        self._task_ewma[spec.name] = (
                            dur if prev is None
                            else 0.8 * prev + 0.2 * dur)
                        if len(self._task_ewma) > 4096:
                            self._task_ewma.pop(
                                next(iter(self._task_ewma)))
                    self._record_task_locked(spec, "FINISHED",
                                             finished_at=time.time(),
                                             duration_s=msg.get("dur"))
                    loc = self._own_store_loc_locked(w)
                    for oid in spec.return_ids:
                        e = self.directory.get(oid)
                        if e is not None and e.state == PENDING:
                            # (a SPILLED return must stay SPILLED)
                            e.state = READY
                        if e is not None and loc is not None:
                            e.add_location(loc)
                        # a consumer may have dropped its ref while we were
                        # still PENDING; re-check now that we're final
                        self._maybe_free_locked(oid)
                    # dynamic-generator items: deterministic ids + the
                    # producing spec as lineage, so they reconstruct like
                    # regular returns
                    for ob in msg.get("dynamic_items", ()):  # bytes
                        ioid = ObjectID(ob)
                        ie = self.directory.get(ioid)
                        if ie is None:
                            ie = self.directory[ioid] = DirEntry(READY)
                        ie.lineage = spec
                        if loc is not None:
                            ie.add_location(loc)
                        self._maybe_free_locked(ioid)
                    self._drop_task_dep_interest_locked(spec)
                elif msg.get("retryable"):
                    self._handle_failed_task_locked(
                        spec, exc.RayError(msg.get("err", "")), retryable=True)
                else:
                    self.counters["tasks_failed"] += 1
                    self._record_task_locked(spec, "FAILED",
                                             finished_at=time.time(),
                                             error=msg.get("err"))
                    for oid in spec.return_ids:
                        e = self.directory.get(oid)
                        if e is not None:
                            e.state = FAILED
                            e.error_brief = msg.get("err")
                        self._maybe_free_locked(oid)
                    self._drop_task_dep_interest_locked(spec)
                    self._sweep_failed_deps_locked()
            self._schedule_locked()
            self.cv.notify_all()

    # ------------------------------------------------------------------ #
    # actors (reference: gcs_actor_manager.h:352, gcs_actor_scheduler.h:150,
    # transport/actor_task_submitter.h:49)
    # ------------------------------------------------------------------ #

    def create_actor(self, spec: ActorSpec) -> None:
        with self.lock:
            self._create_actor_locked(spec)

    def _create_actor_locked(self, spec: ActorSpec):
        if spec.named:
            if spec.named in self.named_actors:
                raise ValueError(f"actor name {spec.named!r} already taken")
        self.counters["actors_created"] += 1
        a = ActorInfo(spec)
        if spec.named:
            self.named_actors[spec.named] = spec.actor_id
        self.actors[spec.actor_id] = a
        if spec.ready_oid is not None:
            self.directory[spec.ready_oid] = DirEntry(PENDING)
        self._schedule_actor_locked(a)

    def _schedule_actor_locked(self, a: ActorInfo):
        spec = a.spec
        fake = TaskSpec(  # reuse node-picking with a synthetic spec
            task_id=TaskID.from_random(), func_id="", name=spec.name,
            args_blob=b"", dep_oids=[], return_ids=[],
            resources=spec.resources, pg_id=spec.pg_id,
            pg_bundle_index=spec.pg_bundle_index,
            node_affinity=spec.node_affinity,
            node_affinity_soft=spec.node_affinity_soft,
            label_selector=spec.label_selector)
        node = self._pick_node_locked(fake)
        if node is None:
            # retry async until resources appear
            threading.Thread(target=self._retry_actor_schedule,
                             args=(a,), daemon=True).start()
            return
        w = self._spawn_worker_locked(
            node, tpus=spec.resources.get("TPU", 0))
        w.actor_id = spec.actor_id
        a.wid = w.wid
        self._mark_busy(w, node, fake)
        w.state = "starting"
        w.pending_actor = a

    def _retry_actor_schedule(self, a: ActorInfo,
                              timeout: float | None = None):
        from .config import cfg as _cfg
        if timeout is None:
            timeout = _cfg.pg_retry_timeout_s
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            time.sleep(0.05)
            with self.lock:
                if self._shutdown or a.state == "dead":
                    return
                fake = TaskSpec(
                    task_id=TaskID.from_random(), func_id="", name=a.spec.name,
                    args_blob=b"", dep_oids=[], return_ids=[],
                    resources=a.spec.resources, pg_id=a.spec.pg_id,
                    pg_bundle_index=a.spec.pg_bundle_index,
                    node_affinity=a.spec.node_affinity,
                    node_affinity_soft=a.spec.node_affinity_soft,
                    label_selector=a.spec.label_selector)
                if self._pick_node_locked(fake) is not None:
                    self._schedule_actor_locked(a)
                    return
        with self.lock:
            self._fail_actor_locked(a, exc.ActorDiedError(
                f"actor {a.spec.name} could not be scheduled in {timeout}s "
                f"(infeasible or saturated resources: {a.spec.resources})"))

    def _dispatch_actor_locked(self, w: WorkerInfo, a: ActorInfo):
        if a.state == "dead":
            return
        if a.spec.runtime_env and w.env_hash is None:
            self._ship_renv_locked(w, a.spec.runtime_env)
        cls_blob = self.func_registry.get(a.spec.class_id)
        # _wsend keeps ordering with any pass-buffered func/renv ships for
        # this worker (everything lands in the same batch envelope)
        self._wsend(w, {"t": "func", "fid": a.spec.class_id,
                        "blob": cls_blob})
        w.funcs.add(a.spec.class_id)
        self._wsend(w, {"t": "actor_create", "spec": a.spec})
        w.state = "actor"

    def _on_actor_ready(self, wid: str, msg: dict):
        with self.lock:
            a = self.actors.get(msg["actor_id"])
            if a is None:
                return
            if msg["ok"]:
                a.state = "alive"
                self.pubsub.publish("actors", {
                    "actor_id": a.spec.actor_id.hex(), "state": "alive",
                    "name": a.spec.name})
                if a.spec.ready_oid is not None:
                    ro = a.spec.ready_oid
                    e = self.directory.get(ro)
                    if e is not None:
                        e.state = READY
                    self._ready_ok.add(ro)
                    if ro in self.interest and \
                            not self.store.contains(ro):
                        # a __ray_ready__ waiter parked BEFORE init
                        # finished: seal its payload now (later
                        # observers materialize at ref-add)
                        try:
                            self.store.put(ro, True)
                        except Exception:
                            pass  # store full: waiter falls back to
                            # the ensure/locate path
                while a.queue:
                    self._route_actor_task_locked(a.queue.popleft())
            else:
                self._fail_actor_locked(a, exc.ActorDiedError(
                    f"actor {a.spec.name} __init__ failed: {msg.get('err')}"),
                    creation_failed=True)
            self.cv.notify_all()

    def submit_actor_task_spec(self, spec: TaskSpec) -> list[ObjectRef]:
        if self._submitq_on and not self._shutdown:
            return self._queue_submit("actor", spec)
        with self.lock:
            refs = [ObjectRef(o) for o in spec.return_ids]  # interest first
            self._submit_actor_task_locked(spec)
        return refs

    def _submit_actor_task_locked(self, spec: TaskSpec) -> None:
        self.counters["tasks_submitted"] += 1
        self._record_task_locked(spec, "PENDING")
        for oid in spec.return_ids:
            self.directory[oid] = DirEntry(PENDING, lineage=None)
        holder = f"task:{spec.task_id.hex()}"
        for d in spec.dep_oids:
            self.interest.setdefault(d, set()).add(holder)
        self._route_actor_task_locked(spec)

    def _route_actor_task_locked(self, spec: TaskSpec):
        a = self.actors.get(spec.actor_id)
        if a is None or a.state == "dead":
            cause = a.death_cause if a else "actor not found"
            self._handle_failed_task_locked(
                spec, exc.ActorDiedError(
                    f"actor task {spec.name} failed: {cause}"),
                retryable=False)
            return
        if a.state != "alive":
            a.queue.append(spec)
            return
        w = self.workers.get(a.wid)
        if w is None or w.state == "dead":
            a.queue.append(spec)
            return
        self._ship_function_locked(w, spec.func_id)
        a.running[spec.task_id] = spec
        self._record_task_locked(spec, "RUNNING", worker=w.wid,
                                 node=w.node_id.hex(),
                                 started_at=time.time())
        # _wsend: must share the batch with the func ship above when a
        # scheduling pass is open (send failure then surfaces at flush)
        if not self._wsend(w, {"t": "actor_task", "spec": spec}):
            self._on_worker_death(w.wid)

    def _on_actor_worker_death_locked(self, actor_id: ActorID, wid: str):
        a = self.actors.get(actor_id)
        if a is None or a.state == "dead":
            return
        cause = f"actor worker {wid} died"
        # decide per-task: retry only when max_task_retries allows
        running = list(a.running.values())
        a.running.clear()
        can_restart = a.restarts_left != 0
        for spec in running:
            # ray.get returns at object-seal; the done message may still be
            # in flight when a kill lands. A call whose returns are ALL
            # sealed DID complete — failing it would overwrite results a
            # consumer already holds refs to.
            if self._returns_complete_locked(spec):
                self.counters["tasks_finished"] += 1
                self._record_task_locked(spec, "FINISHED",
                                         finished_at=time.time())
                for oid in spec.return_ids:
                    e = self.directory.get(oid)
                    if e is not None and e.state == PENDING:
                        e.state = READY
                    self._maybe_free_locked(oid)
                self._drop_task_dep_interest_locked(spec)
                continue
            if can_restart and a.spec.max_task_retries != 0 and \
                    spec.retries_left > 0:
                spec.retries_left -= 1
                a.queue.appendleft(spec)
            else:
                self._handle_failed_task_locked(
                    spec, exc.ActorDiedError(
                        f"{spec.name}: {cause}"), retryable=False)
        if can_restart:
            if a.restarts_left > 0:
                a.restarts_left -= 1
            a.state = "restarting"
            a.wid = None
            self.pubsub.publish("actors", {
                "actor_id": a.spec.actor_id.hex(), "state": "restarting",
                "name": a.spec.name})
            self._schedule_actor_locked(a)
        else:
            self._fail_actor_locked(a, exc.ActorDiedError(
                f"actor {a.spec.name} died ({cause}) and has no restarts left"))

    def _fail_actor_locked(self, a: ActorInfo, err: BaseException,
                           creation_failed: bool = False):
        a.state = "dead"
        a.death_cause = str(err)
        self.pubsub.publish("actors", {
            "actor_id": a.spec.actor_id.hex(), "state": "dead",
            "name": a.spec.name, "cause": a.death_cause})
        if a.spec.named and self.named_actors.get(a.spec.named) == a.spec.actor_id:
            del self.named_actors[a.spec.named]
        if a.spec.ready_oid is not None:
            ro = a.spec.ready_oid
            self._ready_ok.discard(ro)
            e = self.directory.get(ro)
            if ro in self.interest or self.xfer_pins.get(ro, 0) > 0:
                # a live __ray_ready__ ref reads the real error; its
                # refcount frees the object like any task result. The
                # registry entry stays regardless: once the holder drops
                # and the object is freed, a LATER ref (a ready ref
                # deserialized from an old pickled handle) still needs
                # the error re-materialized
                self._store_error(ro, err)
                if e is not None:
                    e.state = FAILED
                self._ready_failed[ro] = str(err)[:200]
            else:
                # nobody holds a ready ref: a stored error would leak
                # one store object per dead actor forever (ready oids
                # never enter refcounting). Seal-less FAILED keeps a
                # still-present entry loud for dependency scans; the
                # registry lets a late __ray_ready__ ref materialize
                # the real error at ref-add time — including when the
                # entry was already freed by an earlier ready ref's
                # drop (e is None here).
                if e is not None:
                    e.state = FAILED
                    e.error_brief = str(err)[:200]
                self._ready_failed[ro] = str(err)[:200]
            self._sweep_failed_deps_locked()
        for spec in list(a.queue) + list(a.running.values()):
            self._handle_failed_task_locked(spec, err, retryable=False)
        a.queue.clear()
        a.running.clear()
        self.cv.notify_all()

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        with self.lock:
            a = self.actors.get(actor_id)
            if a is None:
                return
            if no_restart:
                a.restarts_left = 0
            wid = a.wid
            w = self.workers.get(wid) if wid else None
            if w is None and no_restart and a.state in ("pending",
                                                        "restarting"):
                # no worker to kill yet — mark dead so the retry threads
                # stop and queued tasks fail instead of resurrecting it
                self._fail_actor_locked(a, exc.ActorDiedError(
                    f"actor {a.spec.name} was killed before being scheduled"))
                return
        if w is not None and w.proc is not None:
            try:
                w.proc.kill()
            except Exception:
                pass  # already dead
        # death is observed by the recv loop EOF → _on_worker_death

    def get_actor_by_name(self, name: str):
        with self.lock:
            aid = self.named_actors.get(name)
            if aid is None:
                raise ValueError(f"no actor named {name!r}")
            return self.actors[aid].spec

    # ------------------------------------------------------------------ #
    # placement groups (reference: gcs_placement_group_mgr.h:232,
    # policy/bundle_scheduling_policy.h:31)
    # ------------------------------------------------------------------ #

    def create_placement_group(self, bundles: list[dict[str, float]],
                               strategy: str, name: str = "",
                               pg_id: PlacementGroupID | None = None,
                               same_label: str | None = None,
                               bundle_selectors: list[dict | None] | None = None,
                               ) -> PlacementGroupState:
        # pg_id is supplied on session restore so actor specs that
        # reference the old group stay valid (gcs_store.restore)
        pg = PlacementGroupState(pg_id or PlacementGroupID.from_random(),
                                 bundles, strategy, name,
                                 same_label=same_label,
                                 bundle_selectors=bundle_selectors)
        with self.lock:
            self.pgs[pg.pg_id] = pg
            self._try_reserve_pg_locked(pg)
        if pg.state != "created":
            threading.Thread(target=self._retry_pg, args=(pg,),
                             daemon=True).start()
        return pg

    def _try_reserve_pg_locked(self, pg: PlacementGroupState) -> bool:
        alive = [n for n in self.nodes.values() if n.alive]
        if pg.same_label:
            # gang-to-one-label-group (whole-slice) placement: only nodes
            # carrying the label compete, and all bundles must land inside
            # one label value's node group (one ICI domain).
            groups: dict[str, list[NodeInfo]] = {}
            for n in alive:
                val = n.labels.get(pg.same_label)
                if val is not None:
                    groups.setdefault(val, []).append(n)
            plan = None
            # prefer the busiest feasible group so idle slices stay whole
            # for future gangs (pack-onto-used, SURVEY §2.4)
            for val in sorted(
                    groups,
                    key=lambda v: -max(n.utilization() for n in groups[v])):
                plan = self._plan_pg_locked(groups[val], pg)
                if plan is not None:
                    break
        else:
            plan = self._plan_pg_locked(alive, pg)
        if plan is None:
            return False
        # commit
        for b, n in plan:
            b.node_id = n.node_id
            b.avail = dict(b.resources)
            for k, v in b.resources.items():
                n.resources_avail[k] = n.resources_avail.get(k, 0) - v
        pg.state = "created"
        pg.ready_event.set()
        return True

    def _plan_pg_locked(self, nodes: list[NodeInfo], pg: PlacementGroupState,
                        ) -> Optional[list[tuple[BundleState, NodeInfo]]]:
        """Bundle→node assignment over `nodes` per pg.strategy, or None if
        infeasible. Does not mutate node state."""
        plan: list[tuple[BundleState, NodeInfo]] = []
        avail = {n.node_id: dict(n.resources_avail) for n in nodes}
        selectors = pg.bundle_selectors

        def eligible(n: NodeInfo, bi: int) -> bool:
            sel = selectors[bi] if bi < len(selectors) else None
            return sel is None or all(
                n.labels.get(k) == v for k, v in sel.items())

        def fits(nid, res):
            return all(avail[nid].get(k, 0) >= v - 1e-9 for k, v in res.items())

        def take(nid, res):
            for k, v in res.items():
                avail[nid][k] = avail[nid].get(k, 0) - v

        strategy = pg.strategy
        if strategy in ("PACK", "STRICT_PACK"):
            # try to fit all bundles on one node (requirement for STRICT_PACK)
            packed = False
            for n in sorted(nodes, key=lambda n: n.utilization()):
                trial = dict(avail[n.node_id])
                ok = True
                for b in pg.bundles:
                    if eligible(n, b.index) and all(
                            trial.get(k, 0) >= v - 1e-9
                            for k, v in b.resources.items()):
                        for k, v in b.resources.items():
                            trial[k] = trial.get(k, 0) - v
                    else:
                        ok = False
                        break
                if ok:
                    for b in pg.bundles:
                        plan.append((b, n))
                        take(n.node_id, b.resources)
                    packed = True
                    break
            if not packed:
                if strategy == "STRICT_PACK":
                    return None
                # soft PACK: greedy spill
                for b in pg.bundles:
                    tgt = next((n for n in nodes
                                if eligible(n, b.index)
                                and fits(n.node_id, b.resources)), None)
                    if tgt is None:
                        return None
                    plan.append((b, tgt))
                    take(tgt.node_id, b.resources)
        else:  # SPREAD / STRICT_SPREAD
            used_nodes: set[NodeID] = set()
            for b in pg.bundles:
                cands = [n for n in nodes
                         if eligible(n, b.index)
                         and fits(n.node_id, b.resources)]
                fresh = [n for n in cands if n.node_id not in used_nodes]
                if strategy == "STRICT_SPREAD":
                    cands = fresh
                elif fresh:
                    cands = fresh
                if not cands:
                    return None
                tgt = min(cands, key=lambda n: n.utilization())
                plan.append((b, tgt))
                take(tgt.node_id, b.resources)
                used_nodes.add(tgt.node_id)
        return plan

    def _retry_pending_pgs_locked(self) -> None:
        """Re-attempt every pending PG. Called when a node registers: the
        _retry_pg polling thread gives up after pg_retry_timeout_s, but a
        cloud TPU slice can take minutes to boot — registration must be
        able to place gangs that outlived the poller."""
        for pg in self.pgs.values():
            if pg.state == "pending":
                self._try_reserve_pg_locked(pg)

    def _retry_pg(self, pg: PlacementGroupState,
                  timeout: float | None = None):
        from .config import cfg as _cfg
        if timeout is None:
            timeout = _cfg.pg_retry_timeout_s
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            time.sleep(0.05)
            with self.lock:
                if self._shutdown or pg.state != "pending":
                    return
                if self._try_reserve_pg_locked(pg):
                    self._schedule_locked()
                    return

    def remove_placement_group(self, pg_id: PlacementGroupID):
        with self.lock:
            pg = self.pgs.get(pg_id)
            if pg is None or pg.state == "removed":
                return
            if pg.state == "created":
                for b in pg.bundles:
                    n = self.nodes.get(b.node_id)
                    if n is not None and n.alive:
                        for k, v in b.resources.items():
                            n.resources_avail[k] = \
                                n.resources_avail.get(k, 0) + v
            pg.state = "removed"
            pg.ready_event.set()  # wake pg_wait-ers; they check state
            self._schedule_locked()

    # ------------------------------------------------------------------ #
    # nodes (cluster fixture support; reference: gcs_node_manager.h:49)
    # ------------------------------------------------------------------ #

    def add_node(self, resources: dict[str, float],
                 labels: dict[str, str] | None = None,
                 name: str = "") -> NodeID:
        node = NodeInfo(NodeID.from_random(), resources, labels, name)
        with self.lock:
            self.nodes[node.node_id] = node
            self._retry_pending_pgs_locked()
            self._schedule_locked()
        self.pubsub.publish("nodes", {"node_id": node.node_id.hex(),
                                      "event": "added", "name": node.name})
        return node.node_id

    def remove_node(self, node_id: NodeID):
        with self.lock:
            node = self.nodes.get(node_id)
            if node is None or not node.alive:
                return
            if node is self.head_node:
                raise ValueError("cannot remove the head node")
            node.alive = False
            wids = list(node.workers)
            # placement bundles on this node are lost → re-reserve elsewhere
            for pg in self.pgs.values():
                if pg.state == "created" and any(
                        b.node_id == node_id for b in pg.bundles):
                    for b in pg.bundles:
                        n = self.nodes.get(b.node_id)
                        if n is not None and n.alive and n.node_id != node_id:
                            for k, v in b.resources.items():
                                n.resources_avail[k] += v
                        b.node_id = None
                    pg.state = "pending"
                    pg.ready_event.clear()
                    threading.Thread(target=self._retry_pg, args=(pg,),
                                     daemon=True).start()
        self.pubsub.publish("nodes", {"node_id": node_id.hex(),
                                      "event": "removed", "name": node.name})
        for wid in wids:
            with self.lock:
                w = self.workers.get(wid)
            if w is not None:
                try:
                    w.proc.kill()
                except Exception:
                    pass  # already dead
                self._on_worker_death(wid)

    # ------------------------------------------------------------------ #
    # get / wait / cancel (driver side)
    # ------------------------------------------------------------------ #

    def get(self, refs, timeout: float | None = None):
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        deadline = None if timeout is None else time.monotonic() + timeout
        if len(ref_list) > 1:
            # bulk fast path: park in chunked wait_sealed calls (GIL
            # released, one futex wait services whichever result seals
            # first) until everything is readable, THEN materialize in
            # order — instead of a blocking store.get per ref, each of
            # which woke the driver on every unrelated seal
            self._wait_all_present([r.id() for r in ref_list], deadline)
        out = []
        for r in ref_list:
            out.append(self._get_one(r.id(), deadline))
        return out[0] if single else out

    def _sealed_is_exception(self, oid: ObjectID) -> bool:
        """Peek a sealed object's frame flags without deserializing."""
        view = self.store.get_raw(oid, timeout_ms=0)
        if view is None:
            return False
        try:
            from .object_store import _FLAG_EXCEPTION
            return bool(view[0] & _FLAG_EXCEPTION)
        finally:
            del view
            self.store.release(oid)

    def _spilled_is_exception(self, oid: ObjectID) -> bool:
        """Peek a spilled frame's flags byte (same wire framing)."""
        try:
            from .object_store import _FLAG_EXCEPTION
            with open(self.spill._path(oid), "rb") as f:
                b = f.read(1)
            return bool(b and b[0] & _FLAG_EXCEPTION)
        except OSError:
            return False

    def _satisfiable_elsewhere_locked(self, oid: ObjectID) -> bool:
        """True when _get_one can resolve `oid` without a LOCAL seal:
        spilled to disk, terminally failed, or a live remote copy that
        the per-ref loop will pull over."""
        e = self.directory.get(oid)
        if e is None:
            return False
        if e.state in (SPILLED, FAILED):
            return True
        if e.state == READY and e.locations:
            alive = {n.node_id.hex() for n in self.nodes.values()
                     if n.alive}
            return bool(e.locations & alive)
        return False

    def _wait_all_present(self, oids, deadline):
        """Block until every oid the ordered materialization loop will
        actually reach is readable (sealed locally, spilled, failed, or
        pullable from a live remote copy). Sequential-get parity: a
        stored task error at index j stops this wait from blocking on
        anything at or past j — an error ahead of a never-completing ref
        must surface now, not after the hang. Returns on deadline expiry
        and leaves the per-ref timeout error to _get_one. The growing
        slice only bounds how often directory states are re-checked and
        evicted READY objects re-ensured; a seal wakes the wait
        immediately regardless."""
        flags = self.store.wait_sealed(oids, len(oids), 0)
        missing = [(i, o) for i, (o, f) in enumerate(zip(oids, flags))
                   if not f]
        err_before = len(oids)
        if missing:
            with self.lock:
                still = []
                for i, o in missing:
                    if not self._satisfiable_elsewhere_locked(o):
                        still.append((i, o))
                        continue
                    e = self.directory.get(o)
                    if e is not None and e.state == FAILED:
                        # terminally failed with NO sealed/spilled frame
                        # (e.g. a lost spill with no lineage): _get_one
                        # raises here — never block past this index
                        err_before = min(err_before, i)
                missing = still
        if not missing:
            return
        # index of the first already-errored ref: only the prefix before
        # it must resolve before _get_one raises it in order. Peeked only
        # now that we know we'd otherwise block, and only up to the last
        # missing index.
        miss_idx = {i for i, _ in missing}
        for i in range(min(err_before, missing[-1][0])):
            if i in miss_idx:
                continue
            present_err = (self._sealed_is_exception(oids[i]) if flags[i]
                           else self._spilled_is_exception(oids[i]))
            if present_err:
                err_before = i
                break
        missing = [(i, o) for i, o in missing if i < err_before]
        slice_ms = 10
        next_ensure = 0.0
        while missing:
            if deadline is not None:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    return
                slice_ms = min(slice_ms, max(1, int(remain * 1000)))
            got = self.store.wait_sealed([o for _, o in missing],
                                         len(missing), slice_ms)
            now = time.monotonic()
            do_ensure = now >= next_ensure
            if do_ensure:
                next_ensure = now + 0.2
            still = []
            with self.lock:
                for (i, o), f in zip(missing, got):
                    if f:
                        if self._sealed_is_exception(o):
                            err_before = min(err_before, i)
                        continue
                    if self._satisfiable_elsewhere_locked(o):
                        e = self.directory.get(o)
                        if (e is not None and e.state == FAILED) or \
                                self._spilled_is_exception(o):
                            err_before = min(err_before, i)
                        continue
                    if do_ensure:
                        # evicted READY objects need lineage re-exec,
                        # same as get() (object_recovery_manager.h:43)
                        self._ensure_available_locked(o)
                    still.append((i, o))
                if do_ensure and still:
                    self._schedule_locked()
            missing = [(i, o) for i, o in still if i < err_before]
            slice_ms = min(slice_ms * 2, 200)

    def _mux_nudge(self, oid: ObjectID):
        """Completion-mux recovery hook (core/completion.py): an awaited
        oid stayed unsealed past the nudge window — re-ensure it (lineage
        re-execution of evicted objects) and, when a live remote copy
        exists, pull it off-thread so the mux never blocks on transfer
        IO."""
        pull = False
        with self.lock:
            e = self.directory.get(oid)
            if e is None or e.state == PENDING:
                # producer still running (the common case for a slow
                # awaited task): nothing to recover, and a scheduling
                # pass per nudge would just contend with the hot path
                return
            self._ensure_available_locked(oid)
            e = self.directory.get(oid)
            if e is not None and e.state == PENDING:
                # the re-ensure requeued its lineage: run one pass so
                # the reconstruction actually dispatches
                self._schedule_locked()
            elif e is not None and e.state == READY and e.locations:
                pull = True
        if pull:
            self._rpc_pool.submit(self._fetch_remote, oid)

    def _recover_lost_spill(self, oid: ObjectID) -> None:
        """A SPILLED object's file is gone and no live node holds a copy:
        flip to the lineage path (reconstructable) or FAILED (loud)."""
        with self.lock:
            e = self.directory.get(oid)
            if e is None or e.state != SPILLED:
                return
            alive = {n.node_id.hex() for n in self.nodes.values()
                     if n.alive}
            if (e.locations or set()) & alive or self.spill.contains(oid):
                return  # a holder is still up; keep pulling
            if e.lineage is not None:
                e.state = READY      # reuse the evicted-object recovery
                e.locations = None
                self._ensure_available_locked(oid)
                self._schedule_locked()
            else:
                self._store_error(oid, exc.ObjectLostError(
                    f"object {oid} was spilled on a node that died and "
                    f"has no lineage to reconstruct from"))
                e.state = FAILED
                self._sweep_failed_deps_locked()

    def _fetch_remote(self, oid: ObjectID) -> bool:
        """Pull an object produced on an own-store node into the head's
        store (object_transfer.py); False when no remote copy exists."""
        with self.lock:
            e = self.directory.get(oid)
            locs = set(e.locations or ()) if e is not None else set()
            locs.discard(self.head_node.node_id.hex())
            addrs = [n.data_addr for n in self.nodes.values()
                     if n.alive and n.own_store and n.data_addr
                     and n.node_id.hex() in locs]
        from .object_transfer import fetch_resilient
        try:
            return fetch_resilient(addrs, oid, self.store, self.spill)
        except OSError:
            return False

    def _get_one(self, oid: ObjectID, deadline: float | None):
        while True:
            slice_ms = 200
            if deadline is not None:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    raise exc.GetTimeoutError(
                        f"ray_tpu.get timed out waiting for {oid}")
                slice_ms = max(1, min(slice_ms, int(remain * 1000)))
            try:
                value = self.store.get(oid, timeout_ms=slice_ms)
            except StoreTimeout:
                with self.lock:
                    e = self.directory.get(oid)
                    spilled = e is not None and e.state == SPILLED
                if spilled:
                    # objects bigger than the store never leave disk
                    try:
                        return self.spill.load(oid)
                    except FileNotFoundError:
                        # spilled on an own-store NODE: pull it over; if
                        # every holder died, reconstruct via lineage or
                        # fail loudly — never spin silently
                        if not self._fetch_remote(oid):
                            self._recover_lost_spill(oid)
                        continue
                    except exc.RayTaskError as e:
                        raise e.as_instanceof_cause() from e
                if self._fetch_remote(oid):
                    continue  # pulled into the local store; next get hits
                with self.lock:
                    self._ensure_available_locked(oid)
                    self._schedule_locked()
                continue
            except exc.RayTaskError as e:
                raise e.as_instanceof_cause() from e
            return value

    def wait(self, refs, num_returns=1, timeout: float | None = None,
             fetch_local=True):
        # event-driven: one multi-oid futex wait (store.wait_sealed)
        # services whichever result seals first — a completion wakes this
        # waiter immediately instead of on the next 5ms poll boundary.
        # The growing slice only bounds how often directory states
        # (FAILED/SPILLED never seal in shm) are re-checked and evicted
        # READY objects re-ensured.
        ref_list = list(refs)
        if num_returns > len(ref_list):
            raise ValueError("num_returns exceeds number of refs")
        deadline = None if timeout is None else time.monotonic() + timeout
        ready: list[ObjectRef] = []
        pending = list(ref_list)
        slice_ms = 0          # first round is a non-blocking scan
        next_ensure = 0.0
        while len(ready) < num_returns and pending:
            if deadline is not None and slice_ms:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    break
                slice_ms = min(slice_ms, max(1, int(remain * 1000)))
            flags = self.store.wait_sealed(
                [r.id() for r in pending],
                num_returns - len(ready), slice_ms)
            now = time.monotonic()
            do_ensure = now >= next_ensure
            if do_ensure:
                next_ensure = now + 0.2
            still = []
            with self.lock:
                for r, f in zip(pending, flags):
                    if f:
                        ready.append(r)
                        continue
                    e = self.directory.get(r.id())
                    if e is not None and e.state in (FAILED, SPILLED):
                        # errors count as ready; spilled objects are
                        # readable from disk
                        ready.append(r)
                        continue
                    if do_ensure:
                        # evicted-but-READY objects need lineage re-exec,
                        # same as get() (object_recovery_manager.h:43)
                        self._ensure_available_locked(r.id())
                    still.append(r)
                if do_ensure and still:
                    self._schedule_locked()
            pending = still
            if deadline is not None and time.monotonic() >= deadline:
                break
            slice_ms = min(max(slice_ms * 2, 2), 50)  # backoff fallback
        # reference contract: at most num_returns refs in ready; extra
        # already-ready refs stay in the remaining list
        return ready[:num_returns], ready[num_returns:] + pending

    def cancel(self, ref: ObjectRef, force: bool = False,
               recursive: bool = True):
        # queued driver submits are invisible to the scans below: admit
        # them first so a cancel-right-after-submit finds its task
        self._drain_submit_q()
        with self.lock:
            # pending?
            for spec in list(self.pending):
                if ref.id() in spec.return_ids:
                    self.pending.remove(spec)
                    self._handle_failed_task_locked(
                        spec, exc.TaskCancelledError(
                            f"task {spec.name} was cancelled"),
                        retryable=False)
                    return
            # running?
            for w in self.workers.values():
                spec = w.current
                if spec is not None and ref.id() in spec.return_ids:
                    spec.retries_left = 0
                    if force:
                        try:
                            w.proc.kill()
                        except Exception:
                            pass  # already dead
                    else:
                        w.send({"t": "cancel", "task_id": spec.task_id})
                    return
                # pipelined behind a running task: steal it back and fail
                for item in list(w.queued):
                    s, nonce = item
                    if ref.id() in s.return_ids:
                        w.queued.remove(item)
                        w.send({"t": "steal", "nonces": [nonce]})
                        self._handle_failed_task_locked(
                            s, exc.TaskCancelledError(
                                f"task {s.name} was cancelled"),
                            retryable=False)
                        return

    # ------------------------------------------------------------------ #
    # introspection
    # ------------------------------------------------------------------ #

    def cluster_resources(self) -> dict[str, float]:
        with self.lock:
            out: dict[str, float] = {}
            for n in self.nodes.values():
                if n.alive:
                    for k, v in n.resources_total.items():
                        out[k] = out.get(k, 0) + v
            return out

    def available_resources(self) -> dict[str, float]:
        with self.lock:
            out: dict[str, float] = {}
            for n in self.nodes.values():
                if n.alive:
                    for k, v in n.resources_avail.items():
                        out[k] = out.get(k, 0) + v
            return out

    def node_table(self) -> list[dict]:
        with self.lock:
            return [
                {"NodeID": n.node_id.hex(), "Alive": n.alive,
                 "Resources": dict(n.resources_total),
                 "Available": dict(n.resources_avail),
                 "Labels": dict(n.labels), "NodeName": n.name}
                for n in self.nodes.values()
            ]

    def record_trace_span(self, rec: dict) -> None:
        """A completed trace span (util/tracing.py) enters the timeline as
        a chrome complete event whose args carry the trace/span/parent ids
        — flow-stitchable across processes (reference:
        tracing_helper.py:293 context-in-metadata)."""
        with self.lock:
            self.events.append({
                "name": rec.get("name", "span"), "cat": "trace",
                "ph": "X", "pid": rec.get("task_id", "driver"),
                "ts": rec["start_s"] * 1e6,
                "dur": rec.get("dur_s", 0.0) * 1e6,
                "args": {**rec.get("args", {}),
                         **{k: rec[k] for k in
                            ("trace_id", "span_id", "parent_id",
                             "request_id")
                            if rec.get(k) is not None}}})

    def timeline(self) -> list[dict]:
        with self.lock:
            return list(self.events)

    # ------------------------------------------------------------------ #
    # flight recorder (core/flight.py) cluster collection
    # ------------------------------------------------------------------ #

    def _pull_from_peers(self, make_msg, pulls: dict,
                         evt: threading.Event, timeout_s: float,
                         wids: Optional[list] = None):
        """Shared nonce-pull machinery behind flight_collect and
        stack_collect: register a nonce per connected worker/driver in
        `pulls` (the dict the matching reply handler fills), send
        ``make_msg(nonce)`` to each, and wait out the deadline on
        `evt`. Returns ({nonce: {"snap"}}, {nonce: wid}) for the peers
        that were actually sent to; late repliers are dropped at
        cleanup. Never waits under the scheduler lock."""
        with self.lock:
            targets = [w for w in self.workers.values()
                       if w.conn is not None and w.state != "dead"
                       and (wids is None or w.wid in wids)]
        mine: dict[bytes, dict] = {}
        names: dict[bytes, str] = {}
        for w in targets:
            nonce = os.urandom(12)
            rec = {"snap": None}
            pulls[nonce] = rec
            mine[nonce] = rec
            names[nonce] = w.wid
            if not w.send(make_msg(nonce)):
                pulls.pop(nonce, None)
                mine.pop(nonce, None)
                names.pop(nonce, None)
        deadline = time.monotonic() + timeout_s
        try:
            while any(r["snap"] is None for r in mine.values()):
                remain = deadline - time.monotonic()
                if remain <= 0:
                    break
                evt.wait(timeout=min(0.1, remain))
                evt.clear()
        finally:
            for nonce in mine:
                pulls.pop(nonce, None)
        return mine, names

    def flight_collect(self, timeout_s: float = 3.0,
                       stats_only: bool = False) -> list[dict]:
        """Pull every live worker's flight-recorder ring (or just its
        stats) over the control plane, plus this process's own. Each
        remote snapshot carries ``offset_ns`` — its monotonic clock
        minus ours, estimated through the wall-clock bridge (see the
        flight_ring handler) and clamped to 0 for same-host clocks — so
        export_chrome can stitch all tracks onto the head clock.
        Dead/unresponsive workers are skipped at the deadline;
        collection never blocks the scheduler lock."""
        local = flight.snapshot(stats_only) or flight.stats()
        local["offset_ns"] = 0
        snaps = [local]
        pulls, _ = self._pull_from_peers(
            lambda nonce: {"t": "flight_pull", "nonce": nonce,
                           "stats_only": stats_only},
            self._flight_pulls, self._flight_evt, timeout_s)
        snaps.extend(r["snap"] for r in pulls.values()
                     if r["snap"] is not None)
        return snaps

    def flight_stats(self) -> list[dict]:
        """Per-process recorder health (events recorded/dropped, channel
        endpoint counters) for state.summary(). stats_only pulls are
        tiny frames answered straight from each recv loop; the short
        deadline bounds how long a summary poll can stall on one
        backlogged worker (it is skipped, not waited out)."""
        out = []
        for snap in self.flight_collect(timeout_s=0.5, stats_only=True):
            cnt = snap.get("counters", {})
            out.append({
                "proc": snap.get("proc", ""), "pid": snap.get("pid"),
                "recorded": snap.get("recorded", 0),
                "dropped": snap.get("dropped", 0),
                "bad": snap.get("bad", 0),
                "chan_open": cnt.get("chan_open", 0),
                "chan_closed": cnt.get("chan_closed", 0),
            })
        return out

    def flight_timeline(self, since_ns: int = 0) -> dict:
        """Cluster-stitched Chrome-trace/Perfetto object: every
        process's flight ring on one clock, plus the span-tracing
        timeline events merged in (state.timeline(flight=True)).
        Span events are wall-clock stamped — rebase them onto the head
        monotonic microseconds the flight events use, so both layers
        land on one Perfetto timeline."""
        trace = flight.export_chrome(self.flight_collect(),
                                     since_ns=since_ns)
        delta_us = (time.monotonic_ns() / 1000.0
                    - time.time_ns() / 1000.0)
        for ev in self.timeline():
            ev = dict(ev)
            ev["ts"] = float(ev.get("ts", 0.0)) + delta_us
            if ev["ts"] * 1000.0 >= since_ns:
                trace["traceEvents"].append(ev)
        return trace

    # ------------------------------------------------------------------ #
    # stall doctor (core/stacks.py): live stacks, stuck-task watchdog,
    # wait-graph deadlock detection
    # ------------------------------------------------------------------ #

    def stack_collect(self, timeout_s: float = 3.0,
                      wids: Optional[list] = None,
                      include_stacks: bool = True,
                      include_local: bool = True):
        """Pull live thread stacks (+ wait-beacon/task annotations) from
        every connected worker AND driver over the control plane, plus
        this process's own. Replies are built on each peer's recv thread
        (the flight_pull precedent), so a dump succeeds even when the
        target's executor threads are wedged — which is exactly when it
        is needed. Returns (snapshots, unresponsive_wids); dead or
        backlogged peers are skipped at the deadline, never waited out
        under the scheduler lock."""
        snaps = [stacks.capture(include_stacks)] if include_local else []
        pulls, names = self._pull_from_peers(
            lambda nonce: {"t": "stack_dump", "nonce": nonce,
                           "no_stacks": not include_stacks},
            self._stack_pulls, self._stack_evt, timeout_s, wids=wids)
        unresponsive = [names[n] for n, r in pulls.items()
                        if r["snap"] is None]
        for nonce, r in pulls.items():
            if r["snap"] is not None:
                r["snap"]["wid"] = names[nonce]
                snaps.append(r["snap"])
        return snaps, unresponsive

    def _stall_maps_locked(self):
        """Resolution tables for snapshot annotation + the wait-graph
        fold: task lo48 -> its state record, and PENDING-object lo48 ->
        the lo48 of the task whose lineage produces it."""
        task_by48 = {}
        for tid_key, rec in self.task_records.items():
            task_by48[flight.lo48(tid_key)] = rec
        obj_task48 = {}
        obj_hex48 = {}
        for oid, e in self.directory.items():
            if e.state == PENDING:
                obj_hex48[flight.lo48(oid)] = oid.hex()
                if e.lineage is not None:
                    obj_task48[flight.lo48(oid)] = \
                        flight.lo48(e.lineage.task_id)
        return task_by48, obj_task48, obj_hex48

    @staticmethod
    def _fold_producers(snaps: list) -> dict:
        """Channel-base lo48 -> (pid, tid) across every collected
        process's endpoint table."""
        producers = {}
        for s in snaps:
            for b48, tid in (s.get("chan_producers") or {}).items():
                producers[int(b48)] = (s["pid"], int(tid))
        return producers

    def _annotate_snaps(self, snaps: list, maps=None) -> None:
        """Resolve each thread's task48/wait id48 against what the head
        knows: task names, PENDING objects and their producing tasks,
        channel producer endpoints across every collected process.
        `maps` lets hang_report share one _stall_maps_locked build (and
        one lock hold) with cycle detection."""
        if maps is None:
            with self.lock:
                maps = self._stall_maps_locked()
        task_by48, obj_task48, obj_hex48 = maps
        producers = self._fold_producers(snaps)
        proc_of = {}
        for s in snaps:
            proc_of[s["pid"]] = s.get("proc") or f"pid-{s['pid']}"
        for s in snaps:
            for t in s.get("threads", ()):
                t48 = t.get("task48")
                if t48:
                    rec = task_by48.get(t48)
                    if rec is not None:
                        t["task"] = (f"{rec.get('name')} "
                                     f"[{rec.get('task_id', '')[:12]}]")
                w = t.get("wait")
                if not w:
                    continue
                id48 = w.get("id48", 0)
                tgt = producers.get(id48)
                if tgt is not None:
                    w["target"] = (f"channel 0x{id48:012x} (producer: "
                                   f"{proc_of.get(tgt[0], tgt[0])} "
                                   f"thread {tgt[1]})")
                    continue
                prod48 = obj_task48.get(id48)
                if prod48 is not None:
                    rec = task_by48.get(prod48)
                    if rec is not None:
                        w["target"] = (
                            f"object {obj_hex48.get(id48, '')[:12]} <- "
                            f"task {rec.get('name')} "
                            f"({rec.get('state')} on "
                            f"{rec.get('worker', '?')})")
                        continue
                if id48 in obj_hex48:
                    w["target"] = f"object {obj_hex48[id48][:12]}"

    def stack_report(self, timeout_s: float = 3.0,
                     wids: Optional[list] = None,
                     include_stacks: bool = True) -> dict:
        """Cluster-wide annotated live-stack report
        (state.stack_report() / `cli stack` / GET /api/stacks)."""
        snaps, unresponsive = self.stack_collect(
            timeout_s=timeout_s, wids=wids,
            include_stacks=include_stacks)
        self._annotate_snaps(snaps)
        return {"procs": snaps, "unresponsive": unresponsive,
                "collected_at": time.time()}

    def _detect_wait_cycles(self, snaps: list,
                            min_wait_s: float = 1.0,
                            maps=None) -> list[dict]:
        """Fold wait beacons + channel endpoint tables + the object
        directory into a waiter->producer graph and return its cycles.
        Nodes are (pid, tid) threads; each waiting thread has at most
        one outgoing edge (what it waits on resolves to at most one
        producing thread), so cycle detection is one pass over a
        functional graph.

        Only waits parked at least ``min_wait_s`` become edges: the
        snapshots are not simultaneous (each peer captures when its
        recv loop reaches the dump, up to the collection timeout
        apart), so millisecond-transient waits on a healthy
        backpressured pipeline could otherwise pair up into a phantom
        cycle. A real deadlock is sustained by definition and crosses
        any such floor."""
        producers = self._fold_producers(snaps)
        threads = {(s["pid"], t["tid"]): (s, t)
                   for s in snaps for t in s.get("threads", ())}
        task_thread = {t["task48"]: (s["pid"], t["tid"])
                       for s in snaps for t in s.get("threads", ())
                       if t.get("task48")}
        if maps is None:
            with self.lock:
                maps = self._stall_maps_locked()
        _, obj_task48, _ = maps
        edges = {}
        for key, (s, t) in threads.items():
            w = t.get("wait")
            if not w or w.get("for_s", 0.0) < min_wait_s:
                continue
            id48 = w.get("id48", 0)
            tgt = producers.get(id48)
            if tgt is None:
                prod48 = obj_task48.get(id48)
                if prod48 is not None:
                    tgt = task_thread.get(prod48)
            if tgt is not None and tgt in threads and tgt != key:
                edges[key] = tgt
        done: set = set()
        cycles = []
        for start in list(edges):
            if start in done:
                continue
            path, seen_at = [], {}
            node = start
            while node in edges and node not in done \
                    and node not in seen_at:
                seen_at[node] = len(path)
                path.append(node)
                node = edges[node]
            if node in seen_at:
                cyc = path[seen_at[node]:]
                parties = []
                for pid, tid in cyc:
                    s, t = threads[(pid, tid)]
                    w = t.get("wait", {})
                    parties.append({
                        "proc": s.get("proc") or f"pid-{pid}",
                        "pid": pid, "tid": tid,
                        "thread_name": t.get("name"),
                        "task": t.get("task"),
                        "wait_kind": w.get("kind"),
                        "target": w.get("target")
                        or f"0x{w.get('id48', 0):012x}",
                    })
                cycles.append({"parties": parties})
            done.update(path)
        return cycles

    def hang_report(self, timeout_s: float = 3.0,
                    min_wait_s: float = 1.0) -> dict:
        """One-shot hang diagnosis (state.hang_report() / `cli doctor`):
        watchdog-flagged stuck tasks (with their attached worker
        stacks), suspected wait-graph deadlocks naming every party, and
        watchdog health. The annotated stack snapshots the diagnosis
        was computed from ride along as ``procs`` so consumers (`cli
        doctor`) render them without a second cluster-wide pull."""
        snaps, unresponsive = self.stack_collect(timeout_s=timeout_s)
        with self.lock:
            maps = self._stall_maps_locked()
        # one maps build + lock hold serves annotation AND the cycle fold
        self._annotate_snaps(snaps, maps=maps)
        cycles = self._detect_wait_cycles(snaps, min_wait_s=min_wait_s,
                                          maps=maps)
        report = {"procs": snaps, "unresponsive": unresponsive,
                  "collected_at": time.time()}
        now = time.time()
        with self.lock:
            # one DEADLOCK event per incident: a poller (dashboard
            # auto-refresh, a doctor loop) re-observing the same
            # sustained cycle must not inflate the flight ring. A key is
            # forgotten (so a recurrence re-reports) only when a FULL
            # collection no longer shows it — a cycle merely invisible
            # because one party missed the reply deadline must not be
            # re-announced when it reappears.
            keys = [frozenset((p["pid"], p["tid"])
                    for p in cyc["parties"]) for cyc in cycles]
            for key, cyc in zip(keys, cycles):
                if key not in self._seen_cycles:
                    flight.evt(flight.DEADLOCK, len(cyc["parties"]))
            if not unresponsive:
                self._seen_cycles &= set(keys)
            self._seen_cycles |= set(keys)
        with self.lock:
            stuck = []
            for rec in self.task_records.values():
                if rec.get("stuck") and rec.get("state") == "RUNNING":
                    r = dict(rec)
                    r["running_s"] = now - rec.get("started_at", now)
                    stuck.append(r)
            wd = dict(self._watchdog)
        return {"stuck_tasks": stuck, "deadlocks": cycles,
                "watchdog": wd, "procs": report["procs"],
                "unresponsive": report["unresponsive"],
                "collected_at": report["collected_at"]}

    def watchdog_health(self) -> dict:
        with self.lock:
            return dict(self._watchdog)

    def _stall_watchdog_loop(self):
        from .config import cfg
        if not cfg.stall_watchdog:
            return
        period = max(0.1, cfg.stall_watchdog_period_s)
        while not self._shutdown:
            time.sleep(period)
            if self._shutdown:
                return
            try:
                self._stall_watchdog_scan()
            except Exception:
                pass  # diagnosis must never take down the head; the
                # next scan retries with fresh state

    def _stall_watchdog_scan(self):
        """One watchdog pass: flag RUNNING tasks past their per-name
        threshold (EWMA multiple, floored), attach the owning worker's
        live stack to the task record, emit the task_stuck flight event
        and rtpu_core_stuck_tasks metrics. A scan that flags nothing
        does no control-plane traffic at all."""
        from .config import cfg
        from ..util.metrics import Counter, Gauge, cached_metric
        now = time.time()
        floor = cfg.stuck_task_floor_s
        mult = cfg.stuck_task_multiple
        newly = []
        n_stuck = 0
        with self.lock:
            self._watchdog["scans"] += 1
            self._watchdog["last_scan"] = now
            for rec in self.task_records.values():
                if rec.get("state") != "RUNNING":
                    continue
                t0 = rec.get("started_at")
                if t0 is None:
                    continue
                running = now - t0
                ewma = self._task_ewma.get(rec.get("name"))
                thr = max(floor, mult * ewma) if ewma is not None \
                    else floor
                if running < thr:
                    continue
                n_stuck += 1
                if not rec.get("stuck"):
                    rec["stuck"] = True
                    rec["stuck_at"] = now
                    rec["threshold_s"] = thr
                    rec["ewma_s"] = ewma
                    # live record ref kept: the stack attaches to it
                    # below without re-searching under the lock
                    newly.append((rec, running, thr))
            self._watchdog["stuck_running"] = n_stuck
            if newly:
                self._watchdog["flagged_total"] += len(newly)
        cached_metric(Gauge, "rtpu_core_stuck_tasks",
                      "tasks currently RUNNING past their stuck "
                      "threshold").set(float(n_stuck))
        if not newly:
            return
        cached_metric(Counter, "rtpu_core_stuck_tasks_total",
                      "tasks flagged stuck by the stall watchdog"
                      ).inc(float(len(newly)))
        # ONE stack pull per distinct owning worker, not per task: a
        # node wedging a whole batch at once must not serialize N
        # 2s-deadline pulls (stalling further scans exactly when timely
        # diagnosis matters) or spam an unresponsive worker
        by_wid: dict[str, list] = {}
        for rec, running, thr in newly:
            try:
                t48 = flight.lo48(bytes.fromhex(rec.get("task_id", "")))
            except ValueError:
                t48 = 0
            flight.evt(flight.TASK_STUCK, t48,
                       int(max(0.0, running - thr) * 1000))
            wid = rec.get("worker")
            if wid:
                by_wid.setdefault(wid, []).append((rec, t48))
        for wid, recs in by_wid.items():
            snaps, _ = self.stack_collect(timeout_s=2.0, wids=[wid],
                                          include_local=False)
            if not snaps:
                continue
            self._annotate_snaps(snaps)
            threads = snaps[0].get("threads", [])
            busy = [t for t in threads
                    if t.get("task48") or t.get("wait")]
            with self.lock:
                for rec, t48 in recs:
                    if not rec.get("stuck"):
                        # the attempt failed and a retry re-entered
                        # RUNNING while we collected: the fresh attempt
                        # must not inherit the wedged one's stack
                        continue
                    hit = [t for t in threads
                           if t48 and t.get("task48") == t48]
                    rec["stack"] = hit or busy or threads

    # ------------------------------------------------------------------ #
    # shutdown
    # ------------------------------------------------------------------ #

    def shutdown(self):
        global _runtime
        with self.lock:
            if self._shutdown:
                return
            self._shutdown = True
            workers = list(self.workers.values())
        # flush any worker output the tailer hasn't echoed yet
        if getattr(self, "_logtail_state", None) is not None:
            try:
                self._log_tail_scan()
            except Exception:
                pass  # final log echo is best-effort
        # final metric flush BEFORE the snapshot: counter deltas recorded
        # since the last 2s tick merge into user_metrics and persist
        from ..util.metrics import shutdown_flush
        shutdown_flush()
        # durable snapshot FIRST: killing workers below tears actors out
        # of the tables (watch-proc death path), and a successor must see
        # them as they were while alive
        self.memory_monitor.stop()
        if self.obs is not None:
            self.obs.stop()
        if self._snapshot_stop is not None:
            self._snapshot_stop.set()
        try:
            from .gcs_store import snapshot
            snapshot(self)
        except Exception:
            pass  # failed snapshot must not block teardown
        self.jobs.shutdown()
        for w in workers:
            w.send({"t": "exit"})
        for node in list(self.nodes.values()):
            if node.agent is not None:
                node.agent.send({"t": "shutdown"})
        # wake pg_wait blockers so rpc-pool threads exit promptly, then
        # release the pool without joining in-flight handlers
        self._sched_evt.set()  # release the scheduler pump
        for pg in self.pgs.values():
            pg.ready_event.set()
        self._rpc_pool.shutdown(wait=False, cancel_futures=True)
        deadline = time.monotonic() + 1.0
        for w in workers:
            if w.proc is None:
                continue
            try:
                w.proc.wait(timeout=max(0.01, deadline - time.monotonic()))
            except Exception:
                try:
                    w.proc.kill()
                except Exception:
                    pass  # already dead
        for lst in (self.listener, self.tcp_listener):
            try:
                lst.close()
            except Exception:
                pass  # already closed
        # sever control-plane connections so recv threads exit before the
        # store mapping goes away (they may touch the store while handling
        # late messages)
        for w in workers:
            try:
                if w.conn is not None:
                    w.conn.close()
            except Exception:
                pass  # already closed
        try:
            from .usage import write_usage_file
            write_usage_file(self.session_dir)
        except Exception:
            pass  # usage file is best-effort
        try:
            self.kv.close()
        except Exception:
            pass  # sqlite already closed
        self.store.close(unlink=True)
        try:
            os.unlink(self.cluster_file)  # address='auto' must not find us
        except OSError:
            pass
        if _runtime is self:
            _runtime = None


class LocalModeRuntime:
    """`ray_tpu.init(local_mode=True)`: tasks run synchronously in-process.

    Reference analog: python/ray/_private/worker.py LOCAL_MODE. Useful for
    debugging user code with pdb; actors are plain objects, objects live in a
    dict.
    """

    # refcounting is a no-op in local mode (objects live in a plain dict)
    def ref_created(self, oid, from_transfer):
        pass

    def ref_deleted(self, oid):
        pass

    def ref_serialized(self, oid):
        pass

    def __init__(self):
        self.objects: dict[ObjectID, Any] = {}
        self.job_id = JobID.from_random()
        self.func_registry: dict[str, Any] = {}
        self._actors: dict[ActorID, Any] = {}
        self.named_actors: dict[str, ActorID] = {}

    def register_function(self, fid, blob):
        self.func_registry.setdefault(fid, cloudpickle.loads(blob))

    def register_renv(self, h, blob):
        pass  # local mode runs in-process; runtime envs are validated only

    def put(self, value, pin=True):
        oid = ObjectID.from_random()
        self.objects[oid] = ("ok", value)
        return ObjectRef(oid)

    def _resolve_args(self, args_blob):
        args, kwargs = cloudpickle.loads(args_blob)
        args = [self.get(a) if isinstance(a, ObjectRef) else a for a in args]
        kwargs = {k: self.get(v) if isinstance(v, ObjectRef) else v
                  for k, v in kwargs.items()}
        return args, kwargs

    def submit_task(self, spec: TaskSpec):
        fn = self.func_registry[spec.func_id]
        args, kwargs = self._resolve_args(spec.args_blob)
        try:
            res = fn(*args, **kwargs)
            n = len(spec.return_ids)
            if getattr(spec, "dynamic_returns", False):
                vals = [[self.put(item) for item in res]]
            else:
                vals = (list(res) if n > 1 else [res])
            for oid, v in zip(spec.return_ids, vals):
                self.objects[oid] = ("ok", v)
        except BaseException as e:  # noqa: BLE001
            err = exc.RayTaskError(spec.name, e)
            for oid in spec.return_ids:
                self.objects[oid] = ("err", err)
        return [ObjectRef(o) for o in spec.return_ids]

    def create_actor(self, spec: ActorSpec):
        cls = self.func_registry[spec.class_id]
        args, kwargs = self._resolve_args(spec.args_blob)
        inst = cls(*args, **kwargs)
        self._actors[spec.actor_id] = inst
        if spec.named:
            self.named_actors[spec.named] = spec.actor_id
        if spec.ready_oid is not None:
            self.objects[spec.ready_oid] = ("ok", None)

    def submit_actor_task_spec(self, spec: TaskSpec):
        inst = self._actors.get(spec.actor_id)
        if inst is None:
            err = exc.ActorDiedError(f"actor for {spec.name} is dead")
            for oid in spec.return_ids:
                self.objects[oid] = ("err", err)
            return [ObjectRef(o) for o in spec.return_ids]
        args, kwargs = self._resolve_args(spec.args_blob)
        try:
            res = getattr(inst, spec.method_name)(*args, **kwargs)
            n = len(spec.return_ids)
            vals = (list(res) if n > 1 else [res])
            for oid, v in zip(spec.return_ids, vals):
                self.objects[oid] = ("ok", v)
        except BaseException as e:  # noqa: BLE001
            err = exc.RayTaskError(spec.name, e)
            for oid in spec.return_ids:
                self.objects[oid] = ("err", err)
        return [ObjectRef(o) for o in spec.return_ids]

    def get(self, refs, timeout=None):
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        out = []
        for r in ref_list:
            # deferred refs (pg.ready() — pre-registered via expect()) are
            # resolved by a waiter thread; anything else is synchronous in
            # local mode, so an unknown oid is an immediate error
            deadline = None if timeout is None else time.monotonic() + timeout
            while self.objects.get(r.id(), (None,))[0] == "pending":
                if deadline is not None and time.monotonic() > deadline:
                    raise exc.GetTimeoutError(f"timed out on {r.id()}")
                time.sleep(0.001)
            if r.id() not in self.objects:
                raise exc.ObjectLostError(
                    f"object {r.id()} does not exist in local mode")
            st, v = self.objects[r.id()]
            if st == "err":
                raise v.as_instanceof_cause() if isinstance(
                    v, exc.RayTaskError) else v
            out.append(v)
        return out[0] if single else out

    def wait(self, refs, num_returns=1, timeout=None, fetch_local=True):
        ref_list = list(refs)
        return ref_list[:num_returns], ref_list[num_returns:]

    def kill_actor(self, actor_id, no_restart=True):
        self._actors.pop(actor_id, None)

    def get_actor_by_name(self, name):
        aid = self.named_actors.get(name)
        if aid is None:
            raise ValueError(f"no actor named {name!r}")
        spec = ActorSpec(actor_id=aid, class_id="", name=name, args_blob=b"",
                         dep_oids=[], resources={})
        return spec

    def cancel(self, ref, force=False, recursive=True):
        pass

    def cluster_resources(self):
        return {"CPU": float(os.cpu_count() or 1)}

    def available_resources(self):
        return self.cluster_resources()

    def node_table(self):
        return [{"NodeID": "local", "Alive": True,
                 "Resources": self.cluster_resources(),
                 "Available": self.cluster_resources(), "Labels": {},
                 "NodeName": "local"}]

    def timeline(self):
        return []

    def create_placement_group(self, bundles, strategy, name="",
                               same_label=None, bundle_selectors=None):
        pg = PlacementGroupState(PlacementGroupID.from_random(), bundles,
                                 strategy, name, same_label=same_label,
                                 bundle_selectors=bundle_selectors)
        pg.state = "created"
        pg.ready_event.set()
        return pg

    def remove_placement_group(self, pg_id):
        pass

    def pg_wait(self, pg_id, timeout: float = 30.0) -> bool:
        return True  # local-mode PGs are always immediately "reserved"

    def expect(self, oid):
        """Register an oid a background waiter will put_at shortly, so get()
        blocks on it instead of failing fast on an unknown oid."""
        self.objects.setdefault(oid, ("pending", None))

    def put_at(self, oid, value, is_exception: bool = False):
        self.objects[oid] = ("err" if is_exception else "ok", value)
        return ObjectRef(oid)

    def shutdown(self):
        global _runtime
        if _runtime is self:
            _runtime = None
