"""The driver/worker runtime and the core public API implementation.

TPU-native analogue of the reference's CoreWorker + worker.py pair:
- ``Runtime`` plays the role of CoreWorker (reference:
  src/ray/core_worker/core_worker.h:291 — SubmitTask/CreateActor/
  SubmitActorTask/Get/Put/Wait) plus the per-process singleton
  (core_worker_process.h).
- Module functions (``init``/``get``/``put``/``wait``/…) mirror
  python/ray/_private/worker.py:1219+ (ray.init), :2547 (get), :2679
  (put), :2744 (wait), :2890 (get_actor).

Execution modes: by default tasks run on dispatcher threads (lowest
latency, shared address space). With ``init(process_workers=N)`` tasks
run on a pool of N OS worker processes behind a cloudpickle
serialization boundary with shared-memory object transport
(ray_tpu._private.worker_pool + shm_store) — real CPU parallelism for
fan-out workloads. Actors opt into a dedicated worker process with
``@remote(process=True)``.
"""

from __future__ import annotations

import atexit
import collections
import concurrent.futures
import logging
import os
import queue
import threading
import time
from typing import Any, Iterable, Sequence

from ray_tpu._private import accelerators
from ray_tpu._private import dispatch_lanes
from ray_tpu._private import perf_plane as perf
from ray_tpu._private import scheduler as scheduler_mod
from ray_tpu._private import speculation as spec_mod
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu._private.gcs import (
    ActorRecord,
    GlobalControlService,
    JobRecord,
    NodeRecord,
    TaskEvent,
)
from ray_tpu._private.ids import ActorID, JobID, NodeID, ObjectID, TaskID
from ray_tpu._private.object_ref import ObjectRef, resolve_args
from ray_tpu._private.object_store import ObjectStore, ReferenceCounter
from ray_tpu._private.placement_groups import PlacementGroupManager
from ray_tpu._private.scheduler import (
    BlockedResourceContext,
    ClusterState,
    Dispatcher,
    NodeState,
    format_traceback,
)
from ray_tpu._private.task import SchedulingStrategy, TaskSpec
from ray_tpu._private.actor_runtime import LocalActor, _ActorCall
from ray_tpu.util import tracing
from ray_tpu.exceptions import (
    ActorDiedError,
    ChipOwnershipError,
    GetTimeoutError,
    SystemOverloadedError,
    TaskCancelledError,
    TaskError,
    TaskTimeoutError,
)

logger = logging.getLogger("ray_tpu")

_runtime_env_warned = False

# Bounds for the serialized-args memo (_convert_remote_args): only
# argument tuples made of small immutables are keyed by VALUE — safe to
# share one framed blob across tasks because nothing can mutate them
# and they can never contain an ObjectRef.
_ARG_CACHE_MAX_ENTRIES = 512
_ARG_CACHE_MAX_STR = 256
_ARG_CACHE_MAX_BLOB = 4096


def _simple_arg(value, depth: int = 0) -> bool:
    t = type(value)
    if t is int or t is float or t is bool or value is None:
        return True
    if t is str or t is bytes:
        return len(value) <= _ARG_CACHE_MAX_STR
    if t is tuple and depth < 2 and len(value) <= 8:
        return all(_simple_arg(v, depth + 1) for v in value)
    return False


# Columnar submit eligibility: exact scalar types only at the top
# level (the raw codec's shape minus containers — container args keep
# the classic ring path, whose pickle-time machinery they may need).
_COL_ARG_TYPES = frozenset((int, float, bool, str, bytes, type(None)))

# Counter-key registries for execution_pipeline_stats()'s driver-side
# submit/dispatch groups (the analysis counter-keys pass matches them
# against the builder and metrics_agent exports them as the
# ray_tpu_node_submit / ray_tpu_node_dispatch families).
SUBMIT_STAT_KEYS = (
    "ring_submits", "flushes", "flush_tasks", "ring_full_waits",
    "buffered_cancels", "arg_cache_hits", "col_submits",
    "col_flush_tasks", "flush_wall_us",
)
DISPATCH_STAT_KEYS = (
    "batches", "batch_tasks", "singles", "batch_overcommit",
    "deadline_sweeps", "lanes", "lane_dispatches", "lane_tasks",
    "lane_busy_us", "lane_overcommits", "col_groups",
    "lane_outstanding",
)


def _warn_runtime_env_ignored(context: str) -> None:
    """runtime_env only takes effect across a process boundary (pool
    workers / process actors); warn once when it is silently dropped."""
    global _runtime_env_warned
    if _runtime_env_warned:
        return
    _runtime_env_warned = True
    logger.warning(
        "runtime_env is ignored for thread-mode execution (%s): "
        "env_vars/working_dir need a process boundary — enable the "
        "worker pool (init(process_workers=N)) or use process=True "
        "actors", context)

_runtime_lock = threading.Lock()
_runtime: "Runtime | None" = None


class _DaemonPool:
    """Fixed-size pool of daemon threads draining a work queue.

    Replaces thread-per-actor spawning on the submission path:
    ``threading.Thread.start`` blocks until the new thread's bootstrap
    runs, which costs tens of milliseconds per call once the box has
    hundreds of runnable threads — at a 100-actor creation wave those
    stalls serialize and dominate the wave (measured ~40ms/actor).
    A stdlib ThreadPoolExecutor is unsuitable here: its workers are
    non-daemon and its atexit hook joins them, so one creation body
    parked in a lease wait would hang interpreter exit."""

    def __init__(self, max_workers: int, name: str):
        self._queue: "queue.Queue" = queue.Queue()
        self._max = max(1, max_workers)
        self._name = name
        self._spawned = 0
        self._idle = 0
        self._lock = threading.Lock()

    def submit(self, fn, *args) -> None:
        self._queue.put((fn, args))
        with self._lock:
            if self._idle == 0 and self._spawned < self._max:
                self._spawned += 1
                n = self._spawned
                threading.Thread(
                    target=self._work, daemon=True,
                    name=f"{self._name}-{n}").start()

    def _work(self) -> None:
        while True:
            with self._lock:
                self._idle += 1
            try:
                fn, args = self._queue.get()
            finally:
                with self._lock:
                    self._idle -= 1
            try:
                fn(*args)
            except BaseException:  # noqa: BLE001 — bodies own their errors
                logger.exception("daemon-pool task failed (%s)", self._name)


class RuntimeContext:
    """Per-task/actor execution context (reference:
    python/ray/runtime_context.py)."""

    _tls = threading.local()

    @classmethod
    def current(cls) -> dict:
        return getattr(cls._tls, "ctx", None) or {}

    @classmethod
    def set(cls, **kwargs):
        cls._tls.ctx = kwargs

    @classmethod
    def clear(cls):
        cls._tls.ctx = None


class _SubmitRecord:
    """One buffered ``.remote()`` call: ids/refs were handed out
    inline; everything else is deferred to the submitter flush."""

    __slots__ = ("func", "args", "kwargs", "name", "num_returns",
                 "resources", "max_retries", "retry_exceptions",
                 "strategy", "runtime_env", "task_id", "return_ids",
                 "submit_ts", "trace_ctx", "cancelled", "state",
                 "deadline")

    # Lifecycle (state transitions under the ring condition lock):
    BUFFERED = 0   # in the ring; a cancel is handled ring-side
    DRAINING = 1   # claimed by a flush; a cancel is deferred to the
    #                flush's post-pass (the dispatcher knows it by then)
    SUBMITTED = 2  # out of the ring entirely


class _SubmitRing:
    """Bounded driver-side submit ring (the tentpole of the pipelined
    submit path): ``.remote()`` pushes a lightweight record and returns
    its pre-allocated refs; a dedicated submitter thread drains
    flushes, amortizing TaskSpec build, store/lineage/GCS record-
    keeping and the scheduler wakeup across a whole flush
    (Runtime._flush_submits). A full ring blocks the submitter —
    backpressure, never loss."""

    def __init__(self, runtime, capacity: int, flush_max: int):
        self._runtime = runtime
        self._capacity = max(2, int(capacity))
        self._flush_max = max(1, int(flush_max))
        self._cond = threading.Condition()
        self._ring: collections.deque = collections.deque()
        self._by_rid: dict = {}  # return ObjectID -> record (pre-SUBMITTED)
        self._stop = False
        self._parked = False
        # Test seam: clearing the gate holds the drain so races against
        # BUFFERED records (cancel, overflow) are deterministic.
        self._gate = threading.Event()
        self._gate.set()
        self.submits = 0
        self.flushes = 0
        self.flush_tasks = 0
        self.ring_full_waits = 0
        self.buffered_cancels = 0
        self._thread = threading.Thread(
            target=self._drain_loop, daemon=True, name="ray_tpu-submitter")
        self._thread.start()

    def holds(self, object_id) -> bool:
        """True while ``object_id`` belongs to a not-yet-dispatched
        buffered submit (attach_future treats those as pending)."""
        with self._cond:
            return object_id in self._by_rid

    def push(self, rec: _SubmitRecord) -> None:
        with self._cond:
            if len(self._ring) >= self._capacity:
                self.ring_full_waits += 1
                while len(self._ring) >= self._capacity and not self._stop:
                    self._cond.wait(0.1)
            self._ring.append(rec)
            for rid in rec.return_ids:
                self._by_rid[rid] = rec
            self.submits += 1
            if self._parked:
                self._cond.notify_all()

    def cancel(self, object_id) -> "_SubmitRecord | None":
        """Flag a buffered/draining submit cancelled. Returns the
        record when the ring owns the cancel (caller does nothing
        more): BUFFERED records are sealed with TaskCancelledError
        right here; DRAINING ones are cancelled by the flush's
        post-pass once the dispatcher knows them. None => unknown to
        the ring — the caller falls through to the dispatcher."""
        with self._cond:
            rec = self._by_rid.get(object_id)
            if rec is None:
                return None
            if rec.cancelled:
                return rec  # second cancel of the same ref: a no-op
            rec.cancelled = True
            buffered = rec.state == _SubmitRecord.BUFFERED
            if buffered:
                self.buffered_cancels += 1
        if buffered:
            # The flush skips cancelled BUFFERED records entirely, so
            # this is the one place their error is sealed.
            self._runtime._seal_cancelled_submit(rec)
        return rec

    def _aux_depth(self) -> int:
        """Columnar records buffered alongside the classic ring (the
        submitter thread drains both)."""
        return len(self._runtime._col_buf)

    def kick(self) -> None:
        """Wake a parked drain loop after a lock-free columnar push
        (the parked-flag read costs nothing during a burst)."""
        if self._parked:
            with self._cond:
                self._cond.notify_all()

    def col_backpressure(self) -> None:
        """Bounded blocking for a full columnar buffer — same
        semantics as a full ring: the submitter waits, never drops."""
        with self._cond:
            if self._aux_depth() < self._capacity:
                return
            self.ring_full_waits += 1
            while self._aux_depth() >= self._capacity \
                    and not self._stop:
                self._cond.wait(0.1)

    def _drain_loop(self) -> None:
        while True:
            with self._cond:
                while not self._ring and not self._aux_depth() \
                        and not self._stop:
                    self._parked = True
                    try:
                        self._cond.wait(timeout=0.2)
                    finally:
                        self._parked = False
                if not self._ring and not self._aux_depth() \
                        and self._stop:
                    return
            # Test seam sits between wake and claim so a cleared gate
            # deterministically holds records in the BUFFERED state.
            self._gate.wait()
            # Adaptive accumulation: while a BURST is in progress
            # (dozens already buffered and more arriving), briefly
            # yield so the producer fills a whole flush instead of
            # ping-ponging the GIL with it record-for-record — on a
            # busy box this is the difference between the submitter
            # and the .remote() loop splitting one core 50/50 and the
            # loop running hot. A lone interactive submit (small
            # depth) flushes immediately; the linger is bounded so a
            # stalling producer can never hold a batch hostage.
            if len(self._ring) + self._aux_depth() >= 64:
                deadline = time.monotonic() + 0.05
                last_depth = -1
                stalls = 0
                while not self._stop:
                    depth = len(self._ring) + self._aux_depth()
                    if depth >= self._flush_max \
                            or time.monotonic() >= deadline:
                        break
                    if depth == last_depth:
                        # One stalled tick can just be the producer
                        # losing the GIL to a runner/daemon burst;
                        # only a SUSTAINED stall ends the linger —
                        # bigger flushes mean deeper dispatch slices.
                        stalls += 1
                        if stalls >= 2:
                            break
                    else:
                        stalls = 0
                    last_depth = depth
                    time.sleep(0.002)
            # Columnar records flush first (their own groups, one lock
            # pass); failures there seal errors per record, never kill
            # the drain thread.
            if self._aux_depth():
                try:
                    self._runtime._flush_columnar(self)
                except BaseException:  # noqa: BLE001 — never die
                    logger.exception("columnar flush failed")
            with self._cond:
                n = min(len(self._ring), self._flush_max)
                batch = [self._ring.popleft() for _ in range(n)]
                self._cond.notify_all()  # unblock backpressured pushers
            if not batch:
                continue
            try:
                self._runtime._flush_submits(self, batch)
            except BaseException as exc:  # noqa: BLE001 — never die
                logger.exception("submit flush failed")
                for rec in batch:
                    with self._cond:
                        for rid in rec.return_ids:
                            self._by_rid.pop(rid, None)
                        already = rec.cancelled \
                            and rec.state == _SubmitRecord.BUFFERED
                        rec.state = _SubmitRecord.SUBMITTED
                    if not already:
                        for rid in rec.return_ids:
                            self._runtime.store.put_error(rid, exc)
            with self._cond:
                self.flushes += 1
                self.flush_tasks += n

    def depth(self) -> int:
        with self._cond:
            return len(self._ring) + self._aux_depth()

    def stop(self) -> None:
        """Flush whatever is buffered, then join the submitter."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._gate.set()
        self._thread.join(timeout=10.0)


class Runtime:
    """Everything a node/driver needs: store, control plane, scheduler."""

    def __init__(
        self,
        num_cpus: float | None = None,
        num_tpus: float | None = None,
        resources: dict[str, float] | None = None,
        object_store_memory: int | None = None,
        namespace: str = "default",
        process_workers: int | None = None,
        metrics_port: int | None = None,
        dashboard_port: int | None = None,
        address: str | None = None,
    ):
        cfg = GLOBAL_CONFIG
        self.namespace = namespace
        self.job_id = JobID()
        # Always-on performance plane: arm/disarm from the (possibly
        # system_config-overridden) knob, and clear the previous
        # session's histograms — an init/shutdown cycle must not
        # replay old latencies into this session's scrape.
        perf.init_from_config()
        perf.reset()
        # Locality-/load-aware placement + straggler speculation: arm
        # the module gates from the (possibly system_config-overridden)
        # knobs — same discipline as the perf plane above.
        scheduler_mod.init_sched_from_config()
        spec_mod.init_from_config()
        # Fused in-daemon execution + raw small-immutable framing:
        # driver-side module gates (daemons and pool workers re-arm
        # from config/env at their own import).
        from ray_tpu._private import node_executor as node_executor_mod
        from ray_tpu._private import serialization as serialization_mod

        node_executor_mod.init_fused_from_config()
        serialization_mod.init_raw_from_config()
        # Watermark-driven spill tier (spill_manager.py): arm the
        # module gate; the managers themselves attach to the stores
        # further down (after the lease tables they filter on exist).
        from ray_tpu._private import spill_manager as spill_mod

        spill_mod.init_from_config()
        # Driver-side flight recorder: ring only (no flusher thread,
        # no per-driver files) — `ray_tpu debug` reads it live.
        from ray_tpu._private import flight_recorder

        flight_recorder.install("driver")
        # Connected-cluster mode: register this driver with an external
        # head GCS (python -m ray_tpu start --head) and mirror its node
        # table into nodes()/state listings. Task execution stays local
        # to this driver's runtime; the control plane is cluster-wide.
        self.gcs_client = None
        self._node_agent = None
        if address:
            from ray_tpu._private.node import NodeAgent
            from ray_tpu._private.rpc import MuxRpcClient, RpcError

            # Pipelined head-GCS client: the watcher's long-poll sync,
            # location flushes, named-actor publication and KV traffic
            # ride one socket concurrently instead of serializing under
            # a per-call lock (reference: gRPC channels multiplex every
            # GCS service call).
            self.gcs_client = MuxRpcClient(address, timeout_s=60.0)
            self.gcs_client.on_reply_meta = self._on_gcs_reply_meta
            try:
                self._node_agent = NodeAgent(
                    address,
                    {"CPU": float(num_cpus if num_cpus is not None
                                  else cfg.num_cpus)},
                    labels={"node_role": "driver"},
                    usage_fn=self.available_resources)
            except (RpcError, OSError) as exc:
                self.gcs_client.close()
                self.gcs_client = None
                raise ConnectionError(
                    f"cannot connect to ray_tpu head at {address}: "
                    f"{exc}") from exc
        self.gcs = GlobalControlService()
        if self.gcs_client is not None:
            # Mirror local actor lifecycle to the head's cluster actor
            # registry (queued here, flushed by the node watcher).
            self.gcs.pubsub.subscribe("actors", self._queue_actor_mirror)
        self.store = ObjectStore(
            memory_limit_bytes=(object_store_memory
                                or cfg.object_store_memory_mb * 1024 * 1024),
            spill_dir=cfg.object_spilling_dir,
        )
        self.reference_counter = ReferenceCounter(self.store)
        self.cluster = ClusterState(spread_threshold=cfg.scheduler_spread_threshold)
        self.dispatcher = Dispatcher(self.cluster, self.store)
        # Overload-control counters (under _fault_lock, surfaced via
        # fault_stats): deadline-sealed tasks and admission sheds.
        self._task_timeouts = 0
        self._admission_shed = 0
        self.dispatcher.set_deadline_hook(self._seal_deadline)
        # Locality-aware placement inputs: the dispatcher asks this
        # hook for byte-weighted argument residency per admission
        # (scheduler.LOCALITY_ON gates every call). The threshold is
        # cached here so the dispatch hot path never takes the config
        # lock per task.
        self._locality_min_bytes = int(cfg.locality_min_arg_kb) * 1024
        # Learned residency: args >= the threshold accrue the nodes
        # that executed tasks consuming them (a pulled copy is cached
        # there) — bounded LRU. Plus the head ObjectDirectory's
        # multi-holder view, synced by the node watcher.
        self._arg_locality: collections.OrderedDict = \
            collections.OrderedDict()
        self._arg_locality_lock = threading.Lock()
        self._holder_cache: dict = {}
        # {object hex -> node hex} of holders whose copy is currently
        # on their disk tier (spill-aware locality discount).
        self._spilled_holders: dict = {}
        self._sched_feed_at = 0.0
        self.dispatcher.set_locality_hook(self._locality_for_spec)
        # Straggler speculation: driver-side watcher comparing each
        # in-flight task's elapsed wall against the perf plane's
        # per-function p99 (speculation.py); only exists while armed.
        self._spec_watcher = None
        if spec_mod.SPEC_ON:
            self._spec_watcher = spec_mod.SpeculationWatcher(self)
        self.placement_groups = PlacementGroupManager(self.cluster, self.store)
        self._actors: dict[ActorID, LocalActor] = {}
        # Signalled whenever an actor lands in _actors: submit queues
        # block on it instead of spin-polling (hundreds of concurrent
        # creations would otherwise busy-wake the GIL thousands of
        # times a second).
        self._actors_changed = threading.Condition()
        self._actor_queues: dict[ActorID, Any] = {}
        # Actor-creation bodies (lease + handle construction) run on a
        # shared pool instead of a thread per .remote(): at creation
        # waves, per-actor Thread.start stalls (~tens of ms each under
        # load) otherwise serialize on the submitting thread. Bodies
        # can park in lease waits, so the pool is deep; beyond it,
        # creations queue FIFO — a saner regime than 1000 unthrottled
        # creation threads anyway.
        self._actor_create_pool = _DaemonPool(64, "ray_tpu-actor-create")
        # Separate tiny pool for plain Thread.start offloads: those
        # must never queue behind parked creation bodies.
        self._thread_start_pool = _DaemonPool(4, "ray_tpu-thread-start")
        self._foreign_proxies: dict[tuple[str, str], Any] = {}
        self._actor_leases: dict[ActorID, tuple[NodeID, dict, Any]] = {}
        # (deadline, [refs]) grace pins for nested args of in-flight
        # submissions (see _pin_nested_arg_refs).
        self._arg_pin_pen: collections.deque = collections.deque()
        self._placement_record_lock = threading.Lock()
        self._futures_lock = threading.Lock()
        self._futures: dict[ObjectID, list[concurrent.futures.Future]] = {}
        self.store.add_seal_listener(self._resolve_futures)
        self._task_counter = 0

        # Multiprocess worker pool (opt-in): serialization boundary +
        # shared-memory transport; see worker_pool.py.
        from ray_tpu._private.shm_store import ShmClient, ShmDirectory

        import weakref

        self.shm_directory = ShmDirectory()
        self.shm_client = ShmClient()
        self.worker_pool = None
        self._promote_lock = threading.Lock()
        # Native shared arena (plasma-lite, _native/plasma_store.cpp):
        # the driver owns it; pool workers attach via RAY_TPU_ARENA_NAME.
        # Best-effort — without a C++ toolchain everything stays on the
        # segment-per-object path.
        self.arena = None
        arena_bytes = int(cfg.object_arena_bytes or 0)
        if arena_bytes > 0:
            from ray_tpu._private.arena_store import (
                ArenaStore,
                default_arena_name,
            )

            self.arena = ArenaStore.create(default_arena_name(), arena_bytes)
            if self.arena is not None:
                os.environ["RAY_TPU_ARENA_NAME"] = self.arena.name
                os.environ["RAY_TPU_ARENA_MAX"] = str(
                    int(cfg.object_arena_max_object_bytes))
                self.shm_client.set_arena(self.arena)
                self.shm_directory.set_arena(self.arena)
        self._func_blobs: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        pool_size = (process_workers if process_workers is not None
                     else cfg.worker_pool_size)
        self.log_monitor = None
        self.memory_monitor = None
        # Nested-submission plumbing: pool workers call the public API
        # back through this client server (reference: every Ray worker is
        # a full CoreWorker, core_worker.h:291); blocked nested gets ship
        # a task token so the owning task's CPU is released while waiting.
        self.worker_client_server = None
        self._inflight_blocks: dict[str, BlockedResourceContext] = {}
        self._inflight_blocks_lock = threading.Lock()
        # The client server backs nested submission from worker
        # processes (pool workers and process actors) and fronts this
        # driver's actors for other drivers in a connected cluster. It
        # starts eagerly in connected mode (named-actor publication
        # needs its address); otherwise lazily at the first process
        # spawn, so thread-only runtimes pay nothing.
        if self.gcs_client is not None:
            self.ensure_client_server()
        if pool_size and pool_size > 0:
            self.ensure_client_server()
            from ray_tpu._private.worker_pool import WorkerPool

            # Worker stdout/stderr -> per-worker files; the log monitor
            # tails them back to the driver console (reference:
            # log_monitor.py).
            if cfg.log_to_driver:
                import tempfile
                import uuid

                # Unique per SESSION (not just pid): an init/shutdown
                # cycle in one process must not replay or append to the
                # previous session's worker logs.
                log_dir = os.path.join(
                    tempfile.gettempdir(),
                    f"ray_tpu_session_{os.getpid()}_"
                    f"{uuid.uuid4().hex[:6]}", "logs")
                os.environ["RAY_TPU_WORKER_LOG_DIR"] = log_dir
                from ray_tpu._private.log_monitor import LogMonitor

                self.log_monitor = LogMonitor(
                    log_dir,
                    context_fn=self._worker_log_context).start()
            self.worker_pool = WorkerPool(
                int(pool_size), self.shm_directory, self.shm_client)
            refresh_ms = int(cfg.memory_monitor_refresh_ms or 0)
            if refresh_ms > 0:
                from ray_tpu._private.memory_monitor import MemoryMonitor

                self.memory_monitor = MemoryMonitor(
                    self, threshold=float(cfg.memory_usage_threshold),
                    period_s=refresh_ms / 1000.0).start()

        # Lineage + recovery + node health (reference:
        # object_recovery_manager.h:41, gcs_health_check_manager.h:39).
        from ray_tpu._private.recovery import (
            LineageTable,
            NodeHealthMonitor,
            ObjectRecoveryManager,
        )

        self.lineage = LineageTable(cfg.lineage_table_max_entries)
        self.recovery = ObjectRecoveryManager(self)
        # Serialized-args memo for the remote dispatch path: repeated
        # identical small-immutable argument tuples reuse one framed
        # blob instead of re-pickling per task (function blobs already
        # intern via _func_blobs; args did not).
        self._arg_blob_cache: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._arg_blob_lock = threading.Lock()
        self.arg_cache_hits = 0
        # Columnar submit records (dispatch_lanes.py, ISSUE 15):
        # eligible .remote() calls append ONE tuple to this lock-free
        # buffer; the ring's flush thread drains it into per-template
        # ColumnarGroups for the dispatch lanes. _col_index maps every
        # in-flight columnar return id to its state (the record's
        # TaskID while buffered, then its group) for cancel /
        # attach_future / lazy expansion; _col_lock serializes flush
        # claims against cancels — the submit hot path never takes it.
        dispatch_lanes.init_from_config()
        self._col_buf: collections.deque = collections.deque()
        self._col_index: dict = {}
        self._col_lock = threading.Lock()
        self._lanes = None
        self._col_submits = 0
        self._col_flush_tasks = 0
        self._col_buffered_cancels = 0
        self._flush_wall_us = 0
        # Pipelined submission: .remote() returns pre-allocated refs and
        # defers the per-task record-keeping to the ring's flush thread.
        self._submit_ring = None
        if bool(cfg.submit_pipeline):
            self._submit_ring = _SubmitRing(
                self, int(cfg.submit_ring_size), int(cfg.submit_flush_max))
        self._object_locations: dict[ObjectID, NodeID] = {}
        # RLock: _forget_object can re-enter from ObjectRef.__del__ (GC
        # may fire while _record_location holds this lock).
        self._locations_lock = threading.RLock()
        # Location deltas pending publication to the head's object-
        # location table (reference: ownership_based_object_directory.h;
        # flushed in batches by the node watcher).
        self._loc_dirty_adds: dict[str, str] = {}
        self._loc_dirty_removes: set[str] = set()
        self._loc_keepalive = 0.0
        # Epoch fencing (connected mode): the head's incarnation epoch
        # observed on reply metadata. Stamped on every control-plane
        # WRITE this driver publishes (locations, actors, PGs); a bump
        # or a typed StaleEpochError triggers a full re-publish under
        # the new epoch (_flush_control_mirror / location keepalive).
        self._gcs_epoch: int | None = None
        self._epoch_republish = False
        # Cluster actor-registry mirror: local actor lifecycle events
        # queue their ids here; the watcher flushes batched
        # actor_update upserts to the head (whose snapshot+WAL make
        # the registry durable). PG snapshots publish on version bumps.
        self._mirror_lock = threading.Lock()
        self._actor_dirty: set = set()
        self._pg_published_version = -1
        self._gcs_persist_cache: tuple = (0.0, None)
        self._gcs_shard_cache: tuple = (0.0, None)
        self._history_cache: tuple = (0.0, None, None)
        self._health_cache: tuple = (0.0, None)
        # Remote execution plane state (threads start at the end of
        # __init__, but callbacks may touch these during construction).
        self._remote_nodes: dict[NodeID, Any] = {}
        self._remote_nodes_lock = threading.Lock()
        self._remote_ever: set[NodeID] = set()
        # node -> consecutive absent-but-pinging sync passes (only the
        # watcher thread touches it; bounded by node_amnesia_max_passes).
        self._amnesia_misses: dict[NodeID, int] = {}
        self._remote_free_queue: list[tuple[NodeID, bytes]] = []
        self._remote_free_lock = threading.Lock()
        self._watcher_stop = threading.Event()
        self._node_watcher = None
        self._export_store = None
        self._export_directory = None
        self._obj_server = None
        self._export_addr = ""
        # Same-host plane, driver side: exported args above the map
        # threshold get a named-segment (or arena) twin that co-hosted
        # daemons map instead of chunk-pulling (same_host.py).
        from ray_tpu._private.same_host import LeaseTable, host_identity

        self.host_id = host_identity()
        self._export_sources: dict[bytes, tuple] = {}
        self._export_segments: dict[bytes, Any] = {}
        self._export_leases = LeaseTable()
        self._export_lock = threading.Lock()
        self._lease_sweep_at = 0.0
        self.same_host_copy_hits = 0  # driver-side mapped-copy fetches
        # Driver-side spill tier: the value store's heap copies move
        # to checksummed session-dir files past the high watermark
        # (their shm/arena twins freed with them — unleased victims
        # only), torn restores fall back to lineage reconstruction.
        self._export_spill_mgr = None
        # ObjectID -> monotonic stamp of the last worker-bound shm
        # promotion: the spiller must not free a segment an in-flight
        # pool frame is about to attach.
        self._recent_promotes: dict = {}
        if spill_mod.SPILL_ON:
            self.store.enable_managed_spill(
                leased_fn=self._spill_protected_ids,
                on_backing_free=self._on_value_spilled,
                on_torn=self._recover_torn_object)
            from ray_tpu._private.memory_monitor import (
                set_store_bytes_provider,
            )

            set_store_bytes_provider(self._resident_store_bytes)
        # Driver-side failure counters (fault_stats): batch entries
        # requeued invisibly after a daemon death.
        self._fault_lock = threading.Lock()
        self._fault_batch_requeues = 0
        # Fused in-daemon execution, as seen from this driver (the
        # batch RPCs' ("done", n, stats) replies): surfaced via
        # execution_pipeline_stats()["fused"].
        self._fused_runs = 0
        self._fused_tasks = 0
        self._fused_fallbacks = 0
        self._pkg_hashes: dict[str, str] = {}
        # Refcount-zero eviction must also drop directory + lineage
        # entries, or they leak for the runtime's lifetime.
        self.reference_counter.on_evict = self._forget_object
        # Grace pins expire on TIME, not on the next submission: an
        # idle driver must still let its last pens lapse so normal
        # refcounting can free the objects.
        threading.Thread(target=self._arg_pin_sweeper, daemon=True,
                         name="ray_tpu-arg-pin-sweeper").start()
        self.health_monitor = NodeHealthMonitor(
            self.gcs, period_s=cfg.health_check_period_ms / 1000.0,
            failure_threshold=cfg.health_check_failure_threshold,
            on_node_dead=self._on_node_dead)

        # Prometheus /metrics endpoint (opt-in via metrics_port; 0 picks
        # a free port — reference: _private/metrics_agent.py per node).
        self.metrics_agent = None
        if metrics_port is not None:
            from ray_tpu._private.metrics_agent import start_metrics_agent

            self.metrics_agent = start_metrics_agent(self, port=metrics_port)

        # HTTP dashboard (opt-in via dashboard_port; 0 picks a free
        # port — reference: dashboard/head.py).
        self.dashboard = None
        if dashboard_port is not None:
            from ray_tpu.dashboard import Dashboard, runtime_provider

            self.dashboard = Dashboard(
                runtime_provider(self), port=dashboard_port).start()

        # Head node: autodetect CPU and TPU resources.
        detected = accelerators.detect_resources()
        head_resources = {"CPU": float(num_cpus if num_cpus is not None else cfg.num_cpus)}
        if num_tpus is not None:
            head_resources["TPU"] = float(num_tpus)
        elif detected.get("TPU"):
            head_resources["TPU"] = detected["TPU"]
        head_resources.update(
            {k: v for k, v in detected.items() if k not in head_resources})
        if resources:
            head_resources.update({k: float(v) for k, v in resources.items()})
        # One process per chip: this driver's threads, or leased children.
        self.chip_leases = accelerators.ChipLeases(
            int(head_resources.get("TPU", 0)))
        self.head_node_id = self.add_node(head_resources, labels={"node_type": "head"})
        self.gcs.register_job(JobRecord(self.job_id))

        # Connected-cluster execution plane: mirror the GCS node table
        # into ClusterState so pick_node can choose worker daemons, and
        # dispatch to them over RPC (reference: the two-level scheduler —
        # cluster view + remote raylet lease, cluster_task_manager.h:42).
        if self.gcs_client is not None:
            # Driver-side object export server: driver-held task args
            # above the inline threshold are served from here so each
            # node pulls (and caches) them ONCE instead of the driver
            # re-shipping the bytes with every task (reference: plasma +
            # object manager — args are objects nodes fetch, not
            # payloads inlined per task).
            from ray_tpu._private.node import _own_address
            from ray_tpu._private.node_executor import (
                ChunkDirectory,
                NodeObjectStore,
            )
            from ray_tpu._private.rpc import RpcServer

            self._export_store = NodeObjectStore()
            if spill_mod.SPILL_ON:
                # Exported args ride the same tier: spilling a blob
                # frees its segment/arena twin (unleased only — the
                # lease filter covers co-hosted daemons mid-map).
                self._export_spill_mgr = \
                    self._export_store.enable_managed_spill(
                        leased_fn=self._export_leases.pinned_ids,
                        on_spilled=lambda key, _owner:
                            self._drop_export_source(key))
            self._export_directory = ChunkDirectory()
            self._obj_server = RpcServer(host="0.0.0.0", port=0)
            self._obj_server.register("ping", lambda: "pong")
            # Pooled: pipelined chunk pulls from many nodes interleave
            # instead of serializing on each connection's serve loop.
            self._obj_server.register(
                "fetch_object", self._export_fetch_object,
                concurrent="pooled")
            self._obj_server.register(
                "fetch_plan", self._export_fetch_plan,
                concurrent="pooled")
            self._obj_server.register(
                "unpin_object", self._export_leases.release)
            self._obj_server.start()
            self._export_addr = \
                f"{_own_address()}:{self._obj_server.port}"
            self._node_watcher = threading.Thread(
                target=self._watch_remote_nodes, daemon=True,
                name="ray_tpu-node-watcher")
            self._node_watcher.start()
            # Pipelined execute path: tasks claimed for one remote node
            # in a dispatch pass ride a single execute_task_batch RPC.
            self.dispatcher.set_batch_hooks(self._task_batch_key,
                                            self._run_task_batch)
            # Sharded dispatch lanes (ISSUE 15): columnar groups of
            # fused-eligible DEFAULT submits bypass the classic
            # dispatcher entirely — N lanes acquire whole per-node
            # allocation plans from the cluster ledger (one lock pass
            # per flush) and ship compact columnar batch RPCs.
            if dispatch_lanes.SHARD_ON:
                self._lanes = dispatch_lanes.DispatchLanes(
                    self.cluster, self._run_columnar_slice,
                    fallback=self._columnar_starved,
                    node_filter=self._columnar_node_filter)

    # ------------------------------------------------------ remote exec plane

    def _export_fetch_object(self, id_bytes: bytes, offset: int,
                             length: int):
        from ray_tpu._private.node_executor import wrap_chunk_reply

        reply = self._export_store.read_chunk(id_bytes, offset, length)
        return None if reply is None else wrap_chunk_reply(reply)

    def _export_fetch_plan(self, id_bytes: bytes,
                           puller_addr: str | None = None,
                           puller_host: str | None = None):
        """Transfer plan for a driver-exported object: (size, holders,
        map_source). Registers the puller so the NEXT puller fetches
        chunks from it too — the driver seeds a broadcast once and
        receivers relay (reference: the owner hands out locations via
        the object directory; data flows node-to-node). Co-hosted
        pullers instead get a map source + pin lease and move no bytes
        at all (same_host.py)."""
        from ray_tpu._private.node_executor import plan_holders
        from ray_tpu._private.same_host import map_enabled

        total = self._export_store.size(id_bytes)
        if total is None:
            return None
        map_info = None
        if puller_addr and puller_host and map_enabled() \
                and puller_host == self.host_id:
            map_info = self._grant_export_lease(id_bytes, puller_addr)
        reg_addr = None if map_info is not None else puller_addr
        return (total, plan_holders(
            self._export_directory, id_bytes, reg_addr, total), map_info)

    def _grant_export_lease(self, id_bytes: bytes,
                            holder: str) -> dict | None:
        with self._export_lock:
            source = self._export_sources.get(id_bytes)
        if source is None:
            return None
        kind, name, size = source[0], source[1], source[2]
        key = source[3] if len(source) > 3 else b""
        if kind == "arena":
            if self.arena is None or self.arena.pin(key) is None:
                return None
            arena = self.arena
            token = self._export_leases.grant(
                id_bytes, holder, on_release=lambda: arena.unpin(key))
        else:
            token = self._export_leases.grant(id_bytes, holder)
        return {"kind": kind, "name": name, "key": key, "size": size,
                "host": self.host_id, "token": token}

    def _register_export_source(self, id_bytes: bytes, header,
                                buffers, size: int):
        """Back a large export with named shared memory so same-host
        daemons map it. Returns the buffer the framed bytes were
        written into (a segment's memoryview), or None when the caller
        should keep a heap blob (plane off / segment unavailable).

        ≥ map threshold -> dedicated segment (consumers map zero-copy);
        below it but arena-sized -> the driver's arena (consumers take
        a cross-arena descriptor or one memcpy)."""
        from multiprocessing import shared_memory

        from ray_tpu._private import serialization
        from ray_tpu._private.same_host import (
            map_enabled,
            map_min_bytes,
        )
        from ray_tpu._private.shm_store import ShmObjectWriter

        if not map_enabled():
            return None
        if size >= map_min_bytes():
            try:
                seg = shared_memory.SharedMemory(create=True,
                                                 size=max(size, 1))
            except OSError:
                return None  # /dev/shm full: heap blob + chunked pull
            serialization.write_framed(seg.buf, header, buffers)
            with self._export_lock:
                self._export_sources[id_bytes] = ("seg", seg.name, size)
                self._export_segments[id_bytes] = seg
            return memoryview(seg.buf)[:size]
        if self.arena is not None and size <= int(
                GLOBAL_CONFIG.object_arena_max_object_bytes):
            # Arena twin under the object id — the same key the export
            # carries, so peers peek it by id after attaching. The
            # export store keeps its own heap copy (the arena twin is
            # evictable state; the store copy serves chunked pulls).
            adesc = ShmObjectWriter.put_arena_serialized(
                self.arena, id_bytes, header, buffers, size)
            if adesc is not None:
                with self._export_lock:
                    self._export_sources[id_bytes] = (
                        "arena", self.arena.name, size, id_bytes)
                buf = bytearray(size)
                serialization.write_framed(memoryview(buf), header,
                                           buffers)
                return bytes(buf)
        return None

    def _drop_export_source(self, id_bytes: bytes) -> None:
        """Free path: release peers' leases, then the backing shared
        memory. Unlink-while-mapped is safe for segments (POSIX keeps
        existing mappings); arena twins need their pin dropped before
        delete can take effect."""
        with self._export_lock:
            source = self._export_sources.pop(id_bytes, None)
            seg = self._export_segments.pop(id_bytes, None)
        if source is None:
            return
        self._export_leases.release_object(id_bytes)
        if source[0] == "arena" and self.arena is not None:
            self.arena.unpin(id_bytes)   # the seal_pinned creation ref
            self.arena.delete(id_bytes)
        if seg is not None:
            try:
                seg.unlink()
            except (OSError, FileNotFoundError):
                pass  # segment already unlinked by the tracker
            try:
                seg.close()
            except (BufferError, OSError):
                # An in-flight chunk read still views the mapping:
                # leak it until process exit rather than invalidating.
                from ray_tpu._private.shm_store import _defuse

                _defuse(seg)

    def _watch_remote_nodes(self) -> None:
        """Mirror the head's node table into ClusterState, reacting to
        membership PUSH events (the head's "nodes" pubsub channel —
        reference: GcsNodeManager broadcasts node-dead over pubsub)
        with a long-poll, plus a periodic resync as the safety net;
        each wake also flushes queued object frees and location
        deltas."""
        from ray_tpu._private.gcs_pubsub import GcsSubscriber
        from ray_tpu._private.rpc import (
            RpcError,
            RpcMethodError,
            call_with_retry,
        )

        subscriber = None
        try:
            subscriber = GcsSubscriber(
                self.gcs_client.address,
                ["nodes", "node_resources", "object_loss"])
        except Exception:  # noqa: BLE001 — pre-pubsub head: poll only
            subscriber = None
        last_sync = 0.0
        try:
            while not self._watcher_stop.is_set():
                events = []
                # Queued frees / dirty locations shorten the wait: the
                # flush cadence must not degrade to the full long-poll
                # interval while work is pending (the free queue is
                # bounded; slow flushing would overflow it 10x sooner).
                with self._remote_free_lock:
                    pending_frees = bool(self._remote_free_queue)
                with self._locations_lock:
                    dirty_locs = bool(self._loc_dirty_adds
                                      or self._loc_dirty_removes)
                poll_s = 0.5 if (pending_frees or dirty_locs) else 5.0
                if subscriber is not None:
                    try:
                        # Blocks server-side until a membership event
                        # (push) or the timeout.
                        events = subscriber.poll(timeout_s=poll_s)
                    except Exception:  # noqa: BLE001 — head gone
                        self._watcher_stop.wait(0.5)
                else:
                    self._watcher_stop.wait(0.5)
                if self._watcher_stop.is_set():
                    return
                # Syncer pushes: per-node availability deltas update the
                # scheduler's reported view directly — no list_nodes
                # round trip (reference: ray_syncer resource stream).
                membership_events = []
                for channel, message in events:
                    if channel == "node_resources":
                        try:
                            hex_id, available = message
                            self.cluster.update_reported(
                                NodeID(bytes.fromhex(hex_id)), available)
                        except Exception:  # noqa: BLE001 — malformed push
                            pass
                    elif channel == "object_loss":
                        # Head pruned the LAST holder of these objects
                        # (node death): rebuild from lineage now
                        # instead of waiting for a get() to trip over
                        # the dead holder.
                        try:
                            self._handle_object_loss(message)
                        except Exception:  # noqa: BLE001 — best-effort
                            logger.exception("object-loss push failed")
                    else:
                        membership_events.append((channel, message))
                try:
                    # Frees/location deltas flush every wake; the FULL
                    # node-table resync only on a MEMBERSHIP push event
                    # or the periodic safety net (a pre-pubsub head
                    # keeps the old per-wake cadence); resource deltas
                    # alone never trigger it.
                    self._flush_remote_frees()
                    self._flush_object_locations()
                    self._flush_control_mirror()
                    now = time.monotonic()
                    if scheduler_mod.LOCALITY_ON \
                            and now - self._sched_feed_at >= 2.0:
                        # Load-/locality-aware placement inputs: the
                        # node-stats ages + the holder table.
                        self._sched_feed_at = now
                        self._sync_sched_feed()
                    if (membership_events or subscriber is None
                            or now - last_sync >= 10.0):
                        # Idempotent GCS read on the shared retry
                        # policy: one dropped frame must not stall the
                        # node view a full poll interval.
                        self._sync_remote_nodes(call_with_retry(
                            self.gcs_client.call, "list_nodes",
                            attempts=2, timeout_s=10.0))
                        last_sync = now
                except (RpcError, RpcMethodError, OSError,
                        AttributeError):
                    continue  # head down / client mid-teardown: next pass
                except Exception:  # noqa: BLE001 — watcher must survive
                    logger.exception("remote node sync failed")
        finally:
            # Closed HERE, not in shutdown(): the watcher may still be
            # constructing/polling the subscriber when shutdown() runs,
            # and only this thread knows the final reference.
            if subscriber is not None:
                subscriber.close()

    def _sync_remote_nodes(self, nodes: list[dict]) -> None:
        from ray_tpu._private.node_executor import RemoteNodeHandle

        listed: dict[NodeID, dict] = {}
        for info in nodes:
            if info.get("executor_address"):
                listed[NodeID(bytes.fromhex(info["node_id"]))] = info

        # Reconcile disappearances: a node the head declared DEAD, or
        # whose executor address changed, is dropped; so is an old id
        # superseded by a re-registration under a fresh id (same
        # executor_address must not double-count capacity). A node
        # merely ABSENT from the table gets a direct-ping grace first:
        # a freshly restarted head starts with an empty table, and the
        # daemon (which keeps its node id across head restarts) may
        # simply not have re-registered yet — its in-flight work is
        # alive and must not be failed by head amnesia. The grace is
        # BOUNDED: a daemon that pings but stays absent from the head's
        # table past node_amnesia_max_passes consecutive sync passes is
        # partitioned from the control plane (it cannot re-register) —
        # keeping it schedulable forever would strand its results
        # outside the directory, so it is dropped like a dead node.
        with self._remote_nodes_lock:
            known = dict(self._remote_nodes)
        alive_addrs = {info["executor_address"] for nid, info
                       in listed.items() if info["alive"]}
        amnesia_candidates = []
        for node_id, handle in known.items():
            info = listed.get(node_id)
            superseded = (info is None
                          and handle.address in alive_addrs)
            declared_dead = info is not None and (
                not info["alive"]
                or info["executor_address"] != handle.address)
            if superseded or declared_dead:
                self._drop_remote_node(node_id)
            elif info is None:
                amnesia_candidates.append((node_id, handle))
            else:
                self._amnesia_misses.pop(node_id, None)
        if amnesia_candidates:
            # Direct-ping grace pings run CONCURRENTLY: after a head
            # restart with many genuinely dead daemons, serial 5s ping
            # timeouts would stall this watcher for minutes while dead
            # handles keep receiving (and failing) dispatches.
            from concurrent.futures import ThreadPoolExecutor

            max_passes = max(1, int(GLOBAL_CONFIG.node_amnesia_max_passes))
            with ThreadPoolExecutor(
                    max_workers=min(8, len(amnesia_candidates))) as tpe:
                alive_flags = list(tpe.map(
                    lambda nh: nh[1].ping(), amnesia_candidates))
            for (node_id, _), is_alive in zip(amnesia_candidates,
                                              alive_flags):
                misses = self._amnesia_misses.get(node_id, 0) + 1
                if not is_alive or misses > max_passes:
                    self._amnesia_misses.pop(node_id, None)
                    self._drop_remote_node(node_id)
                else:
                    self._amnesia_misses[node_id] = misses

        for node_id, info in listed.items():
            if not info["alive"]:
                continue
            with self._remote_nodes_lock:
                already = node_id in self._remote_nodes
            if already:
                # Safety net for the push channel: refresh the reported
                # availability from the table (a missed pubsub delta
                # must not wedge dispatch on a stale low-water mark).
                if info.get("available"):
                    self.cluster.update_reported(
                        node_id, info["available"])
                continue
            handle = RemoteNodeHandle(node_id, info["executor_address"])
            if not handle.ping():
                handle.close()
                continue
            with self._remote_nodes_lock:
                self._remote_nodes[node_id] = handle
                self._remote_ever.add(node_id)
            # Re-join after a transient drop keeps the old ledger (in-
            # flight task releases must balance); only genuinely new
            # nodes get a fresh NodeState.
            if not self.cluster.revive_node(node_id):
                self.cluster.add_node(NodeState(
                    node_id=node_id,
                    total=dict(info["resources"]),
                    available=dict(info["resources"]),
                    labels={**info.get("labels", {}), "remote": "1"},
                ))
            logger.info("remote node %s (%s) joined with %s",
                        info["node_id"][:8], info["executor_address"],
                        info["resources"])

    def _drop_remote_node(self, node_id: NodeID) -> None:
        with self._remote_nodes_lock:
            handle = self._remote_nodes.pop(node_id, None)
            alive = set(self._remote_nodes)
        if handle is None:
            return
        handle.close()
        # Busy-spillback avoid sets were computed against the OLD
        # membership: with this node gone they can exclude every
        # surviving candidate, leaving their tasks queued forever (the
        # spillback reset only re-evaluates on the NEXT bounce, which
        # an un-dispatchable task never gets).
        self.dispatcher.reset_unsatisfiable_avoids(alive)
        self._on_node_dead(node_id)

    def _flush_remote_frees(self) -> None:
        with self._remote_free_lock:
            queued, self._remote_free_queue = self._remote_free_queue, []
        if not queued:
            return
        by_node: dict[NodeID, list[bytes]] = {}
        for node_id, id_bytes in queued:
            by_node.setdefault(node_id, []).append(id_bytes)
        retained: list[tuple[NodeID, bytes]] = []
        for node_id, ids in by_node.items():
            with self._remote_nodes_lock:
                handle = self._remote_nodes.get(node_id)
            if handle is None:
                # Node transiently absent: keep the frees for its
                # return (its store only drops results on owner free).
                retained.extend((node_id, i) for i in ids)
                continue
            try:
                handle.free(ids)
            except Exception:  # noqa: BLE001 — best-effort, retry later
                retained.extend((node_id, i) for i in ids)
        if retained:
            with self._remote_free_lock:
                self._remote_free_queue.extend(retained)
                # Bounded: drop the oldest if a node never comes back.
                if len(self._remote_free_queue) > 100_000:
                    del self._remote_free_queue[:-50_000]

    def _materialize_value(self, object_id: ObjectID, value: Any) -> Any:
        """Resolve a RemoteBlob placeholder by chunked pull from the
        holding node; on failure fall back to lineage reconstruction
        (reference: pull via object directory, recovery via
        object_recovery_manager.h:41)."""
        from ray_tpu._private.node_executor import RemoteBlob, fetch_blob
        from ray_tpu._private import serialization

        if not isinstance(value, RemoteBlob):
            return value
        node_id = NodeID(bytes.fromhex(value.node_hex))
        with self._remote_nodes_lock:
            handle = self._remote_nodes.get(node_id)
        try:
            # Co-hosted holder: one memcpy out of its shared memory
            # beats a chunked pull (same_host.py); falls through to the
            # chunked path when no map lease is granted.
            from ray_tpu._private.same_host import (
                fetch_mapped_blob,
                map_enabled,
            )

            blob = None
            if map_enabled() and self._export_addr:
                call = (handle.pool.call if handle is not None else None)
                if call is not None:
                    blob = fetch_mapped_blob(
                        call, object_id.binary(), self._export_addr,
                        self.host_id)
                    if blob is not None:
                        self.same_host_copy_hits += 1
            if blob is not None:
                pass
            elif handle is not None:
                blob = handle.fetch(object_id.binary())
            else:
                from ray_tpu._private.rpc import RpcClient

                client = RpcClient(value.addr)
                try:
                    if map_enabled() and self._export_addr:
                        blob = fetch_mapped_blob(
                            client.call, object_id.binary(),
                            self._export_addr, self.host_id)
                        if blob is not None:
                            self.same_host_copy_hits += 1
                    if blob is None:
                        blob = fetch_blob(client, object_id.binary())
                finally:
                    client.close()
            real = serialization.deserialize_from_buffer(memoryview(blob))
        except Exception as exc:  # noqa: BLE001 — node gone: try lineage
            from ray_tpu.exceptions import ObjectLostError

            if not self.store.mark_lost(object_id):
                raise
            recovered = False
            try:
                recovered = self.recovery.recover(object_id)
            except Exception:  # noqa: BLE001
                pass
            if recovered:
                return self._materialize_value(
                    object_id, self.store.get(object_id))
            err = ObjectLostError(
                ObjectRef(object_id, _register=False),
                f"object {object_id.hex()} was on unreachable node "
                f"{value.node_hex[:8]} and has no lineage: {exc}")
            self.store.put_error(object_id, err)
            raise err from exc
        self.store.put(object_id, real)  # reseal with the local copy
        return real

    # ------------------------------------------------------------ spill tier

    _SHM_PROMOTE_GRACE_S = 30.0

    def _spill_protected_ids(self) -> set:
        """Id bytes the driver spiller must skip: export leases held
        by co-hosted daemons plus values promoted to worker-bound shm
        within the grace window (their frames may not have attached
        the segment yet)."""
        out = set(self._export_leases.pinned_ids())
        now = time.monotonic()
        with self._promote_lock:
            for oid in [o for o, at in self._recent_promotes.items()
                        if now - at > self._SHM_PROMOTE_GRACE_S]:
                del self._recent_promotes[oid]
            out.update(oid.binary() for oid in self._recent_promotes)
        return out

    def _on_value_spilled(self, object_id: ObjectID) -> None:
        """A driver-store value moved to the disk tier: free its
        shm/arena twin (the victim filter excluded leased ids, so no
        co-hosted daemon holds a pin; already-mapped segments stay
        valid past the unlink) and its export-plane state."""
        try:
            self.shm_directory.free(object_id)
        except Exception:  # noqa: BLE001 — backing free is best-effort
            pass
        self._drop_export_source(object_id.binary())

    def _recover_torn_object(self, object_id: ObjectID) -> None:
        """A managed spill file failed its checksum on restore: the
        store marked the entry lost — rebuild it from lineage (the
        getter is blocked on the reseal), or seal ObjectLostError so
        waiters fail typed instead of hanging."""
        from ray_tpu.exceptions import ObjectLostError

        from ray_tpu._private import flight_recorder

        flight_recorder.record("spill.torn", object_id.hex()[:16])
        recovered = False
        try:
            recovered = self.recovery.recover(object_id,
                                              reason="spill_torn")
        except Exception:  # noqa: BLE001 — fall through to the error
            pass
        if not recovered:
            self.store.put_error(object_id, ObjectLostError(
                ObjectRef(object_id, _register=False),
                f"object {object_id.hex()} spill file was torn and no "
                f"lineage can rebuild it"))

    def _resident_store_bytes(self) -> int:
        """Resident SPILLABLE bytes for admission's two-axis pressure
        classifier: the value store's heap usage plus exported blobs
        (both relieved by the spill tier, unlike true host RSS)."""
        total = self.store._memory_used  # int read, no lock needed
        if self._export_store is not None:
            total += getattr(self._export_store, "_primary_bytes", 0)
        return total

    def spill_stats(self) -> dict:
        """Driver-side spill tier counters (value store + export
        store), zero-valued when the tier is disarmed — the
        ``ray_tpu_spill_*`` /metrics families and the envelope's spill
        row read these."""
        from ray_tpu._private.spill_manager import merged_stats

        return merged_stats(getattr(self.store, "_spill", None),
                            self._export_spill_mgr)

    # -------------------------------------------------------------- cluster

    def add_node(self, resources: dict[str, float],
                 labels: dict[str, str] | None = None) -> NodeID:
        """Add a virtual node (reference: cluster_utils.Cluster.add_node)."""
        node_id = NodeID()
        state = NodeState(
            node_id=node_id,
            total=dict(resources),
            available=dict(resources),
            labels=labels or {},
        )
        self.cluster.add_node(state)
        self.gcs.register_node(NodeRecord(
            node_id=node_id, address=f"local://{node_id.hex()[:8]}",
            resources=dict(resources), labels=labels or {}))
        return node_id

    def remove_node(self, node_id: NodeID) -> None:
        self.cluster.remove_node(node_id)
        self.gcs.mark_node_dead(node_id)

    def kill_node(self, node_id: NodeID) -> None:
        """Chaos: simulate a node crash (reference:
        test_utils.NodeKillerActor, :1498). The health monitor stops
        heartbeating it; staleness then drives the normal death path
        (_on_node_dead) — detection, not fiat.
        """
        self.health_monitor.suppress(node_id)

    def _on_node_dead(self, node_id: NodeID) -> None:
        """Node death: remove from scheduling, lose its objects, rebuild
        what lineage allows (reference: GcsNodeManager node-dead
        broadcast + ObjectRecoveryManager re-execution)."""
        from ray_tpu.exceptions import ObjectLostError

        logger.warning("Node %s died; reconstructing its objects",
                       node_id.hex()[:8])
        from ray_tpu._private import flight_recorder

        flight_recorder.record("node.dead", node_id.hex()[:16])
        self.remove_node(node_id)
        # Queued tasks HARD-pinned to the dead node can never run; fail
        # them now instead of hanging their waiters forever (soft
        # affinity and unpinned tasks reschedule on survivors).
        for spec in self.dispatcher.fail_hard_affinity(node_id.hex()):
            err = TaskError(
                RuntimeError(
                    f"node {node_id.hex()[:8]} died and task "
                    f"{spec.name} is hard-pinned to it"),
                None, spec.name)
            for rid in spec.return_ids:
                self.store.put_error(rid, err)
        # Actors hosted on the dead node restart on a survivor (or die
        # permanently) — even parked ones with no call in flight
        # (reference: GcsActorManager restarts actors on node death).
        for actor in list(self._actors.values()):
            if getattr(actor, "node_id", None) == node_id:
                actor.notify_node_death(node_id)
        with self._locations_lock:
            lost = [oid for oid, nid in self._object_locations.items()
                    if nid == node_id]
            for oid in lost:
                del self._object_locations[oid]
        # Mark everything lost BEFORE recovering anything: recovery checks
        # is_lost() on dependencies, so a partially-marked set would let a
        # parent resubmit against a dep about to vanish.
        marked = [oid for oid in lost if self.store.mark_lost(oid)]
        for oid in marked:
            try:
                if not self.recovery.recover(oid):
                    # _register=False: the error lives inside the entry it
                    # describes — a registered ref would pin the refcount
                    # above zero forever.
                    self.store.put_error(oid, ObjectLostError(
                        ObjectRef(oid, _register=False),
                        f"object {oid.hex()} was on dead node "
                        f"{node_id.hex()[:8]} and has no lineage"))
            except Exception:  # noqa: BLE001 — one object must not strand
                logger.exception("failed to handle loss of object %s",
                                 oid.hex())

    def _handle_object_loss(self, obj_hexes) -> None:
        """Push-path twin of _on_node_dead's object handling: the head
        pruned the LAST holder of these objects from its directory (the
        holding node died). Only objects this driver still tracks as
        remote placeholders react — a locally materialized copy
        survives its producer's node, and foreign owners' ids simply
        don't resolve here."""
        from ray_tpu._private.node_executor import RemoteBlob
        from ray_tpu.exceptions import ObjectLostError

        from ray_tpu._private import flight_recorder

        flight_recorder.record("object.loss", len(obj_hexes))
        for obj_hex in obj_hexes:
            try:
                oid = ObjectID(bytes.fromhex(obj_hex))
            except (ValueError, TypeError):
                continue
            with self.store._lock:
                entry = self.store._entries.get(oid)
                remote = (entry is not None and entry.sealed
                          and isinstance(entry.value, RemoteBlob))
            if not remote or not self.store.mark_lost(oid):
                continue
            with self._locations_lock:
                self._object_locations.pop(oid, None)
            try:
                if not self.recovery.recover(oid):
                    self.store.put_error(oid, ObjectLostError(
                        ObjectRef(oid, _register=False),
                        f"object {oid.hex()} lost its last holder "
                        f"and has no lineage"))
            except Exception:  # noqa: BLE001 — one object must not strand
                logger.exception("failed to rebuild lost object %s",
                                 oid.hex())

    # ----------------------------------------------------------------- tasks

    _ARG_PIN_GRACE_S = 10.0

    def _pin_nested_arg_refs(self, args, kwargs) -> None:
        """Hold handles to refs NESTED in submitted args for a grace
        period. Nested refs aren't resolved by the submitter — the
        callee registers as a borrower — but that registration is
        asynchronous; without this pin, a driver that drops its own
        handle right after submit can free the object before the
        borrow lands (reference: the owner keeps in-flight task args
        reachable while the borrower list is being established,
        reference_count.h:61).

        This walk covers plain list/tuple/dict shapes at SUBMIT time;
        refs inside custom objects are caught later, completely, by the
        pickle-time collector in _convert_remote_args (until that
        serialization happens, the queued args tuple itself keeps every
        nested ObjectRef Python object — and hence its registered
        refcount — alive)."""
        refs: list = []

        def walk(v, depth=0):
            if isinstance(v, ObjectRef):
                refs.append(v)
            elif depth < 8 and type(v) in (list, tuple):
                for x in v:
                    walk(x, depth + 1)
            elif depth < 8 and type(v) is dict:
                for x in v.values():
                    walk(x, depth + 1)

        for a in args:
            walk(a, 1)  # TOP-LEVEL refs resolve before execution
        for v in kwargs.values():
            walk(v, 1)
        if refs:
            self._arg_pin_pen.append(
                (time.monotonic() + self._ARG_PIN_GRACE_S, refs))

    def _sweep_arg_pins(self) -> None:
        now = time.monotonic()
        while self._arg_pin_pen:
            deadline, _ = self._arg_pin_pen[0]
            if deadline > now:
                break
            try:
                self._arg_pin_pen.popleft()
            except IndexError:
                break

    def _arg_pin_sweeper(self) -> None:
        from ray_tpu._private.same_host import pin_ttl_s

        while not self._watcher_stop.wait(1.0):
            self._sweep_arg_pins()
            # Export map leases: liveness-gated TTL expiry, so a
            # SIGKILLed daemon cannot pin driver shared memory forever.
            now = time.monotonic()
            if now - self._lease_sweep_at >= 5.0:
                self._lease_sweep_at = now
                try:
                    self._export_leases.sweep(pin_ttl_s(),
                                              self._probe_peer)
                except Exception:  # noqa: BLE001 — sweep is best-effort
                    pass
                # Crashed co-hosted daemons' native arena segments
                # have no surviving unlinker; the driver reaps them
                # too (same_host.sweep_orphan_shm).
                try:
                    from ray_tpu._private.same_host import (
                        sweep_orphan_shm,
                    )

                    sweep_orphan_shm()
                except Exception:  # noqa: BLE001 — sweep is best-effort
                    pass
                # Same for SIGKILLed co-hosted owners' per-pid spill
                # directories (spill_manager.sweep_orphan_spill_dirs).
                try:
                    from ray_tpu._private import (
                        spill_manager as spill_mod,
                    )

                    if spill_mod.SPILL_ON:
                        spill_mod.sweep_orphan_spill_dirs()
                except Exception:  # noqa: BLE001 — sweep is best-effort
                    pass

    @staticmethod
    def _probe_peer(addr: str) -> bool:
        from ray_tpu._private.rpc import RpcClient

        probe = RpcClient(addr, timeout_s=2.0, connect_timeout_s=1.0)
        try:
            return probe.call("ping") == "pong"
        finally:
            probe.close()

    # ------------------------------------------------- overload control

    @staticmethod
    def _absolute_deadline(deadline_s: float | None) -> float | None:
        """now + budget, falling back to task_default_deadline_s."""
        if deadline_s is None:
            default = float(GLOBAL_CONFIG.task_default_deadline_s or 0)
            if default <= 0:
                return None
            deadline_s = default
        return time.time() + float(deadline_s)

    def _seal_deadline(self, spec_or_rec, stage: str) -> None:
        """Seal TaskTimeoutError onto a task whose end-to-end budget
        died at ``stage`` (shared by the ring flush, the dispatcher's
        queued/claim expiry hook, and the execute paths). The FAILED
        event records the stage so timeline() shows where the budget
        died."""
        err = TaskTimeoutError(
            getattr(spec_or_rec, "name", ""), stage,
            getattr(spec_or_rec, "deadline", 0.0) or 0.0)
        for rid in spec_or_rec.return_ids:
            self.store.put_error(rid, err)
        with self._fault_lock:
            self._task_timeouts += 1
        self.gcs.record_task_event(TaskEvent(
            spec_or_rec.task_id, getattr(spec_or_rec, "name", ""),
            "FAILED", end_time=time.time(),
            error=f"deadline expired at stage {stage!r}"))

    def _seal_overloaded(self, spec_or_rec, reason: str) -> None:
        """Shed a deadline-armed submit at admission: seal a retryable
        SystemOverloadedError instead of queueing unboundedly."""
        err = SystemOverloadedError(reason)
        for rid in spec_or_rec.return_ids:
            self.store.put_error(rid, err)
        with self._fault_lock:
            self._admission_shed += 1
        self.gcs.record_task_event(TaskEvent(
            spec_or_rec.task_id, getattr(spec_or_rec, "name", ""),
            "FAILED", end_time=time.time(), error=f"shed: {reason}"))

    def _admission_overload_reason(self) -> str | None:
        """Why admission should shed right now, or None. Queue-depth
        cap on the dispatcher backlog + host-memory watermark (both
        off by default; the watermark read is memoized)."""
        cap = int(GLOBAL_CONFIG.admission_max_queue_depth or 0)
        if cap > 0:
            depth = self.dispatcher.pending_count()
            if self._lanes is not None:
                depth += self._lanes.outstanding()
            if depth > cap:
                return (f"dispatcher backlog over "
                        f"admission_max_queue_depth={cap}")
        watermark = float(GLOBAL_CONFIG.admission_memory_watermark or 0)
        if watermark > 0:
            from ray_tpu._private import spill_manager as spill_mod
            from ray_tpu._private.memory_monitor import (
                memory_pressure_kind,
                memory_watermark_exceeded,
            )

            mgr = getattr(self.store, "_spill", None)
            if spill_mod.SPILL_ON and mgr is not None:
                # Two-axis split: STORE pressure is recoverable — kick
                # the spillers and admit (the job degrades to disk
                # instead of failing) unless the spill disk is full,
                # which sheds exactly like true HOST pressure.
                kind = memory_pressure_kind(watermark)
                if kind == "store":
                    if not mgr.backing_off():
                        mgr.request_spill()
                        if self._export_spill_mgr is not None:
                            self._export_spill_mgr.request_spill()
                        kind = None
                    else:
                        return ("store memory over admission_memory_"
                                f"watermark={watermark} and the spill "
                                "disk is full (backing off)")
                if kind == "host":
                    return (f"host memory over admission_memory_"
                            f"watermark={watermark}")
            elif memory_watermark_exceeded(watermark):
                # Spill tier disarmed: the PR-7 single-axis shed.
                return (f"host memory over admission_memory_watermark"
                        f"={watermark}")
        return None

    def submit_task(
        self,
        func,
        args: tuple,
        kwargs: dict,
        *,
        name: str,
        num_returns: int = 1,
        resources: dict[str, float],
        max_retries: int = 0,
        retry_exceptions: bool | list = False,
        scheduling_strategy: SchedulingStrategy | None = None,
        runtime_env: dict | None = None,
        deadline_s: float | None = None,
    ) -> list[ObjectRef]:
        """Reference: CoreWorker::SubmitTask (core_worker.cc:1998).

        With the submit pipeline armed (default), ``.remote()`` only
        allocates the task/return ids and pushes a record onto the
        submit ring — refs still come back synchronously, and
        pre-dispatch failures (runtime_env packaging, cancellation of
        a buffered submit) surface as errors sealed onto those refs.
        The ring's flush thread performs the batched record-keeping
        (_flush_submits).

        ``deadline_s`` arms the end-to-end deadline: an ABSOLUTE
        expiry (now + deadline_s) stamped on the spec and checked at
        every later stage; tasks without one inherit
        ``task_default_deadline_s`` (0 = no budget)."""
        deadline = self._absolute_deadline(deadline_s)
        ring = self._submit_ring
        if ring is None:
            return self._submit_task_inline(
                func, args, kwargs, name=name, num_returns=num_returns,
                resources=resources, max_retries=max_retries,
                retry_exceptions=retry_exceptions,
                scheduling_strategy=scheduling_strategy,
                runtime_env=runtime_env, deadline=deadline)
        rec = _SubmitRecord()
        rec.func = func
        rec.args = args
        rec.kwargs = kwargs
        rec.name = name
        rec.num_returns = num_returns
        rec.resources = resources
        rec.max_retries = max_retries
        rec.retry_exceptions = retry_exceptions
        rec.strategy = scheduling_strategy or SchedulingStrategy()
        rec.runtime_env = runtime_env
        rec.task_id = TaskID()
        rec.return_ids = [ObjectID() for _ in range(num_returns)]
        rec.submit_ts = 0.0
        rec.trace_ctx = None
        rec.cancelled = False
        rec.deadline = deadline
        rec.state = _SubmitRecord.BUFFERED
        if tracing.TRACE_ON or perf.PERF_ON:
            # Submit stamped at the TRUE .remote() call: the perf
            # plane's submit→dispatch histogram measures ring + queue
            # wait from here (always-on); the trace context (tracing
            # armed only) additionally links to the caller's open span
            # — the flush thread has no ambient span context, so
            # neither can be made there.
            now = time.time()
            rec.submit_ts = now
            if tracing.TRACE_ON:
                rec.trace_ctx = tracing.make_trace_context(anchor=now)
        # Register the refs directly against OUR counter: the generic
        # ObjectRef constructor re-resolves the global runtime per ref,
        # which is measurable at 100k submits.
        add_ref = self.reference_counter.add_ref
        refs = []
        for rid in rec.return_ids:
            ref = ObjectRef(rid, _register=False)
            add_ref(rid)
            ref._registered = True
            refs.append(ref)
        ring.push(rec)
        return refs

    def _submit_task_inline(
        self,
        func,
        args: tuple,
        kwargs: dict,
        *,
        name: str,
        num_returns: int = 1,
        resources: dict[str, float],
        max_retries: int = 0,
        retry_exceptions: bool | list = False,
        scheduling_strategy: SchedulingStrategy | None = None,
        runtime_env: dict | None = None,
        deadline: float | None = None,
    ) -> list[ObjectRef]:
        """The classic per-task submit path (submit_pipeline=0)."""
        if deadline is not None:
            # Fail-fast admission for deadline-armed inline submits:
            # the caller declared a latency budget, so reject instead
            # of queueing into a backlog that will eat it (the ring
            # path makes the same call per flush).
            reason = self._admission_overload_reason()
            if reason is not None:
                with self._fault_lock:
                    self._admission_shed += 1
                raise SystemOverloadedError(reason)
        task_id = TaskID()
        self._pin_nested_arg_refs(args, kwargs)
        return_ids = [ObjectID() for _ in range(num_returns)]
        strategy = scheduling_strategy or SchedulingStrategy()
        spec = TaskSpec(
            task_id=task_id, name=name, func=func, args=args, kwargs=kwargs,
            num_returns=num_returns, resources=resources,
            max_retries=max_retries, retry_exceptions=retry_exceptions,
            scheduling_strategy=strategy, return_ids=return_ids,
            runtime_env=self._package_runtime_env(runtime_env),
            deadline=deadline,
        )
        for rid in return_ids:
            self.store.create_pending(rid)
        refs = [ObjectRef(rid) for rid in return_ids]
        self.lineage.record(spec)
        submit_stages = {}
        if tracing.TRACE_ON or perf.PERF_ON:
            now = time.time()
            spec._submit_ts = now
            if tracing.TRACE_ON:
                # Root of this task's distributed trace: the context
                # rides the execute RPCs so daemon/worker spans link
                # back here.
                spec._trace_ctx = tracing.make_trace_context(anchor=now)
                if bool(GLOBAL_CONFIG.tracing_stage_timestamps):
                    submit_stages = {"submit": now}
        self.gcs.record_task_event(TaskEvent(task_id, name, "PENDING",
                                             stage_ts=submit_stages))
        deps = [a for a in args if isinstance(a, ObjectRef)] + [
            v for v in kwargs.values() if isinstance(v, ObjectRef)]

        if strategy.kind == "PLACEMENT_GROUP" and strategy.placement_group is not None:
            self._submit_pg_task(spec, deps, strategy)
        else:
            self.dispatcher.submit(spec, self._execute_task, deps)
        return refs

    def _seal_cancelled_submit(self, rec: _SubmitRecord) -> None:
        """A buffered (never-dispatched) submit was cancelled: seal the
        cancellation error onto its refs (put_error creates the store
        entries — they may not exist yet) and record the failure."""
        err = TaskCancelledError(rec.task_id)
        for rid in rec.return_ids:
            self.store.put_error(rid, err)
        self.gcs.record_task_event(TaskEvent(
            rec.task_id, rec.name, "FAILED", error="cancelled"))

    def _cancel_registered(self, object_id) -> None:
        """Cancel a task the dispatcher knows about (the classic
        cancel body, shared with the ring's post-flush cancel)."""
        spec = self.dispatcher.cancel_by_return_id(object_id)
        if spec is not None:
            err = TaskCancelledError(spec.task_id)
            for rid in spec.return_ids:
                self.store.put_error(rid, err)
            self.gcs.record_task_event(TaskEvent(
                spec.task_id, spec.name, "FAILED", error="cancelled"))

    def _flush_submits(self, ring: _SubmitRing,
                       records: "list[_SubmitRecord]") -> None:
        """Drain one submit-ring flush: build the TaskSpecs, then do
        ONE store.create_pending_batch lock pass, ONE
        lineage.record_many, ONE gcs.record_task_events PENDING batch
        and ONE dispatcher.submit_many wakeup for the whole flush —
        the per-task costs the inline path pays 100k times are paid
        once per flush here. ``ring`` is passed in (not read off self):
        shutdown detaches self._submit_ring before the final flush."""
        t_flush0 = time.perf_counter()
        live: list[_SubmitRecord] = []
        with ring._cond:
            for rec in records:
                if rec.cancelled:
                    # Sealed by ring.cancel() while BUFFERED: drop it.
                    for rid in rec.return_ids:
                        ring._by_rid.pop(rid, None)
                    continue
                rec.state = _SubmitRecord.DRAINING
                live.append(rec)
        if not live:
            return
        stamp_stages = tracing.TRACE_ON \
            and bool(GLOBAL_CONFIG.tracing_stage_timestamps)
        # Admission control at the flush boundary: over the queue-depth
        # cap / memory watermark, deadline-armed records are shed with
        # a retryable SystemOverloadedError (fail-fast — their budget
        # would die in the backlog anyway) while deadline-free records
        # wait here, which backpressures the ring and ultimately blocks
        # .remote() (bounded blocking, never loss).
        overload = self._admission_overload_reason()
        if overload is not None:
            armed = [rec for rec in live if rec.deadline is not None]
            if armed:
                for rec in armed:
                    self._seal_overloaded(rec, overload)
                shed_ids = {id(rec) for rec in armed}
                live = [rec for rec in live
                        if id(rec) not in shed_ids]
                with ring._cond:
                    for rec in armed:
                        rec.state = _SubmitRecord.SUBMITTED
                        for rid in rec.return_ids:
                            ring._by_rid.pop(rid, None)
            while live and self._admission_overload_reason() is not None:
                if ring._stop:
                    break  # shutdown flush must not wedge on overload
                time.sleep(0.02)
        now = time.time()
        specs: list[tuple[_SubmitRecord, TaskSpec, list]] = []
        events: list[TaskEvent] = []
        failed: list[tuple[_SubmitRecord, BaseException]] = []
        expired: list[_SubmitRecord] = []
        for rec in live:
            if rec.deadline is not None and now > rec.deadline:
                # The budget died while the record sat BUFFERED in the
                # ring (stage "submit"): seal the typed timeout without
                # ever creating scheduler-side state.
                expired.append(rec)
                continue
            try:
                # One scan serves both dep collection and the
                # container check gating the nested-ref grace pin
                # (top-level refs stay alive via spec.args itself;
                # refs inside custom objects are pinned later by the
                # pickle-time collector in _convert_remote_args).
                deps: list = []
                need_pin = False
                for a in rec.args:
                    if isinstance(a, ObjectRef):
                        deps.append(a)
                    elif type(a) in (list, tuple, dict):
                        need_pin = True
                for v in rec.kwargs.values():
                    if isinstance(v, ObjectRef):
                        deps.append(v)
                    elif type(v) in (list, tuple, dict):
                        need_pin = True
                if need_pin:
                    self._pin_nested_arg_refs(rec.args, rec.kwargs)
                spec = TaskSpec(
                    task_id=rec.task_id, name=rec.name, func=rec.func,
                    args=rec.args, kwargs=rec.kwargs,
                    num_returns=rec.num_returns, resources=rec.resources,
                    max_retries=rec.max_retries,
                    retry_exceptions=rec.retry_exceptions,
                    scheduling_strategy=rec.strategy,
                    return_ids=rec.return_ids,
                    runtime_env=self._package_runtime_env(rec.runtime_env),
                    deadline=rec.deadline,
                )
            except BaseException as exc:  # noqa: BLE001 — pre-dispatch
                failed.append((rec, exc))
                continue
            if rec.trace_ctx is not None:
                spec._trace_ctx = rec.trace_ctx
            if rec.submit_ts:
                # Perf plane: the submit→dispatch histogram anchors on
                # the true .remote() stamp, not the flush time.
                spec._submit_ts = rec.submit_ts
            events.append(TaskEvent(
                rec.task_id, rec.name, "PENDING",
                stage_ts={"submit": rec.submit_ts}
                if stamp_stages and rec.submit_ts else {}))
            specs.append((rec, spec, deps))
        # Batched record-keeping: one lock pass per subsystem. Every
        # pending entry exists before ANY task of this flush reaches
        # the dispatcher, so intra-flush dependencies gate correctly.
        self.store.create_pending_batch(
            [rid for _, spec, _ in specs for rid in spec.return_ids])
        self.lineage.record_many([spec for _, spec, _ in specs])
        if events:
            self.gcs.record_task_events(events)
        plain: list = []
        pg: list = []
        for rec, spec, deps in specs:
            strategy = spec.scheduling_strategy
            if strategy is not None and strategy.kind == "PLACEMENT_GROUP" \
                    and strategy.placement_group is not None:
                pg.append((spec, deps, strategy))
            else:
                plain.append((spec, self._execute_task, deps))
        if plain:
            self.dispatcher.submit_many(plain)
        for spec, deps, strategy in pg:
            self._submit_pg_task(spec, deps, strategy)
        for rec, exc in failed:
            # Pre-dispatch failure (e.g. runtime_env packaging): the
            # inline path would have raised out of .remote(); the
            # pipelined semantics surface it on the refs instead.
            for rid in rec.return_ids:
                self.store.put_error(rid, exc)
            self.gcs.record_task_event(TaskEvent(
                rec.task_id, rec.name, "FAILED", error=str(exc)))
        for rec in expired:
            self._seal_deadline(rec, "submit")
        # Hand the records over: cancels from here on ride the
        # dispatcher. A cancel that raced THIS flush (arrived while
        # DRAINING) is replayed against the dispatcher now.
        post_cancel: list[_SubmitRecord] = []
        with ring._cond:
            for rec in live:
                rec.state = _SubmitRecord.SUBMITTED
                for rid in rec.return_ids:
                    ring._by_rid.pop(rid, None)
                if rec.cancelled:
                    post_cancel.append(rec)
        for rec in post_cancel:
            if rec.return_ids:
                self._cancel_registered(rec.return_ids[0])
        self._flush_wall_us += int(
            (time.perf_counter() - t_flush0) * 1e6)

    # -------------------------------------------- columnar submit (ISSUE 15)

    def submit_columnar(self, template, args) -> "ObjectRef | None":
        """Columnar fast path for an eligible ``.remote()``: mint the
        ids, seed the ref, append ONE tuple to the lock-free buffer —
        no _SubmitRecord, no per-push lock, no notify during a burst.
        Returns None to send the caller down the classic ring path
        (ineligible args, lanes absent, tracing/speculation armed)."""
        lanes = self._lanes
        if lanes is None:
            return None
        ring = self._submit_ring
        if ring is None or ring._stop:
            return None
        # Per-task trace contexts / speculation tracking need real
        # TaskSpecs: the classic path owns those. (A disarmed watcher
        # object sticks around after configure_speculation toggles
        # off — SPEC_ON is the live gate.) One gate per branch.
        if tracing.TRACE_ON:
            return None
        if spec_mod.SPEC_ON and self._spec_watcher is not None:
            return None
        for a in args:
            if type(a) not in _COL_ARG_TYPES:
                return None
        buf = self._col_buf
        if len(buf) >= ring._capacity:
            ring.col_backpressure()
        task_id = TaskID()
        rid = ObjectID()
        # Index BEFORE the buffer append: a record popped by the flush
        # always finds its index entry (GIL program order).
        self._col_index[rid] = task_id
        buf.append((template, task_id, rid, args,
                    time.time() if perf.PERF_ON else 0.0))
        ref = ObjectRef(rid, _register=False)
        self.reference_counter.seed_ref(rid)
        ref._registered = True
        if ring._parked:
            ring.kick()
        return ref

    def _flush_columnar(self, ring: "_SubmitRing") -> None:
        """Drain one columnar flush: group the claimed records by
        template and do O(1) work per GROUP — one ColumnarGroup, one
        bulk rid->group index update, one lineage group record, one
        TaskEvent group record, one lane submission. The per-task
        TaskSpec/TaskEvent/ObjectEntry objects the classic flush
        builds are expanded lazily, only when something touches one."""
        buf = self._col_buf
        n = min(len(buf), ring._flush_max)
        if n <= 0:
            return
        t0 = time.perf_counter()
        records = []
        pop = buf.popleft
        for _ in range(n):
            try:
                records.append(pop())
            except IndexError:
                break
        # Admission control at the flush boundary: columnar records
        # are deadline-free by construction, so over the cap they WAIT
        # (which backpressures the buffer and ultimately .remote()) —
        # bounded blocking, never loss.
        while self._admission_overload_reason() is not None:
            if ring._stop:
                break
            time.sleep(0.02)
        index = self._col_index
        groups: list = []
        with self._col_lock:
            per: dict = {}
            for template, task_id, rid, args, ts in records:
                if index.get(rid) is not task_id:
                    continue  # cancelled while buffered (sealed there)
                cols = per.get(template)
                if cols is None:
                    cols = per[template] = ([], [], [], [])
                cols[0].append(task_id)
                cols[1].append(rid)
                cols[2].append(args)
                cols[3].append(ts)
            for template, cols in per.items():
                group = dispatch_lanes.ColumnarGroup(
                    template, cols[0], cols[1], cols[2], cols[3])
                index.update(dict.fromkeys(cols[1], group))
                groups.append(group)
        lanes = self._lanes
        for group in groups:
            # Lineage + PENDING events as per-flush group records,
            # registered BEFORE the lanes can dispatch any member.
            self.lineage.record_group(group)
            group.event_group = self.gcs.record_task_event_group(
                group.task_ids, group.template.name)
            lanes.submit_group(group)
        self._col_submits += len(records)
        self._col_flush_tasks += sum(len(g) for g in groups)
        self._flush_wall_us += int((time.perf_counter() - t0) * 1e6)
        with ring._cond:
            ring._cond.notify_all()  # unblock col_backpressure waiters

    def _cancel_columnar(self, object_id) -> bool:
        """Cancel routing for columnar ids. True => handled here (the
        error was sealed, or a racing cancel/seal already resolved the
        ref); False => not ours / already dispatched — the caller
        falls through to the dispatcher."""
        index = self._col_index
        st = index.get(object_id)
        if st is None:
            return False
        with self._col_lock:
            st = index.get(object_id)
            if st is None:
                return True  # raced a cancel or a terminal seal
            if st.__class__ is TaskID:
                # Still BUFFERED: the flush will skip the record (its
                # index entry no longer matches); seal here.
                index.pop(object_id, None)
                self._col_buffered_cancels += 1
                task_id, name = st, ""
            else:
                group = st
                if not self._lanes.cancel(object_id, group):
                    return False  # dispatched: best-effort no-op
                index.pop(object_id, None)
                idx = group.by_rid[object_id]
                task_id = group.task_ids[idx]
                name = group.template.name
        err = TaskCancelledError(task_id)
        self.store.put_error(object_id, err)
        self.gcs.record_task_event(TaskEvent(
            task_id, name, "FAILED", error="cancelled"))
        return True

    def _columnar_node_filter(self, node: NodeState) -> bool:
        # Dict membership under the GIL; lanes only dispatch to nodes
        # with a live daemon handle.
        return node.node_id in self._remote_nodes

    def _columnar_indexes_to_classic(self, group, idxs) -> None:
        """Hand columnar tasks to the classic dispatcher (starvation
        fallback, invisible requeues): expand the touched records into
        TaskSpecs, create their store pending entries (attach_future /
        state queries now see them there) and submit_many in one
        pass. The caller has already released any held claims."""
        index = self._col_index
        rids = [group.return_ids[gidx] for gidx in idxs]
        self.store.create_pending_batch(rids)
        items = []
        for gidx in idxs:
            index.pop(group.return_ids[gidx], None)
            items.append((group.spec_for(gidx), self._execute_task, []))
        if items:
            self.dispatcher.submit_many(items)
            self._lanes.task_done(len(items))

    def _columnar_starved(self, group, idxs) -> None:
        """Lane starvation fallback: no filtered (remote) node could
        admit this group for a while — the classic dispatcher owns the
        wait (it can also run the tasks locally)."""
        self._columnar_indexes_to_classic(group, idxs)

    def _columnar_local_fallback(self, group, sent, node) -> None:
        """The function can't cross a process boundary (unpicklable):
        run the slice in-thread via the classic single path, exactly
        like the classic batch runner's fallback."""
        resources = group.template.resources
        index = self._col_index
        for gidx in sent:
            rid = group.return_ids[gidx]
            index.pop(rid, None)
            self.store.create_pending(rid)
            spec = group.spec_for(gidx)
            try:
                self._execute_task(spec, node)
            finally:
                self.cluster.release(node.node_id, resources)
                self._lanes.task_done()

    def _run_columnar_slice(self, group, indexes, node,
                            n_over: int) -> None:
        """Runner-thread executor for one lane allocation: build the
        compact columnar batch RPC, seal streamed reply groups through
        the completion fast path, and route every non-happy reply
        through a lazily materialized TaskSpec on the classic
        machinery. Exactly-once discipline matches the classic batch
        runner: entries the daemon never announced requeue invisibly
        on a cut stream; announced ones fail as WorkerCrashedError
        (retried under the system-failure budget)."""
        from ray_tpu._private import serialization
        from ray_tpu._private.rpc import RpcError, RpcMethodError
        from ray_tpu.exceptions import WorkerCrashedError

        template = group.template
        resources = template.resources
        sent = list(indexes)
        with self._remote_nodes_lock:
            handle = self._remote_nodes.get(node.node_id)
        if handle is None:
            # Node dropped between plan and launch.
            self.cluster.release_many(node.node_id,
                                      [resources] * len(sent))
            self._columnar_indexes_to_classic(group, sent)
            return
        try:
            digest, func_blob = self._function_blob(template.func)
        except Exception:  # noqa: BLE001 — unpicklable: run locally
            self._columnar_local_fallback(group, sent, node)
            return
        with handle._digest_lock:
            known = digest in handle.known_digests
            handle.known_digests.add(digest)
        ser_raw = serialization.try_serialize_raw
        ser_framed = serialization.serialize_framed
        args_col = group.args_col
        rids = group.return_ids
        # Columnar wire: the blob encodes the ARGS TUPLE alone —
        # kwargs are empty by eligibility, so both ends skip the
        # (args, kwargs) nesting the classic frames carry.
        args_blobs = []
        return_keys = []
        for idx in sent:
            args = args_col[idx]
            blob = ser_raw(args)
            args_blobs.append(blob if blob is not None
                              else ser_framed(args))
            return_keys.append(rids[idx].binary())
        descriptor = ("col1", digest, None if known else func_blob,
                      args_blobs, return_keys, resources,
                      group.task_ids[sent[0]].hex())
        n = len(sent)
        done = bytearray(n)
        started: "set[int]" = set()
        cpu_only = {k: v for k, v in resources.items() if k == "CPU"}
        client_addr = self._client_server_addr() or None
        t_send = time.time()
        if perf.PERF_ON:
            ts_col = group.submit_ts
            if ts_col:
                perf.record_stage_many("submit_dispatch", [
                    max(0.0, t_send - ts_col[idx]) for idx in sent
                    if ts_col[idx]])

        def on_col(payload):
            start_local, items = payload
            self._seal_columnar_group(group, sent, done, start_local,
                                      items, node, handle, t_send)

        def on_results(pairs):
            # Classic replies: budget-spilled entries riding the
            # worker pipeline inside the columnar batch.
            for local_idx, reply in pairs:
                if done[local_idx]:
                    continue
                done[local_idx] = 1
                self._finish_columnar_classic(
                    group, sent[local_idx], node, handle, reply)

        def on_parked(local_idx):
            # Over-subscribed entry parked in daemon admission: give
            # its CPU back on the driver ledger until it resumes.
            if cpu_only:
                self.cluster.release(node.node_id, cpu_only)

        def on_resumed(local_idx):
            if cpu_only:
                self.cluster.force_acquire(node.node_id, cpu_only)

        transport_exc = None
        try:
            _, fused_stats = handle.execute_batch(
                descriptor, on_results, on_parked, on_resumed,
                client_addr, on_started=started.add, on_col=on_col)
            if fused_stats.get("fused") \
                    or fused_stats.get("fused_fallbacks"):
                with self._fault_lock:
                    if fused_stats.get("fused"):
                        self._fused_runs += 1
                        self._fused_tasks += int(fused_stats["fused"])
                    self._fused_fallbacks += int(
                        fused_stats.get("fused_fallbacks", 0))
        except (RpcError, RpcMethodError, OSError) as exc:
            transport_exc = exc
        except BaseException as exc:  # noqa: BLE001 — never strand
            # A reply-handler failure mid-stream must not strand the
            # slice's tasks (no seal = a get() hangs forever): treat
            # it like a cut stream — unfinished entries requeue/retry.
            logger.exception("columnar slice reply handling failed")
            transport_exc = exc
        missing = [i for i in range(n) if not done[i]]
        if not missing:
            return
        if transport_exc is not None and not handle.ping():
            self._drop_remote_node(node.node_id)
        for local_idx in missing:
            gidx = sent[local_idx]
            self.cluster.release(node.node_id, resources)
            requeues = group.requeues.get(gidx, 0)
            if local_idx not in started and requeues < 3:
                # Provably never ran (no started window covered it):
                # invisible requeue, no retry budget consumed.
                group.requeues[gidx] = requeues + 1
                with self._fault_lock:
                    self._fault_batch_requeues += 1
                self._columnar_indexes_to_classic(group, [gidx])
                continue
            spec = group.spec_for(gidx)
            self._col_index.pop(rids[gidx], None)
            self._lanes.task_done()
            self.store.create_pending(rids[gidx])
            err = WorkerCrashedError(
                f"node {node.node_id.hex()[:8]} lost task "
                f"{template.name} mid-batch: {transport_exc}")
            self._finish_task_failure(spec, err, t_send)

    def _seal_columnar_group(self, group, sent, done, start_local,
                             items, node, handle, t_send) -> None:
        """Completion FAST path: one store lock pass seals the whole
        reply group (batch listeners only — get-less tasks touch zero
        future machinery), one group-finished counter bump replaces
        per-task FINISHED events, one ledger pass releases the claims,
        and futures resolve only when any are actually attached."""
        from ray_tpu._private import serialization

        deser = serialization.deserialize_from_buffer
        rids = group.return_ids
        pairs = []
        classic = []
        for i, payload in enumerate(items):
            local_idx = start_local + i
            if done[local_idx]:
                continue
            done[local_idx] = 1
            if type(payload) is bytes:
                pairs.append((rids[sent[local_idx]],
                              deser(memoryview(payload))))
            else:
                classic.append((local_idx, payload))
        if pairs:
            self.store.put_group(pairs)
            if self._futures:
                for rid, _ in pairs:
                    self._resolve_futures(rid)
            event_group = group.event_group
            if event_group is not None:
                self.gcs.record_task_group_finished(event_group,
                                                    len(pairs))
            self.cluster.release_many(
                node.node_id, [group.template.resources] * len(pairs))
            self._lanes.task_done(len(pairs))
            index = self._col_index
            for rid, _ in pairs:
                index.pop(rid, None)
            if perf.PERF_ON:
                perf.record_stage_n("rpc_seal",
                                    max(0.0, time.time() - t_send),
                                    len(pairs))
        for local_idx, payload in classic:
            self._finish_columnar_classic(group, sent[local_idx],
                                          node, handle, payload)

    def _finish_columnar_classic(self, group, gidx, node, handle,
                                 reply) -> None:
        """A columnar entry left the happy path ('stored' results,
        errors, requeue shapes): expand the one touched record into a
        TaskSpec and give it to the classic machinery — retries,
        spillback, overload handling and events all behave exactly as
        on the classic batch path."""
        from ray_tpu._private import serialization

        spec = group.spec_for(gidx)
        rid = group.return_ids[gidx]
        self._col_index.pop(rid, None)
        self._lanes.task_done()
        self.store.create_pending(rid)
        resources = group.template.resources
        kind = reply[0]
        start = time.time()
        if kind == "ok":
            try:
                pairs: list = []
                self._collect_remote_results(
                    spec.return_ids, reply[1], node.node_id,
                    handle.address, pairs)
                if pairs:
                    self.store.put_batch(pairs)
                event_group = group.event_group
                if event_group is not None:
                    self.gcs.record_task_group_finished(event_group, 1)
            except BaseException as exc:  # noqa: BLE001
                self._finish_task_failure(spec, exc, start)
            self.cluster.release(node.node_id, resources)
            return
        if kind == "err":
            exc, tb = serialization.deserialize_from_buffer(
                memoryview(reply[1]))
            exc.__ray_tpu_remote_tb__ = tb
            self._finish_task_failure(spec, exc, start)
            self.cluster.release(node.node_id, resources)
            return
        if kind == "need_func":
            # Daemon restarted: re-ship via the single path (which
            # sends the function blob) on its own thread; the claim is
            # released when it completes.
            def redo(spec=spec):
                try:
                    self._execute_task(spec, node)
                finally:
                    self.cluster.release(node.node_id, resources)

            threading.Thread(target=redo, daemon=True,
                             name="ray_tpu-task-refunc").start()
            return
        # Requeue/terminal shapes release the claim first — their next
        # dispatch re-acquires through the classic admission.
        self.cluster.release(node.node_id, resources)
        if kind == "busy":
            self._spillback_requeue(spec, node)
        elif kind == "overloaded":
            self._handle_overloaded_reply(spec, node,
                                          "daemon admission shed")
        elif kind == "timeout":
            self._seal_deadline(
                spec, reply[1] if len(reply) > 1 and reply[1]
                else "admitted")
        elif kind == "cancelled":
            err = TaskCancelledError(spec.task_id)
            for r in spec.return_ids:
                self.store.put_error(r, err)
            self.gcs.record_task_event(TaskEvent(
                spec.task_id, spec.name, "FAILED", error="cancelled"))
        else:
            self._finish_task_failure(
                spec, RuntimeError(f"unknown columnar reply {kind!r}"),
                start)

    def _submit_pg_task(self, spec: TaskSpec, deps, strategy) -> None:
        """Route through the bundle ledger once the PG is committed."""
        pg = strategy.placement_group

        def run_when_ready(shadow=None):
            if shadow is not None:
                # The dispatcher stamped its claim time on the SHADOW
                # spec; fold it back onto the real one or PG tasks lose
                # their dispatch stage in merged traces.
                ts = getattr(shadow, "_stage_dispatch", None)
                if ts is not None:
                    spec._stage_dispatch = ts
            try:
                self.store.get(pg.ready_ref.id())  # wait for commit
                node_id = self.placement_groups.acquire_from_bundle(
                    pg.id, strategy.placement_group_bundle_index, spec.resources)
            except BaseException as exc:  # noqa: BLE001
                for rid in spec.return_ids:
                    self.store.put_error(rid, exc)
                return
            node = self.cluster.get_node(node_id)
            try:
                self._execute_task(spec, node, acquired=False)
            finally:
                self.placement_groups.release_to_bundle(
                    pg.id, strategy.placement_group_bundle_index, spec.resources)

        # PG tasks bypass cluster admission (resources come from the bundle),
        # but still respect dependency gating via the dispatcher.
        pg_spec = TaskSpec(
            task_id=spec.task_id, name=spec.name, func=spec.func, args=spec.args,
            kwargs=spec.kwargs, num_returns=spec.num_returns, resources={},
            return_ids=spec.return_ids, scheduling_strategy=SchedulingStrategy(),
            deadline=spec.deadline)
        pg_spec._original = spec
        # The shadow must carry the trace context too: the dispatcher
        # and event paths read the spec THEY were handed, and dropping
        # the context here made PG tasks vanish from merged traces.
        ctx = getattr(spec, "_trace_ctx", None)
        if ctx is not None:
            pg_spec._trace_ctx = ctx
        self.dispatcher.submit(pg_spec, lambda s, n: run_when_ready(s), deps)

    @staticmethod
    def _dispatch_stages(spec: TaskSpec) -> dict:
        """Stage stamps accumulated driver-side before execution (the
        scheduler's claim time); {} when tracing was off at claim."""
        ts = getattr(spec, "_stage_dispatch", None)
        return {"dispatch": ts} if ts is not None else {}

    def _ingest_reply_trace(self, spec: TaskSpec, handle, trace,
                            t_send: float, t_recv: float) -> None:
        """Fold a reply's piggybacked trace payload into the merged
        view: anchor the node's ClockSync on the exchange (half-RTT),
        offset-correct the daemon/worker stage stamps into driver
        clock, merge them into the task's event, and ingest the
        shipped spans."""
        if trace is None:
            return
        offset = 0.0
        now_remote = trace.get("now")
        if now_remote is not None:
            # Full NTP form: the daemon's admission stamp is its
            # request-receive time (t1), its "now" the reply-send time
            # (t2) — server processing time cancels out of the RTT.
            remote_recv = (trace.get("stages") or {}).get("admitted")
            offset = handle.clock.observe(t_send, t_recv,
                                          float(now_remote),
                                          remote_recv)
        stages = {}
        for key, value in (trace.get("stages") or {}).items():
            if key in tracing.STAGES and isinstance(value, (int, float)):
                stages[key] = float(value) + offset
        stages["rpc_sent"] = t_send
        stages["seal"] = time.time()
        # Causal floor: a sub-ms offset-estimation error must never
        # reorder stages across the clock boundary (admitted cannot
        # precede the rpc that carried it) — enforce happened-before
        # along the canonical chain.
        prev = None
        for key in tracing.STAGES:
            ts = stages.get(key)
            if ts is None:
                continue
            if prev is not None and ts < prev:
                stages[key] = ts = prev
            prev = ts
        self.gcs.merge_stage_ts(spec.task_id, stages)
        spans = trace.get("spans")
        if spans:
            tracing.ingest_spans(spans, offset)

    def _execute_task(self, spec: TaskSpec, node: NodeState, acquired: bool = True) -> None:
        """Reference: CoreWorker::ExecuteTask (core_worker.cc:2717)."""
        start = time.time()
        if spec.deadline is not None and start > spec.deadline:
            # Budget died between claim and launch (PG gating, requeue
            # waits, spillback backoff): seal, never execute dead work.
            self._seal_deadline(spec, "execute")
            return
        self.gcs.record_task_event(TaskEvent(
            spec.task_id, spec.name, "RUNNING", start_time=start,
            node_id=node.node_id.hex() if node else "",
            stage_ts=self._dispatch_stages(spec)
            if tracing.TRACE_ON else {}))
        RuntimeContext.set(
            task_id=spec.task_id, task_name=spec.name, job_id=self.job_id,
            node_id=node.node_id if node else None, actor_id=None)
        block_ctx = BlockedResourceContext(
            self.cluster, node.node_id, spec.resources) if (node and acquired) else None
        remote_handle = None
        if node is not None:
            with self._remote_nodes_lock:
                remote_handle = self._remote_nodes.get(node.node_id)
        watcher = self._spec_watcher
        tracked = spec_mod.SPEC_ON and watcher is not None \
            and watcher.track(spec, node)
        try:
            if remote_handle is not None:
                from ray_tpu._private.node_executor import (
                    NodeBusyError,
                    NodeOverloadedError,
                    TaskDeadlineExpired,
                    TaskSpeculationCancelled,
                )

                try:
                    ran_on_pool = self._try_execute_remote(
                        spec, node, remote_handle)
                except NodeBusyError:
                    self._spillback_requeue(spec, node)
                    return
                except TaskSpeculationCancelled:
                    # The daemon refused the lease: this member's token
                    # was loser-cancelled before its user function ran
                    # (a sibling copy already sealed). Nothing to seal.
                    if watcher is not None:
                        watcher.mark_cancelled(spec)
                    self.gcs.record_task_event(TaskEvent(
                        spec.task_id, spec.name, "FAILED",
                        start_time=start, end_time=time.time(),
                        error="speculation: cancelled before exec"))
                    return
                except TaskDeadlineExpired:
                    # The daemon found the budget dead at admission.
                    self._seal_deadline(spec, "admitted")
                    return
                except NodeOverloadedError as exc:
                    self._handle_overloaded_reply(spec, node, str(exc))
                    return
            elif self.worker_pool is not None:
                ran_on_pool = self._try_execute_on_pool(spec, node)
            else:
                ran_on_pool = False
            if not ran_on_pool:
                if any(k.startswith("TPU") for k in spec.resources):
                    self.chip_leases.claim_in_process(
                        f"task {spec.name!r}")
                if spec.runtime_env:
                    _warn_runtime_env_ignored(
                        f"task {spec.name!r} runs in-thread")
                resolved_args, resolved_kwargs, _ = resolve_args(
                    spec.args, spec.kwargs, lambda ref: self.get([ref])[0])
                if block_ctx is not None:
                    block_ctx.__enter__()
                sample = perf.sample_start() if perf.PERF_ON else None
                try:
                    result = spec.func(*resolved_args, **resolved_kwargs)
                finally:
                    if block_ctx is not None:
                        block_ctx.__exit__(None, None, None)
                if sample is not None:
                    # In-thread execution: the driver is the worker, so
                    # attribution samples land directly.
                    s = perf.sample_end(spec.name, sample)
                    perf.record_task_resources(*s)
                    perf.record_stage("exec_local", s[1])
                self._store_task_result(spec, result, node)
            if tracked:
                # Completed wall sample for the speculation trigger's
                # per-function p99 (only successful completions feed
                # it — spillbacks/failures would skew the baseline).
                watcher.untrack(spec, completed=True)
            self.gcs.record_task_event(TaskEvent(
                spec.task_id, spec.name, "FINISHED", start_time=start,
                end_time=time.time(),
                node_id=node.node_id.hex() if node else ""))
        except BaseException as exc:  # noqa: BLE001 — becomes a TaskError ref
            self._finish_task_failure(spec, exc, start)
        finally:
            if tracked:
                watcher.untrack(spec)
            RuntimeContext.clear()

    def _finish_task_failure(self, spec: TaskSpec, exc: BaseException,
                             start: float) -> None:
        """Terminal failure handling shared by the single and batched
        execute paths: retry when policy allows, else seal the error."""
        watcher = self._spec_watcher
        if watcher is not None and watcher.absorb_failure(spec):
            # A speculation sibling already sealed the result (or is
            # still live and may yet): never seal an error over it —
            # speculation doubles as a hedge against node death.
            self.gcs.record_task_event(TaskEvent(
                spec.task_id, spec.name, "FAILED", start_time=start,
                end_time=time.time(),
                error=f"speculation: absorbed {exc!r}"))
            return
        if self._maybe_retry(spec, exc):
            return
        from ray_tpu.exceptions import ObjectLostError, WorkerCrashedError

        # ObjectLostError and WorkerCrashedError pass through unwrapped:
        # a task that failed because its input is unrecoverable (or its
        # worker died under it) should surface the system failure, not a
        # generic TaskError around it (reference: ray.exceptions raises
        # WorkerCrashedError directly).
        error = exc if isinstance(
            exc, (TaskError, TaskCancelledError, ObjectLostError,
                  WorkerCrashedError)) else \
            TaskError(exc,
                      getattr(exc, "__ray_tpu_remote_tb__", None)
                      or format_traceback(exc), spec.name)
        for rid in spec.return_ids:
            self.store.put_error(rid, error)
        self.gcs.record_task_event(TaskEvent(
            spec.task_id, spec.name, "FAILED", start_time=start,
            end_time=time.time(), error=repr(exc)))

    def _handle_overloaded_reply(self, spec: TaskSpec, node: NodeState,
                                 reason: str) -> None:
        """A daemon shed this task at admission (queue-depth cap /
        memory watermark / overload.saturate chaos). Deadline-armed
        tasks fail fast with the retryable SystemOverloadedError —
        their budget would die waiting anyway; deadline-free ones
        requeue like a busy spillback (bounded blocking, never loss)."""
        if spec.deadline is not None:
            self._seal_overloaded(
                spec, f"node {node.node_id.hex()[:8]} shed the task: "
                      f"{reason}")
            return
        with self._fault_lock:
            self._admission_shed += 1
        self._spillback_requeue(spec, node)

    def _spillback_requeue(self, spec: TaskSpec, node: NodeState) -> None:
        """Spillback (reference: the raylet redirects the lease):
        requeue avoiding this node; once every remote node has
        rejected, the avoid set resets so the task keeps probing as
        capacity frees up — after a growing delay, so saturated
        clusters are polled, not hammered with submit/RPC hot spins."""
        avoid = getattr(spec, "_avoid_nodes", set())
        avoid.add(node.node_id)
        delay = 0.0
        with self._remote_nodes_lock:
            if avoid >= set(self._remote_nodes):
                avoid = set()
                spills = getattr(spec, "_spill_rounds", 0) + 1
                spec._spill_rounds = spills
                delay = min(0.05 * (2 ** min(spills, 6)), 2.0)
        spec._avoid_nodes = avoid
        deps = [a for a in spec.args
                if isinstance(a, ObjectRef)] + [
            v for v in spec.kwargs.values()
            if isinstance(v, ObjectRef)]

        def requeue():
            self.dispatcher.submit(spec, self._execute_task, deps)

        if delay > 0:
            timer = threading.Timer(delay, requeue)
            timer.daemon = True
            timer.start()
        else:
            requeue()

    def _try_execute_on_pool(self, spec: TaskSpec, node=None) -> bool:
        """Run the task on a pool worker process behind the serialization
        boundary. Returns False (caller falls back to in-thread execution)
        when the function/args cannot cross it (unpicklable closures) or
        the task needs accelerator resources (pool workers are CPU
        processes; a TPU task runs on this process's own JAX, which
        must then be the one owner of the host's chips).
        """
        from ray_tpu._private.worker_pool import _RemoteTaskError

        if any(k.startswith("TPU") for k in spec.resources):
            return False
        try:
            args_blob = self.worker_pool.marshal_args(
                spec.args, spec.kwargs, self._promote_to_shm)
            digest, func_blob = self._function_blob(spec.func)
        except Exception:  # noqa: BLE001 — not serializable: run in-thread
            return False
        # Registered for the task's lifetime: a nested get() from the
        # worker carries this token and releases the task's CPU here.
        token = spec.task_id.hex()
        if node is not None:
            with self._inflight_blocks_lock:
                self._inflight_blocks[token] = BlockedResourceContext(
                    self.cluster, node.node_id, spec.resources)
        # stages_out doubles as the perf-plane carrier even untraced:
        # the pool reply's resource sample rolls up on this driver.
        perf_stages: dict | None = {} if perf.PERF_ON else None
        try:
            results = self.worker_pool.run_task_blobs(
                digest, func_blob, args_blob, spec.num_returns,
                spec.return_ids, runtime_env=spec.runtime_env,
                task_token=token, stages_out=perf_stages)
        except _RemoteTaskError as rte:
            rte.cause.__ray_tpu_remote_tb__ = rte.remote_tb
            raise rte.cause from None
        finally:
            with self._inflight_blocks_lock:
                ctx = self._inflight_blocks.pop(token, None)
            if ctx is not None:
                # If the worker died/timed out mid-blocked-get, the CPU
                # release is still outstanding; undo it before the
                # dispatcher's own release double-counts availability.
                ctx.drain()
        if perf_stages:
            sample = perf_stages.get("perf")
            if sample is not None:
                try:
                    perf.record_task_resources(sample[0], sample[1],
                                               sample[2], sample[3])
                    perf.record_stage("exec_local", float(sample[1]))
                except (TypeError, IndexError):
                    pass
        watcher = self._spec_watcher
        if watcher is not None and not watcher.claim_win(spec):
            return True  # sibling sealed first: skip the loser's write
        for rid, value in results:
            self.store.put(rid, value)
            if node is not None:
                self._record_location(rid, node.node_id)
        return True

    def _convert_remote_args(self, args: tuple, kwargs: dict) -> bytes:
        """ObjectRef args become FetchRef location hints (the consuming
        node pulls peer-to-peer; the driver never relays the bytes) or
        inline values; everything else ships by value. Returns the
        framed args blob; raises when the args cannot cross a process
        boundary (reference: args are objects nodes fetch via the
        ownership directory, not payloads inlined per task)."""
        from ray_tpu._private import serialization
        from ray_tpu._private.node_executor import (
            FetchRef,
            RemoteBlob,
            _inline_reply_bytes,
        )
        from ray_tpu._private.object_store import _sizeof

        cache_key = None
        if len(args) <= 8 and len(kwargs) <= 8 \
                and all(_simple_arg(a, 1) for a in args) \
                and all(_simple_arg(v, 1) for v in kwargs.values()):
            cache_key = (args, tuple(sorted(kwargs.items())))
            with self._arg_blob_lock:
                blob = self._arg_blob_cache.get(cache_key)
                if blob is not None:
                    self._arg_blob_cache.move_to_end(cache_key)
                    self.arg_cache_hits += 1
                    return blob
            # Simple-arg tuples are exactly the raw-framing-eligible
            # shape: encode with the tag scheme instead of pickling
            # (the daemon/worker decode dispatches on the sentinel).
            raw = serialization.try_serialize_raw((args, kwargs))
            if raw is not None:
                with self._arg_blob_lock:
                    self._arg_blob_cache[cache_key] = raw
                    while len(self._arg_blob_cache) \
                            > _ARG_CACHE_MAX_ENTRIES:
                        self._arg_blob_cache.popitem(last=False)
                return raw

        inline_max = _inline_reply_bytes()

        def convert(a):
            if not isinstance(a, ObjectRef):
                return a
            id_bytes = a.id().binary()
            if self._export_store is not None \
                    and self._export_store.get(id_bytes) is not None:
                return FetchRef(id_bytes, self._export_addr)
            value = self.store.get(a.id())  # deps sealed at dispatch
            if isinstance(value, RemoteBlob):
                return FetchRef(id_bytes, value.addr)
            if self._export_store is not None \
                    and _sizeof(value) > inline_max:
                # Export once; every node pulls + caches it by id
                # instead of the driver re-shipping per task. Large
                # exports serialize STRAIGHT into named shared memory
                # (no transient heap copy): same-host daemons then map
                # the segment/arena zero-copy, and the chunked
                # cross-host path serves from the same mapping.
                header, buffers = serialization.serialize(value)
                size = serialization.framed_size(header, buffers)
                shm_blob = self._register_export_source(
                    id_bytes, header, buffers, size)
                if shm_blob is not None:
                    self._export_store.put(id_bytes, shm_blob)
                else:
                    blob = serialization.serialize_framed(value)
                    self._export_store.put(id_bytes, blob)
                return FetchRef(id_bytes, self._export_addr)
            return value

        # Refs nested in CUSTOM objects ship as pickled ObjectRefs the
        # callee re-registers as a borrower; collect them here (pickle
        # sees every ref, unlike any structural walk) and grace-pin so
        # a driver dropping its handle right after this serialization
        # can't free the object before that registration lands. The
        # collector wraps the WHOLE conversion: convert() itself
        # serializes large values into the export store, and refs
        # nested inside those must be pinned too.
        from ray_tpu._private.object_ref import collect_reduced_refs

        nested: list = []
        with collect_reduced_refs(nested):
            conv_args = tuple(convert(a) for a in args)
            conv_kwargs = {k: convert(v) for k, v in kwargs.items()}
            blob = serialization.serialize_framed((conv_args, conv_kwargs))
        if nested:
            self._arg_pin_pen.append(
                (time.monotonic() + self._ARG_PIN_GRACE_S, nested))
        if cache_key is not None and not nested \
                and len(blob) <= _ARG_CACHE_MAX_BLOB:
            with self._arg_blob_lock:
                self._arg_blob_cache[cache_key] = blob
                while len(self._arg_blob_cache) > _ARG_CACHE_MAX_ENTRIES:
                    self._arg_blob_cache.popitem(last=False)
        return blob

    def _seal_remote_results(self, return_ids, results, node_id,
                             address) -> None:
        """Seal an execute/actor-call reply: inline values locally,
        larger results as lazy RemoteBlob placeholders with a recorded
        location."""
        from ray_tpu._private import serialization
        from ray_tpu._private.node_executor import RemoteBlob

        for rid, packed in zip(return_ids, results):
            if packed[0] == "inline":
                self.store.put(rid, serialization.deserialize_from_buffer(
                    memoryview(packed[1])))
            elif packed[0] == "stored":
                # Result stays on the producing node; pull lazily.
                self.store.put(rid, RemoteBlob(
                    node_id.hex(), address, packed[1]))
                self._record_location(rid, node_id)
            else:  # ("err", blob): this return value failed to pickle
                exc, tb = serialization.deserialize_from_buffer(
                    memoryview(packed[1]))
                exc.__ray_tpu_remote_tb__ = tb
                raise exc

    def _try_execute_remote(self, spec: TaskSpec, node: NodeState,
                            handle) -> bool:
        """Dispatch to a worker-node daemon's executor (reference: lease
        request to a remote raylet + push to its worker pool,
        node_manager.cc:1714). Returns False when the function/args
        can't cross a process boundary (caller runs the task locally
        in-thread)."""
        from ray_tpu._private.rpc import RpcError
        from ray_tpu.exceptions import WorkerCrashedError

        try:
            digest, func_blob = self._function_blob(spec.func)
            args_blob = self._convert_remote_args(spec.args, spec.kwargs)
        except Exception:  # noqa: BLE001 — unpicklable: run locally
            return False
        return_keys = [rid.binary() for rid in spec.return_ids]
        # The task token keys the daemon's admission entry AND this
        # driver's block context: a nested get() from the daemon's pool
        # worker releases the task's CPU on BOTH ledgers while blocked.
        token = spec.task_id.hex()
        ctx = _RemoteBlockContext(self.cluster, node.node_id,
                                  spec.resources, handle, token)
        with self._inflight_blocks_lock:
            self._inflight_blocks[token] = ctx
        trace_ctx = getattr(spec, "_trace_ctx", None) \
            if tracing.TRACE_ON else None
        t_send = time.time()
        if perf.PERF_ON:
            claim = getattr(spec, "_stage_dispatch", None)
            if claim is not None:
                perf.record_stage("dispatch_rpc",
                                  max(0.0, t_send - claim))
        try:
            results, reply_trace = handle.execute(
                digest, func_blob, args_blob, spec.num_returns,
                return_keys, spec.runtime_env, spec.resources,
                task_token=token,
                client_addr=self._client_server_addr() or None,
                trace_ctx=trace_ctx, deadline=spec.deadline)
        except (RpcError, OSError) as exc:
            # Distinguish a dead node from a transient call failure: a
            # drop marks every object on the node lost and fires
            # lineage recovery — far too heavy for one reset socket.
            if not handle.ping():
                self._drop_remote_node(node.node_id)
            err = WorkerCrashedError(
                f"node {node.node_id.hex()[:8]} unreachable during "
                f"task {spec.name}: {exc}")
            raise err from exc
        finally:
            with self._inflight_blocks_lock:
                popped = self._inflight_blocks.pop(token, None)
            if popped is not None:
                popped.drain()
        watcher = self._spec_watcher
        if watcher is None or watcher.claim_win(spec):
            self._seal_remote_results(spec.return_ids, results,
                                      node.node_id, handle.address)
            if scheduler_mod.LOCALITY_ON:
                # The node now caches this task's pulled large args:
                # future tasks consuming them score it for locality.
                self._learn_arg_locality(spec, node)
        if perf.PERF_ON:
            # The remote round-trip envelope (rpc_sent → seal): the
            # daemon-side breakdown of this window lives in ITS
            # admit_worker/exec histograms.
            perf.record_stage("rpc_seal", time.time() - t_send)
        if reply_trace is not None:
            self._ingest_reply_trace(spec, handle, reply_trace, t_send,
                                     time.time())
        return True

    # ----------------------------------------------------- batched dispatch

    def _task_batch_key(self, spec: TaskSpec, node, run):
        """Dispatcher hook: tasks claimed for the same REMOTE node in
        one pass coalesce into a single execute_task_batch RPC. Local
        tasks, TPU tasks and custom run callables (placement-group
        wrappers) keep the A/B-measured thread-per-task path."""
        if node is None or run != self._execute_task:
            return None
        if any(k.startswith("TPU") for k in spec.resources):
            return None
        with self._remote_nodes_lock:
            if node.node_id not in self._remote_nodes:
                return None
        return node.node_id

    def _collect_remote_results(self, return_ids, results, node_id,
                                address, out_pairs) -> None:
        """Per-task reply descriptors -> (rid, value) seal pairs
        appended to ``out_pairs`` (the caller seals the whole
        completion group in one store.put_batch). Raises on an err
        descriptor — failing only ITS task."""
        from ray_tpu._private import serialization
        from ray_tpu._private.node_executor import RemoteBlob

        for rid, packed in zip(return_ids, results):
            if packed[0] == "inline":
                out_pairs.append((rid, serialization
                                  .deserialize_from_buffer(
                                      memoryview(packed[1]))))
            elif packed[0] == "stored":
                out_pairs.append((rid, RemoteBlob(
                    node_id.hex(), address, packed[1])))
                self._record_location(rid, node_id)
            else:  # ("err", blob): this return value failed to pickle
                exc, tb = serialization.deserialize_from_buffer(
                    memoryview(packed[1]))
                exc.__ray_tpu_remote_tb__ = tb
                raise exc

    def _run_task_batch(self, specs: list[TaskSpec], node: NodeState,
                        complete) -> None:
        """Batch runner handed to the dispatcher: ONE
        execute_task_batch RPC carries the whole run to ``node``;
        grouped completions seal in batches as they stream back, and
        each task's admission releases individually via ``complete``
        (no barrier on the slowest sibling)."""
        from ray_tpu._private import serialization
        from ray_tpu._private.rpc import RpcError, RpcMethodError
        from ray_tpu.exceptions import WorkerCrashedError

        with self._remote_nodes_lock:
            handle = self._remote_nodes.get(node.node_id)
        if handle is None:
            # Node dropped between claim and launch: the single path
            # owns the unreachable-node bookkeeping.
            for spec in specs:
                try:
                    self._execute_task(spec, node)
                finally:
                    complete(spec)
            return
        start = time.time()
        client_addr = self._client_server_addr() or None
        entries: list = []
        ctx_by_idx: dict[int, Any] = {}
        spec_by_idx: dict[int, TaskSpec] = {}
        fallback: list[TaskSpec] = []
        events = []
        for spec in specs:
            try:
                digest, func_blob = self._function_blob(spec.func)
                args_blob = self._convert_remote_args(spec.args,
                                                      spec.kwargs)
            except Exception:  # noqa: BLE001 — unpicklable: run locally
                fallback.append(spec)
                continue
            has_refs = any(isinstance(a, ObjectRef) for a in spec.args) \
                or any(isinstance(v, ObjectRef)
                       for v in spec.kwargs.values())
            token = spec.task_id.hex()
            with handle._digest_lock:
                known = digest in handle.known_digests
                # Optimistic: a daemon restart surfaces as a per-task
                # need_func reply, retried through the single path.
                handle.known_digests.add(digest)
            idx = len(entries)
            # Flags bit 0: args carry FetchRef placeholders; bit 2: the
            # dispatcher over-subscribed this claim past the node's
            # free slots (the daemon parks it in admission instead of
            # bouncing a busy spillback).
            entry = (
                digest, None if known else func_blob, args_blob,
                spec.num_returns,
                [rid.binary() for rid in spec.return_ids],
                spec.runtime_env, spec.resources, token,
                (1 if has_refs else 0)
                | (2 if getattr(spec, "_overcommit", False) else 0))
            trace_ctx = getattr(spec, "_trace_ctx", None) \
                if tracing.TRACE_ON else None
            if trace_ctx is not None or spec.deadline is not None:
                # Optional 10th/11th elements: trace context and the
                # absolute deadline — absent on both counts keeps the
                # plain wire shape byte-identical.
                entry = entry + (trace_ctx,)
            if spec.deadline is not None:
                entry = entry + (spec.deadline,)
            entries.append(entry)
            spec_by_idx[idx] = spec
            if spec_mod.SPEC_ON and self._spec_watcher is not None:
                self._spec_watcher.track(spec, node)
            ctx = _RemoteBlockContext(self.cluster, node.node_id,
                                      spec.resources, handle, token)
            ctx_by_idx[idx] = ctx
            with self._inflight_blocks_lock:
                self._inflight_blocks[token] = ctx
            events.append(TaskEvent(
                spec.task_id, spec.name, "RUNNING", start_time=start,
                node_id=node.node_id.hex(),
                stage_ts=self._dispatch_stages(spec)
                if trace_ctx is not None else {}))
        self.gcs.record_task_events(events)

        complete_many = getattr(complete, "many", None)

        def finish_idx(idx: int, defer: "list | None" = None) -> None:
            spec = spec_by_idx.pop(idx, None)
            if spec is None:
                return
            if self._spec_watcher is not None:
                self._spec_watcher.untrack(spec)
            ctx = ctx_by_idx.pop(idx, None)
            if ctx is not None:
                with self._inflight_blocks_lock:
                    self._inflight_blocks.pop(spec.task_id.hex(), None)
                ctx.drain()
            if defer is not None:
                # Group path: the caller releases the whole group's
                # claims in one ledger pass (complete_many).
                defer.append(spec)
            else:
                complete(spec)

        def on_results(group) -> None:
            pairs: list = []
            done_events = []
            deferred: "list | None" = [] if complete_many is not None \
                else None
            end = time.time()
            for idx, reply in group:
                spec = spec_by_idx.get(idx)
                if spec is None:
                    continue  # duplicate reply
                if perf.PERF_ON and reply[0] in ("ok", "err"):
                    # rpc_sent→seal per task (the streamed group's
                    # arrival is each member's seal moment).
                    perf.record_stage("rpc_seal", max(0.0, end - t_send))
                if reply[0] == "ok":
                    watcher = self._spec_watcher
                    if watcher is not None \
                            and not watcher.claim_win(spec):
                        # Speculation loser: sibling sealed first —
                        # skip the write, just release the claim.
                        finish_idx(idx, deferred)
                        continue
                    try:
                        self._collect_remote_results(
                            spec.return_ids, reply[1], node.node_id,
                            handle.address, pairs)
                        if watcher is not None:
                            watcher.untrack(spec, completed=True)
                        if scheduler_mod.LOCALITY_ON:
                            self._learn_arg_locality(spec, node)
                        done_events.append(TaskEvent(
                            spec.task_id, spec.name, "FINISHED",
                            start_time=start, end_time=end,
                            node_id=node.node_id.hex()))
                        if len(reply) > 2 and reply[2] is not None:
                            # Piggybacked trace payload: daemon/worker
                            # stage stamps + spans, offset-corrected
                            # against this exchange.
                            self._ingest_reply_trace(
                                spec, handle, reply[2], t_send, end)
                    except BaseException as exc:  # noqa: BLE001
                        self._finish_task_failure(spec, exc, start)
                    finish_idx(idx, deferred)
                elif reply[0] == "err":
                    exc, tb = serialization.deserialize_from_buffer(
                        memoryview(reply[1]))
                    exc.__ray_tpu_remote_tb__ = tb
                    self._finish_task_failure(spec, exc, start)
                    finish_idx(idx, deferred)
                elif reply[0] == "busy":
                    finish_idx(idx, deferred)
                    self._spillback_requeue(spec, node)
                elif reply[0] == "timeout":
                    # Daemon-side deadline expiry at admission or on
                    # the worker pipe (the reply names no stage; the
                    # error does).
                    self._seal_deadline(
                        spec, reply[1] if len(reply) > 1 and reply[1]
                        else "admitted")
                    finish_idx(idx, deferred)
                elif reply[0] == "overloaded":
                    finish_idx(idx, deferred)
                    self._handle_overloaded_reply(
                        spec, node, "daemon admission shed")
                elif reply[0] == "cancelled":
                    # Loser-cancelled before exec (speculation): the
                    # sibling's seal already carries the result.
                    if self._spec_watcher is not None:
                        self._spec_watcher.mark_cancelled(spec)
                    finish_idx(idx, deferred)
                else:  # ("need_func", _): single path re-ships the blob
                    def redo(spec=spec):
                        try:
                            self._execute_task(spec, node)
                        finally:
                            complete(spec)

                    spec_by_idx.pop(idx, None)
                    ctx = ctx_by_idx.pop(idx, None)
                    if ctx is not None:
                        with self._inflight_blocks_lock:
                            self._inflight_blocks.pop(
                                spec.task_id.hex(), None)
                        ctx.drain()
                    threading.Thread(target=redo, daemon=True,
                                     name="ray_tpu-task-refunc").start()
            if pairs:
                self.store.put_batch(pairs)
            if done_events:
                self.gcs.record_task_events(done_events)
            if deferred:
                # One ledger pass + one wakeup for the whole group's
                # claim releases (after the seal, so pending_count
                # never undercounts sealed-but-running work).
                complete_many(deferred)

        def on_parked(idx: int) -> None:
            # The daemon queued this task's frame behind a blocked
            # lease head: it holds admission without running — release
            # its CPU on the driver ledger until it actually starts.
            ctx = ctx_by_idx.get(idx)
            if ctx is not None:
                ctx.block()

        def on_resumed(idx: int) -> None:
            ctx = ctx_by_idx.get(idx)
            if ctx is not None:
                ctx.unblock(force=True)

        # Entries the daemon marked maybe-started (their frame reached
        # a worker before the stream cut): on node death these retry
        # under the system-failure budget; everything else provably
        # never ran and requeues invisibly.
        started_idx: set[int] = set()

        transport_exc: BaseException | None = None
        t_send = time.time()  # rpc_sent stamp + the ClockSync anchor
        if perf.PERF_ON:
            for spec in spec_by_idx.values():
                claim = getattr(spec, "_stage_dispatch", None)
                if claim is not None:
                    perf.record_stage("dispatch_rpc",
                                      max(0.0, t_send - claim))
        if entries:
            try:
                _, fused_stats = handle.execute_batch(
                    entries, on_results, on_parked, on_resumed,
                    client_addr, on_started=started_idx.add)
                if fused_stats.get("fused") \
                        or fused_stats.get("fused_fallbacks"):
                    with self._fault_lock:
                        if fused_stats.get("fused"):
                            self._fused_runs += 1
                            self._fused_tasks += int(
                                fused_stats["fused"])
                        self._fused_fallbacks += int(
                            fused_stats.get("fused_fallbacks", 0))
            except (RpcError, RpcMethodError, OSError) as exc:
                transport_exc = exc
        if spec_by_idx:
            # Stream cut (or daemon replied short): maybe-started
            # leftovers are in the same in-flight-loss state as a
            # failed single RPC; unstarted ones requeue invisibly (no
            # retry budget consumed — mirroring the daemon-internal
            # per-worker crash semantics one level up). A bounded
            # invisible-requeue count per spec stops a flapping daemon
            # from cycling a task forever without consuming budget.
            if transport_exc is not None and not handle.ping():
                self._drop_remote_node(node.node_id)
            for idx in list(spec_by_idx):
                spec = spec_by_idx.get(idx)
                if spec is None:
                    continue
                invisible = getattr(spec, "_invisible_requeues", 0)
                if idx not in started_idx and invisible < 3:
                    spec._invisible_requeues = invisible + 1
                    with self._fault_lock:
                        self._fault_batch_requeues += 1
                    finish_idx(idx)  # releases claim + block context
                    deps = [a for a in spec.args
                            if isinstance(a, ObjectRef)] + [
                        v for v in spec.kwargs.values()
                        if isinstance(v, ObjectRef)]
                    self.dispatcher.submit(spec, self._execute_task,
                                           deps)
                    continue
                err = WorkerCrashedError(
                    f"node {node.node_id.hex()[:8]} lost task "
                    f"{spec.name} mid-batch: {transport_exc}")
                self._finish_task_failure(spec, err, start)
                finish_idx(idx)
        for spec in fallback:
            try:
                self._execute_task(spec, node)
            finally:
                complete(spec)

    def ensure_client_server(self) -> None:
        """Start the client server on first need (idempotent)."""
        if self.worker_client_server is not None:
            return
        from ray_tpu.util.client import ClientServer

        host = "0.0.0.0" if self.gcs_client is not None else "127.0.0.1"
        self.worker_client_server = ClientServer(host=host, port=0).start()
        # Worker processes spawned after this inherit it via os.environ.
        os.environ["RAY_TPU_DRIVER_CLIENT_ADDR"] = \
            f"127.0.0.1:{self.worker_client_server.port}"

    def _package_runtime_env(self, renv: dict | None) -> dict | None:
        """Turn local working_dir / py_modules directories into content-
        hashed packages served from the export store, so remote nodes
        can fetch + cache them (reference:
        _private/runtime_env/packaging.py). Local-only runtimes (no
        export server) keep raw paths — every worker shares the
        filesystem there."""
        if not renv or self._obj_server is None:
            return renv
        from ray_tpu._private.runtime_env_packaging import (
            hash_directory,
            package_directory,
        )

        def pack(path, keep_name):
            if not (isinstance(path, str) and os.path.isdir(path)):
                return path
            key = os.path.abspath(path)
            # Re-hash per submit (cheap): edits to the directory must
            # ship fresh content, never a stale cached package.
            hash_hex = hash_directory(key)
            if self._pkg_hashes.get(key) != hash_hex:
                zipped_hash, blob = package_directory(key)
                self._export_store.put(bytes.fromhex(zipped_hash), blob)
                self._pkg_hashes[key] = zipped_hash
                hash_hex = zipped_hash
            member = os.path.basename(key.rstrip("/")) if keep_name \
                else None
            return {"__pkg__": [hash_hex, self._export_addr, member]}

        out = dict(renv)
        if "working_dir" in out:
            out["working_dir"] = pack(out["working_dir"], keep_name=False)
        if out.get("py_modules"):
            # py_modules stay importable by their directory NAME.
            out["py_modules"] = [pack(m, keep_name=True)
                                 for m in out["py_modules"]]
        if out.get("pip"):
            out["pip"] = self._package_pip_spec(out["pip"])
        return out

    def _package_pip_spec(self, spec):
        """Local wheel/requirement FILES in a pip spec become content-
        hashed export-store entries so remote daemons (no shared
        filesystem) can fetch them; requirement strings pass through
        (reference: runtime_env/pip.py + packaging.py URI scheme)."""
        from ray_tpu._private.runtime_env_pip import (
            _file_content_hash,
            normalize_pip_spec,
        )

        norm = normalize_pip_spec(spec)
        packages = []
        for entry in norm["packages"]:
            if os.path.isdir(entry):
                raise ValueError(
                    f"runtime_env pip entry {entry!r} is a directory; "
                    "build a wheel (source installs need a build "
                    "toolchain on every node)")
            if os.path.isfile(entry):
                # Content hash is memoized by (path, mtime, size); the
                # export-store put is skipped when this exact content
                # was already exported (repeat submits are free, like
                # the working_dir path's _pkg_hashes memo).
                hash_hex = _file_content_hash(entry)
                if self._pkg_hashes.get(("pip", entry)) != hash_hex:
                    with open(entry, "rb") as f:
                        self._export_store.put(
                            bytes.fromhex(hash_hex), f.read())
                    self._pkg_hashes[("pip", entry)] = hash_hex
                packages.append({"__pip_file__": [
                    hash_hex, self._export_addr,
                    os.path.basename(entry)]})
            else:
                packages.append(entry)
        return {"packages": packages,
                "pip_install_options": norm["pip_install_options"]}

    def _worker_log_context(self, base: str) -> "str | None":
        """Owner attribution for tailed worker logs: map the log file's
        worker index → live pid → the actor record executing there
        (ActorRecord.pid), so interleaved actor output is labeled with
        the actor id rather than an anonymous worker name."""
        pool = self.worker_pool
        if pool is None or not base.startswith("worker-w"):
            return None
        try:
            index = int(base[len("worker-w"):])
        except ValueError:
            return None
        pids = []
        with pool._index_lock:
            for w in pool._all_workers:
                if w.index == index:
                    pids.append(w.proc.pid)
        if not pids:
            # Process actors own dedicated workers outside the shared
            # pool (ProcessActor -> PoolWorker(-1)).
            for actor in list(self._actors.values()):
                w = getattr(actor, "_worker", None)
                if w is not None and getattr(w, "index", None) == index:
                    pids.append(w.proc.pid)
        if len(pids) != 1:
            return None  # unknown or ambiguous: keep the plain prefix
        for rec in self.gcs.list_actors():
            if rec.pid == pids[0] and rec.state == "ALIVE":
                return f"actor={rec.actor_id.hex()[:8]}"
        return None

    def lookup_block_context(self, token: str):
        """Block context of an in-flight pool task (client server calls
        this when a nested get carries the task's token)."""
        with self._inflight_blocks_lock:
            return self._inflight_blocks.get(token)

    # ------------------------------------------- locality-aware placement

    def _arg_bytes(self, object_id: ObjectID) -> "tuple[int, str | None]":
        """(resident bytes, primary holder hex) of a sealed argument:
        RemoteBlob placeholders report the producing node and true
        blob size; driver-exported args their export-store size (no
        single holder — pullers accrue via the learned map)."""
        from ray_tpu._private.node_executor import RemoteBlob

        with self.store._lock:
            entry = self.store._entries.get(object_id)
            if entry is None or not entry.sealed \
                    or entry.error is not None:
                return 0, None
            value = entry.value
            size = entry.size_bytes
        if isinstance(value, RemoteBlob):
            return int(value.size), value.node_hex
        if self._export_store is not None:
            exported = self._export_store.size(object_id.binary())
            if exported:
                return int(exported), None
        return int(size), None

    def _locality_for_spec(self, spec: TaskSpec) -> dict | None:
        """Dispatcher locality hook: {node hex -> resident bytes of
        this task's large args}. Sources, byte-weighted per arg at or
        above locality_min_arg_kb: the primary holder recorded by the
        owner-side object directory (stored results), the learned
        residency map (nodes that already pulled+cached the arg), and
        the head ObjectDirectory's multi-holder view."""
        min_bytes = self._locality_min_bytes
        if min_bytes <= 0:
            return None
        refs = [a for a in spec.args if isinstance(a, ObjectRef)]
        refs += [v for v in spec.kwargs.values()
                 if isinstance(v, ObjectRef)]
        if not refs:
            return None
        out: dict[str, float] = {}
        holder_cache = self._holder_cache
        spilled = self._spilled_holders
        for ref in refs:
            oid = ref.id()
            size, primary = self._arg_bytes(oid)
            if size < min_bytes:
                continue
            holders: set[str] = set()
            if primary:
                holders.add(primary)
            with self._arg_locality_lock:
                learned = self._arg_locality.get(oid)
                if learned:
                    holders |= learned
            extra = holder_cache.get(oid.hex())
            if extra:
                holders.update(extra)
            # Spill-aware discount: a holder whose copy currently
            # lives on its disk tier must pay a restore before serving
            # — it gets no free byte credit over pulling from memory
            # elsewhere (it still counts, at a fraction, since disk
            # beats a cross-node transfer).
            spilled_at = spilled.get(oid.hex())
            for node_hex in holders:
                credit = size * (0.25 if node_hex == spilled_at
                                 else 1.0)
                out[node_hex] = out.get(node_hex, 0.0) + credit
        return out or None

    def _learn_arg_locality(self, spec: TaskSpec,
                            node: NodeState) -> None:
        """A task consuming large args just completed on ``node``: the
        node's pull cache now holds those args, so score it for future
        placements (bounded LRU; the broadcast-arg pattern turns into
        locality hits from the second wave on)."""
        refs = [a for a in spec.args if isinstance(a, ObjectRef)]
        refs += [v for v in spec.kwargs.values()
                 if isinstance(v, ObjectRef)]
        if not refs or node is None:
            return
        min_bytes = self._locality_min_bytes
        eligible = [r.id() for r in refs
                    if self._arg_bytes(r.id())[0] >= min_bytes]
        if not eligible:
            return
        node_hex = node.node_id.hex()
        with self._arg_locality_lock:
            for oid in eligible:
                holders = self._arg_locality.get(oid)
                if holders is None:
                    holders = self._arg_locality[oid] = set()
                holders.add(node_hex)
                self._arg_locality.move_to_end(oid)
            while len(self._arg_locality) > 4096:
                self._arg_locality.popitem(last=False)

    def _sync_sched_feed(self) -> None:
        """Node-watcher beat: fold the GCS node-stats table (with
        receipt ages) into the scheduler's load view and refresh the
        ObjectDirectory holder cache — the two live inputs of
        locality-/load-aware pick_node."""
        if self.gcs_client is None:
            return
        try:
            table = self.gcs_client.call("node_stats",
                                         timeout_s=5.0) or {}
        except Exception:  # noqa: BLE001 — head unreachable: keep last
            return
        for hex_id, stats in table.items():
            if not isinstance(stats, dict):
                continue
            try:
                node_id = NodeID(bytes.fromhex(hex_id))
            except (ValueError, TypeError):
                continue
            hist = stats.get("stage_hist") or {}
            wait = 0.0
            for stage in ("admit_worker", "exec"):
                snap = hist.get(stage)
                if isinstance(snap, dict):
                    wait += perf.quantile(snap, 0.5)
            self.cluster.update_node_stats(
                node_id,
                running=float(stats.get("running", 0.0) or 0.0),
                depth=float(stats.get(
                    "depth", stats.get("running", 0.0)) or 0.0),
                wait_s=wait,
                age_s=float(stats.get("age_s", 0.0) or 0.0))
        try:
            locs = self.gcs_client.call("list_object_locations",
                                        None, True, timeout_s=5.0)
            if isinstance(locs, tuple) and len(locs) == 2:
                # Spill-aware view: holders whose only copy is on
                # their disk tier should not win byte-weighted
                # locality (a restore costs disk IO the byte credit
                # assumed was free).
                self._holder_cache, self._spilled_holders = locs
            elif isinstance(locs, dict):  # pre-spill-aware head
                self._holder_cache = locs
        except Exception:  # noqa: BLE001 — best-effort holder view
            pass

    def gcs_persist_stats(self) -> dict | None:
        """The head's durable-control-plane counters + live epoch
        (``/metrics`` ray_tpu_gcs_* families), cached a few seconds so
        scrapes don't turn into head RPC storms. None when there is no
        head to ask (local-only runtime)."""
        if self.gcs_client is None:
            return None
        now = time.monotonic()
        fetched_at, cached = self._gcs_persist_cache
        if cached is not None and now - fetched_at < 5.0:
            return cached
        try:
            stats = self.gcs_client.call("gcs_persist_stats",
                                         timeout_s=2.0)
        except Exception:  # noqa: BLE001 — head unreachable: last known
            return cached
        if isinstance(stats, dict):
            self._gcs_persist_cache = (now, stats)
            return stats
        return cached

    def gcs_shard_stats(self) -> list | None:
        """Per-shard stats rows from a sharded head (``/metrics``
        ray_tpu_gcs_shard{shard=,key=} family), same short cache as
        gcs_persist_stats. Empty list on an unsharded head; None when
        there is no head (or it predates sharding)."""
        if self.gcs_client is None:
            return None
        now = time.monotonic()
        fetched_at, cached = self._gcs_shard_cache
        if cached is not None and now - fetched_at < 5.0:
            return cached
        try:
            rows = self.gcs_client.call("gcs_shard_stats",
                                        timeout_s=2.0)
        except Exception:  # noqa: BLE001 — old/unreachable head
            return cached
        if isinstance(rows, list):
            self._gcs_shard_cache = (now, rows)
            return rows
        return cached

    def metrics_history(self, window_s: float | None = None,
                        node: str | None = None) -> dict | None:
        """Windowed per-node history from the head's ring store
        (cluster history plane): per-interval delta samples +
        rate-over-window per counter, ``degraded`` naming any stalled
        shard domains. Cached ~1s — ``top`` refreshing every second
        must not turn into a head RPC storm. None when there is no
        head (or it predates the history plane); a disarmed head
        answers ``armed=False``."""
        if self.gcs_client is None:
            return None
        now = time.monotonic()
        fetched_at, key, cached = self._history_cache
        if cached is not None and key == (window_s, node) \
                and now - fetched_at < 1.0:
            return cached
        try:
            hist = self.gcs_client.call(
                "metrics_history", window_s=window_s, node=node,
                timeout_s=2.0)
        except Exception:  # noqa: BLE001 — old/unreachable head
            return cached if key == (window_s, node) else None
        if isinstance(hist, dict):
            self._history_cache = (now, (window_s, node), hist)
            return hist
        return cached if key == (window_s, node) else None

    def cluster_health(self) -> dict | None:
        """The head watchdog's typed verdicts (active + recent fired
        ring with evidence windows). Same caching/None contract as
        metrics_history."""
        if self.gcs_client is None:
            return None
        now = time.monotonic()
        fetched_at, cached = self._health_cache
        if cached is not None and now - fetched_at < 1.0:
            return cached
        try:
            health = self.gcs_client.call("cluster_health",
                                          timeout_s=2.0)
        except Exception:  # noqa: BLE001 — old/unreachable head
            return cached
        if isinstance(health, dict):
            self._health_cache = (now, health)
            return health
        return cached

    def configure_speculation(self, enabled: bool) -> None:
        """Arm/disarm straggler speculation at runtime (benches A/B
        this; init honors the speculation_enabled knob). The watcher
        thread is created on first arm and survives disarms (SPEC_ON
        gates every site)."""
        GLOBAL_CONFIG.update({"speculation_enabled": bool(enabled)})
        (spec_mod.enable if enabled else spec_mod.disable)()
        if enabled and self._spec_watcher is None:
            self._spec_watcher = spec_mod.SpeculationWatcher(self)

    def _record_location(self, object_id: ObjectID, node_id: NodeID) -> None:
        """Owner-side object directory (reference:
        ownership_based_object_directory.h): which node holds the primary
        copy — the set of objects that die with that node."""
        node = self.cluster.get_node(node_id)
        if node is None or not node.alive:
            # A task that finished after its node was declared dead keeps
            # its driver-held result; recording the dead node would leave
            # a permanently stale entry.
            return
        with self._locations_lock:
            self._object_locations[object_id] = node_id
            self._loc_dirty_adds[object_id.hex()] = node_id.hex()
            self._loc_dirty_removes.discard(object_id.hex())

    def _on_gcs_reply_meta(self, meta: dict) -> None:
        """Reader-thread observer for the head's reply metadata: an
        epoch bump (head restart) schedules a full re-publish of
        everything this driver owns at the head — locations, actor
        registry, placement groups — under the new epoch."""
        epoch = meta.get("epoch") if isinstance(meta, dict) else None
        if not isinstance(epoch, int):
            return
        prior = self._gcs_epoch
        self._gcs_epoch = epoch
        if prior is not None and epoch != prior:
            from ray_tpu._private import flight_recorder

            flight_recorder.record("epoch.bump", prior, epoch)
            self._epoch_republish = True
            self._loc_keepalive = 0.0  # next flush full-republishes

    def _handle_stale_epoch(self, exc) -> bool:
        """True when ``exc`` is the typed stale-epoch fence: re-sync
        the epoch (the rejecting reply's error carries it) and
        schedule the full re-publish; the caller requeues its payload
        and the next flush lands under the current epoch."""
        from ray_tpu._private.gcs import StaleEpochError
        from ray_tpu._private.rpc import RpcMethodError

        cause = exc.cause if isinstance(exc, RpcMethodError) else exc
        if not isinstance(cause, StaleEpochError):
            return False
        from ray_tpu._private import flight_recorder

        flight_recorder.record("gcs.stale_epoch", cause.current_epoch)
        self._gcs_epoch = cause.current_epoch
        self._epoch_republish = True
        self._loc_keepalive = 0.0
        return True

    def _queue_actor_mirror(self, event) -> None:
        """Local pubsub 'actors' callback (any lifecycle transition —
        REGISTERED/ALIVE/RESTARTING/DEAD): queue the id for the
        watcher's batched publish. Must stay cheap — it runs inline
        with the transition."""
        try:
            _state, actor_id = event
        except (TypeError, ValueError):
            return
        with self._mirror_lock:
            self._actor_dirty.add(actor_id)

    def _flush_control_mirror(self) -> None:
        """Watcher-beat publish of the driver's control-plane state to
        the head: dirty actor records (full upserts — RESTARTING state
        and num_restarts included) and the placement-group snapshot on
        version bumps. After an epoch bump EVERYTHING re-publishes —
        the restarted head's snapshot may predate recent transitions,
        and a stale-epoch rejection proves the head never saw them."""
        if self.gcs_client is None:
            return
        if self._epoch_republish:
            self._epoch_republish = False
            with self._mirror_lock:
                self._actor_dirty.update(
                    r.actor_id for r in self.gcs.list_actors())
                self._pg_published_version = -1
        with self._mirror_lock:
            dirty, self._actor_dirty = self._actor_dirty, set()
        records = []
        for actor_id in dirty:
            record = self.gcs.get_actor(actor_id)
            if record is not None:
                records.append(self.gcs._actor_plain(record))
        if records:
            try:
                self.gcs_client.call(
                    "actor_update", records, epoch=self._gcs_epoch,
                    timeout_s=10.0)
            except Exception as exc:  # noqa: BLE001 — requeue, retry next beat
                self._handle_stale_epoch(exc)
                with self._mirror_lock:
                    self._actor_dirty.update(dirty)
        pg_version = getattr(self.placement_groups, "version", 0)
        if pg_version != self._pg_published_version:
            try:
                self.gcs_client.call(
                    "pg_update", self.job_id.hex(),
                    self.placement_groups.snapshot(),
                    epoch=self._gcs_epoch, timeout_s=10.0)
                self._pg_published_version = pg_version
            except Exception as exc:  # noqa: BLE001 — retry next beat
                self._handle_stale_epoch(exc)

    def _flush_object_locations(self) -> None:
        """Batched publish of location deltas to the head's object-
        location table; an empty update every 10s keeps the owner's
        entries leased while it lives."""
        if self.gcs_client is None or not self._export_addr:
            return
        with self._locations_lock:
            adds = list(self._loc_dirty_adds.items())
            removes = list(self._loc_dirty_removes)
            self._loc_dirty_adds.clear()
            self._loc_dirty_removes.clear()
            have_entries = bool(self._object_locations)
        now = time.monotonic()
        if not adds and not removes:
            if not have_entries or now - self._loc_keepalive < 10.0:
                return
            # Keepalive doubles as a FULL re-publish: a restarted head
            # (in-memory table) or a >TTL driver stall must not lose
            # the surviving entries forever.
            with self._locations_lock:
                adds = [(oid.hex(), nid.hex()) for oid, nid
                        in self._object_locations.items()]
        try:
            self.gcs_client.call("object_locations_update",
                                 self._export_addr, adds, removes,
                                 epoch=self._gcs_epoch)
            self._loc_keepalive = now
        except Exception as exc:  # noqa: BLE001 — head unreachable: requeue
            # Stale-epoch fence: the head restarted and this driver's
            # deltas were rejected typed so an old incarnation's view
            # can't corrupt the restored directory. Re-sync + requeue;
            # the next flush FULL-republishes under the new epoch.
            self._handle_stale_epoch(exc)
            with self._locations_lock:
                for obj_hex, node_hex in adds:
                    self._loc_dirty_adds.setdefault(obj_hex, node_hex)
                self._loc_dirty_removes.update(removes)

    def _forget_object(self, object_id: ObjectID) -> None:
        with self._locations_lock:
            node_id = self._object_locations.pop(object_id, None)
            if node_id is not None:
                self._loc_dirty_removes.add(object_id.hex())
                self._loc_dirty_adds.pop(object_id.hex(), None)
        if self._export_store is not None:
            self._export_store.free([object_id.binary()])
        if self._export_directory is not None:
            self._export_directory.drop([object_id.binary()])
        self._drop_export_source(object_id.binary())
        if node_id is not None:
            # Remote primary copy: tell the holder to drop it (owner-
            # driven GC — batched by the node watcher). Queue even when
            # the handle is transiently gone: the flush retains entries
            # until the node returns, else the blob leaks in its store.
            with self._remote_nodes_lock:
                ever_remote = node_id in self._remote_ever
            if ever_remote:
                with self._remote_free_lock:
                    self._remote_free_queue.append(
                        (node_id, object_id.binary()))
        self.lineage.forget([object_id])

    def _function_blob(self, func) -> tuple[str, bytes]:
        """Serialize a task function once per identity (reference:
        function_manager.py exports each function to the GCS KV once).
        Like the reference, closures are captured at first export."""
        import hashlib

        from ray_tpu._private import serialization

        try:
            cached = self._func_blobs.get(func)
        except TypeError:  # unhashable callable
            cached = None
        if cached is not None:
            return cached
        blob = serialization.dumps_function(func)
        entry = (hashlib.sha1(blob).hexdigest(), blob)
        try:
            self._func_blobs[func] = entry
        except TypeError:
            pass
        return entry

    def _promote_to_shm(self, ref: ObjectRef):
        """Object directory lookup-or-promote: make a driver-held object
        reachable by worker processes via a shared-memory segment.

        Serialized under a lock: two dispatcher threads promoting the
        same ref concurrently would otherwise race the arena's
        duplicate-id check and leak a pinned arena entry.
        """
        from ray_tpu._private.shm_store import ShmObjectWriter

        from ray_tpu._private import serialization

        with self._promote_lock:
            self._recent_promotes[ref.id()] = time.monotonic()
            desc = self.shm_directory.lookup(ref.id())
            if desc is not None:
                return desc
            value = self._materialize_value(
                ref.id(), self.store.get(ref.id()))  # deps sealed at dispatch
            header, buffers = serialization.serialize(value)
            size = serialization.framed_size(header, buffers)
            if (self.arena is not None and size <= int(
                    GLOBAL_CONFIG.object_arena_max_object_bytes)):
                # Arena-first: keyed by the object id, so repeated
                # promotes of the same object are one table hit, not a
                # new segment.
                adesc = ShmObjectWriter.put_arena_serialized(
                    self.arena, ref.id().binary(), header, buffers, size)
                if adesc is not None:
                    self.shm_directory.register_arena(ref.id(), adesc)
                    return adesc
            desc, seg = ShmObjectWriter.put_serialized(
                header, buffers, size)
            self.shm_directory.register(ref.id(), desc, seg)
            return desc

    def _maybe_retry(self, spec: TaskSpec, exc: BaseException) -> bool:
        """Owner-driven retry (reference: task_manager.h:195, max_task_retries
        common.proto:645). System failures (worker death) retry whenever
        retries remain; application errors only if retry_exceptions
        allows them."""
        from ray_tpu.exceptions import WorkerCrashedError

        # OOM kills by the memory monitor carry their own retry budget
        # (reference: OOM failures retry independently of
        # max_task_retries — the task did nothing wrong).
        oom_kill = (isinstance(exc, WorkerCrashedError)
                    and self.memory_monitor is not None
                    and getattr(exc, "worker_pid", None)
                    in self.memory_monitor.killed_pids)
        if oom_kill and spec.attempt + 1 >= int(
                GLOBAL_CONFIG.task_oom_retries):
            # Final OOM attempt: consume the attribution so a recycled
            # pid cannot reclassify a future unrelated crash.
            self.memory_monitor.consume_attribution(exc.worker_pid)
        retry_budget = max(spec.max_retries,
                           int(GLOBAL_CONFIG.task_oom_retries)
                           if oom_kill else spec.max_retries)
        if spec.attempt >= retry_budget:
            return False
        retry_ok = False
        if isinstance(exc, (ActorDiedError, WorkerCrashedError)):
            retry_ok = True
        elif spec.retry_exceptions is True:
            retry_ok = True
        elif isinstance(spec.retry_exceptions, (list, tuple)):
            retry_ok = any(isinstance(exc, t) for t in spec.retry_exceptions)
        if not retry_ok:
            return False
        spec.attempt += 1
        logger.info("Retrying task %s (attempt %d/%d) after %r",
                    spec.name, spec.attempt, spec.max_retries, exc)
        deps = [a for a in spec.args if isinstance(a, ObjectRef)] + [
            v for v in spec.kwargs.values() if isinstance(v, ObjectRef)]
        self.dispatcher.submit(spec, self._execute_task, deps)
        return True

    def _store_task_result(self, spec: TaskSpec, result: Any,
                           node: NodeState | None = None) -> None:
        watcher = self._spec_watcher
        if watcher is not None and not watcher.claim_win(spec):
            # Speculation first-seal-wins: a sibling already sealed —
            # never overwrite the winning value with a late loser's.
            return
        if spec.num_returns == 1:
            self.store.put(spec.return_ids[0], result)
        elif spec.num_returns == 0:
            pass
        else:
            if not isinstance(result, (tuple, list)) or len(result) != spec.num_returns:
                raise ValueError(
                    f"Task {spec.name} declared num_returns={spec.num_returns} but "
                    f"returned {type(result).__name__} of length "
                    f"{len(result) if isinstance(result, (tuple, list)) else 'n/a'}")
            for rid, value in zip(spec.return_ids, result):
                self.store.put(rid, value)
        if node is not None:
            for rid in spec.return_ids:
                self._record_location(rid, node.node_id)

    # ---------------------------------------------------------------- actors

    def create_actor(
        self,
        cls: type,
        args: tuple,
        kwargs: dict,
        *,
        name: str | None = None,
        namespace: str | None = None,
        resources: dict[str, float],
        max_concurrency: int = 1,
        max_restarts: int = 0,
        max_pending_calls: int = -1,
        lifetime: str | None = None,
        scheduling_strategy: SchedulingStrategy | None = None,
        get_if_exists: bool = False,
        process: bool = False,
        runtime_env: dict | None = None,
        deadline_s: float | None = None,
    ) -> tuple[ActorID, ObjectRef]:
        """Reference: CoreWorker::CreateActor (core_worker.cc:2069) +
        GcsActorManager registration. ``deadline_s`` becomes the
        actor's default per-call end-to-end budget."""
        ns = namespace or self.namespace
        if name is not None and get_if_exists:
            existing = self.gcs.get_named_actor(name, ns)
            if existing is not None:
                ready = ObjectRef(ObjectID())
                self.store.put(ready.id(), None)
                return existing.actor_id, ready
        actor_id = ActorID()
        creation_rid = ObjectID()
        self.store.create_pending(creation_rid)
        creation_ref = ObjectRef(creation_rid)
        method_meta = {}
        for attr_name in dir(cls):
            attr = getattr(cls, attr_name, None)
            if callable(attr) and hasattr(attr, "__ray_tpu_num_returns__"):
                method_meta[attr_name] = {
                    "num_returns": attr.__ray_tpu_num_returns__}
        record = ActorRecord(
            actor_id=actor_id, name=name, namespace=ns,
            class_name=cls.__name__, max_restarts=max_restarts,
            method_meta=method_meta,
            default_deadline_s=float(deadline_s or 0.0))
        try:
            self.gcs.register_actor(record)
            # Publish synchronously at registration so an actor is
            # resolvable from other drivers the moment .remote()
            # returns (calls queue until it is alive; every failure
            # path below unpublishes).
            if name is not None:
                self._publish_named_actor(record)
        except ValueError:
            # Named-actor registration race: two concurrent get_if_exists
            # creators both passed the existence check; the loser joins
            # the winner's actor (reference: GcsActorManager resolves
            # RegisterActor name collisions the same way). Seal the
            # already-created pending ref so nothing waits on it forever.
            if name is not None and get_if_exists:
                existing = self.gcs.get_named_actor(name, ns)
                if existing is not None:
                    self.store.put(creation_rid, None)
                    return existing.actor_id, creation_ref
            raise

        strategy = scheduling_strategy or SchedulingStrategy()

        # Remote placement probe: an actor can only execute on a worker
        # daemon when its class and init args cross a process boundary.
        # Unserializable actors (closures over driver state) stay on the
        # driver host, as do zero-resource default-strategy actors
        # (cheap; keeping them local preserves thread-actor semantics).
        serializable = True
        with self._remote_nodes_lock:
            any_remote = bool(self._remote_nodes)
        if any_remote:
            from ray_tpu._private import serialization as _ser

            try:
                # _function_blob caches by identity, so RemoteActor's own
                # dumps_function of the same class is a cache hit.
                self._function_blob(cls)
                if args or kwargs:  # skip the probe for no-arg actors
                    probe_args = tuple(
                        None if isinstance(a, ObjectRef) else a
                        for a in args)
                    probe_kwargs = {
                        k: None if isinstance(v, ObjectRef) else v
                        for k, v in kwargs.items()}
                    _ser.serialize_framed((probe_args, probe_kwargs))
            except Exception:  # noqa: BLE001 — not remotable
                serializable = False

        def remote_exclude() -> set | None:
            """Nodes an actor must avoid: remote daemons when the actor
            cannot leave the driver process."""
            keep_local = (not serializable or (
                strategy.kind == "DEFAULT"
                and not any(resources.values())))
            if not keep_local:
                return None
            with self._remote_nodes_lock:
                return set(self._remote_nodes) or None

        def start_actor():
            # Lease actor resources for its lifetime.
            node_id = None
            pg_info = None
            try:
                if strategy.kind == "PLACEMENT_GROUP" and strategy.placement_group is not None:
                    pg = strategy.placement_group
                    self.store.get(pg.ready_ref.id())
                    node_id = self.placement_groups.acquire_from_bundle(
                        pg.id, strategy.placement_group_bundle_index, resources)
                    pg_info = (pg.id, strategy.placement_group_bundle_index)
                else:
                    deadline = time.monotonic() + float(
                        GLOBAL_CONFIG.actor_lease_timeout_s)
                    while node_id is None:
                        node = self.cluster.pick_node(
                            resources, strategy, exclude=remote_exclude())
                        if node is not None and self.cluster.try_acquire(
                                node.node_id, resources):
                            node_id = node.node_id
                            break
                        if time.monotonic() > deadline:
                            raise TimeoutError(
                                f"Could not lease resources {resources} "
                                f"for actor {cls.__name__} within "
                                f"{GLOBAL_CONFIG.actor_lease_timeout_s}s")
                        self.cluster.wait_for_change(0.05)
            except BaseException as exc:  # noqa: BLE001
                self.store.put_error(creation_rid, exc)
                self.gcs.update_actor_state(actor_id, "DEAD", repr(exc))
                if name is not None:
                    self._unpublish_named_actor(ns, name)
                return

            def on_death(aid, reason):
                self.gcs.update_actor_state(aid, "DEAD", reason)
                if name is not None:
                    self._unpublish_named_actor(ns, name)
                self._release_actor_lease(aid)
                self.chip_leases.release(aid)

            def on_restart(aid):
                actor = self._actors.get(aid)
                rec = self.gcs.get_actor(aid)
                if actor is not None and rec is not None:
                    # Restarts may have RELOCATED the actor.
                    self._record_actor_placement(
                        rec, actor, getattr(actor, "node_id", None))
                self.gcs.update_actor_state(aid, "ALIVE")

            # Record the lease BEFORE constructing the actor: a remote
            # actor's creation thread may relocate (busy daemon) and
            # must find the current lease to release it.
            self._actor_leases[actor_id] = (node_id, resources, pg_info)
            remote_handle = None
            if node_id is not None and serializable:
                with self._remote_nodes_lock:
                    remote_handle = self._remote_nodes.get(node_id)
            tpu_chips = None
            n_chips = accelerators.tpu_chip_demand(
                resources, self.chip_leases.num_chips)
            if n_chips and remote_handle is None:
                # The actor computes on THIS host: in its own process
                # on chips leased to it, or on this process's threads.
                try:
                    if process:
                        tpu_chips = self.chip_leases.lease(
                            actor_id, n_chips,
                            f"process actor {cls.__name__}")
                    else:
                        self.chip_leases.claim_in_process(
                            f"thread actor {cls.__name__}")
                except ChipOwnershipError as exc:
                    self.store.put_error(creation_rid, exc)
                    on_death(actor_id, repr(exc))
                    return
            if remote_handle is not None:
                from ray_tpu._private.remote_actor import RemoteActor

                # The actor executes ON the leased daemon node — its
                # process lives in that daemon's tree, so the lease and
                # the execution site agree (reference: the GCS actor
                # scheduler creates the actor on the node whose
                # resources it claimed, gcs_actor_scheduler.h).
                self.ensure_client_server()
                actor = RemoteActor(
                    actor_id, cls, args, kwargs, self,
                    node_id=node_id, handle=remote_handle,
                    resources=resources,
                    max_restarts=max_restarts,
                    max_pending_calls=max_pending_calls,
                    max_concurrency=max_concurrency,
                    creation_return_id=creation_rid, on_death=on_death,
                    on_restart=on_restart,
                    runtime_env=self._package_runtime_env(runtime_env))
            elif process:
                from ray_tpu._private.worker_pool import ProcessActor

                # The actor's process needs the nested-API endpoint in
                # its inherited env BEFORE it spawns.
                self.ensure_client_server()
                actor = ProcessActor(
                    actor_id, cls, args, kwargs, self,
                    max_restarts=max_restarts,
                    max_pending_calls=max_pending_calls,
                    max_concurrency=max_concurrency,
                    creation_return_id=creation_rid, on_death=on_death,
                    on_restart=on_restart,
                    runtime_env=self._package_runtime_env(runtime_env),
                    tpu_chips=tpu_chips)
            else:
                if runtime_env:
                    _warn_runtime_env_ignored(
                        f"actor {cls.__name__} runs in-process "
                        "(pass process=True)")
                actor = LocalActor(
                    actor_id, cls, args, kwargs, self,
                    max_concurrency=max_concurrency, max_restarts=max_restarts,
                    max_pending_calls=max_pending_calls,
                    creation_return_id=creation_rid, on_death=on_death,
                    on_restart=on_restart)
            with self._actors_changed:
                self._actors[actor_id] = actor
                self._actors_changed.notify_all()
            record.handle = actor
            self._record_actor_placement(record, actor, node_id)
            self.gcs.update_actor_state(actor_id, "ALIVE")

        self._actor_create_pool.submit(start_actor)
        return actor_id, creation_ref

    def submit_actor_task(self, actor_id: ActorID, method_name: str,
                          args: tuple, kwargs: dict,
                          num_returns: int = 1,
                          deadline_s: float | None = None) -> list[ObjectRef]:
        """Reference: CoreWorker::SubmitActorTask (core_worker.cc:2304).

        All calls for one actor flow through a per-actor ordered submission
        queue so per-caller call order is preserved even across actor
        startup and ObjectRef-argument resolution (reference:
        transport/sequential_actor_submit_queue.h).

        ``deadline_s`` (or the actor's default, or
        task_default_deadline_s) arms an end-to-end budget: a call
        whose deadline dies queued seals TaskTimeoutError instead of
        executing."""
        # What ``.remote()`` costs its caller.
        with tracing.phase("runtime.actor.submit") as hop:
            return_ids = [ObjectID() for _ in range(max(1, num_returns))]
            self._pin_nested_arg_refs(args, kwargs)
            for rid in return_ids:
                self.store.create_pending(rid)
            refs = [ObjectRef(rid) for rid in return_ids]
            call = _ActorCall(method_name, args, kwargs, return_ids,
                              deadline=self._absolute_deadline(deadline_s))

            record = self.gcs.get_actor(actor_id)
            if record is None or (record.state == "DEAD" and actor_id not in self._actors):
                err = ActorDiedError(actor_id, (record.death_cause if record else None)
                                     or "actor not found")
                for rid in return_ids:
                    self.store.put_error(rid, err)
                return refs
            if hop.live:
                hop.set(actor=actor_id.hex()[:8], method=method_name)
                call.trace_ctx = tracing.make_trace_context()
                # Last: the call's age starts where its caller lets go.
                call.submitted_ns = tracing.stamp_ns()
            self._actor_submit_queue(actor_id).put(call)
            return refs

    def _actor_submit_queue(self, actor_id: ActorID):
        """Lazily start the per-actor ordered submission worker."""
        import queue as queue_mod

        with self._futures_lock:
            entry = self._actor_queues.get(actor_id)
            if entry is not None:
                return entry
            submit_queue: queue_mod.Queue = queue_mod.Queue()
            self._actor_queues[actor_id] = submit_queue

        def drain():
            while True:
                call = submit_queue.get()
                # Wait for the actor to come alive (or die trying):
                # condition-signalled by start_actor, with a periodic
                # timeout to notice DEAD records.
                actor = self._actors.get(actor_id)
                deadline = time.monotonic() + 300.0
                while actor is None and time.monotonic() < deadline:
                    rec = self.gcs.get_actor(actor_id)
                    if rec is None or rec.state == "DEAD":
                        break
                    with self._actors_changed:
                        actor = self._actors.get(actor_id)
                        if actor is None:
                            self._actors_changed.wait(0.25)
                            actor = self._actors.get(actor_id)
                if actor is None:
                    # The creation ref carries the typed error; calls
                    # at least say why (e.g. a ChipOwnershipError).
                    rec = self.gcs.get_actor(actor_id)
                    cause = rec.death_cause if rec is not None else None
                    err = ActorDiedError(
                        actor_id, "actor failed to start"
                        + (f": {cause}" if cause else ""))
                    for rid in call.return_ids:
                        self.store.put_error(rid, err)
                    call = None  # see below
                    continue
                # Resolve ObjectRef args in queue order (blocking keeps order).
                try:
                    if getattr(actor, "resolves_refs", False):
                        # Remote actors convert refs to FetchRef
                        # location hints themselves (node-to-node
                        # pulls); here just wait for the deps to seal
                        # WITHOUT materializing remote blobs locally.
                        for dep in [a for a in call.args
                                    if isinstance(a, ObjectRef)] + [
                                v for v in call.kwargs.values()
                                if isinstance(v, ObjectRef)]:
                            self.store.get(dep.id())
                    else:
                        call.args, call.kwargs, _ = resolve_args(
                            call.args, call.kwargs,
                            lambda ref: self.get([ref])[0])
                except BaseException as exc:  # noqa: BLE001
                    for rid in call.return_ids:
                        self.store.put_error(rid, exc)
                    call = None
                    continue
                actor.submit(call)
                # Unbind before blocking in get(): the stale frame
                # local would otherwise keep the LAST call's args —
                # and any ObjectRefs nested in them — registered until
                # the next call arrives, pinning freed objects.
                call = None

        # The drain thread is long-lived per actor, but its START is
        # offloaded: Thread.start blocks until the child's bootstrap
        # gets scheduled, and on a loaded box that stall lands on every
        # first method call of a creation wave. The queue buffers calls
        # until the drain attaches.
        drain_thread = threading.Thread(
            target=drain, daemon=True,
            name=f"ray_tpu-actor-submit-{actor_id.hex()[:8]}")
        self._thread_start_pool.submit(drain_thread.start)
        return submit_queue

    def execution_pipeline_stats(self) -> dict:
        """Driver-side per-stage drain counters for the pipelined
        execute path (the daemon-side stages live in each node's
        ``executor_stats()['pipeline']``): submit = the submit ring,
        dispatch = scheduler batch coalescing, seal = grouped result
        sealing."""
        return {
            "submit": self._submit_stats(),
            "dispatch": self._dispatch_stats(),
            "seal": {
                "batch_seals": self.store.batch_seals,
                "batch_sealed_objects": self.store.batch_sealed_objects,
            },
            # Fused in-daemon execution, accumulated from the batch
            # RPCs' done replies: batch RPCs whose runs fused at least
            # one task, tasks executed on daemon dispatch threads, and
            # fused-eligible entries that fell back to the worker
            # pipeline when a run's wall budget expired.
            "fused": self._fused_stats(),
            # Placement decisions (locality/load scoring) + straggler
            # speculation outcomes — the observability loop's own
            # observability (also exported as the
            # ray_tpu_sched_decisions_total /metrics family).
            "sched": self._sched_stats(),
        }

    def _submit_stats(self) -> dict:
        """Submit-stage counters (SUBMIT_STAT_KEYS): the classic ring,
        the columnar intake (ISSUE 15) and the cumulative flush wall
        — flush latency derives as flush_wall_us over flushes."""
        ring = self._submit_ring
        return {
            "ring_submits": ring.submits if ring else 0,
            "flushes": ring.flushes if ring else 0,
            "flush_tasks": ring.flush_tasks if ring else 0,
            "ring_full_waits": ring.ring_full_waits if ring else 0,
            "buffered_cancels": (ring.buffered_cancels if ring else 0)
            + self._col_buffered_cancels,
            "arg_cache_hits": self.arg_cache_hits,
            "col_submits": self._col_submits,
            "col_flush_tasks": self._col_flush_tasks,
            "flush_wall_us": self._flush_wall_us,
        }

    def _dispatch_stats(self) -> dict:
        """Dispatch-stage counters (DISPATCH_STAT_KEYS): classic batch
        coalescing plus the sharded lanes' occupancy/throughput.
        batch_tasks and batch_overcommit span BOTH engines (the
        >4-tasks/RPC invariant is engine-agnostic)."""
        lanes = self._lanes
        lane_stats = lanes.stats() if lanes is not None else {}
        return {
            "batches": self.dispatcher.batches_launched,
            "batch_tasks": self.dispatcher.batch_tasks_launched
            + lane_stats.get("lane_tasks", 0),
            "singles": self.dispatcher.singles_launched,
            "batch_overcommit": self.dispatcher.batch_overcommit
            + lane_stats.get("lane_overcommits", 0),
            # Deadline-heap sweeps that actually ran (the zero-armed
            # fast path skips them outright).
            "deadline_sweeps": self.dispatcher.deadline_sweeps,
            "lanes": lane_stats.get("lanes", 0),
            "lane_dispatches": lane_stats.get("lane_dispatches", 0),
            "lane_tasks": lane_stats.get("lane_tasks", 0),
            "lane_busy_us": lane_stats.get("lane_busy_us", 0),
            "lane_overcommits": lane_stats.get("lane_overcommits", 0),
            "col_groups": lane_stats.get("col_groups", 0),
            "lane_outstanding": lane_stats.get("lane_outstanding", 0),
        }

    def _fused_stats(self) -> dict:
        with self._fault_lock:
            return {
                "fused_runs": self._fused_runs,
                "fused_tasks": self._fused_tasks,
                "fused_fallbacks": self._fused_fallbacks,
            }

    def _sched_stats(self) -> dict:
        out = dict(self.cluster.sched_counters())
        watcher = self._spec_watcher
        if watcher is not None:
            out.update(watcher.counters())
        else:
            out.update({"speculations_launched": 0,
                        "speculations_won": 0,
                        "speculations_lost": 0})
        return out

    def fault_stats(self) -> dict:
        """Driver-side failure counters, same shape as the daemon's
        executor_stats()["faults"]: how often each recovery path fired
        in this process. The deterministic chaos tests assert these;
        the envelope records them per row."""
        from ray_tpu._private.rpc import breaker_stats, rpc_retry_count

        with self._fault_lock:
            batch_requeues = self._fault_batch_requeues
            task_timeouts = self._task_timeouts
            admission_shed = self._admission_shed
        return {
            "rpc_retries": rpc_retry_count(),
            "batch_requeues": batch_requeues,
            "peer_blacklists": 0,  # drivers pull whole blobs, not chunks
            "lease_orphans_swept": self._export_leases.expired,
            "lineage_rebuilds": self.recovery.num_recoveries,
            # Overload-control plane: deadline-sealed tasks (driver-side
            # seals, all stages), admission sheds (driver + daemon
            # replies), and circuit-breaker opens in this process.
            "task_timeouts": task_timeouts,
            "admission_shed": admission_shed,
            "breaker_open": breaker_stats()["opens"],
        }

    def _release_actor_lease(self, actor_id: ActorID) -> None:
        """Give back an actor's resource lease (idempotent)."""
        lease = self._actor_leases.pop(actor_id, None)
        if lease is None:
            return
        node_id, resources, pg_info = lease
        if pg_info is not None:
            self.placement_groups.release_to_bundle(
                pg_info[0], pg_info[1], resources)
        else:
            self.cluster.release(node_id, resources)

    def _record_actor_placement(self, record, actor, node_id) -> None:
        """Actor-table placement columns (reference: the GCS actor
        table records the executing address, gcs_actor_manager.h).
        The creation path and the async fillers (RemoteActor's create
        reply, ProcessActor's spawn) all funnel through here: the lock
        plus fresh reads of the actor's own attributes mean the last
        writer always records current values — a thread that captured
        state before a relocation can't overwrite the relocated
        placement with its stale copy."""
        # FIRST: async fillers race this method and must find the
        # record to complete it.
        actor._gcs_record = record
        with self._placement_record_lock:
            current = getattr(actor, "node_id", None) or node_id
            if current is None:
                # Local/process actors don't carry a node attribute;
                # their placement is wherever their lease sits (the
                # driver's node unless relocated).
                lease = self._actor_leases.get(record.actor_id)
                if lease is not None:
                    current = lease[0]
            if current is not None:
                record.node_id_hex = current.hex()
            pid = getattr(actor, "pid", None)
            if pid is None and getattr(actor, "_worker", None) is not None:
                pid = actor._worker.proc.pid
            if (pid is None and not hasattr(actor, "_worker")
                    and not hasattr(actor, "pid")):
                pid = os.getpid()  # thread actor: runs in this process
            if pid is not None:
                record.pid = pid
            record.num_restarts = getattr(actor, "_num_restarts", 0)

    def _relocate_actor_lease(self, actor_id: ActorID,
                              resources: dict[str, float],
                              exclude: set | None = None,
                              timeout: float = 300.0):
        """Move a remote actor's resource lease to a (different) worker
        daemon: release the current lease, acquire on another remote
        node. Returns (node_id, handle) or None when no remote node can
        host it within the timeout (reference: GcsActorScheduler re-
        schedules restarting actors onto surviving nodes)."""
        lease = self._actor_leases.pop(actor_id, None)
        if lease is not None:
            old_node, old_resources, old_pg = lease
            if old_pg is not None:
                # A placement-group actor is pinned to its bundle: it
                # may only be recreated where the bundle lives, never
                # silently relocated outside the gang (STRICT_* co-
                # location contracts). If the bundle's node is gone the
                # TERMINAL sentinel makes the actor die — group-level
                # recovery (FailureConfig) re-forms the whole gang,
                # slice semantics. (Plain None would send the caller's
                # retry loop through the generic path and silently
                # un-pin the actor.)
                self.placement_groups.release_to_bundle(
                    old_pg[0], old_pg[1], old_resources)
                try:
                    node_id = self.placement_groups.acquire_from_bundle(
                        old_pg[0], old_pg[1], resources)
                except Exception:  # noqa: BLE001 — bundle gone
                    return "pg_dead"
                node_state = self.cluster.get_node(node_id)
                with self._remote_nodes_lock:
                    handle = self._remote_nodes.get(node_id)
                if (handle is None or node_state is None
                        or not node_state.alive
                        or (exclude and node_id in exclude)):
                    self.placement_groups.release_to_bundle(
                        old_pg[0], old_pg[1], resources)
                    return "pg_dead"
                self._actor_leases[actor_id] = (node_id, resources, old_pg)
                return node_id, handle
            self.cluster.release(old_node, old_resources)
        deadline = time.monotonic() + timeout
        exclude = set(exclude or ())
        while True:
            with self._remote_nodes_lock:
                remote_ids = set(self._remote_nodes)
            # Only worker daemons can host a RemoteActor.
            local_ids = {n.node_id for n in self.cluster.nodes()
                         if n.node_id not in remote_ids}
            node = self.cluster.pick_node(
                resources, SchedulingStrategy(),
                exclude=local_ids | exclude)
            if node is not None and self.cluster.try_acquire(
                    node.node_id, resources):
                with self._remote_nodes_lock:
                    handle = self._remote_nodes.get(node.node_id)
                if handle is None:  # dropped between pick and acquire
                    self.cluster.release(node.node_id, resources)
                else:
                    self._actor_leases[actor_id] = (
                        node.node_id, resources, None)
                    return node.node_id, handle
            if time.monotonic() > deadline:
                return None
            self.cluster.wait_for_change(0.1)

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        actor = self._actors.get(actor_id)
        if actor is not None:
            actor.kill("killed via kill()", no_restart=no_restart)
        else:
            self.gcs.remove_actor(actor_id)

    def get_actor_handle(self, name: str, namespace: str | None = None):
        from ray_tpu.actor import ActorHandle, ForeignActorHandle

        ns = namespace or self.namespace
        record = self.gcs.get_named_actor(name, ns)
        if record is not None:
            return ActorHandle(record.actor_id, record.class_name)
        # Cluster actor directory: the actor may live in ANOTHER
        # driver's runtime (reference: named actors resolve through the
        # GCS actor table, gcs_actor_manager.h).
        if self.gcs_client is not None:
            import pickle

            try:
                blob = self.gcs_client.call(
                    "kv_get", f"{ns}/{name}".encode(), "named_actors")
            except Exception:  # noqa: BLE001 — head unreachable
                blob = None
            if blob is not None:
                info = pickle.loads(blob)
                if info["owner_addr"] == self._client_server_addr():
                    # Our own published actor (registered under this
                    # driver): serve it locally.
                    return ActorHandle(
                        ActorID(bytes.fromhex(info["actor_key"])),
                        info["class_name"])
                return ForeignActorHandle(
                    info["owner_addr"], info["actor_key"],
                    info["class_name"],
                    method_meta=info.get("method_meta", {}))
        raise ValueError(f"Failed to look up actor with name {name!r}")

    def _client_server_addr(self) -> str:
        if self.worker_client_server is None:
            return ""
        from ray_tpu._private.node import _own_address

        return f"{_own_address()}:{self.worker_client_server.port}"

    def _publish_named_actor(self, record) -> None:
        """Advertise a named actor in the cluster directory (GCS KV)."""
        if self.gcs_client is None or self.worker_client_server is None:
            return
        import pickle

        entry = pickle.dumps({
            "actor_key": record.actor_id.hex(),
            "class_name": record.class_name,
            "owner_addr": self._client_server_addr(),
            # Per-method defaults (num_returns) so foreign callers match
            # local ActorHandle semantics.
            "method_meta": dict(record.method_meta),
        })
        try:
            self.gcs_client.call(
                "kv_put", f"{record.namespace}/{record.name}".encode(),
                entry, "named_actors")
        except Exception:  # noqa: BLE001 — best-effort advertisement
            logger.warning("failed to publish named actor %s",
                           record.name)

    def _unpublish_named_actor(self, namespace: str, name: str) -> None:
        if self.gcs_client is None:
            return
        try:
            self.gcs_client.call(
                "kv_del", f"{namespace}/{name}".encode(), "named_actors")
        except Exception:  # noqa: BLE001 — best-effort cleanup
            pass

    def submit_foreign_actor_task(self, owner_addr: str, actor_key: str,
                                  method_name: str, args: tuple,
                                  kwargs: dict,
                                  num_returns: int = 1) -> list[ObjectRef]:
        """Call an actor owned by another driver: ordered per-handle
        proxy thread drives the owner's client server and seals the
        results into OUR store as they arrive."""
        return_ids = [ObjectID() for _ in range(max(1, num_returns))]
        for rid in return_ids:
            self.store.create_pending(rid)
        refs = [ObjectRef(rid) for rid in return_ids]
        key = (owner_addr, actor_key)
        with self._futures_lock:
            proxy = self._foreign_proxies.get(key)
            if proxy is None:
                proxy = _ForeignActorProxy(self, owner_addr, actor_key)
                self._foreign_proxies[key] = proxy
        proxy.submit(method_name, args, kwargs, return_ids)
        return refs

    def kill_foreign_actor(self, owner_addr: str, actor_key: str) -> None:
        from ray_tpu._private.rpc import RpcClient

        client = RpcClient(owner_addr, timeout_s=30.0)
        try:
            client.call("client_kill_actor", actor_key)
        finally:
            client.close()

    # ------------------------------------------------------------ get/put/…

    def put(self, value: Any) -> ObjectRef:
        if isinstance(value, ObjectRef):
            raise TypeError("Calling put() on an ObjectRef is not allowed")
        object_id = ObjectID()
        self.store.put(object_id, value)
        return ObjectRef(object_id)

    def get(self, refs: Sequence[ObjectRef], timeout: float | None = None) -> list[Any]:
        block_ctx = BlockedResourceContext.current()
        results = []
        deadline = None if timeout is None else time.monotonic() + timeout
        for ref in refs:
            if not isinstance(ref, ObjectRef):
                raise TypeError(
                    f"get() expects ObjectRef (or list of them), got {type(ref)}")
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            if self.store.contains(ref.id()):
                results.append(self._materialize_value(
                    ref.id(), self.store.get(ref.id())))
                continue
            if block_ctx is not None:
                block_ctx.block()
            try:
                # The caller's wait for a ref not yet sealed; age_us is
                # sealed -> awake (the store stamps the entry).
                with tracing.phase("runtime.get") as hop:
                    value = self.store.get(ref.id(), timeout=remaining)
                    if hop.live:
                        hop.set(age_us=tracing.age_us(
                            self.store.sealed_ns(ref.id())))
                results.append(self._materialize_value(ref.id(), value))
            finally:
                if block_ctx is not None:
                    block_ctx.unblock()
        return results

    def wait(self, refs: Sequence[ObjectRef], num_returns: int = 1,
             timeout: float | None = None) -> tuple[list[ObjectRef], list[ObjectRef]]:
        if num_returns > len(refs):
            raise ValueError(
                f"num_returns={num_returns} exceeds the number of refs ({len(refs)})")
        by_id = {ref.id(): ref for ref in refs}
        block_ctx = BlockedResourceContext.current()
        if block_ctx is not None:
            block_ctx.block()
        try:
            ready_ids, not_ready_ids = self.store.wait(
                [r.id() for r in refs], num_returns, timeout)
        finally:
            if block_ctx is not None:
                block_ctx.unblock()
        return ([by_id[i] for i in ready_ids], [by_id[i] for i in not_ready_ids])

    def cancel(self, ref: ObjectRef) -> None:
        # Best-effort: only not-yet-dispatched tasks can be cancelled in the
        # thread-worker slice (threads are not preemptible). A task that is
        # already running completes normally — matching non-force cancel in
        # the reference.
        ring = self._submit_ring
        if ring is not None and ring.cancel(ref.id()) is not None:
            # Still buffered (or mid-flush): the ring owns the cancel —
            # buffered records seal TaskCancelledError immediately,
            # draining ones via the flush's post-pass.
            return
        if self._lanes is not None and self._cancel_columnar(ref.id()):
            return
        self._cancel_registered(ref.id())

    def free(self, refs: Sequence[ObjectRef]) -> None:
        self.store.free([r.id() for r in refs])
        self.lineage.forget([r.id() for r in refs])
        with self._locations_lock:
            for r in refs:
                self._object_locations.pop(r.id(), None)
        for r in refs:
            desc = self.shm_directory.lookup(r.id())
            if desc is not None:
                self.shm_client.close_segment(desc.name)
                self.shm_directory.free(r.id())
            if self._export_store is not None:
                self._export_store.free([r.id().binary()])
            self._drop_export_source(r.id().binary())

    # -------------------------------------------------------------- futures

    def attach_future(self, ref: ObjectRef, fut: concurrent.futures.Future) -> None:
        ring = self._submit_ring
        with self._futures_lock:
            if not self.store.contains(ref.id()) and (
                    self.store.is_pending(ref.id())
                    or (ring is not None and ring.holds(ref.id()))
                    or ref.id() in self._col_index):
                # A ring-buffered submit has no store entry yet but IS
                # pending — its flush creates the entry and the seal
                # listener resolves the future.
                self._futures.setdefault(ref.id(), []).append(fut)
                return
        # Already sealed (or unknown): resolve immediately.
        self._resolve_one_future(ref.id(), fut)

    def _resolve_futures(self, object_id: ObjectID) -> None:
        with self._futures_lock:
            futs = self._futures.pop(object_id, [])
        for fut in futs:
            self._resolve_one_future(object_id, fut)

    def _resolve_one_future(self, object_id: ObjectID, fut) -> None:
        try:
            value = self._materialize_value(
                object_id, self.store.get(object_id, timeout=0))
            fut.set_result(value)
        except BaseException as exc:  # noqa: BLE001
            try:
                fut.set_exception(exc)
            except Exception:
                pass  # future already resolved by a racing seal

    # --------------------------------------------------------------- status

    def cluster_resources(self) -> dict[str, float]:
        return self.cluster.total_resources()

    def available_resources(self) -> dict[str, float]:
        return self.cluster.available_resources()

    def shutdown(self) -> None:
        if self._spec_watcher is not None:
            self._spec_watcher.stop()
        if self._submit_ring is not None:
            # Flush buffered submits (their owners may still hold refs)
            # and retire the submitter before the planes below close.
            ring, self._submit_ring = self._submit_ring, None
            ring.stop()
        if self._lanes is not None:
            self._lanes.shutdown()
        self._watcher_stop.set()
        with self._remote_nodes_lock:
            handles = list(self._remote_nodes.values())
            self._remote_nodes.clear()
        for handle in handles:
            handle.close()
        if self._obj_server is not None:
            self._obj_server.stop()
            self._obj_server = None
        for proxy in list(self._foreign_proxies.values()):
            proxy.close()
        self._foreign_proxies.clear()
        # Kill actors while the GCS connection is still open: their
        # on_death hooks unpublish cluster named-actor entries, which
        # would otherwise go stale forever.
        for actor in list(self._actors.values()):
            actor.kill("runtime shutdown", no_restart=True)
        if self._node_agent is not None:
            self._node_agent.stop()
            self._node_agent = None
        if self.gcs_client is not None:
            self.gcs_client.close()
            self.gcs_client = None
        if self.dashboard is not None:
            self.dashboard.stop()
            self.dashboard = None
        if self.metrics_agent is not None:
            self.metrics_agent.shutdown()
        self.health_monitor.shutdown()
        self.dispatcher.shutdown()
        # Spill tier: retire the spiller threads and drop this
        # session's spill files (the per-pid dir would otherwise wait
        # for a survivor's orphan sweep after the process exits).
        for mgr in (getattr(self.store, "_spill", None),
                    self._export_spill_mgr):
            if mgr is not None:
                mgr.stop()
        from ray_tpu._private import spill_manager as _spill_mod

        if _spill_mod.live_manager_count() == 0:
            # Last manager in this process: the per-pid dir holds no
            # live store's files anymore (in-process executors would
            # still be registered).
            import shutil as _shutil

            _shutil.rmtree(_spill_mod.process_spill_dir(),
                           ignore_errors=True)
        if self.memory_monitor is not None:
            self.memory_monitor.stop()
        if self.worker_pool is not None:
            self.worker_pool.shutdown()
        if self.worker_client_server is not None:
            self.worker_client_server.stop()
            os.environ.pop("RAY_TPU_DRIVER_CLIENT_ADDR", None)
            self.worker_client_server = None
        if self.log_monitor is not None:
            self.log_monitor.stop()
            os.environ.pop("RAY_TPU_WORKER_LOG_DIR", None)
            import shutil

            shutil.rmtree(os.path.dirname(self.log_monitor.log_dir),
                          ignore_errors=True)
            self.log_monitor = None
        self.shm_client.close_all()
        self.shm_directory.shutdown()
        # Export twins: leases die with the runtime; segments must be
        # unlinked here or they outlive the process in /dev/shm. The
        # export store's memoryviews into them are dropped FIRST so the
        # close doesn't trip on exported pointers.
        self._export_leases.clear()
        with self._export_lock:
            export_ids = list(self._export_segments)
            export_segs = list(self._export_segments.values())
            self._export_segments.clear()
            self._export_sources.clear()
        if self._export_store is not None and export_ids:
            self._export_store.free(export_ids)
        for seg in export_segs:
            try:
                seg.unlink()
            except (OSError, FileNotFoundError):
                pass  # segment already unlinked by the tracker
            try:
                seg.close()
            except (BufferError, OSError):
                from ray_tpu._private.shm_store import _defuse

                _defuse(seg)
        if self.arena is not None:
            self.arena.close()  # owner: destroys the shared arena
            os.environ.pop("RAY_TPU_ARENA_NAME", None)
            self.arena = None
        self.gcs.finish_job(self.job_id)


class _RemoteBlockContext(BlockedResourceContext):
    """Block context for a task executing on a worker-node daemon: a
    nested blocked get() releases the task's CPU on the driver's
    cluster ledger (base class) AND on the daemon's admission ledger
    (task_block/task_unblock RPCs), so dependent work can be admitted
    to the same daemon while the parent waits."""

    def __init__(self, cluster, node_id, resources, handle, token):
        super().__init__(cluster, node_id, resources)
        self._handle = handle
        self._token = token

    def _on_release(self):
        try:
            self._handle._control.call("task_block", self._token)
        except Exception:  # noqa: BLE001 — daemon gone; best-effort
            pass

    def _on_reacquire(self):
        try:
            self._handle._control.call("task_unblock", self._token)
        except Exception:  # noqa: BLE001 — daemon gone; best-effort
            pass


class _ForeignActorProxy:
    """Ordered call pipe to one foreign actor: a drain thread issues
    client_actor_call + long-poll gets against the owning driver's
    client server and seals results into the local store (the foreign
    analogue of the per-actor submit queue,
    transport/sequential_actor_submit_queue.h)."""

    def __init__(self, runtime: "Runtime", owner_addr: str,
                 actor_key: str):
        import queue as queue_mod

        from ray_tpu._private.rpc import RpcClient

        self._runtime = runtime
        self._actor_key = actor_key
        self._owner_addr = owner_addr
        self._rpc = RpcClient(owner_addr, timeout_s=60.0)
        self._queue: queue_mod.Queue = queue_mod.Queue()
        self._thread = threading.Thread(
            target=self._drain, daemon=True,
            name=f"ray_tpu-foreign-actor-{actor_key[:8]}")
        self._thread.start()

    def submit(self, method_name: str, args: tuple, kwargs: dict,
               return_ids: list[ObjectID]) -> None:
        self._queue.put((method_name, args, kwargs, return_ids))

    def close(self) -> None:
        self._queue.put(None)
        self._rpc.close()

    def _fail(self, return_ids, exc) -> None:
        for rid in return_ids:
            self._runtime.store.put_error(rid, exc)

    def _drain(self) -> None:
        from ray_tpu._private import serialization
        from ray_tpu._private.rpc import RpcError, RpcMethodError

        while True:
            item = self._queue.get()
            if item is None:
                return
            method_name, args, kwargs, return_ids = item
            sealed: set = set()
            try:
                # Resolve refs to values locally: the owner cannot
                # dereference OUR object ids.
                args, kwargs, _ = resolve_args(
                    args, kwargs, lambda r: self._runtime.get([r])[0])
                blob = serialization.serialize_framed((args, kwargs))
                keys = self._rpc.call(
                    "client_actor_call", self._actor_key, method_name,
                    blob, len(return_ids))
                if len(keys) != len(return_ids):
                    raise ValueError(
                        f"{method_name} returned {len(keys)} values but "
                        f"the handle expected {len(return_ids)} (declare "
                        f"num_returns via .options or @method)")
                for key, rid in zip(keys, return_ids):
                    while True:
                        status, vblob = self._rpc.call(
                            "client_get", [key], 10.0)
                        if status == "ok":
                            value = serialization.deserialize_from_buffer(
                                memoryview(vblob))[0]
                            self._runtime.store.put(rid, value)
                            sealed.add(rid)
                            break
                try:
                    self._rpc.call("client_release", keys)
                except (RpcError, RpcMethodError):
                    pass
            except RpcMethodError as exc:
                self._fail([r for r in return_ids if r not in sealed],
                           exc.cause)
            except (RpcError, OSError) as exc:
                # Never clobber results already delivered: only the
                # still-pending returns become errors.
                self._fail([r for r in return_ids if r not in sealed],
                           ActorDiedError(
                               None, f"owner driver at {self._owner_addr} "
                               f"unreachable: {exc}"))
            except BaseException as exc:  # noqa: BLE001
                self._fail([r for r in return_ids if r not in sealed],
                           exc)
            # Unbind before re-blocking in get(): stale frame locals
            # would keep the last call's args (and nested ObjectRefs)
            # alive until the next call arrives.
            item = args = kwargs = None


# --------------------------------------------------------------------------
# Module-level singleton API
# --------------------------------------------------------------------------


def global_runtime():
    if _runtime is not None:
        return _runtime
    if os.environ.get("RAY_TPU_IN_POOL_WORKER"):
        from ray_tpu._private import worker_client

        active = worker_client.active_worker_runtime()
        if active is not None:
            return active
        # Refs can deserialize BEFORE the worker's first explicit API
        # call (e.g. inside actor-constructor args); borrower
        # registration needs the proxy runtime to exist at that moment,
        # so build it eagerly when the driver address is known.
        if os.environ.get("RAY_TPU_DRIVER_CLIENT_ADDR"):
            try:
                return worker_client.get_worker_runtime()
            except Exception:  # noqa: BLE001 — keep refs inert instead
                return None
    return None


def init(
    *,
    num_cpus: float | None = None,
    num_tpus: float | None = None,
    resources: dict[str, float] | None = None,
    object_store_memory: int | None = None,
    namespace: str = "default",
    ignore_reinit_error: bool = False,
    system_config: dict | None = None,
    logging_level: str | None = None,
    process_workers: int | None = None,
    metrics_port: int | None = None,
    dashboard_port: int | None = None,
    address: str | None = None,
    **_ignored,
) -> Runtime:
    """Initialize the runtime (reference: ray.init, worker.py:1219).

    ``address="host:port"`` connects to a running head's GCS
    (``python -m ray_tpu start --head``); ``address="auto"`` resolves it
    from RAY_TPU_ADDRESS or the local head's session file.
    """
    import os as _os

    if _os.environ.get("RAY_TPU_IN_POOL_WORKER"):
        # Inside a pool worker the public API proxies back to the driver
        # (reference: workers are full CoreWorkers and may submit tasks);
        # init() is a no-op returning the proxy runtime.
        if _os.environ.get("RAY_TPU_DRIVER_CLIENT_ADDR"):
            from ray_tpu._private import worker_client

            return worker_client.get_worker_runtime()
        raise RuntimeError(
            "ray_tpu.init() inside a pool worker requires the driver's "
            "client server (driver predates nested submission)")
    global _runtime
    with _runtime_lock:
        if _runtime is not None:
            if ignore_reinit_error:
                return _runtime
            raise RuntimeError(
                "ray_tpu.init() has already been called; pass "
                "ignore_reinit_error=True to ignore")
        if system_config:
            GLOBAL_CONFIG.update(system_config)
        if logging_level:
            logging.getLogger("ray_tpu").setLevel(logging_level)
        if bool(GLOBAL_CONFIG.tracing_enabled):
            # Arm the tracing plane up front (RAY_TPU_TRACING_ENABLED
            # or init(system_config={"tracing_enabled": True})); daemons
            # inherit the env through daemon_child_env.
            tracing.enable()
        if address == "auto":
            from ray_tpu.scripts import resolve_address

            try:
                address = resolve_address(None)
            except SystemExit as exc:
                # resolve_address is CLI-oriented; surface a catchable
                # library error here instead of exiting the process.
                raise ConnectionError(str(exc)) from None
        _runtime = Runtime(
            num_cpus=num_cpus, num_tpus=num_tpus, resources=resources,
            object_store_memory=object_store_memory, namespace=namespace,
            process_workers=process_workers, metrics_port=metrics_port,
            dashboard_port=dashboard_port, address=address)
        atexit.register(_atexit_shutdown)
        return _runtime


def _atexit_shutdown():
    global _runtime
    with _runtime_lock:
        if _runtime is not None:
            try:
                _runtime.shutdown()
            except Exception:
                pass  # shutdown() is best-effort on interpreter exit
            _runtime = None


def shutdown() -> None:
    global _runtime
    with _runtime_lock:
        if _runtime is not None:
            _runtime.shutdown()
            _runtime = None


def is_initialized() -> bool:
    return _runtime is not None


def _require_runtime():
    if _runtime is None:
        if os.environ.get("RAY_TPU_IN_POOL_WORKER"):
            return init()  # worker-mode proxy runtime
        init()
    return _runtime  # type: ignore[return-value]


def auto_init() -> Runtime:
    return _require_runtime()


def put(value: Any) -> ObjectRef:
    return _require_runtime().put(value)


def get(refs, timeout: float | None = None):
    runtime = _require_runtime()
    if isinstance(refs, ObjectRef):
        return runtime.get([refs], timeout=timeout)[0]
    if isinstance(refs, (list, tuple)):
        return runtime.get(list(refs), timeout=timeout)
    raise TypeError(f"get() expects an ObjectRef or list of ObjectRefs, got {type(refs)}")


def wait(refs, *, num_returns: int = 1, timeout: float | None = None,
         fetch_local: bool = True):
    if isinstance(refs, ObjectRef):
        raise TypeError("wait() expects a list of ObjectRefs")
    return _require_runtime().wait(list(refs), num_returns=num_returns, timeout=timeout)


def kill(actor_handle, *, no_restart: bool = True) -> None:
    from ray_tpu.actor import ActorHandle, ForeignActorHandle

    if isinstance(actor_handle, ForeignActorHandle):
        _require_runtime().kill_foreign_actor(
            actor_handle._owner_addr, actor_handle._actor_key)
        return
    if not isinstance(actor_handle, ActorHandle):
        raise TypeError("kill() expects an ActorHandle")
    _require_runtime().kill_actor(actor_handle._actor_id, no_restart=no_restart)


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True) -> None:
    _require_runtime().cancel(ref)


def get_actor(name: str, namespace: str | None = None):
    return _require_runtime().get_actor_handle(name, namespace)


def cluster_resources() -> dict[str, float]:
    return _require_runtime().cluster_resources()


def available_resources() -> dict[str, float]:
    return _require_runtime().available_resources()


def nodes() -> list[dict]:
    runtime = _require_runtime()
    out = [
        {
            "NodeID": r.node_id.hex(),
            "Alive": r.alive,
            "Resources": dict(r.resources),
            "Labels": dict(r.labels),
            "NodeManagerAddress": r.address,
        }
        for r in runtime.gcs.list_nodes()
    ]
    if runtime.gcs_client is not None:
        from ray_tpu._private.rpc import RpcError

        try:
            for n in runtime.gcs_client.call("list_nodes"):
                out.append({
                    "NodeID": n["node_id"],
                    "Alive": n["alive"],
                    "Resources": n["resources"],
                    "Labels": n["labels"],
                    "NodeManagerAddress": n["address"],
                })
        except RpcError:
            pass  # head unreachable; local view only
    return out


def timeline() -> list[dict]:
    """Chrome-trace-style task events (reference: `ray timeline`).

    With tracing enabled, each task expands into per-stage slices
    (submit→dispatch→rpc→admit→worker→execute→seal) across one process
    lane per node, linked by flow arrows; untraced tasks keep the
    single-slice view. ``util.tracing.export_chrome_trace(path)``
    writes the same merged view (plus spans) to a file."""
    from ray_tpu.util import tracing as _tracing

    return _tracing.build_task_events(_require_runtime())
