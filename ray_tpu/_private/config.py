"""Framework configuration flag system.

TPU-native analogue of the reference's RAY_CONFIG macro table
(reference: src/ray/common/ray_config_def.h — 217 entries, overridable via
RAY_* env vars and the driver's _system_config). Here flags are declared
once in ``_DEFAULTS``; every flag is overridable via ``RAY_TPU_<NAME>``
environment variables and via ``init(system_config={...})``.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any

_DEFAULTS: dict[str, Any] = {
    # Scheduling.
    "num_cpus": os.cpu_count() or 1,
    "scheduler_spread_threshold": 0.5,
    "max_pending_lease_requests_per_scheduling_category": 10,
    "worker_lease_timeout_ms": 500,
    # Object store.
    "object_store_memory_mb": 2048,
    "object_store_full_delay_ms": 100,
    "inline_object_max_size_bytes": 100 * 1024,
    "object_spilling_threshold": 0.8,
    "object_spilling_dir": "/tmp/ray_tpu_spill",
    # Tasks.
    "max_task_retries": 0,
    "task_retry_delay_ms": 0,
    # Actors.
    "actor_max_restarts": 0,
    "actor_graceful_shutdown_timeout_s": 5.0,
    # Health checking.
    "health_check_period_ms": 1000,
    "health_check_failure_threshold": 5,
    # Lineage reconstruction.
    "lineage_table_max_entries": 10_000,
    # Metrics.
    "metrics_report_interval_ms": 2000,
    # Logging.
    "log_level": "INFO",
    # Multiprocess worker pool.
    "worker_pool_size": 0,  # 0 => disabled (thread workers); N>0 => N processes
    "worker_startup_timeout_s": 30.0,
    # Native shared-memory arena (plasma-lite, _native/plasma_store.cpp).
    "object_arena_bytes": 64 * 1024 * 1024,  # 0 => segment-per-object only
    "object_arena_max_object_bytes": 1024 * 1024,
    # Watermark-driven spill tier (spill_manager.py): when a store's
    # resident bytes cross spill_high_watermark x capacity, an async
    # spiller moves unpinned/unleased primaries to checksummed files
    # under $RAY_TPU_SESSION_DIR/spill/<pid>/ and frees the memory
    # (and any shm/arena twin), restoring transparently on read —
    # working sets >> RAM degrade to disk instead of shedding.
    # Disarmed (spill_enabled=0), every site costs one
    # module-attribute branch (spill_manager.SPILL_ON) and the stores
    # keep their legacy inline cap-based spilling byte-identically.
    "spill_enabled": True,
    "spill_high_watermark": 0.85,   # wake the spiller above this
    "spill_low_watermark": 0.60,    # spill down to this (hysteresis)
    "spill_fsync": False,           # fsync each file before rename
    "spill_min_object_kb": 16,      # smallest spillable object
    # Disk-full backoff: after a failed spill write, admission treats
    # store pressure as unrelievable (typed shed) for this long
    # instead of hammering a full disk or crashing the daemon.
    "spill_disk_full_backoff_s": 5.0,
    # Memory monitor (reference: memory_monitor.h kill-on-pressure).
    "memory_usage_threshold": 0.95,
    "memory_monitor_refresh_ms": 1000,  # 0 => disabled
    "task_oom_retries": 3,  # retry budget for monitor-killed tasks
    # Worker log capture + driver-side echo (reference: log_monitor.py).
    "log_to_driver": True,
    # Placement groups.
    "placement_group_commit_timeout_s": 30.0,
    # Worker-node daemon object store (primary copies of task/actor
    # results). Over the cap, the oldest primaries spill to disk and
    # restore on fetch (reference: local_object_manager.h:110 spilling).
    "node_store_primary_limit_mb": 4096,
    "node_store_spill_dir": "/tmp/ray_tpu_node_spill",
    # Owner-death GC on daemons: blobs/actors of a driver whose client
    # endpoint stays unreachable past the grace period are swept
    # (reference: owner-death cleanup in the ownership protocol,
    # reference_count.h:61). 0 disables the sweeper.
    "owner_sweep_period_ms": 5000,
    "owner_dead_grace_s": 15.0,
    # Node-to-node transfer plane (reference: the chunked Push/Pull
    # sizing knobs among the 217 RAY_CONFIG entries).
    "executor_inline_reply_kb": 256,   # results <= this ship inline
    "fetch_chunk_kb": 4096,            # chunk size of node pulls
    "node_pull_cache_mb": 512,         # pulled-copy cache per daemon
    # Actor scheduling (reference: actor creation/restart timeouts).
    "actor_lease_timeout_s": 300.0,
    "actor_restart_relocate_timeout_s": 120.0,
    # End-to-end deadlines (overload-control plane). A task submitted
    # without an explicit ``_deadline_s`` inherits this budget; 0
    # disables. The absolute deadline is stamped on the TaskSpec and
    # checked at every pipeline stage (ring flush, dispatcher claim,
    # daemon admission, worker frame pickup) — expired work seals
    # TaskTimeoutError instead of executing.
    "task_default_deadline_s": 0.0,
    # Admission control / load shedding. Queue-depth cap on the
    # driver's dispatcher (waiting + ready + running); over it, the
    # submit ring blocks deadline-free flushes (bounded backpressure)
    # and sheds deadline-armed submits with SystemOverloadedError.
    # Daemons apply the same cap to their admitted-reservation count.
    # 0 = unlimited.
    "admission_max_queue_depth": 0,
    # Host-memory fraction above which admission sheds instead of
    # queueing (fed by memory_monitor's /proc/meminfo reader, checked
    # with a short memo so the hot path never re-reads per task).
    # 0 disables.
    "admission_memory_watermark": 0.0,
    # RPC plane.
    "rpc_io_pool_workers": 16,         # pooled short-call dispatch
    # Locality- and load-aware placement (closing the observability
    # loop: pick_node consumes the object directory + the heartbeat-
    # shipped node-stats feed). Disarmed, every site costs one
    # module-attribute branch (scheduler.LOCALITY_ON) and pick_node is
    # byte-identical to the classic hybrid policy.
    "locality_aware_scheduling": True,
    # Arguments at/above this size count toward byte-weighted locality
    # scoring (small args are cheaper to move than to chase).
    "locality_min_arg_kb": 64,
    # Node-stats entries older than this (GCS receipt age + local
    # decay) stop contributing to the load score: a wedged daemon that
    # stops heartbeating must not keep looking idle to the scorer.
    "sched_stats_stale_s": 6.0,
    # Straggler speculation (driver-side watcher): an in-flight task
    # whose elapsed wall exceeds speculation_p99_factor x the
    # per-function p99 from the perf plane gets a speculative copy
    # re-dispatched to a different node; first seal wins, the loser is
    # cancelled best-effort. Off by default (speculation re-executes
    # work); disarmed cost is one module-attribute branch
    # (speculation.SPEC_ON) per site.
    "speculation_enabled": False,
    "speculation_p99_factor": 3.0,
    # Max speculative copies per task (bounds wasted re-execution).
    "speculation_max_copies": 1,
    # Completed-sample floor before the per-function p99 is trusted.
    "speculation_min_samples": 8,
    # Watcher sweep cadence.
    "speculation_watch_period_ms": 200,
    # Shared retry/backoff/deadline policy for IDEMPOTENT control-plane
    # calls (rpc.call_with_retry — heartbeats, fetch_plan, GCS reads).
    # Non-idempotent submits never ride it: a maybe-executed failure
    # must surface, not silently re-execute.
    "rpc_retry_attempts": 3,
    "rpc_retry_base_ms": 50,           # exponential backoff base
    "rpc_retry_deadline_s": 15.0,      # overall per-call retry budget
    # Per-destination circuit breaker riding the same retry policy: a
    # destination failing this many CONSECUTIVE logical calls (each
    # call_with_retry invocation counts at most once, however many
    # attempts it burned) opens its breaker — further calls fail fast
    # with a retryable RpcError instead of eating whole retry budgets
    # against a sick node. After rpc_breaker_reset_s one half-open
    # probe is let through; success closes the breaker, failure
    # re-opens it. rpc_breaker_failures=0 disables.
    "rpc_breaker_failures": 5,
    "rpc_breaker_reset_s": 5.0,
    # Deterministic fault injection (chaos.py); "" disables — every
    # injection site then costs one module-attribute branch. Spec:
    # "seed=42,rpc.sever=0.1,rpc.drop_frame=0.05x3,...".
    "chaos": "",
    # Pipelined transport (reference: gRPC completion queues carry many
    # in-flight calls per connection, src/ray/rpc/client_call.h).
    "rpc_pipeline_depth": 8,           # in-flight chunk fetches per pull
    "rpc_batch_flush_ms": 0.0,         # coalescing linger; 0 = natural
    "rpc_batch_max_entries": 128,      # max calls per batched frame
    # Pipelined task execution (batched dispatch -> execute_task_batch
    # -> multi-task worker leases -> grouped completion replies).
    # Tasks per execute_task_batch RPC. Raised 32 -> 128 with fused
    # execution: the dispatcher's batch-fill over-subscription now
    # actually reaches this depth (claims were capped at per-node free
    # slots before), and on a many-node single-core box every batch
    # costs a daemon wake — deeper batches amortize it. The fill
    # budget adapts to backlog//nodes, so small bursts still spread.
    "dispatch_batch_max": 128,
    "worker_pipeline_depth": 4,        # frames in flight per worker lease
    # Fused in-daemon execution: runs of tiny DEFAULT tasks inside an
    # execute_task_batch RPC run directly on the daemon's dispatch
    # thread — no worker-pipe hop, no per-task pickle round trip —
    # sealed back as grouped completions. Ref-bearing / TPU /
    # runtime_env / dedicated-worker entries always take the classic
    # or pipelined worker path. Disarmed (fused_execution=0), every
    # site costs one module-attribute branch (node_executor.FUSED_ON)
    # and the batch path is byte-identical to the worker pipeline.
    "fused_execution": True,
    # Per-RPC fused-run budget: at most this many tasks fuse per batch
    # RPC, and once the run's wall clock exceeds the budget the
    # remaining fused-eligible entries fall back to the pipelined
    # worker path (fused_fallbacks counter) — one long task cannot
    # wedge the daemon's reply stream for the whole batch.
    "fused_max_run_tasks": 256,
    "fused_run_wall_budget_s": 0.25,
    # Raw-bytes framing for small immutable args/results (ints,
    # floats, bools, str/bytes, flat tuples/dicts of them): a compact
    # tag-length encoding written into a thread-local scratch arena
    # replaces the pickle round trip on BOTH ends of the worker pipe
    # and the fused path. Disarmed (raw_framing=0), every encode site
    # costs one module-attribute branch (serialization.RAW_ON) and
    # frames are byte-identical pickles; decoding raw frames stays
    # supported either way (the sentinel header length cannot collide
    # with a pickled frame).
    "raw_framing": True,
    # Pipelined task SUBMISSION (driver-side submit ring): .remote()
    # allocates ids/refs inline and pushes a record onto a bounded
    # ring; a dedicated submitter thread drains flushes through ONE
    # store/lineage/GCS/dispatcher pass each. Disabled, every submit
    # takes the classic inline path.
    "submit_pipeline": True,
    "submit_ring_size": 65536,         # ring capacity; full => backpressure
    "submit_flush_max": 1024,          # records drained per flush pass
    # Sharded driver dispatch + columnar submit records
    # (dispatch_lanes.py): in connected mode, DEFAULT fused-eligible
    # submits (scalar args, one return, no deadline/PG/affinity) skip
    # per-task _SubmitRecord/TaskSpec/TaskEvent/lineage objects — a
    # flush builds ONE columnar group per RemoteFunction (parallel
    # id/args columns off the frozen call template) and hands it to N
    # dispatch lanes, each with its own lock domain and ready queue;
    # the cluster ledger is the only shared structure, acquired once
    # per flush (ClusterState.acquire_batch), and get-less completions
    # seal through a counter-only fast path. Disarmed
    # (driver_sharded_dispatch=0), every submit takes the classic ring
    # path byte-identically; each site costs one module-attribute
    # branch (dispatch_lanes.SHARD_ON).
    "driver_sharded_dispatch": True,
    # Dispatch lanes (threads) the columnar groups shard across, keyed
    # by admission signature. More lanes overlap RPC waits to more
    # nodes; on a single-core box 2 is enough to keep one lane filling
    # while another drains replies.
    "dispatch_lanes": 2,
    # P2P chunked broadcast (reference: the object manager's chunked
    # Push/Pull fans transfers out peer-to-peer via the directory).
    "broadcast_chunk_fanout": 4,       # peer sources used per pull
    "broadcast_min_p2p_chunks": 4,     # smaller objects pull owner-only
    "node_relay_cache_mb": 4096,       # completed relay copies kept
    # Same-host zero-copy plane: co-hosted daemons map each other's
    # shared memory (dedicated segments / the native arena) instead of
    # chunk-pulling bytes over RPC (reference: plasma is host-shared by
    # design, object_manager/plasma/store_runner.h).
    "same_host_plane": True,           # enable same-host mapping
    # Objects at/above this are served to same-host peers by a named
    # segment the peer maps zero-copy; below it the peer does a single
    # memcpy out of the holder's arena/segment (map-vs-memcpy split:
    # small objects aren't worth a per-consumer mapping).
    "same_host_map_min_kb": 1024,
    # Owner-side pin leases outlive this only while the holder still
    # answers pings; a dead puller's pins are swept afterwards.
    "same_host_pin_ttl_s": 30.0,
    # Driver-side node table: absent-but-pinging nodes survive this many
    # consecutive sync passes before being dropped (head amnesia grace).
    "node_amnesia_max_passes": 5,
    # Head control plane.
    "gcs_heartbeat_timeout_s": 10.0,   # node declared dead after this
    # Durable control plane (gcs_persistence.py): the head persists
    # its FULL hot set — KV, jobs, node table, actor registry, object
    # directory incl. spilled marks, placement groups — as a
    # checksummed snapshot plus a length+CRC32-framed WAL between
    # snapshots, with torn-tail truncation and seq-gated replay on
    # restart. Disarmed (gcs_persistence=0) the head keeps the legacy
    # {kv, jobs} raw-pickle snapshot byte-identically and mints no
    # epoch.
    "gcs_persistence": True,
    # Full-snapshot cadence while armed; between snapshots every
    # mutation is WAL-durable, so this bounds restart replay length,
    # not durability.
    "gcs_snapshot_interval_s": 30.0,
    # WAL size that forces an early snapshot + rotate.
    "gcs_wal_max_mb": 64,
    # fsync each WAL append / snapshot (durability vs latency; the
    # default flushes to the OS only — a head SIGKILL loses nothing,
    # a host power cut may lose the tail).
    "gcs_wal_fsync": False,
    # Epoch fencing (requires gcs_persistence): the head mints a
    # persisted incarnation number each start; every RPC reply and
    # heartbeat carries it, stale-epoch writes are rejected typed
    # (StaleEpochError, retryable after re-sync) so a partitioned
    # daemon or lingering old head can never double-register a node,
    # resurrect a dead actor, or corrupt the object directory.
    "gcs_epoch_fencing": True,
    # Sharded hot tables (gcs_shard.py): split the head's object
    # directory, task events and node-stats/stage-latency aggregation
    # across N in-head shard domains — each with its own lock domain,
    # RGW1 WAL + snapshot segment and persisted incarnation epoch, so
    # one shard crash-restarts (replaying only its WAL, fencing its
    # stale writers typed) while the others keep serving. Default 1
    # keeps the PR 12 single-WAL layout byte-identically; changing the
    # count over an existing layout is refused typed (ReshardError),
    # never silently misrouted.
    "gcs_shards": 1,
    # Degraded mode: writes to a stalled/partitioned shard are
    # WAL-durable immediately and queue for in-memory apply until the
    # shard heals; past this cap they shed typed
    # (SystemOverloadedError) instead of queueing unboundedly.
    "gcs_shard_max_queued_writes": 512,
    # LLM inference engine (serve/llm_engine): paged KV-cache
    # continuous batching with prefill/decode scheduling.
    # Tokens per KV block (the page size of the paged cache): small
    # blocks waste less memory on ragged tails, large blocks shrink
    # the block tables. Must divide into max_seq_len cleanly for a
    # full-length sequence.
    "llm_block_size": 16,
    # Prefill chunk length: a long prompt prefills in fixed chunks of
    # this many tokens, interleaved with decode steps, so one long
    # prompt cannot stall in-flight streams; every chunk pads to this
    # shape (one prefill program a table width). An engine whose table
    # is shorter takes the table's positions. 128, because a chunk of T
    # tokens does T FLOP for each byte of bf16 weight it reads (the
    # all-experts products of a sparse model too) and a v5e chip's
    # ridge is 197e12 / 819e9 = 240: up to there a chunk costs the one
    # read of the weights it pays anyway, so 128 tokens go for the
    # price of 32 (on the chip, PR 38: a chunk of Mistral's 16 layers
    # 11.1 ms at 32 tokens, 12.2 at 128, 14.1 at 256). 256 leaves the
    # ridge (9.8 ms of arithmetic at the peak beside 9.2 ms of reading;
    # the two sparse families' all-experts products become bound by
    # arithmetic) and every token that waits behind a chunk pays it.
    "llm_prefill_chunk": 128,
    # Bounded engine waiting queue: requests past this depth shed
    # typed (CacheExhaustedError -> SystemOverloadedError path ->
    # HTTP 503) instead of queueing unboundedly.
    "llm_max_waiting": 64,
    # Serve routers push their live latency_stats() (p50/p99) to the
    # controller at most this often — the feed the latency-driven
    # replica autoscaler consumes. 0 disables the push.
    "serve_latency_report_s": 1.0,
    # Worker pipe transport.
    "worker_inline_result_kb": 64,     # pool results <= this inline
    # Distributed tracing plane (util/tracing.py). Disabled, every
    # instrumentation site costs one module-attribute branch
    # (tracing.TRACE_ON — same discipline as chaos.ACTIVE).
    "tracing_enabled": False,
    # Per-process span buffer cap (local records AND the remote-shipping
    # outbox); overflow increments the dropped-span counter.
    "tracing_buffer_max_spans": 4096,
    # Per-stage TaskEvent timestamps (submit/dispatch/rpc/admit/worker/
    # exec/seal) — stamped only while tracing is enabled; this gates
    # them off independently if the stage map itself is unwanted.
    "tracing_stage_timestamps": True,
    # Always-on performance plane (perf_plane.py): stage-latency
    # histograms + per-task resource attribution, recorded WITHOUT
    # tracing being armed and shipped on heartbeats. Disarmed, every
    # site costs one module-attribute branch (perf_plane.PERF_ON);
    # RAY_TPU_PERF_PLANE=0 disarms a whole cluster via the daemon env.
    "perf_plane": True,
    # Crash flight recorder (flight_recorder.py): bounded per-process
    # event ring, persisted to the session dir by daemons so a
    # SIGKILLed process leaves its last N events for `ray_tpu debug`.
    "flight_recorder_events": 512,
    # Daemon-side ring-flush period (seconds); 0 = dump-on-demand only.
    "flight_recorder_flush_s": 2.0,
    # Runtime lock-order witness (lock_witness.py): armed, the hot
    # modules' locks record a per-thread held-set and a global
    # acquisition-order graph; a cycle (two lock classes taken in both
    # orders — a potential deadlock) flight-records both stacks and
    # raises LockOrderError. Tier-1 and the chaos soak arm it
    # (RAY_TPU_LOCK_WITNESS=1); production stays disarmed — the
    # factories then return plain threading objects, so the acquire
    # path is byte-identical to an unwitnessed build. Bench envelope
    # refreshes record the state and test_bench_regression refuses a
    # witness-armed refresh.
    "lock_witness": False,
    # Cluster history plane (metrics_history.py): head-side
    # fixed-interval ring-buffer store that delta-encodes the per-node
    # cumulative heartbeat stats into per-interval samples, plus the
    # rule-driven health watchdog sweeping it. Disarmed
    # (metrics_history=0 / RAY_TPU_METRICS_HISTORY=0), the head's
    # monitor tick pays one module-attribute branch
    # (metrics_history.HISTORY_ON) and the metrics_history /
    # cluster_health RPCs answer armed=False.
    "metrics_history": True,
    # Sampling cadence: one delta-encoded sample per node per interval.
    "metrics_history_interval_s": 2.0,
    # Bounded retention window; ring capacity = retention / interval.
    # Node series idle past this are evicted.
    "metrics_history_retention_s": 600.0,
    # Health watchdog rule thresholds (metrics_history.HEALTH_RULES).
    # Rates evaluate over this trailing window.
    "health_window_s": 30.0,
    # overload: admission-shed rate past this, sustained over >= 2
    # intervals (one burst is backpressure, not a verdict).
    "health_overload_shed_per_s": 0.5,
    # breaker_storm: circuit-breaker opens inside one window.
    "health_breaker_storm_opens": 3.0,
    # spill_thrash: spill+restore churn rate past this WHILE restore
    # p50 is past health_spill_restore_p50_ms.
    "health_spill_churn_per_s": 2.0,
    "health_spill_restore_p50_ms": 50.0,
    # wedged_node: node-stats receipt age (age_s) past this — the
    # daemon stopped heartbeating but is not yet declared dead.
    "health_wedged_age_s": 10.0,
    # stale_shard: a GCS shard's stall age past this serves stale
    # reads and queued writes (history for its domain is degraded).
    "health_stale_shard_age_s": 3.0,
    # fused_fallback_spike: fused-run fallbacks-to-pipeline per second.
    "health_fused_fallback_per_s": 1.0,
    # Native (C++) daemon blob store (node_store.cpp); falls back to
    # the Python store when the toolchain/library is unavailable.
    "node_store_native": True,
    # Native (C++) GCS KV storage engine (gcs_kv.cpp) for HEAD
    # processes; same fallback behavior.
    "gcs_kv_native": True,
}


class Config:
    """Process-wide flag table with env-var and runtime overrides."""

    def __init__(self):
        self._lock = threading.Lock()
        self._values = dict(_DEFAULTS)
        self._apply_env_overrides()

    def _apply_env_overrides(self):
        for key, default in _DEFAULTS.items():
            env_key = "RAY_TPU_" + key.upper()
            raw = os.environ.get(env_key)
            if raw is None:
                continue
            self._values[key] = _coerce(raw, type(default))

    def update(self, overrides: dict[str, Any] | str | None):
        if not overrides:
            return
        if isinstance(overrides, str):
            overrides = json.loads(overrides)
        with self._lock:
            for key, value in overrides.items():
                if key not in _DEFAULTS:
                    raise KeyError(f"Unknown system config key: {key!r}")
                self._values[key] = value

    def get(self, key: str) -> Any:
        with self._lock:
            return self._values[key]

    def peek(self, key: str) -> Any:
        """Lock-free read for per-call hot paths (the columnar submit
        eligibility check runs per ``.remote()``). Safe: ``_values``
        maps a fixed key set and ``update``/``reset`` replace values
        per key under the GIL — a peek sees either the old or the new
        value, never a torn one."""
        return self._values[key]

    def __getattr__(self, key: str) -> Any:
        if key.startswith("_"):
            raise AttributeError(key)
        try:
            return self.get(key)
        except KeyError:
            raise AttributeError(key) from None

    def reset(self):
        with self._lock:
            self._values = dict(_DEFAULTS)
            self._apply_env_overrides()


def _coerce(raw: str, typ: type) -> Any:
    if typ is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    if typ is int:
        return int(raw)
    if typ is float:
        return float(raw)
    return raw


GLOBAL_CONFIG = Config()
