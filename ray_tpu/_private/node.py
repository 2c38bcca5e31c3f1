"""Node agents + head/worker daemon entrypoints.

Reference: python/ray/_private/node.py (Node starts/owns the per-node
services) and src/ray/raylet (the node agent registering with the GCS
and heartbeating). A ray_tpu cluster is:

- ONE head daemon: GcsServer (RPC control plane) + its own node record;
- N worker daemons: NodeAgent registering resources + heartbeating.

Daemons are started by the CLI (``python -m ray_tpu start``) as
detached subprocesses with pidfiles under /tmp/ray_tpu (reference:
``ray start`` spawning raylet/gcs_server with session dirs).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import sys
import threading
import time

from ray_tpu._private import chaos
from ray_tpu._private.rpc import (  # noqa: F401 — RpcClient re-exported for callers
    MuxRpcClient,
    RpcClient,
    RpcError,
    RpcMethodError,
    call_with_retry,
)

SESSION_DIR = os.environ.get("RAY_TPU_SESSION_DIR", "/tmp/ray_tpu")


def _own_address() -> str:
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return "127.0.0.1"


def daemon_child_env(extra: dict | None = None) -> dict:
    """Environment for spawning a ray_tpu daemon subprocess: this
    checkout resolves on PYTHONPATH even when the package isn't
    installed, and TPU detection is skipped unless the caller opts in.
    Shared by every daemon spawn site (cluster_utils, the autoscaler
    provider, the YAML launcher)."""
    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    prior = env.get("PYTHONPATH", "")
    if pkg_root not in prior.split(os.pathsep):
        env["PYTHONPATH"] = (
            pkg_root + (os.pathsep + prior if prior else ""))
    env.setdefault("RAY_TPU_SKIP_TPU_DETECTION", "1")
    env.update(extra or {})
    return env


class NodeAgent:
    """Registers this node with the head GCS and heartbeats.

    Reference: the raylet's NodeManager registration + ReportHeartbeat
    loop, plus the ray_syncer's push-on-change semantics
    (ray_syncer.h:88): ``poke()`` wakes the loop immediately when the
    executor's load changes, so the head's resource view is event-fresh
    instead of lagging up to a full heartbeat period."""

    def __init__(self, gcs_address: str, resources: dict,
                 labels: dict | None = None,
                 heartbeat_period_s: float = 1.0,
                 usage_fn=None, executor_address: str = "",
                 coalesce_s: float = 0.05, stats_fn=None):
        # Pipelined client: a heartbeat never queues behind a slow
        # re-register (or any other in-flight call) on the same socket,
        # and a dead head is detected by the reader thread the moment
        # the connection drops instead of on the next call's timeout.
        self.client = MuxRpcClient(gcs_address, timeout_s=30.0)
        self.resources = dict(resources)
        self.labels = dict(labels or {})
        self.heartbeat_period_s = heartbeat_period_s
        # Floor between consecutive pushes: a burst of admissions
        # coalesces into one update instead of an RPC per task.
        self.coalesce_s = coalesce_s
        # Optional live-usage callable: () -> {resource: available}
        # piggybacked on heartbeats (ray_syncer-lite).
        self.usage_fn = usage_fn
        # Optional executor-stats callable: () -> dict, piggybacked on
        # heartbeats into the GCS node-stats aggregation table (the
        # per-node /metrics series — no extra RPC, the heartbeat IS the
        # stats channel).
        self.stats_fn = stats_fn
        self.executor_address = executor_address
        self._address = f"{_own_address()}:{os.getpid()}"
        self.node_id: bytes = b""
        # Epoch fencing: the head stamps its incarnation number on
        # every reply (rpc reply metadata). ``gcs_epoch`` is the epoch
        # this agent REGISTERED under and stamps on its heartbeats;
        # ``_seen_epoch`` is the latest observed — a mismatch means
        # the head restarted and this agent must re-register before
        # its writes are accepted again (StaleEpochError fences them
        # meanwhile). None end to end against a fencing-disarmed head.
        self.gcs_epoch: "int | None" = None
        self._seen_epoch: "int | None" = None
        self._epoch_stale = threading.Event()
        self.client.on_reply_meta = self._on_reply_meta
        self.node_id = self._register()
        self._shutdown = threading.Event()
        self._poke = threading.Event()
        self._thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True, name="node-heartbeat")
        self._thread.start()

    def _register(self) -> bytes:
        # prior_id: across a head restart the daemon asks to keep its
        # node id, so drivers' mirrored node tables (and in-flight work
        # keyed by the id) converge without a spurious death+rejoin.
        # Registration is idempotent under prior_id (the head grants
        # the same id back on a retried request), so it rides the
        # shared retry policy — a dropped frame must not cost the node
        # a death verdict.
        from ray_tpu._private.same_host import host_identity

        node_id = call_with_retry(
            self.client.call,
            "register_node", self._address, self.resources, self.labels,
            self.executor_address, prior_id=self.node_id or None,
            host_id=host_identity())
        # The register reply's metadata carried the head's current
        # epoch (observed by _on_reply_meta before the call resolved):
        # registration IS the re-sync, subsequent writes stamp it.
        self.gcs_epoch = self._seen_epoch
        self._epoch_stale.clear()
        return node_id

    def _on_reply_meta(self, meta: dict) -> None:
        """Reader-thread observer for the head's reply metadata: an
        epoch differing from the one we registered under means the
        head restarted — wake the loop to re-register (its next
        stamped write would be fenced anyway)."""
        epoch = meta.get("epoch") if isinstance(meta, dict) else None
        if not isinstance(epoch, int):
            return
        self._seen_epoch = epoch
        if self.gcs_epoch is not None and epoch != self.gcs_epoch \
                and not self._epoch_stale.is_set():
            from ray_tpu._private import flight_recorder

            flight_recorder.record("epoch.bump", self.gcs_epoch, epoch)
            self._epoch_stale.set()
            self._poke.set()

    def poke(self) -> None:
        """Load changed: push a heartbeat now (coalesced)."""
        self._poke.set()

    def _heartbeat_loop(self) -> None:
        while not self._shutdown.is_set():
            # Wake early on poke; always wake by the heartbeat period
            # (liveness at the head depends on the periodic floor).
            self._poke.wait(self.heartbeat_period_s)
            self._poke.clear()
            if self._shutdown.is_set():
                return
            if chaos.ACTIVE is not None:
                # Chaos: a skipped beat ages this node toward the
                # head's death verdict; daemon.die is the harness's
                # in-process SIGKILL (the whole daemon vanishes the way
                # a crashed host does).
                if chaos.ACTIVE.should("daemon.die"):
                    # The one death the flusher can't race: this
                    # process is about to SIGKILL itself, so flush the
                    # flight ring synchronously — the post-mortem
                    # bundle must carry the dying daemon's last events.
                    from ray_tpu._private import flight_recorder

                    flight_recorder.dump("chaos.daemon.die")
                    os.kill(os.getpid(), signal.SIGKILL)
                if chaos.ACTIVE.should("heartbeat.skip"):
                    self._shutdown.wait(self.coalesce_s)
                    continue
            available = None
            if self.usage_fn is not None:
                try:
                    available = self.usage_fn()
                except Exception:  # noqa: BLE001 — usage is best-effort
                    available = None
            stats = None
            if self.stats_fn is not None:
                try:
                    stats = self.stats_fn()
                except Exception:  # noqa: BLE001 — stats are best-effort
                    stats = None
            trace = None
            from ray_tpu.util import tracing

            if tracing.TRACE_ON:
                # Piggyback this daemon's buffered spans (user spans,
                # orphans no reply frame carried) with a wall-clock
                # anchor for the head's one-way offset estimate.
                spans = tracing.drain_buffered()
                if spans:
                    trace = {"spans": spans, "now": time.time()}
            try:
                if self._epoch_stale.is_set():
                    # The head restarted under us (epoch bump seen on
                    # a reply): re-register BEFORE the next stamped
                    # write — the fence would reject it anyway.
                    self.node_id = self._register()
                # Heartbeats are idempotent: ride the shared retry
                # policy with a short per-try timeout so one dropped
                # frame costs a retry, not a liveness-timeout stall.
                accepted = call_with_retry(
                    self.client.call, "heartbeat", self.node_id,
                    available, stats, trace, attempts=2,
                    timeout_s=max(3.0, self.heartbeat_period_s * 3),
                    epoch=self.gcs_epoch)
                if not accepted:
                    # Unknown/dead at the head (stall past the timeout
                    # or a head restart): re-register, asking to keep
                    # our id — the head grants it unless it declared
                    # this id dead (reference: raylet re-registration
                    # after GCS restart keeps the NodeID).
                    from ray_tpu._private import flight_recorder

                    flight_recorder.record("heartbeat.rejected")
                    self.node_id = self._register()
                    flight_recorder.record("re-registered",
                                           self.node_id.hex()[:16])
            except RpcMethodError as exc:
                from ray_tpu._private.gcs import StaleEpochError

                if isinstance(exc.cause, StaleEpochError):
                    # Typed fence: this agent heartbeated with a
                    # previous incarnation's epoch (partitioned across
                    # the head restart). Re-sync by re-registering;
                    # the next beat is accepted.
                    from ray_tpu._private import flight_recorder

                    flight_recorder.record(
                        "heartbeat.stale_epoch",
                        exc.cause.current_epoch)
                    try:
                        self.node_id = self._register()
                    except (RpcError, RpcMethodError, OSError):
                        pass  # head flapped again; next beat retries
                else:
                    from ray_tpu._private import flight_recorder
                    from ray_tpu.exceptions import SystemOverloadedError

                    if isinstance(exc.cause, SystemOverloadedError):
                        # A degraded GCS shard shed this beat's
                        # piggyback typed (queue at cap). Liveness is
                        # unaffected — the next beat retries — but the
                        # shed belongs in the post-mortem ring.
                        flight_recorder.record(
                            "heartbeat.shed",
                            getattr(exc.cause, "retry_after_s", 0.0))
            except (RpcError, OSError):
                pass  # head unreachable; keep trying (it may restart)
            # Coalescing floor: pokes landing during the sleep fold
            # into the next push.
            self._shutdown.wait(self.coalesce_s)

    def stop(self, drain: bool = True) -> None:
        self._shutdown.set()
        if drain:
            try:
                self.client.call("drain_node", self.node_id)
            except (RpcError, RpcMethodError, OSError):
                pass  # drain is advisory; head may be gone
        self.client.close()


def _install_daemon_recorder(role: str, executor) -> "object":
    """Daemon-side flight recorder: flushing armed (the ring file must
    survive SIGKILL) and dumps enriched with this daemon's fault
    counters, breaker state and recent stage histograms — the
    post-mortem trio `ray_tpu debug` bundles."""
    from ray_tpu._private import flight_recorder, perf_plane
    from ray_tpu._private.rpc import breaker_stats

    def extra() -> dict:
        return {"fault_stats": executor._fault_stats(),
                "breaker": breaker_stats(),
                "spill": executor._spill_stats(),
                "stage_hist": perf_plane.stage_snapshot()}

    return flight_recorder.install(role, flush=True, extra_fn=extra)


def default_resources() -> dict:
    from ray_tpu._private import accelerators

    resources = {"CPU": float(os.cpu_count() or 1)}
    resources.update(accelerators.detect_resources())
    return resources


def run_head(port: int, resources: dict | None = None,
             dashboard_port: int | None = 0) -> None:
    """Head daemon: GCS server + dashboard + own node registration.
    Blocks."""
    from ray_tpu._private.gcs_server import GcsServer
    from ray_tpu.dashboard import Dashboard, gcs_provider

    os.makedirs(SESSION_DIR, exist_ok=True)
    snapshot_path = os.path.join(SESSION_DIR, "gcs_snapshot.pkl")
    # Bare ring BEFORE the GCS restores: recovery events (WAL replay,
    # torn-tail truncation, epoch mint) must land in the head's flight
    # ring; _install_daemon_recorder upgrades it with flushing later.
    from ray_tpu._private import flight_recorder

    flight_recorder.install("daemon-head")
    server = GcsServer(port=port, log_dir=SESSION_DIR,
                       persist_path=snapshot_path)
    server.start()
    dashboard = None
    if dashboard_port is not None:
        # Bind all interfaces: the advertised address file carries the
        # external IP, which must actually be reachable.
        dashboard = Dashboard(gcs_provider(server), host="0.0.0.0",
                              port=dashboard_port).start()
        with open(os.path.join(SESSION_DIR, "dashboard_address"),
                  "w") as f:
            f.write(f"{_own_address()}:{dashboard.port}")

    # Client server: remote drivers run tasks/actors against the head's
    # runtime (reference: ray client server inside `ray start --head`).
    import ray_tpu
    from ray_tpu.util.client import ClientServer

    ray_tpu.init(ignore_reinit_error=True)
    client_server = ClientServer(host="0.0.0.0", port=0).start()
    with open(os.path.join(SESSION_DIR, "client_address"), "w") as f:
        f.write(f"{_own_address()}:{client_server.port}")
    # The head's heartbeat availability reflects BOTH consumers of its
    # cores: leased executor tasks and client-server work on the
    # in-process runtime (reporting only one would double-book the
    # node in status/list_nodes).
    from ray_tpu._private.worker import global_runtime

    def head_usage():
        avail = dict(executor.available_resources())
        runtime = global_runtime()
        if runtime is not None:
            rt_avail = runtime.available_resources()
            for key, total in runtime.cluster_resources().items():
                used = total - rt_avail.get(key, 0.0)
                if used > 0:
                    avail[key] = avail.get(key, 0.0) - used
        return avail

    # The head is ALSO an executor node: connected drivers can lease
    # tasks onto it like any worker daemon (reference: `ray start
    # --head` contributes its own raylet + worker pool).
    from ray_tpu._private.node_executor import NodeExecutorService

    head_resources = resources or default_resources()
    os.environ.setdefault("RAY_TPU_NODE_TAG", f"head-{os.urandom(4).hex()}")
    from ray_tpu._private.config import GLOBAL_CONFIG

    if bool(GLOBAL_CONFIG.tracing_enabled):
        from ray_tpu.util import tracing

        tracing.enable()
    executor = NodeExecutorService(resources=head_resources)
    executor.advertised_address = executor.address_for(_own_address())
    executor.start()
    _install_daemon_recorder("daemon-head", executor)

    agent = NodeAgent(f"127.0.0.1:{server._server.port}",
                      head_resources,
                      labels={"node_role": "head"},
                      usage_fn=head_usage,
                      executor_address=executor.address_for(_own_address()),
                      stats_fn=executor.stats_for_sync)
    executor.set_load_listener(agent.poke)

    # Written LAST: `start` blocks on this file, so by the time the CLI
    # returns, the head's own node (executor included) is registered
    # and `status` immediately shows 1 alive node.
    with open(os.path.join(SESSION_DIR, "head_address"), "w") as f:
        f.write(f"{_own_address()}:{server._server.port}")

    stop_event = threading.Event()

    def on_term(signum, frame):
        stop_event.set()

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    try:
        while not stop_event.wait(0.5):
            pass
    finally:
        agent.stop()
        executor.stop()
        client_server.stop()
        if dashboard is not None:
            dashboard.stop()
        server.stop()
        # Clean stop = session over: the snapshot/WAL exist for CRASH
        # recovery only. Leaving them would resurrect stale jobs/actors
        # into the NEXT, unrelated cluster on this machine. The epoch
        # file deliberately SURVIVES: incarnation numbers are monotonic
        # per session dir, so a daemon partitioned across sessions can
        # still never present a current-looking epoch.
        import glob as glob_mod

        # Per-shard segments (<snapshot>.shard<i>[.wal][.prev]) follow
        # the same rule; the gcs_epoch_shard<i> files survive with the
        # head's epoch file for the same fencing reason.
        for path in [snapshot_path + suffix
                     for suffix in ("", ".prev", ".wal", ".wal.prev")] \
                + glob_mod.glob(snapshot_path + ".shard*"):
            try:
                os.unlink(path)
            except OSError:
                pass  # generation file already absent


def run_worker(gcs_address: str, resources: dict | None = None,
               pool_size: int | None = None,
               labels: dict | None = None,
               heartbeat_period_s: float = 1.0) -> None:
    """Worker-node daemon: executor service + register + heartbeat.
    Blocks. (Reference: the raylet — lease-based dispatch onto this
    node's worker pool, node_manager.cc:1714.) ``labels`` merge into
    the node record (e.g. the autoscaler provider's tag)."""
    from ray_tpu._private.node_executor import NodeExecutorService

    resources = resources or default_resources()
    # Unique per-daemon tag, inherited by this node's pool workers (set
    # BEFORE the pool spawns) — tasks can read it to learn where they ran.
    os.environ["RAY_TPU_NODE_TAG"] = os.urandom(6).hex()
    from ray_tpu._private.config import GLOBAL_CONFIG

    if bool(GLOBAL_CONFIG.tracing_enabled):
        # Daemons inherit RAY_TPU_TRACING_ENABLED through the child env:
        # user spans opened inside daemon-hosted tasks collect and ship
        # on heartbeats without any driver involvement.
        from ray_tpu.util import tracing

        tracing.enable()
    executor = NodeExecutorService(
        pool_size=pool_size, resources=resources)
    executor.advertised_address = executor.address_for(_own_address())
    executor.start()
    _install_daemon_recorder(
        f"daemon-{os.environ['RAY_TPU_NODE_TAG'][:8]}", executor)
    agent = NodeAgent(gcs_address, resources,
                      labels={"node_role": "worker", **(labels or {})},
                      heartbeat_period_s=heartbeat_period_s,
                      usage_fn=executor.available_resources,
                      executor_address=executor.address_for(_own_address()),
                      stats_fn=executor.stats_for_sync)
    executor.set_load_listener(agent.poke)
    stop_event = threading.Event()

    def on_term(signum, frame):
        stop_event.set()

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    try:
        while not stop_event.wait(0.5):
            pass
    finally:
        from ray_tpu._private import flight_recorder

        flight_recorder.record("daemon.stop")
        flight_recorder.dump("shutdown")
        agent.stop()
        executor.stop()


def main(argv: list[str]) -> None:
    role = argv[0]
    kwargs = json.loads(argv[1]) if len(argv) > 1 else {}
    if role == "head":
        run_head(**kwargs)
    elif role == "worker":
        run_worker(**kwargs)
    else:
        raise SystemExit(f"unknown node role: {role}")


if __name__ == "__main__":
    main(sys.argv[1:])
