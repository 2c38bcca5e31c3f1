"""Lineage-based object recovery + node health monitoring.

TPU-native analogue of the reference's recovery stack:
- ``LineageTable`` records which task produced each object (reference:
  src/ray/core_worker/reference_count.h:61 keeps lineage refs;
  task_manager.h:195 owns resubmittable specs).
- ``ObjectRecoveryManager`` re-executes lineage when an object is lost
  (reference: src/ray/core_worker/object_recovery_manager.h:41) —
  recursively: a lost dependency of a lost object is rebuilt first.
- ``NodeHealthMonitor`` detects dead nodes from heartbeat staleness
  (reference: src/ray/gcs/gcs_server/gcs_health_check_manager.h:39
  health-checks raylets over gRPC; here virtual nodes heartbeat through
  the GCS node table and chaos tooling stops the beat).

Determinism caveat (same as the reference): recovery re-runs the
producing task, so tasks with external side effects or unseeded
randomness may rebuild a different value.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict, deque
from typing import Callable

from ray_tpu._private.ids import NodeID, ObjectID  # noqa: F401 (NodeID: from_hex)
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.task import TaskSpec


class LineageTable:
    """object_id -> producing TaskSpec, bounded (lineage eviction)."""

    def __init__(self, max_entries: int = 10_000):
        # RLock: forget() can re-enter from ObjectRef.__del__ (GC may
        # fire inside record() while this lock is held).
        self._lock = threading.RLock()
        self._by_object: "OrderedDict[ObjectID, TaskSpec]" = OrderedDict()
        self._max_entries = max_entries
        # Columnar lineage (dispatch_lanes.ColumnarGroup): one GROUP
        # record per submit flush instead of a spec per task. The
        # rid -> group map is bulk-built (dict.fromkeys — one C pass,
        # O(1) Python objects per group) and lookup() expands the one
        # touched record into a real TaskSpec lazily (spec_for).
        # Groups evict FIFO wholesale once the combined entry count
        # passes the cap (same reconstructability-loss semantics as
        # the per-spec eviction above).
        self._group_by_rid: dict = {}
        self._groups: "deque" = deque()
        self._group_entries = 0

    def record(self, spec: TaskSpec) -> None:
        with self._lock:
            for rid in spec.return_ids:
                self._by_object[rid] = spec
                self._by_object.move_to_end(rid)
            while len(self._by_object) > self._max_entries:
                # Oldest entries lose reconstructability (reference:
                # lineage eviction under RAY_max_lineage_bytes).
                self._by_object.popitem(last=False)

    def record_many(self, specs) -> None:
        """One lock pass for a whole submit flush (the pipelined
        submit path amortizes the per-task acquire)."""
        with self._lock:
            by_object = self._by_object
            for spec in specs:
                for rid in spec.return_ids:
                    if rid in by_object:
                        # Re-record (retry/recovery): refresh recency.
                        by_object.move_to_end(rid)
                    by_object[rid] = spec
            while len(by_object) > self._max_entries:
                by_object.popitem(last=False)

    def record_group(self, group) -> None:
        """One lock pass + O(1) allocations for a whole columnar
        group: the per-task specs exist only virtually until a lookup
        touches one (lazy expansion — ISSUE 15)."""
        with self._lock:
            self._group_by_rid.update(
                dict.fromkeys(group.return_ids, group))
            self._groups.append(group)
            self._group_entries += len(group.return_ids)
            while self._groups and len(self._by_object) \
                    + self._group_entries > self._max_entries:
                old = self._groups.popleft()
                self._group_entries -= len(old.return_ids)
                for rid in old.return_ids:
                    if self._group_by_rid.get(rid) is old:
                        del self._group_by_rid[rid]

    def lookup(self, object_id: ObjectID) -> TaskSpec | None:
        with self._lock:
            spec = self._by_object.get(object_id)
            if spec is not None:
                return spec
            group = self._group_by_rid.get(object_id)
            if group is None:
                return None
            # Expand the touched record only (recovery is the rare
            # path); the materialized spec is NOT cached — recovery
            # re-records it through record() when it resubmits.
            return group.spec_for(group.by_rid[object_id])

    def forget(self, object_ids) -> None:
        with self._lock:
            for oid in object_ids:
                self._by_object.pop(oid, None)
                self._group_by_rid.pop(oid, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._by_object) + len(self._group_by_rid)


class ObjectRecoveryManager:
    """Rebuilds lost objects by re-executing their lineage."""

    def __init__(self, runtime):
        self._runtime = runtime
        self._lock = threading.Lock()
        self._in_flight: set[ObjectID] = set()
        self.num_recoveries = 0
        # Rebuilds triggered by a torn SPILL file (checksum mismatch on
        # restore, spill_manager.py) — split out so the chaos tests and
        # /metrics can tell disk corruption from node death.
        self.num_torn_recoveries = 0

    def recover(self, object_id: ObjectID, reason: str = "lost") -> bool:
        """Resubmit the producing task (and lost deps, recursively).

        Returns False when no lineage exists (e.g. ``put()`` objects or
        evicted lineage) — the caller should fail waiters with
        ObjectLostError. Idempotent per in-flight object. ``reason``
        attributes the rebuild ("lost" = node death/object loss,
        "spill_torn" = corrupt spill file)."""
        spec = self._runtime.lineage.lookup(object_id)
        if spec is None:
            return False
        strategy = spec.scheduling_strategy
        if (strategy is not None and strategy.kind == "NODE_AFFINITY"
                and not strategy.soft):
            # Hard affinity to a dead node can never reschedule; fail
            # fast instead of queueing a task that hangs forever.
            node = self._runtime.cluster.get_node(
                NodeID.from_hex(strategy.node_id))
            if node is None or not node.alive:
                return False
        with self._lock:
            already = all(rid in self._in_flight for rid in spec.return_ids)
            if already:
                return True
            self._in_flight.update(spec.return_ids)
            self.num_recoveries += 1
            if reason == "spill_torn":
                self.num_torn_recoveries += 1

        store = self._runtime.store
        deps = []
        unrecoverable_dep = None
        for arg in list(spec.args) + list(spec.kwargs.values()):
            if isinstance(arg, ObjectRef):
                deps.append(arg)
                if store.is_lost(arg.id()):
                    if not self.recover(arg.id()):
                        from ray_tpu.exceptions import ObjectLostError

                        dep_err = ObjectLostError(
                            ObjectRef(arg.id(), _register=False),
                            f"object {arg.id().hex()} lost with no "
                            f"lineage to rebuild it")
                        store.put_error(arg.id(), dep_err)
                        unrecoverable_dep = dep_err
        if unrecoverable_dep is not None:
            # The parent can never produce a correct value; surface the
            # dependency's ObjectLostError instead of resubmitting a task
            # doomed to fail (and burn retries) on argument resolution.
            for rid in spec.return_ids:
                store.put_error(rid, unrecoverable_dep)
            with self._lock:
                self._in_flight.difference_update(spec.return_ids)
            return True
        for rid in spec.return_ids:
            store.create_pending(rid)

        def run_and_clear(s, node, _orig=spec):
            try:
                self._runtime._execute_task(_orig, node)
            finally:
                with self._lock:
                    self._in_flight.difference_update(_orig.return_ids)

        self._runtime.dispatcher.submit(spec, run_and_clear, deps)
        return True


class NodeHealthMonitor:
    """Marks nodes dead when their heartbeat goes stale.

    A beater thread heartbeats every live virtual node (they share the
    process, so liveness is synthetic); chaos tooling removes a node
    from the beat set and the checker thread notices the staleness after
    ``failure_threshold`` missed periods — the same detect-then-broadcast
    flow as the reference's health check manager.
    """

    def __init__(self, gcs, period_s: float, failure_threshold: int,
                 on_node_dead: Callable[[NodeID], None]):
        self._gcs = gcs
        self._period = period_s
        self._threshold = failure_threshold
        self._on_node_dead = on_node_dead
        self._lock = threading.Lock()
        self._suppressed: set[NodeID] = set()
        self._reported: set[NodeID] = set()
        self._stop = threading.Event()
        self._beater = threading.Thread(
            target=self._beat_loop, name="ray_tpu-heartbeat", daemon=True)
        self._checker = threading.Thread(
            target=self._check_loop, name="ray_tpu-health-check", daemon=True)
        self._beater.start()
        self._checker.start()

    def suppress(self, node_id: NodeID) -> None:
        """Chaos: stop heartbeating a node so the checker declares it dead."""
        with self._lock:
            self._suppressed.add(node_id)

    def _beat_loop(self) -> None:
        while not self._stop.wait(self._period / 2):
            with self._lock:
                suppressed = set(self._suppressed)
            for record in self._gcs.list_nodes():
                if record.alive and record.node_id not in suppressed:
                    self._gcs.heartbeat(record.node_id)

    def _check_loop(self) -> None:
        last_round = time.monotonic()
        while not self._stop.wait(self._period):
            now = time.monotonic()
            stalled = now - last_round > self._period * self._threshold
            last_round = now
            if stalled:
                # This round came as late as a heartbeat may be stale:
                # the whole process stood still (a sibling process
                # initialising a TPU chip freezes the machine for
                # seconds at a time), the beater with it, and stale
                # heartbeats then prove nothing. It beats twice a
                # period, so the next round judges fresh ones.
                continue
            for record in self._gcs.list_nodes():
                if not record.alive:
                    continue
                stale = now - record.last_heartbeat
                if stale > self._period * self._threshold:
                    with self._lock:
                        if record.node_id in self._reported:
                            continue
                        self._reported.add(record.node_id)
                    try:
                        self._on_node_dead(record.node_id)
                    except Exception:
                        # Un-report so the next check retries the death
                        # handling; a one-off hiccup must not permanently
                        # strand the node's objects.
                        logging.getLogger("ray_tpu").exception(
                            "node-death handling for %s failed; will retry",
                            record.node_id.hex()[:8])
                        with self._lock:
                            self._reported.discard(record.node_id)

    def shutdown(self) -> None:
        self._stop.set()
