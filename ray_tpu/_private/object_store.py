"""In-memory object store with spilling and reference counting.

TPU-native analogue of the reference's two-tier store: the in-process
memory store for small objects/futures (reference:
src/ray/core_worker/store_provider/memory_store/memory_store.h) plus the
plasma shared-memory store with LRU eviction and disk spilling (reference:
src/ray/object_manager/plasma/object_store.h,
src/ray/raylet/local_object_manager.h:110 SpillObjects).

Objects here are held as live Python objects (zero-copy within the node —
host numpy/jax arrays are shared by reference, the moral equivalent of
plasma's mmap zero-copy reads). When the store exceeds its memory budget,
sealed objects with no pinned readers are spilled to disk (pickled) and
restored transparently on access.

Reference counting follows the ownership model (reference:
src/ray/core_worker/reference_count.h:61): the driver/worker that created
an object owns it; local ObjectRef lifetimes drive the count and an object
with zero references becomes evictable.
"""

from __future__ import annotations

import os
import pickle
import threading

from ray_tpu._private import lock_witness
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from ray_tpu._private.ids import ObjectID
from ray_tpu.exceptions import (
    GetTimeoutError,
    ObjectFreedError,
    ObjectLostError,
)
from ray_tpu.util import tracing


def _sizeof(value: Any) -> int:
    """Best-effort deep size estimate without serializing."""
    # Exact-type fast head: scalar/str/bytes seals (the columnar
    # completion path is almost entirely these) skip the numpy/jax
    # isinstance probes below.
    t = type(value)
    if t is int or t is float or t is bool or value is None:
        return 64
    if t is bytes or t is str or t is bytearray:
        return len(value)
    try:
        import numpy as np

        if isinstance(value, np.ndarray):
            return int(value.nbytes)
    except Exception:
        pass  # numpy absent/half-imported: not an ndarray
    try:
        import jax

        if isinstance(value, jax.Array):
            return int(value.size * value.dtype.itemsize)
    except Exception:
        pass  # jax absent/half-imported: not a jax.Array
    if isinstance(value, (bytes, bytearray, memoryview)):
        return len(value)
    if isinstance(value, str):
        return len(value)
    if isinstance(value, (list, tuple, set)) and len(value) < 1024:
        return 64 + sum(_sizeof(v) for v in value)
    if isinstance(value, dict) and len(value) < 1024:
        return 64 + sum(_sizeof(k) + _sizeof(v) for k, v in value.items())
    return 64


@dataclass
class ObjectEntry:
    object_id: ObjectID
    value: Any = None
    error: BaseException | None = None
    sealed: bool = False
    size_bytes: int = 0
    spilled_path: str | None = None
    freed: bool = False
    # Lost: was sealed, then its node died. Getters block until lineage
    # recovery reseals it (or an ObjectLostError is sealed in).
    lost: bool = False
    created_at: float = field(default_factory=time.monotonic)
    # Pinned while a get() is materializing it; pinned entries never spill.
    pin_count: int = 0
    # Managed spill tier (spill_manager.py): the spilled file carries
    # the length+CRC header and restores verify it (torn -> lineage).
    managed_spill: bool = False
    # LRU signal for the managed victim policy (stamped on get).
    last_used: float = field(default_factory=time.monotonic)
    # When it was sealed, ``time.monotonic_ns()``, while a trace sink
    # was live (``tracing.stamp_ns``; else 0): a getter that waited for
    # it reports how long after the seal it was awake.
    sealed_ns: int = 0


class _TornRestore(Exception):
    """Internal: a managed spill file failed its checksum — the entry
    was marked lost and the getter must wait for lineage recovery."""


class ObjectStore:
    """Node-local object store: seal/get/wait/free with spill-to-disk."""

    def __init__(self, memory_limit_bytes: int, spill_dir: str):
        # REENTRANT: any allocation inside a locked section can trigger
        # GC, which can run ObjectRef.__del__ → remove_ref → evict() on
        # THIS store from the same thread. A plain lock deadlocks there
        # (observed: _seal's _sizeof iterating a container whose temp
        # refs die mid-iteration).
        self._lock = lock_witness.Condition("object_store.ObjectStore")
        self._entries: dict[ObjectID, ObjectEntry] = {}
        self._memory_limit = memory_limit_bytes
        self._memory_used = 0
        self._spill_dir = spill_dir
        self._spilled_bytes_total = 0
        self._restored_bytes_total = 0
        # Callbacks fired (outside the lock) when an object is sealed.
        self._seal_listeners: list[Callable[[ObjectID], None]] = []
        # Batch-aware listeners: put_batch fires them ONCE with the
        # whole sealed group (per-id listeners still fire per object).
        self._batch_seal_listeners: list[Callable] = []
        # Seal-coalescing counters (the "seal" drain stage): how many
        # grouped seals happened and how many objects rode them.
        self.batch_seals = 0
        self.batch_sealed_objects = 0
        # Managed spill tier (spill_manager.py) — armed by the runtime
        # via enable_managed_spill; None keeps the legacy inline path.
        self._spill = None
        self._spill_min_bytes = 4096
        self._leased_fn = None
        self._on_backing_free = None
        self._on_torn = None
        # Values that failed to pickle once: never re-selected (an
        # unpicklable giant would otherwise be re-serialized per pass).
        self._unspillable: set[ObjectID] = set()

    # ------------------------------------------------------- managed spill

    def enable_managed_spill(self, spill_dir: str | None = None,
                             leased_fn=None, on_backing_free=None,
                             on_torn=None):
        """Arm the watermark-driven spill tier: sealed unpinned values
        above spill_high_watermark x the memory limit move to
        checksummed session-dir files asynchronously; restores verify
        the CRC and a torn file falls back to lineage reconstruction
        via ``on_torn(object_id)``. ``leased_fn`` yields id BYTES
        currently leased to same-host peers (never spilled);
        ``on_backing_free(object_id)`` drops the object's shm/arena
        twin after its heap copy moved to disk."""
        from ray_tpu._private.config import GLOBAL_CONFIG
        from ray_tpu._private.spill_manager import SpillManager

        self._leased_fn = leased_fn
        self._on_backing_free = on_backing_free
        self._on_torn = on_torn
        self._spill_min_bytes = max(
            4096, int(GLOBAL_CONFIG.spill_min_object_kb) * 1024)
        self._spill = SpillManager(
            "driver-store", self._memory_limit,
            usage_fn=lambda: self._memory_used,
            victims_fn=self._spill_victims,
            extract_fn=self._spill_extract,
            commit_fn=self._spill_commit,
            spill_dir=spill_dir)
        return self._spill

    def _spill_victims(self, need_bytes: int) -> list:
        leased: set = set()
        if self._leased_fn is not None:
            try:
                leased = {bytes(b) for b in self._leased_fn()}
            except Exception:  # noqa: BLE001
                leased = set()
        with self._lock:
            cands = [
                (e.object_id, e.size_bytes, e.last_used)
                for e in self._entries.values()
                if e.sealed and not e.freed and e.error is None
                and e.spilled_path is None and e.pin_count == 0
                and e.size_bytes >= self._spill_min_bytes
                and e.object_id not in self._unspillable
                and e.object_id.binary() not in leased]
        # Size-ordered (largest first — fewest files free the most
        # bytes), least-recently-used as the tiebreak.
        cands.sort(key=lambda c: (-c[1], c[2]))
        out, covered = [], 0
        for oid, size, _used in cands:
            out.append(oid)
            covered += size
            if covered >= need_bytes:
                break
        return out

    def _spill_extract(self, object_id: ObjectID):
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is None or not entry.sealed or entry.freed \
                    or entry.error is not None or entry.pin_count > 0 \
                    or entry.spilled_path is not None:
                return None
            value = entry.value
        # Pickle OUTSIDE the lock (walks user containers; GC can run
        # arbitrary __del__s — same discipline as _sizeof in _seal).
        try:
            return pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)
        except Exception:  # noqa: BLE001 — unpicklable stays in memory
            with self._lock:
                self._unspillable.add(object_id)
            return None

    def _spill_commit(self, object_id: ObjectID, path: str,
                      size: int) -> bool:
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is None or not entry.sealed or entry.freed \
                    or entry.error is not None or entry.pin_count > 0 \
                    or entry.spilled_path is not None:
                return False
            entry.spilled_path = path
            entry.managed_spill = True
            entry.value = None
            self._memory_used -= entry.size_bytes
            self._spilled_bytes_total += entry.size_bytes
        if self._on_backing_free is not None:
            self._on_backing_free(object_id)
        return True

    def _unlink_spill(self, entry: ObjectEntry) -> None:
        """Drop an entry's spill file (free/evict/reseal pruning) —
        counted by the manager when it owns the format."""
        path, entry.spilled_path = entry.spilled_path, None
        entry.managed_spill = False
        if path is None:
            return
        if self._spill is not None:
            self._spill.delete_file(path)
            return
        try:
            os.unlink(path)
        except OSError:
            pass  # spill file already gone

    # ------------------------------------------------------------------ put

    def create_pending(self, object_id: ObjectID) -> None:
        """Register an object whose value will arrive later (a future)."""
        with self._lock:
            if object_id not in self._entries:
                self._entries[object_id] = ObjectEntry(object_id)

    def create_pending_batch(self, object_ids) -> None:
        """Register a whole submit flush's return objects under ONE
        lock pass (the pipelined submit path's analogue of
        ``put_batch`` on the seal side)."""
        with self._lock:
            entries = self._entries
            for object_id in object_ids:
                if object_id not in entries:
                    entries[object_id] = ObjectEntry(object_id)

    def put(self, object_id: ObjectID, value: Any) -> None:
        self._seal(object_id, value=value, error=None)

    def put_batch(self, items: "list[tuple[ObjectID, Any]]") -> None:
        """Seal a group of objects under ONE lock pass and notify
        batch listeners once for the whole group — the coalesced
        result-seal path for grouped task-batch completions."""
        if not items:
            return
        sizes = [_sizeof(value) for _, value in items]
        with self._lock:
            for (object_id, value), size_bytes in zip(items, sizes):
                self._seal_locked(object_id, value, None, size_bytes)
            self._lock.notify_all()
            self.batch_seals += 1
            self.batch_sealed_objects += len(items)
            listeners = list(self._seal_listeners)
            batch_listeners = list(self._batch_seal_listeners)
        ids = [object_id for object_id, _ in items]
        for cb in batch_listeners:
            cb(ids)
        for object_id in ids:
            for cb in listeners:
                cb(object_id)
        self._maybe_spill()

    def put_group(self, items: "list[tuple[ObjectID, Any]]") -> None:
        """Completion FAST path (ISSUE 15): seal a columnar reply
        group under one lock pass and fire batch listeners only — the
        per-id listener fan-out (concurrent.futures resolution) is
        skipped; the caller resolves futures itself on the rare
        occasions any are attached. Get-less tasks therefore seal with
        zero future machinery."""
        if not items:
            return
        sizes = [_sizeof(value) for _, value in items]
        with self._lock:
            for (object_id, value), size_bytes in zip(items, sizes):
                self._seal_locked(object_id, value, None, size_bytes)
            self._lock.notify_all()
            self.batch_seals += 1
            self.batch_sealed_objects += len(items)
            batch_listeners = list(self._batch_seal_listeners)
        ids = [object_id for object_id, _ in items]
        for cb in batch_listeners:
            cb(ids)
        self._maybe_spill()

    def put_error(self, object_id: ObjectID, error: BaseException) -> None:
        self._seal(object_id, value=None, error=error)

    def _seal(self, object_id: ObjectID, value: Any, error: BaseException | None):
        # Size OUTSIDE the lock: _sizeof walks user containers, which
        # can run arbitrary __del__s via GC.
        size_bytes = _sizeof(value) if error is None else 256
        with self._lock:
            self._seal_locked(object_id, value, error, size_bytes)
            self._lock.notify_all()
            listeners = list(self._seal_listeners)
            batch_listeners = list(self._batch_seal_listeners)
        for cb in batch_listeners:
            cb((object_id,))
        for cb in listeners:
            cb(object_id)
        self._maybe_spill()

    def _seal_locked(self, object_id: ObjectID, value: Any,
                     error: BaseException | None,
                     size_bytes: int) -> None:
        # Caller holds self._lock.
        entry = self._entries.get(object_id)
        if entry is None:
            entry = ObjectEntry(object_id)
            self._entries[object_id] = entry
        if entry.sealed and not entry.freed:
            # Idempotent reseal (e.g. task retry recomputed the value).
            if entry.spilled_path is not None:
                # Spilled copies already gave their bytes back; just drop
                # the stale file.
                self._unlink_spill(entry)
            else:
                self._memory_used -= entry.size_bytes
        entry.value = value
        entry.error = error
        entry.sealed = True
        entry.freed = False
        entry.lost = False
        entry.spilled_path = None
        entry.managed_spill = False
        entry.size_bytes = size_bytes
        entry.sealed_ns = tracing.stamp_ns()
        self._memory_used += entry.size_bytes
        self._unspillable.discard(object_id)

    def add_seal_listener(self, cb: Callable[[ObjectID], None]) -> None:
        with self._lock:
            self._seal_listeners.append(cb)

    def add_batch_seal_listener(self, cb: Callable) -> None:
        """``cb(ids)`` fires once per seal GROUP (a 1-tuple for plain
        puts) — consumers scanning state per notification amortize the
        scan across a grouped batch completion."""
        with self._lock:
            self._batch_seal_listeners.append(cb)

    # ------------------------------------------------------------------ get

    def get(self, object_id: ObjectID, timeout: float | None = None) -> Any:
        """Block until the object is sealed; raise stored errors.

        A managed spill restore that finds its file TORN re-enters the
        wait loop after firing the runtime's lineage-recovery hook —
        the getter blocks until the producing task reseals the value
        (or an ObjectLostError is sealed in), never sees garbage."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                while True:
                    entry = self._entries.get(object_id)
                    if entry is not None and entry.freed:
                        raise ObjectFreedError(object_id, f"object {object_id.hex()} was freed")
                    if entry is not None and entry.sealed:
                        break
                    if entry is None:
                        # Unknown id: wait for it to appear (it may be in flight).
                        pass
                    remaining = None if deadline is None else deadline - time.monotonic()
                    if remaining is not None and remaining <= 0:
                        raise GetTimeoutError(
                            f"get() timed out waiting for object {object_id.hex()}")
                    self._lock.wait(timeout=remaining if remaining is None else min(remaining, 1.0))
                entry.pin_count += 1
                entry.last_used = time.monotonic()
            torn = False
            try:
                value, error = self._materialize(entry)
            except _TornRestore:
                torn = True
            finally:
                with self._lock:
                    entry.pin_count -= 1
            if torn:
                # The entry was marked lost under the lock; hand the
                # loss to the runtime's recovery hook (lineage rebuild
                # or a sealed ObjectLostError) and wait for the reseal.
                if self._on_torn is not None:
                    try:
                        self._on_torn(object_id)
                    except Exception:  # noqa: BLE001
                        pass
                else:
                    # No recovery hook (standalone store): fail the
                    # waiters instead of blocking on a reseal that can
                    # never come.
                    from ray_tpu._private.object_ref import ObjectRef

                    self.put_error(object_id, ObjectLostError(
                        ObjectRef(object_id, _register=False),
                        f"object {object_id.hex()} spill file was torn "
                        f"and no lineage recovery is wired"))
                continue
            if error is not None:
                raise error
            return value

    def _materialize(self, entry: ObjectEntry):
        """Load a (possibly spilled) sealed entry. Called outside hot lock.

        Concurrent restores of the same object race benignly: each reader
        snapshots the path under the lock, and only the thread whose
        snapshot still matches performs the restore/unlink. Managed
        spill files additionally verify their length+CRC header; a
        torn file marks the entry LOST and raises _TornRestore (the
        getter fires lineage recovery and re-waits).
        """
        from ray_tpu._private.spill_manager import TornSpillError

        while True:
            with self._lock:
                path = entry.spilled_path
                managed = entry.managed_spill
            if path is None:
                return entry.value, entry.error
            if managed:
                try:
                    payload = self._spill.restore(
                        entry.object_id.binary(), path)
                except TornSpillError:
                    with self._lock:
                        if entry.spilled_path != path:
                            continue  # raced a reseal; re-check
                        entry.spilled_path = None
                        entry.managed_spill = False
                        entry.value = None
                        entry.sealed = False
                        entry.lost = True
                    raise _TornRestore() from None
                except OSError:
                    continue  # another reader restored it; re-check
                try:
                    value = pickle.loads(payload)
                except Exception as exc:  # noqa: BLE001 — poisoned pickle
                    # The CRC passed but the payload won't load (e.g. a
                    # class definition changed): same fallback as torn.
                    with self._lock:
                        if entry.spilled_path != path:
                            continue
                        entry.spilled_path = None
                        entry.managed_spill = False
                        entry.sealed = False
                        entry.lost = True
                    try:
                        os.unlink(path)
                    except OSError:
                        pass  # torn file: loss handled via _TornRestore
                    raise _TornRestore() from exc
            else:
                try:
                    with open(path, "rb") as f:
                        value = pickle.load(f)
                except FileNotFoundError:
                    continue  # another reader restored it; re-check
            with self._lock:
                if entry.spilled_path == path:
                    try:
                        os.unlink(path)
                    except OSError:
                        pass  # restore won; file unlink is tidy-up
                    entry.spilled_path = None
                    entry.managed_spill = False
                    entry.value = value
                    self._memory_used += entry.size_bytes
                    self._restored_bytes_total += entry.size_bytes
            self._maybe_spill()
            # Return OUR loaded copy, not entry.value: a concurrent
            # reader may have restored and the async spiller re-spilled
            # (entry.value None again) between our read and the lock —
            # the bytes we verified are the object either way.
            return value, entry.error

    def mark_lost(self, object_id: ObjectID) -> bool:
        """Transition a sealed object back to pending because its node
        died (reference: plasma objects vanish with the raylet; the owner
        notices via the object directory). Returns True if it was sealed.
        """
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is None or not entry.sealed or entry.freed:
                return False
            if entry.pin_count > 0:
                # A get() is reading the value right now (same rule as
                # spilling): the driver-held copy survives the node.
                return False
            if entry.spilled_path is not None:
                self._unlink_spill(entry)
            else:
                self._memory_used -= entry.size_bytes
            entry.value = None
            entry.error = None
            entry.sealed = False
            entry.lost = True
            return True

    def is_lost(self, object_id: ObjectID) -> bool:
        with self._lock:
            entry = self._entries.get(object_id)
            return entry is not None and entry.lost and not entry.sealed

    def contains(self, object_id: ObjectID) -> bool:
        with self._lock:
            entry = self._entries.get(object_id)
            return entry is not None and entry.sealed and not entry.freed

    def sealed_ns(self, object_id: ObjectID) -> int:
        """The entry's seal stamp (0: unknown, or sealed while no
        trace sink was live)."""
        entry = self._entries.get(object_id)
        return entry.sealed_ns if entry is not None else 0

    def is_pending(self, object_id: ObjectID) -> bool:
        with self._lock:
            entry = self._entries.get(object_id)
            return entry is not None and not entry.sealed

    def wait(
        self,
        object_ids: list[ObjectID],
        num_returns: int,
        timeout: float | None,
    ) -> tuple[list[ObjectID], list[ObjectID]]:
        """Reference: CoreWorker::Wait (src/ray/core_worker/core_worker.cc:1627)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            while True:
                ready = [
                    oid for oid in object_ids
                    if (e := self._entries.get(oid)) is not None and e.sealed and not e.freed
                ]
                if len(ready) >= num_returns:
                    ready_set = set(ready[:num_returns])
                    # preserve input order
                    ready_ordered = [o for o in object_ids if o in ready_set]
                    not_ready = [o for o in object_ids if o not in ready_set]
                    return ready_ordered, not_ready
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    ready_set = set(ready)
                    return ([o for o in object_ids if o in ready_set],
                            [o for o in object_ids if o not in ready_set])
                self._lock.wait(timeout=remaining if remaining is None else min(remaining, 1.0))

    # ----------------------------------------------------------------- free

    def free(self, object_ids: list[ObjectID]) -> None:
        with self._lock:
            for oid in object_ids:
                entry = self._entries.get(oid)
                if entry is None:
                    continue
                if entry.sealed and entry.spilled_path is None:
                    self._memory_used -= entry.size_bytes
                if entry.spilled_path is not None:
                    self._unlink_spill(entry)
                entry.value = None
                entry.error = None
                entry.freed = True
                entry.sealed = True
                entry.spilled_path = None
                self._unspillable.discard(oid)
            self._lock.notify_all()

    def evict(self, object_id: ObjectID) -> None:
        """Drop an object entirely (refcount reached zero)."""
        with self._lock:
            entry = self._entries.pop(object_id, None)
            self._unspillable.discard(object_id)
            if entry is not None and entry.sealed and not entry.freed \
                    and entry.spilled_path is None:
                self._memory_used -= entry.size_bytes
            if entry is not None and entry.spilled_path is not None:
                self._unlink_spill(entry)

    # ----------------------------------------------------------------- spill

    def _maybe_spill(self) -> None:
        """Spill least-recently-created unpinned objects above the budget.

        Reference: LocalObjectManager::SpillObjects
        (src/ray/raylet/local_object_manager.h:110). With the managed
        tier armed, the async spiller replaces this inline pass — one
        watermark comparison here, the victim work happens off the
        seal path.
        """
        if self._spill is not None:
            self._spill.notify()
            return
        to_spill: list[ObjectEntry] = []
        with self._lock:
            if self._memory_used <= self._memory_limit:
                return
            candidates = sorted(
                (e for e in self._entries.values()
                 if e.sealed and not e.freed and e.error is None
                 and e.spilled_path is None and e.pin_count == 0
                 and e.size_bytes > 4096),
                key=lambda e: e.created_at,
            )
            need = self._memory_used - int(self._memory_limit * 0.7)
            for entry in candidates:
                if need <= 0:
                    break
                to_spill.append(entry)
                need -= entry.size_bytes
        if not to_spill:
            return
        os.makedirs(self._spill_dir, exist_ok=True)
        for entry in to_spill:
            path = os.path.join(self._spill_dir, entry.object_id.hex())
            try:
                with open(path, "wb") as f:
                    pickle.dump(entry.value, f, protocol=pickle.HIGHEST_PROTOCOL)
            except Exception:
                continue  # unpicklable objects just stay in memory
            with self._lock:
                if entry.pin_count == 0 and entry.spilled_path is None and entry.sealed:
                    entry.spilled_path = path
                    entry.value = None
                    self._memory_used -= entry.size_bytes
                    self._spilled_bytes_total += entry.size_bytes
                else:
                    try:
                        os.unlink(path)
                    except OSError:
                        pass  # evicted copy's file already gone

    # ----------------------------------------------------------------- stats

    def snapshot(self) -> list[dict]:
        """Per-object state listing for the state API."""
        with self._lock:
            out = []
            for entry in self._entries.values():
                if entry.freed:
                    state = "FREED"
                elif entry.lost and not entry.sealed:
                    state = "LOST"
                elif entry.sealed and entry.error is not None:
                    state = "ERRORED"
                elif entry.sealed:
                    state = "SEALED"
                else:
                    state = "PENDING"
                holds_bytes = entry.sealed and not entry.freed
                out.append({
                    "object_id": entry.object_id.hex(),
                    "state": state,
                    "size_bytes": entry.size_bytes if holds_bytes else 0,
                    "spilled": entry.spilled_path is not None,
                })
            return out

    def stats(self) -> dict:
        with self._lock:
            return {
                "num_objects": len(self._entries),
                "num_sealed": sum(1 for e in self._entries.values() if e.sealed),
                "memory_used_bytes": self._memory_used,
                "memory_limit_bytes": self._memory_limit,
                "spilled_bytes_total": self._spilled_bytes_total,
                "restored_bytes_total": self._restored_bytes_total,
            }


class ReferenceCounter:
    """Ownership-based distributed reference counting (single-node slice).

    Reference: src/ray/core_worker/reference_count.h:61 — the owner tracks
    local refs plus borrower counts; here all refs are node-local so the
    count is the number of live ObjectRef handles plus task-argument pins.

    GC safety: ObjectRef.__del__ runs at ARBITRARY points — including
    while this thread already holds one of the runtime's locks — so the
    destructor path must be lock-free. ``defer_remove`` appends to a
    deque (GIL-atomic, no lock) and a reaper thread performs the actual
    remove_ref/evict work.
    """

    def __init__(self, store: ObjectStore):
        import collections

        self._lock = lock_witness.Lock("object_store.ReferenceCounter")
        self._counts: dict[ObjectID, int] = {}
        self._store = store
        # Optional hook fired after refcount-zero eviction (the runtime
        # drops its directory/lineage entries there).
        self.on_evict: Callable[[ObjectID], None] | None = None
        self._deferred: "collections.deque[ObjectID]" = collections.deque()
        self._reaper = threading.Thread(
            target=self._reap_loop, daemon=True, name="ray_tpu-ref-reaper")
        self._reaper.start()

    def defer_remove(self, object_id: ObjectID) -> None:
        """Destructor entry point: ONLY a deque append (GIL-atomic).
        Even Event.set() takes a lock and could deadlock a nested GC
        __del__ — the reaper polls instead of being signalled."""
        self._deferred.append(object_id)

    def _reap_loop(self) -> None:
        while True:
            try:
                object_id = self._deferred.popleft()
            except IndexError:
                time.sleep(0.02)
                continue
            try:
                self.remove_ref(object_id)
            except Exception:  # noqa: BLE001 — reaper must survive
                pass

    def add_ref(self, object_id: ObjectID) -> None:
        with self._lock:
            self._counts[object_id] = self._counts.get(object_id, 0) + 1

    def seed_ref(self, object_id: ObjectID) -> None:
        """Register the FIRST reference of a freshly minted id without
        the lock: no other thread can know this id yet, and a dict
        setitem is GIL-atomic — the per-call lock acquire was a
        measurable slice of the columnar submit hot path."""
        self._counts[object_id] = 1

    def remove_ref(self, object_id: ObjectID) -> None:
        evict = False
        with self._lock:
            count = self._counts.get(object_id)
            if count is None:
                return
            if count <= 1:
                del self._counts[object_id]
                evict = True
            else:
                self._counts[object_id] = count - 1
        if evict:
            self._store.evict(object_id)
            if self.on_evict is not None:
                self.on_evict(object_id)

    def count(self, object_id: ObjectID) -> int:
        with self._lock:
            return self._counts.get(object_id, 0)

