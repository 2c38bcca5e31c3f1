"""Node executor service — the cluster's distributed execution plane.

TPU-native analogue of the raylet's lease-and-dispatch loop plus the
object manager's node-to-node transfer:

- ``NodeExecutorService`` runs inside every worker-node daemon and
  serves ``execute_task`` over RPC (reference: the raylet grants a
  worker lease and the task is pushed to that node's worker pool —
  src/ray/raylet/node_manager.cc:1714 HandleRequestWorkerLease,
  local_task_manager.h:58). CPU tasks run on the node's own
  multiprocess worker pool; TPU tasks run in the daemon process (which
  owns the node's JAX/TPU runtime).
- ``NodeObjectStore`` holds serialized task results and pulled objects;
  peers and the driver read them with chunked ``fetch_object`` RPCs
  (reference: src/ray/object_manager/object_manager.h:106-130 —
  chunked Push/Pull between nodes).
- ``RemoteNodeHandle`` is the driver side: it leases the task to the
  node, ships the function once per node by digest (function-manager
  pattern), passes remote-located args as ``FetchRef`` location hints
  so the consuming node pulls them peer-to-peer — the driver never
  relays the bytes (reference: ownership_based_object_directory.h, the
  owner hands out locations, data flows node-to-node).

Results above the inline threshold stay on the producing node; the
driver's store holds a ``RemoteBlob`` placeholder that materializes by
chunked pull only when the value is actually read locally.
"""

from __future__ import annotations

import collections
import os
import threading

from ray_tpu._private import lock_witness
import time
from dataclasses import dataclass
from typing import Any, Callable

from ray_tpu._private import accelerators
from ray_tpu._private import perf_plane as perf
from ray_tpu._private import serialization
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.rpc import (
    MuxRpcClient,
    RpcClient,
    RpcError,
    RpcMethodError,
    RpcServer,
)

# Results at or below executor_inline_reply_kb (config) ship inline in
# the execute_task reply; larger ones stay in the producing node's
# store (driver pulls lazily in fetch_chunk_kb chunks).


def _inline_reply_bytes() -> int:
    from ray_tpu._private.config import GLOBAL_CONFIG

    return int(GLOBAL_CONFIG.executor_inline_reply_kb) * 1024


def _fetch_chunk_bytes() -> int:
    from ray_tpu._private.config import GLOBAL_CONFIG

    return int(GLOBAL_CONFIG.fetch_chunk_kb) * 1024


@dataclass
class FetchRef:
    """Arg placeholder: the value lives in a node's object store —
    resolve by local lookup or a chunked pull from ``addr``."""

    id_bytes: bytes
    addr: str


@dataclass
class RemoteBlob:
    """Driver-store placeholder for a result held on a remote node."""

    node_hex: str
    addr: str
    size: int


class NodeBusyError(Exception):
    """The node rejected the lease at admission (another driver's work
    saturates it); the submitter should spill to a different node."""


class TaskSpeculationCancelled(Exception):
    """The daemon refused the execution because its task token was
    cancelled (speculation first-seal-wins: a sibling copy already
    sealed the result) — nothing ran, nothing to seal."""


class NodeOverloadedError(Exception):
    """The node SHED the lease at admission (queue-depth cap, memory
    watermark, or the overload.saturate chaos site): distinct from
    plain busy — the driver fails deadline-armed tasks fast with
    SystemOverloadedError instead of spilling them into a backlog."""


class TaskDeadlineExpired(Exception):
    """Internal driver-side signal: the daemon found the task's
    end-to-end deadline already dead and refused to execute it."""


# Fused in-daemon execution (the fused_execution knob): runs of tiny
# DEFAULT tasks inside an execute_task_batch RPC execute directly on
# the daemon's dispatch thread — no worker-pipe hop — bounded by the
# fused_max_run_tasks / fused_run_wall_budget_s per-run budget.
# Disarmed cost is this one module-attribute branch per site (the
# chaos.ACTIVE / perf.PERF_ON discipline); daemons inherit
# RAY_TPU_FUSED_EXECUTION through the child env at import.
FUSED_ON: bool = True


def init_fused_from_config() -> None:
    """Arm/disarm fused in-daemon execution from config (Runtime init
    and daemon boot both reach this through import)."""
    global FUSED_ON
    from ray_tpu._private.config import GLOBAL_CONFIG

    FUSED_ON = bool(GLOBAL_CONFIG.fused_execution)


try:
    init_fused_from_config()
except Exception:  # noqa: BLE001 — config unavailable mid-bootstrap
    pass

# Canonical executor_stats() counter keys, exported so the README
# doc-drift check (tests/test_doc_drift.py) can assert every counter is
# documented without standing up a daemon.
PIPELINE_STAT_KEYS = ("batch_rpcs", "batch_tasks", "reply_groups",
                      "worker_lease_runs", "worker_lease_tasks",
                      "worker_pipelined_frames",
                      "fused_runs", "fused_tasks", "fused_fallbacks",
                      "runner_spawns", "runner_reuses")
DATA_PLANE_STAT_KEYS = ("same_host_map_hits", "same_host_copy_hits",
                        "chunked_pulls", "map_sources",
                        "attached_mappings", "leases")
FAULT_STAT_KEYS = ("rpc_retries", "batch_requeues", "peer_blacklists",
                   "lease_orphans_swept", "arena_orphans_swept",
                   "lineage_rebuilds", "task_timeouts",
                   "admission_shed", "breaker_open")
# Always-on performance-plane stage names (perf_plane.py): every hop a
# process can measure inside its own clock. Daemon stages ship on
# heartbeats; driver stages export straight from the local registry.
STAGE_HIST_KEYS = ("submit_dispatch", "dispatch_rpc", "rpc_seal",
                   "exec_local", "admit_worker", "exec")


def _proc_label() -> str:
    """This daemon's process-lane label in merged timelines."""
    tag = os.environ.get("RAY_TPU_NODE_TAG", "")
    return f"node:{tag[:8]}" if tag else f"node:pid{os.getpid()}"


class NodeObjectStore:
    """Serialized-blob store of a node daemon: task/actor results
    (primary copies, owner-tagged, spillable to disk past the cap) +
    pulled peer objects (evictable cache).

    Reference: the raylet's LocalObjectManager — primary copies live
    until the owner frees them or dies (local_object_manager.h:110
    SpillObjects / owner-death cleanup)."""

    def __init__(self, cache_limit_bytes: int | None = None,
                 primary_limit_bytes: int | None = None,
                 spill_dir: str | None = None):
        from ray_tpu._private.config import GLOBAL_CONFIG

        self._lock = lock_witness.Lock("node_executor.NodeObjectStore")
        self._blobs: dict[bytes, bytes] = {}  # insertion-ordered
        self._cached: dict[bytes, None] = {}  # pulled copies, FIFO evict
        self._cache_limit = (
            cache_limit_bytes if cache_limit_bytes is not None
            else int(GLOBAL_CONFIG.node_pull_cache_mb) * 1024 * 1024)
        self._cache_bytes = 0
        self._primary_limit = (
            primary_limit_bytes if primary_limit_bytes is not None
            else int(GLOBAL_CONFIG.node_store_primary_limit_mb) * 1024 * 1024)
        self._spill_dir = (spill_dir or GLOBAL_CONFIG.node_store_spill_dir)
        self._primary_bytes = 0
        # id -> (path, size): primaries moved to disk; restored on fetch.
        self._spilled: dict[bytes, tuple[str, int]] = {}
        # Managed spill tier (spill_manager.py, armed via
        # enable_managed_spill): watermark-driven async spilling with
        # checksummed session-dir files replaces the legacy inline
        # cap-based path. _managed_spills marks which _spilled entries
        # use the headered format.
        self._spill_mgr = None
        self._managed_spills: set[bytes] = set()
        self._spill_min_bytes = 0
        self._leased_fn = None
        self._on_spilled = None
        self._on_restored = None
        # Ownership: id -> owner key; owner -> ids (owner-death sweep).
        self._owner_of: dict[bytes, str] = {}
        self._owned_ids: dict[str, set[bytes]] = {}
        self.fetches_served = 0
        self.spills = 0
        self.restores = 0
        self._purge_stale_spills()

    def _purge_stale_spills(self) -> None:
        """Delete spill files left by crashed prior daemons (shared
        helper — pid-prefixed filenames, liveness-checked)."""
        from ray_tpu._private.node_store_native import purge_stale_spills

        purge_stale_spills(self._spill_dir)

    def enable_managed_spill(self, spill_dir: str | None = None,
                             leased_fn=None, on_spilled=None,
                             on_restored=None):
        """Arm the watermark-driven spill tier on this store: primaries
        above spill_high_watermark x the primary cap move to
        checksummed files asynchronously (legacy inline spilling is
        bypassed), freeing memory AND — via ``on_spilled`` — any
        shm/arena twin. ``leased_fn`` returns the id set currently
        pinned by same-host peers (never spilled); ``on_restored``
        fires after a transparent restore re-registers the copy in
        memory. Returns the SpillManager."""
        from ray_tpu._private.config import GLOBAL_CONFIG
        from ray_tpu._private.spill_manager import SpillManager

        self._leased_fn = leased_fn
        self._on_spilled = on_spilled
        self._on_restored = on_restored
        self._spill_min_bytes = \
            int(GLOBAL_CONFIG.spill_min_object_kb) * 1024
        self._spill_mgr = SpillManager(
            "node-store", self._primary_limit,
            usage_fn=lambda: self._primary_bytes,
            victims_fn=self._spill_victims,
            extract_fn=self._spill_extract,
            commit_fn=self._spill_commit,
            spill_dir=spill_dir)
        return self._spill_mgr

    def _spill_victims(self, need_bytes: int) -> list:
        """Spillable keys covering ``need_bytes``: PRIMARY copies only
        (pulled cache copies already evict), never ids leased to
        same-host peers, size floor applied — ordered size-descending
        (fewest files free the most bytes) with insertion (FIFO/LRU)
        age as the tiebreak."""
        leased: set = set()
        if self._leased_fn is not None:
            try:
                leased = set(self._leased_fn())
            except Exception:  # noqa: BLE001 — no filter beats no spill
                leased = set()
        with self._lock:
            cands = [(key, len(blob), age)
                     for age, (key, blob) in enumerate(self._blobs.items())
                     if key not in self._cached and key not in leased
                     and len(blob) >= self._spill_min_bytes]
        cands.sort(key=lambda c: (-c[1], c[2]))
        out, covered = [], 0
        for key, size, _age in cands:
            out.append(key)
            covered += size
            if covered >= need_bytes:
                break
        return out

    def _spill_extract(self, key: bytes):
        with self._lock:
            if key in self._cached:
                return None
            return self._blobs.get(key)

    def _spill_commit(self, key: bytes, path: str, size: int) -> bool:
        with self._lock:
            blob = self._blobs.get(key)
            if blob is None or key in self._cached or len(blob) != size:
                return False  # freed/resealed since extraction
            del self._blobs[key]
            self._primary_bytes -= size
            self._spilled[key] = (path, size)
            self._managed_spills.add(key)
            self.spills += 1
            owner = self._owner_of.get(key)
        if self._on_spilled is not None:
            self._on_spilled(key, owner)
        return True

    def _restore_managed(self, key: bytes) -> bytes | None:
        """Transparent restore of a managed spilled primary: verify the
        checksummed file, re-insert the blob as the in-memory primary
        (the node is a full holder again — ``on_restored`` clears the
        directory's spill mark), delete the file. Concurrent restores
        race benignly on the path snapshot; a torn file drops the
        entry entirely (the caller sees absence and the owner falls
        back to lineage reconstruction)."""
        from ray_tpu._private.spill_manager import TornSpillError

        mgr = self._spill_mgr
        while True:
            with self._lock:
                blob = self._blobs.get(key)
                if blob is not None:
                    return blob
                entry = self._spilled.get(key)
                if entry is None:
                    return None  # freed (or torn-dropped) meanwhile
                path, size = entry
            try:
                payload = bytes(mgr.restore(key, path))
            except TornSpillError:
                with self._lock:
                    if self._spilled.get(key) == (path, size):
                        # The disk copy is garbage and the memory copy
                        # is long gone: the object is LOST here. Drop
                        # it entirely so fetchers see absence and the
                        # owner reconstructs from lineage.
                        self._forget_locked(key)
                return None
            except OSError:
                continue  # another reader restored + unlinked; re-check
            with self._lock:
                if self._spilled.get(key) != (path, size):
                    if key in self._blobs:
                        # Another reader restored it first: our
                        # verified payload is the same bytes.
                        return self._blobs[key]
                    continue  # raced a free; re-check
                del self._spilled[key]
                self._managed_spills.discard(key)
                self._blobs[key] = payload
                self._primary_bytes += size
                self.restores += 1
                owner = self._owner_of.get(key)
            try:
                os.unlink(path)
            except OSError:
                pass  # restored copy is safe; file is tidy-up
            if self._on_restored is not None:
                self._on_restored(key, owner)
            # The restore may have pushed usage back over the HIGH
            # watermark: let the spiller pick a different victim.
            mgr.notify()
            return payload

    def put(self, id_bytes: bytes, blob: bytes, cached: bool = False,
            owner: str | None = None) -> None:
        spill_victims: list[tuple[bytes, bytes]] = []
        with self._lock:
            old = self._blobs.get(id_bytes)
            if old is not None and id_bytes in self._cached:
                self._cache_bytes -= len(old)
                del self._cached[id_bytes]
            elif old is not None:
                self._primary_bytes -= len(old)
            self._drop_spilled(id_bytes)
            self._blobs[id_bytes] = blob
            if owner is not None and not cached:
                self._owner_of[id_bytes] = owner
                self._owned_ids.setdefault(owner, set()).add(id_bytes)
            if cached:
                self._cached[id_bytes] = None
                self._cache_bytes += len(blob)
                while self._cache_bytes > self._cache_limit and self._cached:
                    victim = next(iter(self._cached))
                    del self._cached[victim]
                    dropped = self._blobs.pop(victim, None)
                    if dropped is not None:
                        self._cache_bytes -= len(dropped)
            else:
                self._primary_bytes += len(blob)
                if self._spill_mgr is None:
                    # Legacy inline path (spill_enabled=0): over the
                    # cap, spill the OLDEST primaries to disk (the
                    # newest blob is the one most likely to be fetched
                    # next). Victims are only SELECTED here — they stay
                    # readable in _blobs until the disk write lands
                    # (_spill_one), so a concurrent fetch/free never
                    # sees the object in neither map.
                    projected = self._primary_bytes
                    for victim in list(self._blobs):
                        if projected <= self._primary_limit:
                            break
                        if victim in self._cached or victim == id_bytes:
                            continue
                        vblob = self._blobs[victim]
                        projected -= len(vblob)
                        spill_victims.append((victim, vblob))
        for victim, vblob in spill_victims:
            self._spill_one(victim, vblob)
        if self._spill_mgr is not None and not cached:
            # Managed tier: one usage-vs-watermark comparison; the
            # async spiller does the victim work off the put path.
            self._spill_mgr.notify()

    def _spill_one(self, id_bytes: bytes, blob: bytes) -> None:
        os.makedirs(self._spill_dir, exist_ok=True)
        # Unique per attempt: two concurrent put()s may both pick this
        # victim; each must own its file so the loser's cleanup cannot
        # unlink the winner's registered copy.
        path = os.path.join(
            self._spill_dir,
            f"{os.getpid()}-{id_bytes.hex()}-{os.urandom(4).hex()}.blob")
        try:
            with open(path, "wb") as f:
                f.write(blob)
        except OSError:
            return  # disk full/unwritable: blob simply stays in memory
        with self._lock:
            # The blob stayed visible during the write; only now swap it
            # to the disk copy — unless a concurrent free() removed it
            # or a reseal replaced it, in which case the file is stale.
            if self._blobs.get(id_bytes) is not blob:
                stale = True
            else:
                del self._blobs[id_bytes]
                self._primary_bytes -= len(blob)
                self._spilled[id_bytes] = (path, len(blob))
                self.spills += 1
                stale = False
        if stale:
            try:
                os.unlink(path)
            except OSError:
                pass  # stale spill file already swept

    def _drop_spilled(self, id_bytes: bytes) -> None:
        # Caller holds self._lock.
        entry = self._spilled.pop(id_bytes, None)
        managed = id_bytes in self._managed_spills
        self._managed_spills.discard(id_bytes)
        if entry is not None:
            if managed and self._spill_mgr is not None:
                # free/owner-death pruning of a managed spill file —
                # counted (files_deleted) + flight-recorded.
                self._spill_mgr.delete_file(entry[0])
                return
            try:
                os.unlink(entry[0])
            except OSError:
                pass  # spill file already gone

    def get(self, id_bytes: bytes) -> bytes | None:
        with self._lock:
            blob = self._blobs.get(id_bytes)
            spilled = self._spilled.get(id_bytes)
            managed = id_bytes in self._managed_spills
        if blob is not None:
            return blob
        if spilled is not None:
            if managed:
                # Checksum-verified restore that re-registers the blob
                # as the in-memory primary (None on a torn file — the
                # object is lost here, lineage rebuilds it).
                return self._restore_managed(id_bytes)
            try:
                with open(spilled[0], "rb") as f:
                    data = f.read()
            except OSError:
                return None
            with self._lock:
                self.restores += 1
            return data
        return None

    def _forget_locked(self, id_bytes: bytes) -> bool:
        # _locked suffix: caller holds self._lock (the lock-discipline
        # pass verifies the convention). Returns True if the id existed.
        existed = False
        blob = self._blobs.pop(id_bytes, None)
        if blob is not None:
            existed = True
            if id_bytes in self._cached:
                del self._cached[id_bytes]
                self._cache_bytes -= len(blob)
            else:
                self._primary_bytes -= len(blob)
        if id_bytes in self._spilled:
            existed = True
            self._drop_spilled(id_bytes)
        owner = self._owner_of.pop(id_bytes, None)
        if owner is not None:
            ids = self._owned_ids.get(owner)
            if ids is not None:
                ids.discard(id_bytes)
                if not ids:
                    del self._owned_ids[owner]
        return existed

    def free(self, ids: list[bytes]) -> int:
        with self._lock:
            return sum(1 for id_bytes in ids if self._forget_locked(id_bytes))

    def free_owner(self, owner: str) -> int:
        """Owner-death sweep: drop every primary the owner left here."""
        with self._lock:
            ids = list(self._owned_ids.get(owner, ()))
            return sum(1 for id_bytes in ids if self._forget_locked(id_bytes))

    def owners(self) -> list[str]:
        with self._lock:
            return list(self._owned_ids)

    def size(self, id_bytes: bytes) -> int | None:
        """Byte size of a stored blob without copying it (and without
        counting as a served fetch — used by transfer-plan probes)."""
        with self._lock:
            blob = self._blobs.get(id_bytes)
            if blob is not None:
                return len(blob)
            spilled = self._spilled.get(id_bytes)
            if spilled is not None:
                return spilled[1]
        return None

    def is_spilled(self, id_bytes: bytes) -> bool:
        """True while the only local copy lives on disk (fetch plans
        advertise it so pullers know a restore precedes the bytes)."""
        with self._lock:
            return (id_bytes in self._spilled
                    and id_bytes not in self._blobs)

    def read_chunk(self, id_bytes: bytes, offset: int,
                   length: int) -> tuple[int, bytes] | None:
        with self._lock:
            blob = self._blobs.get(id_bytes)
            spilled = self._spilled.get(id_bytes)
            managed = id_bytes in self._managed_spills
            if blob is not None:
                self.fetches_served += 1
                return len(blob), blob[offset:offset + length]
        if spilled is not None and managed:
            # Managed tier: restore the WHOLE object once (checksum
            # verification needs the full payload; the restore
            # re-registers this node as an in-memory holder) and serve
            # every chunk from memory — torn files surface as absence,
            # never as silently corrupt chunks.
            blob = self._restore_managed(id_bytes)
            if blob is None:
                return None
            with self._lock:
                self.fetches_served += 1
            return len(blob), blob[offset:offset + length]
        if spilled is None:
            return None
        # Spilled primary: stream the chunk straight from disk (restore
        # on fetch — reference: spilled_object_reader.h).
        path, size = spilled
        try:
            with open(path, "rb") as f:
                f.seek(offset)
                chunk = f.read(length)
        except OSError:
            return None
        with self._lock:
            self.fetches_served += 1
            self.restores += 1
        return size, chunk

    def stats(self) -> dict:
        with self._lock:
            return {
                "num_blobs": len(self._blobs),
                "bytes": sum(len(b) for b in self._blobs.values()),
                "fetches_served": self.fetches_served,
                "spilled_blobs": len(self._spilled),
                "spilled_bytes": sum(s for _, s in self._spilled.values()),
                "spills": self.spills,
                "restores": self.restores,
                "owners": len(self._owned_ids),
            }


class _PeerClients:
    """One multiplexed RPC client per peer address (daemon-side pulls:
    concurrent chunk fetches interleave on a single socket per pair)."""

    def __init__(self):
        self._lock = lock_witness.Lock("node_executor._PeerClients")
        self._clients: dict[str, MuxRpcClient] = {}

    def get(self, addr: str) -> MuxRpcClient:
        with self._lock:
            client = self._clients.get(addr)
            if client is None:
                client = MuxRpcClient(addr, timeout_s=600.0)
                self._clients[addr] = client
            return client

    def close(self) -> None:
        with self._lock:
            for client in self._clients.values():
                client.close()
            self._clients.clear()


def _pipeline_depth() -> int:
    from ray_tpu._private.config import GLOBAL_CONFIG

    return max(1, int(GLOBAL_CONFIG.rpc_pipeline_depth))


def fetch_blob(client: RpcClient, id_bytes: bytes) -> bytes:
    """Chunked pull of one object (reference: object_manager.h chunked
    Push — here pull-oriented, sized by fetch_chunk_kb). On a pipelined
    client (MuxRpcClient) up to rpc_pipeline_depth chunk requests ride
    the socket concurrently, so throughput is not bounded by one
    round-trip per chunk."""
    from collections import deque

    chunk_bytes = _fetch_chunk_bytes()
    first = client.call("fetch_object", id_bytes, 0, chunk_bytes)
    if first is None:
        raise KeyError(
            f"object {id_bytes.hex()} not present on {client.address}")
    total, chunk = first
    if len(chunk) >= total:
        return bytes(chunk)
    buf = bytearray(total)
    buf[:len(chunk)] = chunk
    offset = len(chunk)
    call_async = getattr(client, "call_async", None)
    if call_async is None:
        while offset < total:
            reply = client.call("fetch_object", id_bytes, offset,
                                chunk_bytes)
            if reply is None:
                raise KeyError(
                    f"object {id_bytes.hex()} vanished from "
                    f"{client.address}")
            _, chunk = reply
            buf[offset:offset + len(chunk)] = chunk
            offset += len(chunk)
        return bytes(buf)
    pending: deque = deque()
    depth = _pipeline_depth()
    next_off = offset
    while next_off < total or pending:
        while next_off < total and len(pending) < depth:
            pending.append((next_off, call_async(
                "fetch_object", id_bytes, next_off, chunk_bytes)))
            next_off += chunk_bytes
        off, slot = pending.popleft()
        reply = slot.result()
        if reply is None:
            raise KeyError(
                f"object {id_bytes.hex()} vanished from {client.address}")
        _, chunk = reply
        buf[off:off + len(chunk)] = chunk
    return bytes(buf)


class ChunkDirectory:
    """Owner-side holder registry for one node's (or the driver export
    server's) objects: every puller that starts fetching an object
    registers here and is handed the current holder set, so later
    pullers spread their chunk fetches across peers instead of queueing
    on the owner (reference: ownership_based_object_directory.h — the
    owner hands out locations, data flows node-to-node)."""

    TTL_S = 180.0

    def __init__(self):
        self._lock = lock_witness.Lock("node_executor.ChunkDirectory")
        # id -> {holder addr -> registered-at monotonic}
        self._holders: dict[bytes, dict[str, float]] = {}

    def register(self, id_bytes: bytes, addr: str | None) -> list[str]:
        """Record ``addr`` as a (partial) holder; return the OTHER
        currently-known holders, oldest first (oldest have the most
        chunks)."""
        import time

        now = time.monotonic()
        with self._lock:
            table = self._holders.setdefault(id_bytes, {})
            for holder, seen in list(table.items()):
                if now - seen > self.TTL_S:
                    del table[holder]
            others = [a for a in table if a != addr]
            if addr:
                table.setdefault(addr, now)
            return others

    def drop(self, ids: list[bytes]) -> None:
        with self._lock:
            for id_bytes in ids:
                self._holders.pop(id_bytes, None)

    def prune(self) -> None:
        import time

        now = time.monotonic()
        with self._lock:
            for id_bytes in list(self._holders):
                table = self._holders[id_bytes]
                for holder, seen in list(table.items()):
                    if now - seen > self.TTL_S:
                        del table[holder]
                if not table:
                    del self._holders[id_bytes]


def wrap_chunk_reply(reply):
    """Bulk chunk replies ship as raw tail bytes (TailPayload): the
    payload crosses the RPC layer without a pickle memcpy on either
    side. Small replies keep the plain tuple shape."""
    from ray_tpu._private.rpc import TailPayload

    total, chunk = reply
    if len(chunk) >= (1 << 16):
        return TailPayload(total, chunk)
    return (total, bytes(chunk) if isinstance(chunk, memoryview)
            else chunk)


def plan_holders(directory: ChunkDirectory, id_bytes: bytes,
                 puller_addr: str | None, total: int) -> list[str]:
    """Directory half of a fetch_plan reply: register the puller and
    return the other holders — but only for objects large enough that
    pullers actually take the P2P path; registering sub-threshold
    pullers would advertise peers that never hold servable chunks."""
    from ray_tpu._private.config import GLOBAL_CONFIG

    chunk = _fetch_chunk_bytes()
    n_chunks = -(-total // chunk) if total else 0
    if n_chunks < int(GLOBAL_CONFIG.broadcast_min_p2p_chunks):
        return []
    return directory.register(id_bytes, puller_addr)


class _PartialBlob:
    """An in-progress (or recently finished) pull whose present chunks
    are servable to peers — the relay half of the broadcast tree: a
    receiver starts re-serving chunks the moment it has them, so 1->N
    broadcast throughput scales with the receivers, not the owner's
    socket (Podracer-style weight broadcast; reference: the object
    manager's chunked transfers + directory)."""

    __slots__ = ("total", "chunk", "buf", "have", "lock", "done",
                 "error", "completed_at", "served", "external")

    def __init__(self, total: int, chunk: int, buf=None):
        self.total = total
        self.chunk = chunk
        # ``buf`` may be an external writable buffer (a shared-memory
        # mapping): chunks then land directly where the consuming
        # worker will map them — zero intermediate full-object copies.
        self.external = buf is not None
        self.buf = buf if buf is not None else bytearray(total)
        self.have: set[int] = set()
        self.lock = lock_witness.Lock("node_executor._PartialBlob")
        self.done = threading.Event()
        self.error: BaseException | None = None
        self.completed_at: float | None = None
        self.served = 0  # chunks relayed to peers from this partial

    def n_chunks(self) -> int:
        return -(-self.total // self.chunk) if self.total else 0

    def write(self, index: int, data) -> None:
        off = index * self.chunk
        with self.lock:
            self.buf[off:off + len(data)] = data
            self.have.add(index)

    def read_chunk(self, offset: int, length: int):
        """Serve a range iff every covered chunk is present; None
        otherwise (the puller falls back to another holder)."""
        if offset >= self.total:
            return (self.total, b"")
        end = min(offset + length, self.total)
        first = offset // self.chunk
        last = (end - 1) // self.chunk if end > offset else first
        with self.lock:
            if any(i not in self.have for i in range(first, last + 1)):
                return None
            try:
                data = bytes(self.buf[offset:end])
            except ValueError:
                return None  # buffer released by concurrent eviction
            self.served += 1
            return (self.total, data)

    def finish(self) -> bytes | None:
        """Mark complete; returns the assembled bytes for internal
        buffers (external/shm buffers ARE the final resting place — no
        copy is made and None is returned)."""
        import time

        blob = None
        if not self.external:
            with self.lock:
                blob = bytes(self.buf)
        self.completed_at = time.monotonic()
        self.done.set()
        return blob

    def fail(self, exc: BaseException) -> None:
        self.error = exc
        self.done.set()


class _PipelineInflight:
    """Per-lease token ordering for the pipelined execute path, used
    for blocked-head parking: when the task at a lease's pipe head
    blocks in a nested get(), the frames queued behind it are sent but
    NOT running — their CPU reservations must be returned (daemon
    ledger via task_block, driver ledger via a streamed "parked"
    notification) or a nested child needing that capacity deadlocks
    against tasks that cannot start until the head resumes."""

    def __init__(self, service: "NodeExecutorService"):
        self._service = service
        self._lock = lock_witness.Lock("node_executor._PipelineInflight")
        self._leases: dict = {}        # lease key -> [token, ...]
        self._token_lease: dict = {}   # token -> lease key
        self._parked: set = set()
        # notify(kind, tokens): stream a parked/resumed control part to
        # the owning driver; installed per batch by the handler.
        self._notify: dict = {}        # token -> notify callable

    def register_notify(self, tokens, notify) -> None:
        with self._lock:
            for token in tokens:
                self._notify[token] = notify

    def forget_notify(self, tokens) -> None:
        with self._lock:
            for token in tokens:
                self._notify.pop(token, None)

    def sent(self, key, token) -> None:
        with self._lock:
            self._leases.setdefault(key, []).append(token)
            self._token_lease[token] = key
        # Stream a "started" mark to the owning driver: the frame is in
        # a worker's pipe, so from here on the task is MAYBE-STARTED —
        # if this daemon dies, the driver retries it under the
        # system-failure budget instead of requeueing it invisibly.
        self._fire(token, "started")

    def done(self, key, token) -> None:
        resumed = None
        with self._lock:
            order = self._leases.get(key)
            if order is None:
                return
            try:
                order.remove(token)
            except ValueError:
                pass
            self._token_lease.pop(token, None)
            self._parked.discard(token)
            if not order:
                self._leases.pop(key, None)
            elif order[0] in self._parked:
                # The next frame starts executing the moment this
                # reply was written: it is no longer parked.
                resumed = order[0]
                self._parked.discard(resumed)
        if resumed is not None:
            self._service.task_unblock(resumed)
            self._fire(resumed, "resumed")

    def drop_lease(self, key) -> None:
        """Lease died (worker crash): unpark everything it held —
        unstarted frames are requeued and re-tracked on a new lease."""
        with self._lock:
            order = self._leases.pop(key, [])
            parked = [t for t in order if t in self._parked]
            for token in order:
                self._token_lease.pop(token, None)
                self._parked.discard(token)
        for token in parked:
            self._service.task_unblock(token)
            self._fire(token, "resumed")

    def on_block(self, token) -> None:
        """A running task blocked in a nested get(): park every frame
        queued behind it on its lease."""
        with self._lock:
            key = self._token_lease.get(token)
            order = self._leases.get(key) if key is not None else None
            if not order or order[0] != token:
                return
            parked = [t for t in order[1:] if t not in self._parked]
            self._parked.update(parked)
        for queued in parked:
            self._service.task_block(queued)
            self._fire(queued, "parked")

    def _fire(self, token, kind: str) -> None:
        with self._lock:
            notify = self._notify.get(token)
        if notify is not None:
            try:
                notify(kind, token)
            except Exception:  # noqa: BLE001 — stream gone
                pass


class _ActorNewError(Exception):
    """Daemon-actor constructor failed; carries the serialized
    (exception, traceback) blob from the worker."""

    def __init__(self, blob: bytes):
        super().__init__("actor constructor failed")
        self.blob = blob


class _MuxPipe:
    """Multiplexed driver for an actor worker pipe in concurrent mode
    (max_concurrency > 1): calls are tagged with ids, a reader thread
    matches interleaved replies, and up to max_concurrency calls run
    worker-side simultaneously (reference: actor concurrency groups,
    transport/concurrency_group_manager.h)."""

    def __init__(self, conn):
        import queue as queue_mod

        self._queue_mod = queue_mod
        self._conn = conn
        self._send_lock = lock_witness.Lock("node_executor._MuxPipe.send")
        self._lock = lock_witness.Lock("node_executor._MuxPipe.state")
        self._pending: dict[int, Any] = {}
        self._next_id = 0
        self._closed = False
        threading.Thread(target=self._reader, daemon=True,
                         name="daemon-actor-mux-reader").start()

    def call(self, method: str, args_blob: bytes,
             n_returns: int) -> tuple:
        from ray_tpu.exceptions import WorkerCrashedError

        slot = self._queue_mod.SimpleQueue()
        with self._lock:
            if self._closed:
                raise WorkerCrashedError("actor process died")
            self._next_id += 1
            call_id = self._next_id
            self._pending[call_id] = slot
        try:
            with self._send_lock:
                self._conn.send(("actor_call_async", call_id, method,
                                 args_blob, n_returns))
        except (OSError, BrokenPipeError) as exc:
            with self._lock:
                self._pending.pop(call_id, None)
            raise WorkerCrashedError(
                f"actor pipe broken: {exc!r}") from exc
        result = slot.get()
        if result is None:
            raise WorkerCrashedError(
                "actor process died with the call in flight")
        return result

    def _reader(self) -> None:
        while True:
            try:
                msg = self._conn.recv()
            except (EOFError, OSError):
                break
            if msg[0] != "reply":
                continue
            _, call_id, status, payload = msg
            with self._lock:
                slot = self._pending.pop(call_id, None)
            if slot is not None:
                slot.put((status, payload))
        with self._lock:
            self._closed = True
            stranded = list(self._pending.values())
            self._pending.clear()
        for slot in stranded:
            slot.put(None)


class _DaemonActor:
    """A daemon-hosted actor: a dedicated worker process driven over
    its pipe (reference: a Ray actor IS a worker process with an
    ordered scheduling queue — core_worker.cc:2069 CreateActor lands
    the constructor in a leased worker; transport/actor_scheduling_
    queue.h orders the calls)."""

    def __init__(self, cls_blob: bytes, args_blob: bytes,
                 runtime_env: dict | None, max_concurrency: int,
                 extra_env: dict | None,
                 tpu_chips: "list[int] | None",
                 sys_path: list | None, worker=None):
        from ray_tpu._private.worker_pool import PoolWorker

        self.max_concurrency = max(1, int(max_concurrency or 1))
        self.owner: str | None = None  # creating driver's client addr
        # ``worker``: a prestarted standby process (reference:
        # worker_pool.h "Starts a number of workers ahead of time") —
        # creation then skips the fork on the critical path.
        self._worker = worker if worker is not None else PoolWorker(
            -1, extra_env=extra_env, tpu_chips=tpu_chips)
        self._mux = None
        reply = self._worker.request(
            ("actor_new", cls_blob, args_blob, runtime_env,
             self.max_concurrency, sys_path))
        if reply[0] == "err":
            self._worker.stop()
            raise _ActorNewError(reply[1])
        if self.max_concurrency > 1:
            self._mux = _MuxPipe(self._worker.conn)

    @property
    def pid(self) -> int:
        return self._worker.proc.pid

    def alive(self) -> bool:
        return self._worker.alive()

    def call(self, method: str, args_blob: bytes, n_returns: int) -> tuple:
        """-> ("ok", packed_list) | ("err", blob); raises
        WorkerCrashedError/_WorkerUnavailable on process death."""
        if self._mux is not None:
            return self._mux.call(method, args_blob, n_returns)
        return self._worker.request(
            ("actor_call", method, args_blob, n_returns))

    def kill(self) -> None:
        try:
            if self._worker.alive():
                self._worker.proc.terminate()
            # Always wait: an already-dead child must be reaped or it
            # stays a zombie for the daemon's lifetime.
            self._worker.proc.wait(timeout=2.0)
        except Exception:  # noqa: BLE001 — escalate
            self._worker.proc.kill()
            try:
                self._worker.proc.wait(timeout=2.0)
            except Exception:  # noqa: BLE001
                pass
        try:
            self._worker.conn.close()
        except OSError:
            pass  # worker pipe already torn down


class NodeExecutorService:
    """The daemon-side execution plane: worker pool + object store +
    the RPC surface (execute_task / actor plane / fetch_object /
    free_objects)."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0,
                 pool_size: int | None = None,
                 resources: dict[str, float] | None = None):
        from ray_tpu._private.shm_store import ShmClient, ShmDirectory

        from ray_tpu._private.node_store_native import make_node_store

        self._server = RpcServer(host, port)
        # C++ store by default (reference: the raylet's object store is
        # native); Python fallback keeps identical semantics.
        self.store = make_node_store()
        self._peers = _PeerClients()
        # Watermark-driven spill tier (spill_manager.py): armed on the
        # Python store only (the managed tier needs the lease filter +
        # shm-twin/directory integration below; disarmed keeps the
        # legacy native/inline behavior byte-identically).
        from ray_tpu._private import spill_manager as _spill_mod

        self._spill_mgr = None
        # (owner, obj_hex, "spilled"|"restored") deltas pending the
        # next heartbeat's stats piggyback into the GCS directory.
        self._spill_events: list = []
        self._spill_events_lock = lock_witness.Lock(
            "node_executor.NodeExecutorService.spill_events")
        self.spilled_plan_hits = 0  # pulls whose plan flagged a spill
        if _spill_mod.SPILL_ON and isinstance(self.store,
                                              NodeObjectStore):
            self._spill_mgr = self.store.enable_managed_spill(
                leased_fn=self._spill_protected,
                on_spilled=self._on_blob_spilled,
                on_restored=self._on_blob_restored)
            # Admission's two-axis pressure classifier subtracts THIS
            # store's resident (spillable) bytes from host usage.
            from ray_tpu._private.memory_monitor import (
                set_store_bytes_provider,
            )

            set_store_bytes_provider(
                lambda: getattr(self.store, "_primary_bytes", 0))
        # P2P transfer plane: in-progress/relay pulls servable to peers
        # + the holder directory for objects THIS node owns.
        self._partials: dict[bytes, _PartialBlob] = {}
        self._partials_lock = lock_witness.Lock(
            "node_executor.NodeExecutorService.partials")
        self.chunk_directory = ChunkDirectory()
        self._advertised_address: str | None = None
        self.relay_chunks_served = 0  # cumulative, survives partial GC
        # Same-host zero-copy plane (same_host.py): co-hosted pullers
        # map this daemon's segments/arena instead of chunk-pulling.
        from ray_tpu._private.same_host import (
            LeaseTable,
            PeerArenaRegistry,
            host_identity,
        )

        self.host_id = host_identity()
        self.leases = LeaseTable()            # owner side: peers' pins
        self._peer_arenas = PeerArenaRegistry()  # puller side
        # key -> ("seg", seg_name, size): objects this daemon can serve
        # to same-host peers by name (owned segments only).
        self._map_sources: dict[bytes, tuple] = {}
        # Puller side: key -> (owner_addr, lease_token, seg|None) for
        # peer-owned mappings held by this daemon's shm-args cache.
        self._attached: dict[bytes, tuple] = {}
        # Data-plane path counters (map = zero-copy mapping handed out,
        # copy = single same-host memcpy, chunked = RPC chunk pull).
        self.same_host_map_hits = 0
        self.same_host_copy_hits = 0
        self.chunked_pulls = 0
        # Fault-path counters (executor_stats()["faults"]): peers/owners
        # blacklisted mid-pull and peer-owned mappings swept after their
        # owner died. fail-strike ledger for the attached-mapping sweep
        # (one transient probe miss must not drop a live owner's
        # mappings).
        self.peer_blacklists = 0
        self.lease_orphans_swept = 0
        self.arena_orphans_swept = 0
        # Overload-control counters: tasks refused because their
        # end-to-end deadline was already dead on arrival (daemon
        # admission or worker-frame pickup) and leases shed by the
        # queue-depth/memory-watermark admission caps.
        self.task_timeouts = 0
        self.admission_shed = 0
        self._attached_owner_strikes: dict[str, int] = {}
        # Worker-bound arg blobs promoted to shared memory: keyed by the
        # object's id bytes in the node's shm directory; FIFO-bounded.
        self._shm_args_lock = lock_witness.Lock(
            "node_executor.NodeExecutorService.shm_args")
        self._shm_args_order: list[tuple[bytes, int]] = []
        self._shm_args_bytes = 0
        # key -> monotonic stamp of the last worker-bound _ShmRef
        # hand-out: the spiller must not unlink a segment a dispatched
        # frame is about to attach (attach-after-unlink fails even
        # though existing mappings survive), so recently-out keys are
        # spill-protected for _SHM_ARG_GRACE_S.
        self._shm_out_stamp: dict[bytes, float] = {}
        self._resources = dict(resources or {})
        # One process per chip: this daemon's own threads (in-daemon
        # TPU tasks) or its actor children, never both.
        self._chip_leases = accelerators.ChipLeases(
            int(self._resources.get("TPU", 0)))
        self._running_lock = lock_witness.Lock(
            "node_executor.NodeExecutorService.running")
        self._running: dict[str, dict[str, float]] = {}
        # token -> CPU share temporarily returned by a blocked task.
        self._blocked_cpu: dict[str, float] = {}
        self._func_cache: dict[str, Callable] = {}
        self._func_lock = lock_witness.Lock(
            "node_executor.NodeExecutorService.func")
        # Raw function blobs by digest: the batch path forwards these
        # to pool workers verbatim (the daemon never loads them).
        self._func_blob_cache: dict[str, bytes] = {}
        # need_func retries fetch their stashed args by nonce (bounded).
        self._stashed_args: dict[str, bytes] = {}
        # Pipelined execute path: per-lease frame ordering for
        # blocked-head parking + the per-stage drain counters.
        self._pipeline_inflight = _PipelineInflight(self)
        self.batch_rpcs = 0          # execute_task_batch calls served
        self.batch_tasks_received = 0
        self.reply_groups = 0        # grouped completion parts emitted
        # Fused in-daemon execution counters (FUSED_ON): runs executed
        # on the dispatch thread, tasks fused, and fused-eligible
        # entries that fell back to the worker pipeline because the
        # per-run wall budget expired.
        self.fused_runs = 0
        self.fused_tasks = 0
        self.fused_fallbacks = 0
        # Persistent batch runners: long-lived threads fed by a queue
        # replace the old thread-per-batch spawn — steady-state
        # execution allocates zero threads (reuses >> spawns).
        from ray_tpu._private.rpc import _ThreadRecycler

        self._batch_runners = _ThreadRecycler("exec-batch-runner",
                                              idle_s=30.0)
        # Driver import paths adopted via adopt_sys_path; forwarded to
        # pool workers with each task so by-reference pickles resolve.
        self._driver_sys_path: list[str] = []
        self.tasks_executed = 0
        # Speculation loser-cancel tokens (cancel_task RPC): checked
        # before a task's user function runs — a straggler still held
        # in admission (or a chaos sched.straggle delay) whose sibling
        # copy already sealed provably never executes. Bounded FIFO.
        self._cancel_lock = lock_witness.Lock(
            "node_executor.NodeExecutorService.cancel")
        self._cancelled_tokens: "collections.OrderedDict" = \
            collections.OrderedDict()
        # Fired (outside the ledger lock) whenever admission state
        # changes; the NodeAgent hooks this to push a syncer update
        # instead of waiting out the heartbeat period (reference: the
        # ray_syncer streams deltas on change, ray_syncer.h:88).
        self._load_listener: Callable[[], None] | None = None
        # Actor plane: actor key (bytes) -> _DaemonActor.
        self._actors: dict[bytes, _DaemonActor] = {}
        self._actors_lock = lock_witness.Lock(
            "node_executor.NodeExecutorService.actors")
        # Creation gate: keys whose constructor is in flight. An
        # actor_call declaring awaiting_create waits here instead of
        # bouncing "gone" — the driver pipelines __init__ with the
        # first method call(s) and the daemon orders them.
        self._actors_creating: set[bytes] = set()
        self._actors_creating_cond = threading.Condition(
            self._actors_lock)
        # Prestarted standby workers for actor creation, keyed by the
        # spawn-relevant env (client addr); refilled asynchronously so
        # forks overlap RPC waits instead of sitting on the creation
        # critical path.
        self._standby: dict[tuple, list] = {}
        self._standby_lock = lock_witness.Lock(
            "node_executor.NodeExecutorService.standby")
        self._standby_refilling: set[tuple] = set()
        self._standby_target = 2
        self._stop_event = threading.Event()
        self._sweep_thread: threading.Thread | None = None

        if pool_size is None:
            pool_size = max(1, min(int(self._resources.get(
                "CPU", os.cpu_count() or 1)), 16))
        from ray_tpu._private.worker_pool import WorkerPool

        self._shm_directory = ShmDirectory()
        self._shm_client = ShmClient()
        self.pool = WorkerPool(pool_size, self._shm_directory,
                               self._shm_client)

        s = self._server
        s.register("ping", lambda: "pong")
        s.register("exec_ping", lambda: os.getpid())
        # Long-running methods dispatch concurrently so ONE multiplexed
        # connection carries all of a driver's in-flight work (reference:
        # async completion queues, client_call.h — not a socket per task).
        s.register("execute_task", self.execute_task, concurrent=True)
        s.register("execute_task_batch", self.execute_task_batch,
                   concurrent=True, streaming=True)
        s.register("fetch_object", self.fetch_object,
                   concurrent="pooled")
        s.register("fetch_plan", self.fetch_plan, concurrent="pooled")
        s.register("unpin_object", self.unpin_object)
        s.register("free_objects", self.free_objects)
        s.register("executor_stats", self.executor_stats)
        s.register("flight_ring", self._flight_ring)
        s.register("configure_perf", self._configure_perf)
        s.register("cancel_task", self.cancel_task)
        s.register("task_block", self.task_block)
        s.register("task_unblock", self.task_unblock)
        s.register("adopt_sys_path", self.adopt_sys_path)
        s.register("create_actor", self.create_actor, concurrent=True)
        s.register("actor_call", self.actor_call, concurrent=True)
        s.register("actor_kill", self.actor_kill)

    @property
    def port(self) -> int:
        return self._server.port

    def address_for(self, host: str) -> str:
        return f"{host}:{self._server.port}"

    @property
    def advertised_address(self) -> str:
        """The address peers reach this executor at — what this node
        registers in owners' chunk directories when pulling."""
        if self._advertised_address is None:
            from ray_tpu._private.node import _own_address

            self._advertised_address = self.address_for(_own_address())
        return self._advertised_address

    @advertised_address.setter
    def advertised_address(self, value: str) -> None:
        self._advertised_address = value

    def start(self) -> "NodeExecutorService":
        self._server.start()
        from ray_tpu._private.config import GLOBAL_CONFIG

        period_ms = int(GLOBAL_CONFIG.owner_sweep_period_ms or 0)
        if period_ms > 0:
            self._sweep_thread = threading.Thread(
                target=self._owner_sweep_loop,
                args=(period_ms / 1000.0,
                      float(GLOBAL_CONFIG.owner_dead_grace_s)),
                daemon=True, name="node-owner-sweep")
            self._sweep_thread.start()
        return self

    def _owner_sweep_loop(self, period_s: float, grace_s: float) -> None:
        """Owner-death GC: a driver whose client endpoint stays
        unreachable past the grace period has crashed — drop its primary
        blobs and kill its actors, or a dead driver's results pin daemon
        memory forever (reference: owner-death cleanup in the ownership
        protocol, reference_count.h:61; actor owners dying kill their
        actors, gcs_actor_manager.h)."""
        import time as _time
        from concurrent.futures import ThreadPoolExecutor

        # Sweep requires SUSTAINED unreachability: fail_since records the
        # first of an unbroken run of failed probes; one transient miss
        # (dropped SYN, a slow driver tick) never frees a live owner's
        # state. Probes run concurrently so many dead owners cannot
        # stretch the sweep period and starve probes of live ones.
        fail_since: dict[str, float] = {}
        while not self._stop_event.wait(period_s):
            self._sweep_transfer_plane()
            with self._actors_lock:
                actor_owners = {a.owner: None for a in
                                self._actors.values()
                                if getattr(a, "owner", None)}
            owners = set(self.store.owners()) | set(actor_owners)
            if not owners:
                fail_since.clear()
                continue

            def probe_one(owner: str) -> bool:
                try:
                    probe = RpcClient(owner, timeout_s=3.0,
                                      connect_timeout_s=2.0)
                    try:
                        return probe.call("ping") == "pong"
                    finally:
                        probe.close()
                except Exception:  # noqa: BLE001 — unreachable
                    return False

            with ThreadPoolExecutor(max_workers=min(8, len(owners))) \
                    as pool:
                results = dict(zip(owners, pool.map(probe_one, owners)))
            now = _time.monotonic()
            for owner, alive in results.items():
                if alive:
                    fail_since.pop(owner, None)
                    continue
                first_fail = fail_since.setdefault(owner, now)
                if now - first_fail <= grace_s:
                    continue
                freed = self.store.free_owner(owner)
                with self._actors_lock:
                    dead_keys = [k for k, a in self._actors.items()
                                 if getattr(a, "owner", None) == owner]
                for key in dead_keys:
                    self._reap_actor(key)
                fail_since.pop(owner, None)
                if freed or dead_keys:
                    import logging

                    logging.getLogger("ray_tpu").warning(
                        "owner %s unreachable for %.0fs: swept %d blobs,"
                        " %d actors", owner, grace_s, freed,
                        len(dead_keys))
            for owner in list(fail_since):
                if owner not in owners:
                    del fail_since[owner]

    def stop(self) -> None:
        self._stop_event.set()
        self._server.stop()
        if self._spill_mgr is not None:
            self._spill_mgr.stop()
        # Same-host plane: drop owner-side pins (peers' leases) and
        # this daemon's peer mappings before the directories unwind.
        self.leases.clear()
        with self._shm_args_lock:
            attached = list(self._attached.values())
            self._attached.clear()
            self._map_sources.clear()
        for _, _, seg in attached:
            if seg is not None:
                try:
                    seg.close()
                except (BufferError, OSError):
                    pass  # exported buffers pin the map; tracker reaps
        self._peer_arenas.close_all()
        with self._actors_lock:
            actors = list(self._actors.values())
            self._actors.clear()
        for actor in actors:
            actor.kill()
        with self._standby_lock:
            standby = [w for pool in self._standby.values()
                       for w in pool]
            self._standby.clear()
        for worker in standby:
            worker.stop()
        self.pool.shutdown()
        self._peers.close()
        # Relay partials view shm segments; release the views before
        # the directory unlinks/closes them.
        with self._partials_lock:
            parts, self._partials = list(self._partials.values()), {}
        for part in parts:
            if part.external:
                with part.lock:
                    try:
                        part.buf.release()
                    except BufferError:
                        pass
        self._shm_client.close_all()
        self._shm_directory.shutdown()
        if hasattr(self.store, "close"):
            self.store.close()  # native store: free the C++ handle

    # ------------------------------------------------------------- endpoints

    def execute_task(self, digest: str, func_blob: bytes | None,
                     args_blob: bytes, n_returns: int,
                     return_keys: list[bytes],
                     runtime_env: dict | None = None,
                     resources: dict | None = None,
                     task_token: str | None = None,
                     client_addr: str | None = None,
                     args_ref: str | None = None,
                     trace_ctx: tuple | None = None,
                     deadline: float | None = None) -> tuple:
        """Run one task; reply ("ok", [result descriptors]) where each
        descriptor is ("inline", blob) or ("stored", size), or
        ("need_func", nonce) when the digest is unknown here (args are
        stashed under the nonce so the retry ships the function alone),
        or ("err", exc_blob).

        ``trace_ctx`` (trace_id, parent span_id, anchor): the driver is
        tracing this task — stamp daemon-side stage timestamps, open a
        linked span, and piggyback both (plus any buffered spans) on
        the reply as a third tuple element. The context's presence IS
        the enable signal; without it this path costs nothing."""
        # Admission: with several drivers sharing this node, each one
        # accounts only its own leases — reject work beyond capacity and
        # let the submitter spill to another node (reference: raylet
        # spillback, cluster_task_manager.h:42 / HandleRequestWorkerLease
        # redirecting the lease).
        # The reservation is keyed by the driver's task token so a task
        # blocked in a nested get() can return its CPU (task_block /
        # task_unblock, driven by the owning driver's block context —
        # reference: workers blocked in ray.get return their CPU to the
        # raylet).
        self._warm_factory_once()
        demand = dict(resources or {})
        demand.setdefault("CPU", 1.0)
        token = task_token or f"exec-{digest[:8]}-{os.urandom(4).hex()}"
        if args_blob is None and args_ref is not None:
            with self._func_lock:
                args_blob = self._stashed_args.pop(args_ref, None)
            if args_blob is None:
                return ("stale_args",)
        if deadline is not None and time.time() > deadline:
            # End-to-end budget already dead on arrival: refuse the
            # lease — the driver seals TaskTimeoutError, nothing runs.
            self.task_timeouts += 1
            return ("timeout", "admitted")
        shed_why = self._overload_reason()
        if shed_why is not None:
            self.admission_shed += 1
            return ("overloaded", shed_why)
        if not self._try_reserve(token, demand):
            return ("busy",)
        # ``trace_stages`` doubles as the always-on perf-plane carrier:
        # traced tasks get the full admitted/worker/exec stamp chain,
        # perf-armed untraced tasks a bare dict that only collects the
        # worker's pickup stamp + resource sample (no span machinery).
        perf_on = perf.PERF_ON
        t_admit = time.time() if (trace_ctx is not None or perf_on) \
            else 0.0
        trace_stages = {"admitted": t_admit} \
            if trace_ctx is not None else ({} if perf_on else None)
        try:
            from ray_tpu._private import chaos

            if chaos.ACTIVE is not None \
                    and chaos.ACTIVE.should("sched.straggle"):
                # One slow node: the delay sits BEFORE the user
                # function, so a speculation loser-cancel landing
                # mid-delay provably prevents the execution.
                self._chaos_straggle(task_token)
            if self._token_cancelled(task_token):
                # Speculation first-seal-wins: a sibling copy already
                # sealed and the driver cancelled this token before we
                # ran anything — refuse without executing.
                return ("cancelled",)
            with self._func_lock:
                func = self._func_cache.get(digest)
                if func_blob is not None:
                    # Raw blob kept for the batch path's pool forwards.
                    self._func_blob_cache[digest] = func_blob
            if func is None:
                if func_blob is None:
                    # Stash the args so the retry ships the function
                    # alone (never re-sends possibly-large args). Bounded
                    # by entries AND bytes: a driver that dies between
                    # the two calls must not pin blobs here forever.
                    nonce = os.urandom(8).hex()
                    with self._func_lock:
                        self._stashed_args[nonce] = args_blob
                        total = sum(len(b) for b in
                                    self._stashed_args.values())
                        while self._stashed_args and (
                                len(self._stashed_args) > 256
                                or total > 256 * 1024 * 1024):
                            victim = next(iter(self._stashed_args))
                            total -= len(self._stashed_args.pop(victim))
                    return ("need_func", nonce)
                # Deserialize OUTSIDE the lock: loading can import heavy
                # modules and must not stall other tasks' cache lookups.
                try:
                    func = serialization.loads_function(func_blob)
                except BaseException as exc:  # noqa: BLE001
                    return ("err", _exc_blob(exc))
                with self._func_lock:
                    self._func_cache[digest] = func
            args, kwargs = serialization.deserialize_from_buffer(
                memoryview(args_blob))
            # CPU tasks execute in pool workers: hand large args over
            # as shared-memory descriptors, not re-serialized payloads.
            on_pool = not any(k.startswith("TPU")
                              for k in (resources or {}))
            args, kwargs = self._resolve_fetch_args(args, kwargs,
                                                    to_shm=on_pool)
            if trace_stages is None:
                values = self._run(func, digest, func_blob, args,
                                   kwargs, n_returns, runtime_env,
                                   resources or {}, task_token=token,
                                   client_addr=client_addr)
            elif trace_ctx is None:
                # Perf-armed, tracing off: thread the stages dict so
                # the pool reply's pickup stamp + resource sample land
                # here, without any span/trace-payload work.
                t_exec = time.time()
                values = self._run(func, digest, func_blob, args,
                                   kwargs, n_returns, runtime_env,
                                   resources or {}, task_token=token,
                                   client_addr=client_addr,
                                   trace_stages=trace_stages)
                trace_stages.setdefault("exec_start", t_exec)
                trace_stages.setdefault("exec_end", time.time())
            else:
                from ray_tpu.util import tracing

                t_exec = time.time()
                with tracing.remote_span(
                        "daemon:execute", trace_ctx, _proc_label(),
                        {"digest": digest[:8]}):
                    values = self._run(func, digest, func_blob, args,
                                       kwargs, n_returns, runtime_env,
                                       resources or {},
                                       task_token=token,
                                       client_addr=client_addr,
                                       trace=trace_ctx,
                                       trace_stages=trace_stages)
                # Pool-worker runs reported their own (finer) stamps
                # into trace_stages; in-daemon runs (TPU tasks) get the
                # daemon-level envelope.
                trace_stages.setdefault("exec_start", t_exec)
                trace_stages.setdefault("exec_end", time.time())
                wpid = trace_stages.pop("pid", None)
                if wpid is not None and "exec_start" in trace_stages \
                        and "exec_end" in trace_stages:
                    tracing.buffer_span({
                        "name": "worker:execute",
                        "span_id": os.urandom(8).hex(),
                        "parent_id": trace_ctx[1],
                        "trace_id": trace_ctx[0],
                        "start_time": trace_stages["exec_start"],
                        "end_time": trace_stages["exec_end"],
                        "thread": "task",
                        "proc": f"worker:{wpid}",
                        "attributes": {"token": token},
                    })
        except BaseException as exc:  # noqa: BLE001 — shipped to driver
            return ("err", _exc_blob(exc))
        finally:
            with self._running_lock:
                self._running.pop(token, None)
                self._blocked_cpu.pop(token, None)
            self._notify_load()
        self.tasks_executed += 1
        if perf_on and trace_stages is not None:
            self._record_task_perf(trace_stages, t_admit)

        out = []
        for id_bytes, value in zip(return_keys, values):
            try:
                blob = serialization.serialize_framed(value)
            except BaseException as exc:  # noqa: BLE001
                out.append(("err", _exc_blob(exc)))
                continue
            if len(blob) <= _inline_reply_bytes():
                out.append(("inline", blob))
            else:
                self.store.put(id_bytes, blob, owner=client_addr)
                self._maybe_export_stored(id_bytes, blob)
                out.append(("stored", len(blob)))
        if trace_ctx is not None:
            return ("ok", out, self._trace_payload(trace_stages))
        return ("ok", out)

    def _record_task_perf(self, stages: dict, t_admit: float) -> None:
        """Always-on plane: fold one finished task's stamps into this
        daemon's stage histograms and attribution table. Pops the
        worker's resource sample so traced replies never ship it to the
        driver (resources roll up per node, not per task event)."""
        sample = stages.pop("perf", None)
        pickup = stages.get("worker_start") or stages.get("exec_start")
        if pickup and t_admit:
            perf.record_stage("admit_worker", max(0.0, pickup - t_admit))
        if sample is not None:
            try:
                perf.record_task_resources(sample[0], sample[1],
                                           sample[2], sample[3])
                perf.record_stage("exec", float(sample[1]))
                return
            except (TypeError, IndexError):
                pass
        exec_start = stages.get("exec_start")
        exec_end = stages.get("exec_end")
        if exec_start and exec_end:
            # In-daemon run (TPU task) or a worker without the plane:
            # the daemon-level envelope is the exec wall.
            perf.record_stage("exec", max(0.0, exec_end - exec_start))

    def _flight_ring(self) -> dict:
        """Live post-mortem surface for ``ray_tpu debug``: this
        process's flight-recorder ring plus the fault/breaker/stage
        state the dumped ring files carry."""
        from ray_tpu._private import flight_recorder
        from ray_tpu._private.rpc import breaker_stats

        rec = flight_recorder.get()
        snap = rec.snapshot() if rec is not None else {
            "role": _proc_label(), "pid": os.getpid(), "events": []}
        snap.setdefault("fault_stats", self._fault_stats())
        snap.setdefault("breaker", breaker_stats())
        snap.setdefault("spill", self._spill_stats())
        snap.setdefault("stage_hist", perf.stage_snapshot())
        return snap

    def _configure_perf(self, on: bool) -> bool:
        """Arm/disarm this daemon's always-on plane at runtime (the
        overhead-calibration seam bench_envelope drives)."""
        (perf.enable if on else perf.disable)()
        return perf.PERF_ON

    def _trace_payload(self, stages: dict) -> dict:
        """Reply piggyback: this task's daemon-clock stage stamps, any
        buffered spans (this task's + orphans), and the daemon wall
        clock NOW — the driver's ClockSync anchors its half-RTT offset
        on it so merged timelines line up."""
        from ray_tpu.util import tracing

        return {"stages": stages, "spans": tracing.drain_buffered(),
                "now": time.time()}

    def _maybe_export_stored(self, id_bytes: bytes, blob) -> None:
        """Give a large stored primary a named-segment twin so
        same-host consumers (peer daemons, the driver) map it instead
        of chunk-pulling. One memcpy here buys zero copies per
        consumer; bounded by the shm-args FIFO cache."""
        from ray_tpu._private.same_host import map_enabled, map_min_bytes

        if not map_enabled() or len(blob) < map_min_bytes():
            return
        with self._shm_args_lock:
            if self._shm_directory.lookup(id_bytes) is not None:
                return
        from multiprocessing import shared_memory

        try:
            seg = shared_memory.SharedMemory(create=True,
                                             size=max(len(blob), 1))
        except OSError:
            return  # /dev/shm full: chunked fallback still serves
        seg.buf[:len(blob)] = blob
        self._register_shm_arg(id_bytes, seg, len(blob))

    _SHM_ARG_GRACE_S = 30.0

    def _spill_protected(self) -> set:
        """Ids the spiller must skip: same-host peers' lease pins plus
        keys whose worker-bound _ShmRef went out within the grace
        window (their frames may not have attached the segment yet)."""
        out = set(self.leases.pinned_ids())
        now = time.monotonic()
        with self._shm_args_lock:
            for key in [k for k, at in self._shm_out_stamp.items()
                        if now - at > self._SHM_ARG_GRACE_S]:
                del self._shm_out_stamp[key]
            out.update(self._shm_out_stamp)
        return out

    def _on_blob_spilled(self, key: bytes, owner: str | None) -> None:
        """A primary moved to the disk tier: free its shm/arena twin
        (the spiller's victim filter already excluded leased ids, so
        no same-host peer holds a pin; POSIX keeps already-mapped
        segments valid past the unlink) and queue the spilled-location
        delta for the next heartbeat's directory piggyback."""
        self._drop_shm_arg(key)
        if owner:
            with self._spill_events_lock:
                self._spill_events.append((owner, key.hex(), "spilled"))
                del self._spill_events[:-4096]  # bounded

    def _on_blob_restored(self, key: bytes, owner: str | None) -> None:
        """A spilled primary is back in memory: the node never left the
        holder set, so clearing the directory's spill mark IS the
        re-registration (the shm twin rebuilds lazily on the next
        worker-bound fetch via _blob_to_shm)."""
        if owner:
            with self._spill_events_lock:
                self._spill_events.append((owner, key.hex(), "restored"))
                del self._spill_events[:-4096]

    def _drain_spill_events(self) -> list:
        with self._spill_events_lock:
            out, self._spill_events = self._spill_events, []
        return out

    def set_load_listener(self, listener: Callable[[], None]) -> None:
        self._load_listener = listener

    def _notify_load(self) -> None:
        listener = self._load_listener
        if listener is not None:
            try:
                listener()
            except Exception:  # noqa: BLE001 — sync is best-effort
                pass

    def cancel_task(self, token: str) -> bool:
        """Speculation loser-cancel: flag ``token`` so an execution
        that hasn't reached its user function yet refuses with
        ("cancelled",) instead of running (first-seal-wins — the
        winner's value is already sealed driver-side). Best-effort: a
        task already executing completes normally and its reseal is
        skipped by the driver's claim_win gate."""
        with self._cancel_lock:
            self._cancelled_tokens[token] = True
            while len(self._cancelled_tokens) > 4096:
                self._cancelled_tokens.popitem(last=False)
        return True

    def _token_cancelled(self, token: "str | None") -> bool:
        if token is None:
            return False
        with self._cancel_lock:
            return self._cancelled_tokens.pop(token, None) is not None

    def _chaos_straggle(self, token: "str | None") -> None:
        """sched.straggle chaos site: artificially delay this node's
        exec (making straggler-speculation triggers deterministic in
        tests/benches). Sleeps in short slices so a loser-cancel
        arriving mid-delay aborts the wait — the straggler then
        provably never runs its user function."""
        total = float(os.environ.get("RAY_TPU_STRAGGLE_S", "2.0"))
        deadline = time.monotonic() + total
        while time.monotonic() < deadline:
            if token is not None:
                with self._cancel_lock:
                    if token in self._cancelled_tokens:
                        return  # popped by the caller's cancel check
            time.sleep(0.05)

    def _overload_reason(self) -> "str | None":
        """Why admission should SHED (not merely spill) right now:
        the overload.saturate chaos site, the admitted-reservation
        depth cap, or the host-memory watermark. None = admit
        normally. One seeded chaos draw per call — callers check once
        per RPC/batch, keeping injection deterministic."""
        from ray_tpu._private import chaos

        if chaos.ACTIVE is not None \
                and chaos.ACTIVE.should("overload.saturate"):
            return "chaos: overload.saturate"
        from ray_tpu._private.config import GLOBAL_CONFIG

        cap = int(GLOBAL_CONFIG.admission_max_queue_depth or 0)
        if cap > 0:
            with self._running_lock:
                depth = len(self._running)
            if depth >= cap:
                return (f"admitted reservations at "
                        f"admission_max_queue_depth={cap}")
        watermark = float(
            GLOBAL_CONFIG.admission_memory_watermark or 0)
        if watermark > 0:
            from ray_tpu._private import spill_manager as _spill_mod
            from ray_tpu._private.memory_monitor import (
                memory_pressure_kind,
                memory_watermark_exceeded,
            )

            if _spill_mod.SPILL_ON and self._spill_mgr is not None:
                # Two-axis classification: STORE pressure is
                # recoverable — kick the spiller and admit (degrade to
                # disk, not to failure) — unless disk-full backoff
                # means spilling cannot relieve it, which falls
                # through to the typed shed exactly like true HOST
                # pressure.
                kind = memory_pressure_kind(watermark)
                if kind == "store":
                    if not self._spill_mgr.backing_off():
                        self._spill_mgr.request_spill()
                        kind = None
                    else:
                        return ("store memory over admission_memory_"
                                f"watermark={watermark} and the spill "
                                "disk is full (backing off)")
                if kind == "host":
                    return (f"host memory over admission_memory_"
                            f"watermark={watermark}")
            elif memory_watermark_exceeded(watermark):
                # Disarmed tier: the PR-7 single-axis shed, unchanged.
                return (f"host memory over admission_memory_watermark"
                        f"={watermark}")
        return None

    def _try_reserve(self, token: str, demand: dict) -> bool:
        """Admission: reserve ``demand`` under ``token`` atomically with
        the capacity check (two concurrent calls must not both pass a
        half-full node) — shared by tasks and actors (reference: raylet
        admission before the lease grant, cluster_task_manager.h:42)."""
        with self._running_lock:
            for key, cap in self._resources.items():
                used = sum(float(d.get(key, 0.0))
                           for d in self._running.values())
                if used + float(demand.get(key, 0.0)) > float(cap) + 1e-9:
                    return False
            self._running[token] = demand
        self._notify_load()
        return True

    def _try_reserve_many(self, wants: list) -> list[bool]:
        """Batched admission: one lock pass reserves every entry that
        fits (per-entry accept/reject — a saturating batch admits its
        prefix and the rest spill, exactly like per-task admission)."""
        out = []
        with self._running_lock:
            for token, demand in wants:
                ok = True
                for key, cap in self._resources.items():
                    used = sum(float(d.get(key, 0.0))
                               for d in self._running.values())
                    if used + float(demand.get(key, 0.0)) \
                            > float(cap) + 1e-9:
                        ok = False
                        break
                if ok:
                    self._running[token] = demand
                out.append(ok)
        if any(out):
            self._notify_load()
        return out

    @staticmethod
    def _needs_dedicated_worker(runtime_env: dict | None) -> bool:
        """Entries whose runtime_env demands a fresh interpreter
        (containers, import-sensitive jax/XLA env vars) cannot ride a
        shared pipelined lease."""
        if not runtime_env:
            return False
        if runtime_env.get("container"):
            return True
        from ray_tpu._private.worker_pool import WorkerPool

        return bool(WorkerPool._import_sensitive_env_vars(runtime_env))

    def _pipe_reply_to_task_reply(self, return_keys: list, status: str,
                                  payload, owner: str | None) -> tuple:
        """Worker-pipe batch completion -> the execute_task per-task
        reply shape. Inline worker results are already framed blobs, so
        small results cross daemon-side with ZERO deserialize/
        re-serialize passes (the classic path pays both)."""
        from ray_tpu.exceptions import WorkerCrashedError

        if status == "timeout":
            # The worker found the frame's deadline dead at pickup
            # (budget died queued behind the lease head): typed refusal,
            # nothing executed.
            self.task_timeouts += 1
            return ("timeout", "worker")
        if status == "crash":
            from ray_tpu._private import flight_recorder

            flight_recorder.record("worker.crash", str(payload)[:120])
            # Normalize to WorkerCrashedError (the payload may be a
            # pool-internal _WorkerUnavailable) so the driver's retry
            # policy recognizes the system failure.
            if isinstance(payload, WorkerCrashedError):
                exc = payload
            else:
                exc = WorkerCrashedError(str(payload))
                exc.__cause__ = payload if isinstance(
                    payload, BaseException) else None
            return ("err", _exc_blob(exc))
        if status == "err":
            return ("err", payload)
        out = []
        for id_bytes, packed in zip(return_keys, payload):
            if packed[0] == "inline":
                blob = packed[1]
            else:
                blob = self._packed_to_blob(id_bytes, packed)
                if blob is None:
                    out.append(packed)  # ("err", blob) passthrough
                    continue
            if len(blob) <= _inline_reply_bytes():
                out.append(("inline", blob))
            else:
                self.store.put(id_bytes, blob, owner=owner)
                self._maybe_export_stored(id_bytes, blob)
                out.append(("stored", len(blob)))
        self.tasks_executed += 1
        return ("ok", out)

    def execute_task_batch(self, entries: list,
                           client_addr: str | None = None,
                           _emit_part=None) -> tuple:
        """Run a batch of tasks leased to this node in one RPC,
        streaming grouped completions back as they finish (no barrier
        on the slowest task).

        Each entry: (digest, func_blob, args_blob, n_returns,
        return_keys, runtime_env, resources, task_token, flags) with
        flags bit 0 = args contain FetchRef placeholders. Ref-bearing,
        TPU and dedicated-env entries take the classic per-task path on
        their own dispatch threads; everything else fans across
        pipelined multi-task worker leases (worker_pool.run_task_batch).

        Streamed parts: ("results", [(idx, reply), ...]) with the
        execute_task reply shape per task, plus ("parked", idx) /
        ("resumed", idx) control parts when frames queue behind a
        blocked lease head or an over-subscribed entry waits in daemon
        admission, and ("started", idx) before an entry can first
        side-effect. Final reply: ("done", n, fused_stats).

        While FUSED_ON, a run of eligible entries (no refs, no TPU, no
        runtime_env) executes directly on this dispatch thread — no
        worker-pipe hop — under the fused_max_run_tasks /
        fused_run_wall_budget_s budget; the remainder falls back to the
        pipelined worker path. Entries the driver over-subscribed
        beyond this node's free slots (flags bit 2) PARK in daemon
        admission when the reservation fails — completions free
        capacity and re-admit them — instead of bouncing ("busy",)
        spillbacks per slot."""
        from ray_tpu._private.config import GLOBAL_CONFIG
        from ray_tpu._private.rpc import DISPATCH_POOL
        from ray_tpu._private.worker_pool import _BatchTask

        self._warm_factory_once()
        from ray_tpu._private import chaos as _chaos

        if _chaos.ACTIVE is not None \
                and _chaos.ACTIVE.should("sched.straggle"):
            # Slow-node chaos: one delay per batch RPC (the per-token
            # cancel-aware slicing lives on the single-task path).
            time.sleep(float(os.environ.get("RAY_TPU_STRAGGLE_S",
                                            "2.0")))
        if type(entries) is tuple and entries and entries[0] == "col1":
            # Columnar batch descriptor (driver dispatch lanes): one
            # shared (digest, resources) header + parallel args/key
            # columns instead of a 9-tuple per task.
            return self._execute_columnar(entries, client_addr,
                                          _emit_part)
        self.batch_rpcs += 1
        self.batch_tasks_received += len(entries)
        n = len(entries)
        cond = lock_witness.Condition(
            "node_executor.batch_wait", plain_lock=True)
        completions: list = []
        control: list = []

        def complete(idx: int, reply: tuple) -> None:
            with cond:
                completions.append((idx, reply))
                cond.notify()

        with self._func_lock:
            sys_path = list(self._driver_sys_path) or None
        pipeline: list[_BatchTask] = []
        fused: list[_BatchTask] = []
        # Over-subscribed entries whose reservation failed, waiting for
        # capacity: [(task, demand)] — drained by the reply loop.
        parked: list = []
        reserve_wants: list = []
        demand_by_idx: dict[int, dict] = {}
        token_idx: dict[str, int] = {}
        # One shed decision per batch RPC (one chaos draw; depth and
        # watermark barely move within a batch): under overload the
        # whole batch sheds — the driver fails deadline-armed entries
        # fast and spillback-requeues the rest.
        shed_why = self._overload_reason()
        now = time.time()
        fused_cap = (max(1, int(GLOBAL_CONFIG.fused_max_run_tasks))
                     if FUSED_ON else 0)
        for idx, entry in enumerate(entries):
            (digest, func_blob, args_blob, n_returns, return_keys,
             runtime_env, resources, token, flags) = entry[:9]
            # Optional 10th/11th elements: the driver's trace context
            # and the absolute end-to-end deadline for this entry
            # (absent ⇒ off for it — zero cost).
            trace_ctx = entry[9] if len(entry) > 9 else None
            deadline = entry[10] if len(entry) > 10 else None
            if func_blob is not None:
                with self._func_lock:
                    self._func_blob_cache[digest] = func_blob
            if deadline is not None and now > deadline:
                self.task_timeouts += 1
                complete(idx, ("timeout", "admitted"))
                continue
            if shed_why is not None:
                self.admission_shed += 1
                complete(idx, ("overloaded", shed_why))
                continue
            demand = dict(resources or {})
            demand.setdefault("CPU", 1.0)
            token = token or f"exec-{digest[:8]}-{os.urandom(4).hex()}"
            classic = ((flags & 1)
                       or any(k.startswith("TPU") for k in demand)
                       or self._needs_dedicated_worker(runtime_env))
            if classic:
                def classic_run(idx=idx, digest=digest,
                                func_blob=func_blob,
                                args_blob=args_blob, n_returns=n_returns,
                                return_keys=return_keys,
                                runtime_env=runtime_env,
                                resources=resources, token=token,
                                trace_ctx=trace_ctx, deadline=deadline):
                    try:
                        reply = self.execute_task(
                            digest, func_blob, args_blob, n_returns,
                            return_keys, runtime_env, resources, token,
                            client_addr, trace_ctx=trace_ctx,
                            deadline=deadline)
                    except BaseException as exc:  # noqa: BLE001
                        reply = ("err", _exc_blob(exc))
                    complete(idx, reply)

                # Classic entries begin executing the moment they are
                # submitted: mark them maybe-started for the driver's
                # death accounting before the dispatch.
                with cond:
                    control.append(("started", idx))
                    cond.notify()
                DISPATCH_POOL.submit(classic_run)
                continue
            blob = func_blob
            if blob is None:
                with self._func_lock:
                    blob = self._func_blob_cache.get(digest)
            if blob is None:
                # Daemon restarted since the driver learned the digest:
                # that task retries via the single execute path.
                complete(idx, ("need_func", None))
                continue
            token_idx[token] = idx
            demand_by_idx[idx] = demand
            task = _BatchTask(
                idx=idx, digest=digest, func_blob=blob,
                args_blob=args_blob, n_returns=max(1, n_returns),
                runtime_env=runtime_env, token=token,
                client_addr=client_addr, sys_path=sys_path,
                trace=trace_ctx, deadline=deadline,
                overcommit=bool(flags & 2), return_keys=return_keys)
            if len(fused) < fused_cap and not runtime_env \
                    and not (flags & 8):
                # Fused-eligible: executes on this dispatch thread, no
                # per-entry reservation (the run is one serial thread).
                # Flags bit 3 (no-fuse) marks a columnar run's budget
                # spill: it must ride the worker pipeline so the
                # dispatch thread stays free to stream replies.
                fused.append(task)
                continue
            reserve_wants.append((task, demand))
        admit_ts: dict[int, float] = {}
        return_keys_by_idx = {t.idx: entries[t.idx][4] for t in fused}
        for task, _ in reserve_wants:
            return_keys_by_idx[task.idx] = entries[task.idx][4]

        def notify(kind: str, token: str) -> None:
            with cond:
                control.append((kind, token_idx.get(token)))
                cond.notify()

        def on_result(task, status, payload, wtrace=None):
            with self._running_lock:
                self._running.pop(task.token, None)
                self._blocked_cpu.pop(task.token, None)
            if wtrace and perf.PERF_ON:
                # Always-on plane: the worker's pickup stamp and
                # resource sample ride the reply whether or not
                # tracing armed this task.
                self._record_task_perf(wtrace,
                                       admit_ts.get(task.idx, 0.0))
            try:
                reply = self._pipe_reply_to_task_reply(
                    return_keys_by_idx[task.idx], status, payload,
                    client_addr)
            except BaseException as exc:  # noqa: BLE001
                reply = ("err", _exc_blob(exc))
            if task.trace is not None and reply[0] == "ok":
                reply = (reply[0], reply[1], self._batch_trace(
                    task, admit_ts.get(task.idx), wtrace))
            complete(task.idx, reply)

        notified_tokens: list = []

        def launch(run_tasks: "list[_BatchTask]") -> None:
            tokens = [t.token for t in run_tasks]
            self._pipeline_inflight.register_notify(tokens, notify)
            notified_tokens.extend(tokens)
            depth = max(1, int(GLOBAL_CONFIG.worker_pipeline_depth))
            # Persistent runner threads (LIFO-recycled, fed by a
            # queue): steady-state batch execution spawns no threads.
            self._batch_runners.submit(
                self.pool.run_task_batch, run_tasks, on_result, depth,
                self._pipeline_inflight)

        def reserve_or_park(wants: list, emit_parked) -> list:
            """Batched admission for [(task, demand)]: admitted tasks
            are returned; over-subscribed entries park (the reply loop
            re-admits them as capacity frees); plain rejects spill back
            ("busy",) to the driver exactly as before."""
            accepted = self._try_reserve_many(
                [(t.token, d) for t, d in wants])
            t_admit = time.time()
            admitted = []
            for (task, demand), ok in zip(wants, accepted):
                if ok:
                    admitted.append(task)
                    if task.trace is not None or perf.PERF_ON:
                        admit_ts[task.idx] = t_admit
                elif task.overcommit:
                    parked.append((task, demand))
                    emit_parked(task.idx)
                else:
                    complete(task.idx, ("busy",))
            return admitted

        if reserve_wants:
            pipeline = reserve_or_park(
                reserve_wants,
                lambda idx: _emit_part(("parked", idx)))
        if pipeline:
            launch(pipeline)

        fused_stats = {"fused": 0, "fused_fallbacks": 0}

        def spill_fused(rest: "list[_BatchTask]") -> None:
            # Per-run budget expired mid-fused-run: the remaining
            # fused-eligible entries take the pipelined worker path
            # (admission applies to them like any worker-path entry).
            self.fused_fallbacks += len(rest)
            fused_stats["fused_fallbacks"] += len(rest)
            go = reserve_or_park(
                [(t, demand_by_idx[t.idx]) for t in rest],
                lambda idx: _emit_part(("parked", idx)))
            if go:
                launch(go)

        try:
            done_n = 0
            if fused:
                done_n += self._run_fused(fused, client_addr,
                                          _emit_part, spill_fused,
                                          fused_stats)
            while done_n < n:
                with cond:
                    while not completions and not control:
                        if parked:
                            # Capacity freed by OTHER RPCs' completions
                            # never signals this cond: poll admission
                            # for the parked entries on a short beat.
                            if not cond.wait(timeout=0.05):
                                break
                        else:
                            cond.wait()
                    group, completions = completions, []
                    ctrl, control = control, []
                for kind, idx in ctrl:
                    if idx is not None:
                        _emit_part((kind, idx))
                if group:
                    _emit_part(("results", group))
                    self.reply_groups += 1
                    done_n += len(group)
                    self._notify_load()
                if parked:
                    self._admit_parked(parked, launch, _emit_part,
                                       complete, admit_ts)
        finally:
            if notified_tokens:
                self._pipeline_inflight.forget_notify(notified_tokens)
        return ("done", n, fused_stats)

    # Maybe-started ambiguity window: fused entries are announced to
    # the driver in ("started_many", [idx…]) windows of this many
    # BEFORE any of them can side-effect — one stream part per window
    # instead of one per task. On daemon death, announced-but-
    # never-started entries retry under the system-failure budget
    # (instead of the invisible requeue an unannounced entry gets), so
    # the window bounds how many spurious budget consumptions a death
    # can cost. Results flush in groups of _FUSED_GROUP.
    _FUSED_STARTED_WINDOW = 8
    _FUSED_GROUP = 64
    # Columnar runs announce in wider windows (see _execute_columnar).
    _COL_STARTED_WINDOW = 32

    def _run_fused(self, tasks: list, client_addr: "str | None",
                   emit, spill, fused_stats: dict) -> int:
        """Execute a run of fused entries serially on the calling
        (dispatch) thread, streaming ("started_many", [idx…]) windows
        before their entries can side-effect and grouped
        ("results", ...) parts as they finish. Returns how many entries
        were COMPLETED here; entries past the wall budget are handed to
        ``spill`` (worker path) and complete through the reply loop
        instead.

        Exactly-once accounting leans on stream ordering: a window's
        socket write completes before any of its user functions run,
        and a SIGKILLed daemon's kernel still flushes written stream
        data — so the driver can never invisibly requeue an entry that
        may have executed."""
        from ray_tpu._private.config import GLOBAL_CONFIG

        budget_s = float(GLOBAL_CONFIG.fused_run_wall_budget_s)
        t0 = time.monotonic()
        self.fused_runs += 1
        group: list = []
        done = 0
        announced = 0
        window = self._FUSED_STARTED_WINDOW
        # One resource sample brackets the whole run; per-task wall
        # comes from cheap clock reads and the run's cpu/rss attribute
        # proportionally at the end (per-task getrusage syscalls were
        # a measurable slice of the fused budget).
        perf_on = perf.PERF_ON
        run_sample = perf.sample_start() if perf_on else None
        for pos, task in enumerate(tasks):
            if budget_s > 0 and time.monotonic() - t0 > budget_s:
                if group:
                    emit(("results", group))
                    self.reply_groups += 1
                    done += len(group)
                    group = []
                spill(tasks[pos:])
                break
            if task.deadline is not None and time.time() > task.deadline:
                self.task_timeouts += 1
                group.append((task.idx, ("timeout", "admitted")))
            elif self._cancelled_tokens and \
                    self._token_cancelled(task.token):
                # Speculation first-seal-wins: the sibling copy sealed
                # and this token was loser-cancelled before we ran.
                group.append((task.idx, ("cancelled",)))
            else:
                if pos >= announced:
                    emit(("started_many",
                          [t.idx for t in
                           tasks[announced:announced + window]]))
                    announced += window
                group.append((task.idx,
                              self._exec_fused(task, client_addr)))
                self.fused_tasks += 1
                fused_stats["fused"] += 1
            if len(group) >= self._FUSED_GROUP:
                emit(("results", group))
                self.reply_groups += 1
                done += len(group)
                group = []
        else:
            if group:
                emit(("results", group))
                self.reply_groups += 1
                done += len(group)
        ran = fused_stats["fused"]
        if run_sample is not None and ran:
            # Run-level attribution: exact cpu/wall sums with the
            # task count folded in (per-task getrusage syscalls were a
            # measurable slice of the fused per-task budget). The run
            # is same-signature in the hot path; a mixed run
            # attributes to its first function.
            func = self._func_cache.get(tasks[0].digest)
            name = getattr(func, "__qualname__", tasks[0].digest[:8])
            _, wall, cpu, rss = perf.sample_end(name, run_sample)
            perf.record_task_resources(name, wall, cpu, rss, count=ran)
        self._notify_load()
        return done

    def _exec_fused(self, task, client_addr: "str | None") -> tuple:
        """Run ONE fused entry in-process; returns the execute_task
        reply shape (("ok", descriptors[, trace]) / ("err", blob)).
        No admission reservation, no worker pipe, no per-task pickle of
        the surrounding protocol — the per-task cost is the user
        function plus one args decode and one result encode (both with
        the raw small-immutable fast path)."""
        from ray_tpu._private import worker_client

        try:
            func = self._func_cache.get(task.digest)
            if func is None:
                with self._func_lock:
                    func = self._func_cache.get(task.digest)
                if func is None:
                    func = serialization.loads_function(task.func_blob)
                    with self._func_lock:
                        self._func_cache[task.digest] = func
            args, kwargs = serialization.deserialize_from_buffer(
                memoryview(task.args_blob))
            if client_addr and client_addr != \
                    getattr(self, "_fused_client_addr", None):
                # One env/proxy rebind per owner change, not per task.
                worker_client.set_driver_addr(client_addr)
                self._fused_client_addr = client_addr
            worker_client.set_task_token(task.token)
            perf_on = perf.PERF_ON
            # Cheap per-task exec-stage wall (vDSO clock reads); the
            # cpu/rss attribution samples once per RUN in _run_fused.
            t_exec = time.time() if (perf_on or task.trace is not None) \
                else 0.0
            try:
                result = func(*args, **kwargs)
            finally:
                worker_client.set_task_token(None)
            t_end = time.time() if t_exec else 0.0
            if perf_on and t_exec:
                perf.record_stage("exec", max(0.0, t_end - t_exec))
            n_returns = task.n_returns
            if n_returns == 1:
                values = [result]
            elif n_returns == 0:
                values = []
            else:
                if (not isinstance(result, (tuple, list))
                        or len(result) != n_returns):
                    raise ValueError(
                        f"task declared num_returns={n_returns} but "
                        f"returned {type(result).__name__}")
                values = list(result)
        except BaseException as exc:  # noqa: BLE001 — shipped to driver
            return ("err", _exc_blob(exc))
        out = []
        inline_max = _inline_reply_bytes()
        for id_bytes, value in zip(task.return_keys or (), values):
            try:
                blob = serialization.try_serialize_raw(value)
                if blob is None:
                    blob = serialization.serialize_framed(value)
            except BaseException as exc:  # noqa: BLE001
                out.append(("err", _exc_blob(exc)))
                continue
            if len(blob) <= inline_max:
                out.append(("inline", blob))
            else:
                self.store.put(id_bytes, blob, owner=client_addr)
                self._maybe_export_stored(id_bytes, blob)
                out.append(("stored", len(blob)))
        self.tasks_executed += 1
        if task.trace is not None:
            return ("ok", out, self._batch_trace(
                task, t_exec, {"exec_start": t_exec, "exec_end": t_end,
                               "pid": os.getpid()}))
        return ("ok", out)

    def _execute_columnar(self, descriptor: tuple,
                          client_addr: "str | None",
                          _emit_part) -> tuple:
        """Columnar batch RPC (driver dispatch lanes, ISSUE 15): ONE
        (digest, func_blob, resources) header + parallel
        ``args_blobs`` / ``return_keys`` columns. The whole run is
        fused-eligible by construction (scalar args, no refs, no
        runtime_env, no deadline), so it executes serially on this
        dispatch thread with the per-task cost reduced to one args
        decode + the user function + one result encode — the function
        resolve, client rebind and admission bookkeeping are paid once
        per RUN, not per task.

        Streamed parts: the same ("started_many", [idx…]) exactly-once
        windows as :meth:`_run_fused` (a window's socket write
        completes before any member can side-effect), compact
        ("colresults", (start_idx, [payload…])) groups where a payload
        is the raw inline reply blob (the common case) or a classic
        per-task reply tuple, and — for entries spilled to the worker
        pipeline when the run's wall budget expires — the classic
        ("results", …) / ("parked", …) parts re-indexed into this
        batch. Final reply: ("done", n, fused_stats)."""
        from ray_tpu._private.config import GLOBAL_CONFIG

        (_, digest, func_blob, args_blobs, return_keys, resources,
         token_base) = descriptor
        n = len(args_blobs)
        self.batch_rpcs += 1
        self.batch_tasks_received += n
        fused_stats = {"fused": 0, "fused_fallbacks": 0}
        shed_why = self._overload_reason()
        if shed_why is not None:
            self.admission_shed += n
            _emit_part(("colresults",
                        (0, [("overloaded", shed_why)] * n)))
            return ("done", n, fused_stats)
        if func_blob is not None:
            with self._func_lock:
                self._func_blob_cache[digest] = func_blob
            blob = func_blob
        else:
            with self._func_lock:
                blob = self._func_blob_cache.get(digest)
        if blob is None:
            # Daemon restarted since the driver learned the digest:
            # every entry retries via the single execute path.
            _emit_part(("colresults", (0, [("need_func", None)] * n)))
            return ("done", n, fused_stats)
        fused_cap = (max(1, int(GLOBAL_CONFIG.fused_max_run_tasks))
                     if FUSED_ON else 0)
        budget_s = float(GLOBAL_CONFIG.fused_run_wall_budget_s)
        try:
            func = self._func_cache.get(digest)
            if func is None:
                func = serialization.loads_function(blob)
                with self._func_lock:
                    self._func_cache[digest] = func
        except BaseException as exc:  # noqa: BLE001 — load failure
            err = ("err", _exc_blob(exc))
            _emit_part(("colresults", (0, [err] * n)))
            return ("done", n, fused_stats)
        from ray_tpu._private import worker_client

        if client_addr and client_addr != \
                getattr(self, "_fused_client_addr", None):
            worker_client.set_driver_addr(client_addr)
            self._fused_client_addr = client_addr
        worker_client.set_task_token(token_base)
        # RUN-level admission reservation: one _running entry covers
        # the whole columnar run (shrunk as reply groups flush), so
        # the heartbeat's availability report — and the load-change
        # poke other drivers schedule against — reflects the queued
        # work. Classic per-entry reservations cost a lock pass per
        # task; this is one per run + one per reply group.
        run_token = f"col-{token_base}"
        run_demand = dict(resources or {})
        run_demand.setdefault("CPU", 1.0)

        def _reserve_remaining(remaining: int) -> None:
            with self._running_lock:
                if remaining > 0:
                    self._running[run_token] = {
                        k: v * remaining for k, v in run_demand.items()}
                else:
                    self._running.pop(run_token, None)
            self._notify_load()

        _reserve_remaining(n)
        inline_max = _inline_reply_bytes()
        deser = serialization.deserialize_from_buffer
        ser_raw = serialization.try_serialize_raw
        ser_framed = serialization.serialize_framed
        # Wider exactly-once window than the classic fused run (8):
        # columnar entries are tiny by eligibility, so the daemon-death
        # cost the window bounds (spurious retry-budget consumptions)
        # is cheap, while each announced window is a streamed part —
        # at 32 the announce overhead is a quarter of the classic run.
        window = self._COL_STARTED_WINDOW
        group_max = self._FUSED_GROUP
        perf_on = perf.PERF_ON
        run_sample = perf.sample_start() if perf_on else None
        exec_walls: list = [] if perf_on else None
        t0 = time.monotonic()
        if fused_cap:
            self.fused_runs += 1
        group: list = []
        group_start = 0
        pos = 0
        announced = 0
        try:
            while pos < min(n, fused_cap):
                if budget_s > 0 and time.monotonic() - t0 > budget_s:
                    break  # spill the remainder to the worker path
                if pos >= announced:
                    announced = min(n, pos + window)
                    _emit_part(("started_many",
                                list(range(pos, announced))))
                if self._cancelled_tokens and self._token_cancelled(
                        f"{token_base}:{pos}"):
                    payload = ("cancelled",)
                else:
                    t_exec = time.time() if perf_on else 0.0
                    try:
                        # Columnar blobs encode the args tuple alone
                        # (kwargs empty by eligibility).
                        args = deser(memoryview(args_blobs[pos]))
                        result = func(*args)
                        rblob = ser_raw(result)
                        if rblob is None:
                            rblob = ser_framed(result)
                        if len(rblob) <= inline_max:
                            payload = rblob
                        else:
                            id_bytes = return_keys[pos]
                            self.store.put(id_bytes, rblob,
                                           owner=client_addr)
                            self._maybe_export_stored(id_bytes, rblob)
                            payload = ("ok", [("stored", len(rblob))])
                    except BaseException as exc:  # noqa: BLE001
                        payload = ("err", _exc_blob(exc))
                    if perf_on:
                        exec_walls.append(
                            max(0.0, time.time() - t_exec))
                    self.tasks_executed += 1
                    self.fused_tasks += 1
                    fused_stats["fused"] += 1
                group.append(payload)
                pos += 1
                if len(group) >= group_max:
                    _emit_part(("colresults", (group_start, group)))
                    self.reply_groups += 1
                    group = []
                    group_start = pos
                    _reserve_remaining(n - pos)
        finally:
            worker_client.set_task_token(None)
        if group:
            _emit_part(("colresults", (group_start, group)))
            self.reply_groups += 1
        # Drop the run reservation; a budget-spilled remainder
        # re-reserves per entry through the worker path below.
        _reserve_remaining(0)
        if perf_on and exec_walls:
            perf.record_stage_many("exec", exec_walls)
        if run_sample is not None and fused_stats["fused"]:
            name = getattr(func, "__qualname__", digest[:8])
            _, wall, cpu, rss = perf.sample_end(name, run_sample)
            perf.record_task_resources(name, wall, cpu, rss,
                                       count=fused_stats["fused"])
        self._notify_load()
        if pos < n:
            # Budget spill (or fused disarmed): the remainder rides
            # the classic worker pipeline as over-subscribed no-fuse
            # entries, re-indexed into this batch's idx space.
            rest = list(range(pos, n))
            self.fused_fallbacks += len(rest) if fused_cap else 0
            fused_stats["fused_fallbacks"] += len(rest) \
                if fused_cap else 0
            offset = pos

            def remap(part):
                kind, payload = part
                if kind == "results":
                    _emit_part((kind, [(offset + i, reply)
                                       for i, reply in payload]))
                elif kind == "started_many":
                    _emit_part((kind, [offset + i for i in payload]))
                else:
                    _emit_part((kind, offset + payload))

            entries = []
            for i in rest:
                # Re-frame into the classic (args, kwargs) shape the
                # worker pipe decodes (columnar blobs carry the args
                # tuple alone) — the spill path is rare by design.
                # Flag 8 (no-fuse) WITHOUT the park flag: whatever
                # this node's workers can't admit bounces ("busy",)
                # back to the driver, which SPREADS it across the
                # cluster through the classic dispatcher — a columnar
                # slice that turns out to be long tasks must not
                # serialize a whole run behind one node.
                args = deser(memoryview(args_blobs[i]))
                pair_blob = ser_raw((args, {}))
                if pair_blob is None:
                    pair_blob = ser_framed((args, {}))
                entries.append(
                    (digest, None, pair_blob, 1, [return_keys[i]],
                     None, resources, f"{token_base}:{i}", 8))
            self.execute_task_batch(entries, client_addr,
                                    _emit_part=remap)
        return ("done", n, fused_stats)

    def _admit_parked(self, parked: list, launch, emit, complete,
                      admit_ts: dict) -> None:
        """Daemon-side admission queueing: retry reservation for
        over-subscribed entries parked by this batch RPC. Expired
        budgets seal typed timeouts; newly admitted entries emit
        ("resumed", idx) — the driver re-acquires their CPU — and join
        the worker pipeline as a fresh run."""
        now = time.time()
        still: list = []
        for task, demand in parked:
            if task.deadline is not None and now > task.deadline:
                self.task_timeouts += 1
                complete(task.idx, ("timeout", "admitted"))
            else:
                still.append((task, demand))
        parked[:] = []
        if not still:
            return
        accepted = self._try_reserve_many(
            [(t.token, d) for t, d in still])
        t_admit = time.time()
        go: list = []
        for (task, demand), ok in zip(still, accepted):
            if ok:
                emit(("resumed", task.idx))
                if task.trace is not None or perf.PERF_ON:
                    admit_ts[task.idx] = t_admit
                go.append(task)
            else:
                parked.append((task, demand))
        if go:
            launch(go)

    def fetch_object(self, id_bytes: bytes, offset: int,
                     length: int):
        reply = self.store.read_chunk(id_bytes, offset, length)
        if reply is None:
            # Not (yet) in the store: an in-progress or relay pull may
            # hold the requested chunks — serve them so 1->N broadcast
            # fans out through receivers instead of queueing on the
            # owner.
            with self._partials_lock:
                part = self._partials.get(id_bytes)
            if part is None:
                return None
            reply = part.read_chunk(offset, length)
            if reply is None:
                return None
            self.relay_chunks_served += 1
        return wrap_chunk_reply(reply)

    def fetch_plan(self, id_bytes: bytes,
                   puller_addr: str | None = None,
                   puller_host: str | None = None):
        """Transfer plan for one object: (total_size, other_holders,
        map_source). Registers the puller as a partial holder so later
        pullers fetch chunks from it too. None when the object is
        unknown here.

        ``map_source``: when the puller declared a host identity equal
        to ours and this daemon holds the object in named shared
        memory, the reply carries how to map it directly — kind/name/
        key/size plus a granted lease token that pins the object until
        ``unpin_object`` (or the liveness-gated TTL sweep). Otherwise
        None and the puller takes the chunked path."""
        total = self.store.size(id_bytes)
        if total is None:
            with self._partials_lock:
                part = self._partials.get(id_bytes)
            if part is None:
                with self._shm_args_lock:
                    source = self._map_sources.get(id_bytes)
                if source is None:
                    return None
                total = source[2]
        map_info = None
        if puller_addr and puller_host and puller_host == self.host_id:
            map_info = self._grant_map_lease(id_bytes, puller_addr)
        # A mapping puller never holds servable CHUNKS — registering it
        # as a relay holder would advertise a peer that serves nothing.
        reg_addr = None if map_info is not None else puller_addr
        # Spill-aware reply: a spilled local copy has no shm twin to
        # map (map_info is naturally None — the twin was freed at
        # spill time) and the chunked pull will pay a verify+restore
        # first; the 4th element tells the puller so.
        spilled = bool(getattr(self.store, "is_spilled",
                               lambda _k: False)(id_bytes))
        return (total, plan_holders(self.chunk_directory, id_bytes,
                                    reg_addr, total), map_info,
                {"spilled": spilled})

    def _grant_map_lease(self, id_bytes: bytes,
                         holder: str) -> dict | None:
        """Owner half of the same-host protocol: find a shared-memory
        source for the object and pin it under a lease for ``holder``.
        Segments need no in-memory pin (POSIX keeps a mapped segment
        alive past its unlink), so their lease only tracks the grant;
        arena objects take a real refcount (ArenaStore.pin) that blocks
        eviction/reuse until release."""
        from ray_tpu._private.same_host import map_enabled

        if not map_enabled():
            return None
        with self._shm_args_lock:
            source = self._map_sources.get(id_bytes)
        if source is None:
            return None
        kind, name, size = source[0], source[1], source[2]
        key = source[3] if len(source) > 3 else b""
        if kind == "arena":
            arena = getattr(self, "_owned_arena", None)
            if arena is None or arena.pin(key) is None:
                return None
            token = self.leases.grant(
                id_bytes, holder, on_release=lambda: arena.unpin(key))
        else:
            token = self.leases.grant(id_bytes, holder)
        return {"kind": kind, "name": name, "key": key, "size": size,
                "host": self.host_id, "token": token}

    def unpin_object(self, token: str) -> bool:
        """Release one same-host map lease (puller dropped its
        mapping)."""
        return self.leases.release(token)

    def free_objects(self, ids: list[bytes]) -> int:
        for id_bytes in ids:
            self._drop_shm_arg(id_bytes)
        self.chunk_directory.drop(ids)
        return self.store.free(ids)

    def _drop_shm_arg(self, key: bytes) -> None:
        """Owner GC of one object's transfer-plane state: relay
        partial (buffer view released first — exported-view safety),
        shm segment, and FIFO accounting."""
        with self._partials_lock:
            part = self._partials.pop(key, None)
        if part is not None and part.external:
            with part.lock:
                try:
                    part.buf.release()
                except BufferError:
                    pass
        with self._shm_args_lock:
            self._shm_args_order = [
                (k, sz) for k, sz in self._shm_args_order if k != key]
            self._shm_args_bytes = sum(
                sz for _, sz in self._shm_args_order)
        self._release_plane_state(key)
        self._shm_directory.free(key)

    def _batch_trace(self, task, admitted: float | None,
                     wtrace: dict | None) -> dict:
        """Per-task trace payload for a pipelined batch completion:
        daemon admission stamp + the worker's frame/exec stamps (same
        host, same clock), plus a daemon-lane span and a worker-lane
        span so the merged timeline shows the full hop chain."""
        from ray_tpu.util import tracing

        now = time.time()
        stages: dict = {}
        if admitted is not None:
            stages["admitted"] = admitted
        ctx = task.trace
        if wtrace:
            for key in ("worker_start", "exec_start", "exec_end"):
                if key in wtrace:
                    stages[key] = wtrace[key]
            if "exec_start" in wtrace and "exec_end" in wtrace:
                tracing.buffer_span({
                    "name": "worker:execute",
                    "span_id": os.urandom(8).hex(),
                    "parent_id": ctx[1] if ctx else None,
                    "trace_id": ctx[0] if ctx else "",
                    "start_time": wtrace["exec_start"],
                    "end_time": wtrace["exec_end"],
                    "thread": "task_seq",
                    "proc": f"worker:{wtrace.get('pid', '?')}",
                    "attributes": {"token": task.token or ""},
                })
        if admitted is not None:
            tracing.buffer_span({
                "name": "daemon:task",
                "span_id": os.urandom(8).hex(),
                "parent_id": ctx[1] if ctx else None,
                "trace_id": ctx[0] if ctx else "",
                "start_time": admitted,
                "end_time": now,
                "thread": "batch",
                "proc": _proc_label(),
                "attributes": {"token": task.token or ""},
            })
        return {"stages": stages, "spans": tracing.drain_buffered(),
                "now": now}

    def _pipeline_stats(self) -> dict:
        # Per-stage drain counters for the pipelined execute path
        # (dispatch batches -> batch RPCs -> worker leases/frames ->
        # grouped seal replies) so a throughput regression localizes
        # to one stage in a single read.
        return {
            "batch_rpcs": self.batch_rpcs,
            "batch_tasks": self.batch_tasks_received,
            "reply_groups": self.reply_groups,
            "worker_lease_runs": self.pool.batch_runs,
            "worker_lease_tasks": self.pool.batch_tasks,
            "worker_pipelined_frames": self.pool.batch_frames,
            "fused_runs": self.fused_runs,
            "fused_tasks": self.fused_tasks,
            "fused_fallbacks": self.fused_fallbacks,
            "runner_spawns": self._batch_runners.spawns,
            "runner_reuses": self._batch_runners.reuses,
        }

    def _data_plane_stats(self) -> dict:
        with self._shm_args_lock:
            data_plane = {
                "same_host_map_hits": self.same_host_map_hits,
                "same_host_copy_hits": self.same_host_copy_hits,
                "chunked_pulls": self.chunked_pulls,
                "map_sources": len(self._map_sources),
                "attached_mappings": len(self._attached),
            }
        data_plane["leases"] = self.leases.stats()
        return data_plane

    def _fault_stats(self) -> dict:
        # Failure counters: every recovery path the chaos tests (and
        # the envelope rows) assert — retried idempotent RPCs, batch
        # entries requeued after a worker/daemon death, chunk sources
        # blacklisted mid-pull, orphaned peer mappings swept.
        from ray_tpu._private.rpc import breaker_stats, rpc_retry_count

        return {
            "rpc_retries": rpc_retry_count(),
            "batch_requeues": self.pool.batch_requeues,
            "peer_blacklists": self.peer_blacklists,
            "lease_orphans_swept": self.lease_orphans_swept,
            "arena_orphans_swept": self.arena_orphans_swept,
            "lineage_rebuilds": 0,  # daemons hold no lineage (owners do)
            # Overload-control plane (see FAULT_STAT_KEYS).
            "task_timeouts": self.task_timeouts,
            "admission_shed": self.admission_shed,
            "breaker_open": breaker_stats()["opens"],
        }

    def executor_stats(self) -> dict:
        with self._running_lock:
            running = len(self._running)
        with self._actors_lock:
            num_actors = len(self._actors)
        with self._partials_lock:
            relay = {
                "partials": len(self._partials),
                "relay_chunks_served": self.relay_chunks_served,
            }
        stats = {"tasks_executed": self.tasks_executed,
                 "running": running, "store": self.store.stats(),
                 "num_actors": num_actors, "pid": os.getpid(),
                 "relay": relay,
                 "data_plane": self._data_plane_stats(),
                 "pipeline": self._pipeline_stats(),
                 "faults": self._fault_stats(),
                 "spill": self._spill_stats(),
                 "threads": threading.active_count()}
        engine = self._engine_stats()
        if engine is not None:
            stats["engine"] = engine
        return stats

    def _spill_stats(self) -> dict:
        from ray_tpu._private.spill_manager import merged_stats

        stats = merged_stats(self._spill_mgr)
        stats["spilled_plan_hits"] = self.spilled_plan_hits
        return stats

    @staticmethod
    def _engine_stats() -> "dict | None":
        """LLM-engine counters for engines co-hosted in this process
        (serve replicas run as thread actors here). sys.modules probe:
        a daemon that never served an LLM must not import the serve
        tier just to report stats."""
        import sys

        mod = sys.modules.get("ray_tpu.serve.llm_engine.engine")
        if mod is None:
            return None
        return mod.merged_engine_stats()

    def stats_for_sync(self) -> dict:
        """Heartbeat-piggyback subset of ``executor_stats()``: the
        counter groups the cluster /metrics aggregation serves per node
        (pipeline / data_plane / faults), cheap enough for a 1 s
        cadence — no store-wide byte sums."""
        with self._running_lock:
            running = len(self._running)
            # Admitted-reservation depth net of blocked-in-get tokens:
            # the scheduler's load score wants queue pressure, not
            # parked waiters.
            depth = max(0, running - len(self._blocked_cpu))
        stats = {"tasks_executed": self.tasks_executed,
                 "running": running,
                 "depth": depth,
                 # Snapshot wall stamp: the stats feed carries its own
                 # timestamp so consumers (and the GCS receipt age) can
                 # tell a fresh report from a wedged daemon's last one.
                 "stats_ts": time.time(),
                 "pipeline": self._pipeline_stats(),
                 "data_plane": self._data_plane_stats(),
                 "faults": self._fault_stats()}
        if self._spill_mgr is not None:
            stats["spill"] = self._spill_stats()
            # Spilled/restored location deltas for the GCS object
            # directory (the head pops them before recording stats).
            events = self._drain_spill_events()
            if events:
                stats["spill_events"] = events
        engine = self._engine_stats()
        if engine is not None:
            # LLM-engine counters ride the same heartbeat piggyback
            # into the cluster /metrics (ray_tpu_node_engine family).
            stats["engine"] = engine
        if perf.PERF_ON:
            # Always-on plane piggyback: mergeable-by-addition stage
            # histograms + the per-function attribution table ride the
            # same heartbeat into the GCS node-stats table (the cluster
            # /metrics scrape and summarize_tasks() read them there).
            stats["stage_hist"] = perf.stage_snapshot()
            stats["task_resources"] = perf.resource_snapshot()
        return stats

    def adopt_sys_path(self, paths: list) -> int:
        """Adopt a driver's import paths (existing directories only) so
        functions/classes pickled BY REFERENCE from the driver's modules
        resolve here and in this node's workers. One-machine clusters
        share the filesystem, so the paths are valid; on real multi-host
        the nonexistent ones are skipped and runtime_env py_modules is
        the supported route (reference: the function manager assumes
        importable modules; runtime_env ships the rest)."""
        import sys

        added = 0
        for path in paths:
            if path and path not in sys.path and os.path.isdir(path):
                sys.path.append(path)
                added += 1
        with self._func_lock:
            merged = list(self._driver_sys_path)
            merged += [p for p in paths
                       if p and p not in merged and os.path.isdir(p)]
            self._driver_sys_path = merged
        return added

    def task_block(self, token: str) -> bool:
        """A task on this node blocked in a nested get(): return its CPU
        to the admission ledger so dependent work can land here
        (otherwise a parent waiting on a child scheduled to this node
        deadlocks — reference: blocked workers release their CPU to the
        raylet)."""
        with self._running_lock:
            demand = self._running.get(token)
            if demand is None or token in self._blocked_cpu:
                return False
            cpu = float(demand.get("CPU", 0.0))
            if cpu <= 0:
                return False
            self._blocked_cpu[token] = cpu
            reduced = dict(demand)
            reduced["CPU"] = 0.0
            self._running[token] = reduced
        self._notify_load()
        # Pipelined lease head blocked: frames queued behind it hold
        # CPU without running — park them too (deadlock avoidance).
        self._pipeline_inflight.on_block(token)
        return True

    def task_unblock(self, token: str) -> bool:
        """The blocked task resumed: re-reserve its CPU (may transiently
        overcommit; admission of NEW work still checks the full ledger)."""
        with self._running_lock:
            cpu = self._blocked_cpu.pop(token, None)
            demand = self._running.get(token)
            if cpu is None or demand is None:
                return False
            restored = dict(demand)
            restored["CPU"] = restored.get("CPU", 0.0) + cpu
            self._running[token] = restored
        self._notify_load()
        return True

    # --------------------------------------------------------- actor plane

    def create_actor(self, actor_key: bytes, cls_blob: bytes,
                     args_blob: bytes, runtime_env: dict | None = None,
                     max_concurrency: int = 1,
                     resources: dict | None = None,
                     client_addr: str | None = None,
                     sys_path: list | None = None) -> tuple:
        """Host an actor on this node: admission-reserve its resources
        for its lifetime, spawn a dedicated worker process, run the
        constructor there. -> ("ok", pid) | ("busy",) | ("err", blob).
        (Reference: GcsActorScheduler leases a worker on the chosen node
        and pushes the creation task — gcs_actor_scheduler.h.)"""
        self._warm_factory_once()
        with self._actors_creating_cond:
            self._actors_creating.add(actor_key)
        try:
            return self._create_actor_gated(
                actor_key, cls_blob, args_blob, runtime_env,
                max_concurrency, resources, client_addr, sys_path)
        finally:
            with self._actors_creating_cond:
                self._actors_creating.discard(actor_key)
                self._actors_creating_cond.notify_all()

    def _create_actor_gated(self, actor_key: bytes, cls_blob: bytes,
                            args_blob: bytes,
                            runtime_env: dict | None = None,
                            max_concurrency: int = 1,
                            resources: dict | None = None,
                            client_addr: str | None = None,
                            sys_path: list | None = None) -> tuple:
        with self._actors_lock:
            existing = self._actors.get(actor_key)
        if existing is not None:
            if existing.alive():
                # Driver retry after a lost reply: already up.
                return ("ok", existing.pid)
            # Dead copy: reap it (wait the process, close the pipe,
            # release its reservation) before re-creating.
            self._reap_actor(actor_key)
        demand = dict(resources or {})  # actors default to 0 CPU
        token = "actor-" + actor_key.hex()
        if not self._try_reserve(token, demand):
            return ("busy",)
        try:
            args, kwargs = serialization.deserialize_from_buffer(
                memoryview(args_blob))
            # Actor workers resolve _ShmRef at actor_new: large init
            # args cross as shm descriptors, not pipe payloads.
            args, kwargs = self._resolve_fetch_args(args, kwargs,
                                                    to_shm=True)
            init_blob = serialization.serialize_framed((args, kwargs))
            extra_env = {}
            if client_addr:
                extra_env["RAY_TPU_DRIVER_CLIENT_ADDR"] = client_addr
            # A TPU actor owns whole chips from its own process, seeing
            # only those leased to it (reference: TPU_VISIBLE_CHIPS
            # isolation, tpu.py:30); a chip is never shared, so a
            # fractional demand still takes a whole one.
            tpu_chips = None
            n_chips = accelerators.tpu_chip_demand(
                demand, self._chip_leases.num_chips)
            worker = None
            if n_chips:
                tpu_chips = self._chip_leases.lease(
                    actor_key, n_chips, "actor on this node")
            else:
                worker = self._take_standby(extra_env)
            actor = _DaemonActor(cls_blob, init_blob, runtime_env,
                                 max_concurrency, extra_env, tpu_chips,
                                 sys_path, worker=worker)
        except BaseException as exc:  # noqa: BLE001 — shipped to driver
            self._chip_leases.release(actor_key)
            with self._running_lock:
                self._running.pop(token, None)
            self._notify_load()
            return ("err", exc.blob if isinstance(exc, _ActorNewError)
                    else _exc_blob(exc))
        actor.owner = client_addr  # owner-death sweep kills orphans
        with self._actors_lock:
            self._actors[actor_key] = actor
        return ("ok", actor.pid)

    def actor_call(self, actor_key: bytes, method: str,
                   args_blob: bytes, n_returns: int,
                   return_keys: list[bytes],
                   awaiting_create: bool = False) -> tuple:
        """Invoke a method on a hosted actor. -> ("ok", descriptors)
        with the execute_task result shape (inline/stored per return),
        ("err", blob) for application errors, ("dead", blob) when the
        actor process died, ("gone",) when this daemon does not host the
        actor (e.g. it restarted).

        ``awaiting_create``: the caller pipelined this call behind an
        in-flight create_actor on the same connection — wait for the
        constructor to land (or fail) instead of bouncing "gone", so
        __init__ and the first method call(s) execute back-to-back with
        no driver round trip between them. Plain calls keep the instant
        "gone" (crash detection must not stall)."""
        from ray_tpu._private.worker_pool import (
            _WorkerUnavailable,
        )
        from ray_tpu.exceptions import WorkerCrashedError

        with self._actors_lock:
            actor = self._actors.get(actor_key)
        if actor is None and awaiting_create:
            actor = self._await_actor(actor_key)
        if actor is None:
            return ("gone",)
        try:
            args, kwargs = serialization.deserialize_from_buffer(
                memoryview(args_blob))
            args, kwargs = self._resolve_fetch_args(args, kwargs,
                                                    to_shm=True)
            call_blob = serialization.serialize_framed((args, kwargs))
            status, payload = actor.call(method, call_blob,
                                         max(1, n_returns))
        except (WorkerCrashedError, _WorkerUnavailable) as exc:
            self._reap_actor(actor_key)
            return ("dead", _exc_blob(exc))
        except BaseException as exc:  # noqa: BLE001 — shipped to driver
            return ("err", _exc_blob(exc))
        if status == "err":
            return ("err", payload)
        out = []
        for id_bytes, packed in zip(return_keys, payload):
            blob = self._packed_to_blob(id_bytes, packed)
            if blob is None:
                out.append(packed)  # ("err", blob) passthrough
                continue
            if len(blob) <= _inline_reply_bytes():
                out.append(("inline", blob))
            else:
                self.store.put(id_bytes, blob,
                               owner=getattr(actor, "owner", None))
                self._maybe_export_stored(id_bytes, blob)
                out.append(("stored", len(blob)))
        return ("ok", out)

    def _await_actor(self, actor_key: bytes,
                     grace_s: float = 10.0,
                     create_timeout_s: float = 600.0):
        """Gate for pipelined first calls: wait for the key's in-flight
        creation. The short grace also covers the race where the call's
        dispatch thread outran the create frame's (the driver sent
        create first on the same connection, so the key turns
        "creating" within moments)."""
        import time as _time

        grace_deadline = _time.monotonic() + grace_s
        deadline = _time.monotonic() + create_timeout_s
        seen_creating = False
        with self._actors_creating_cond:
            while True:
                actor = self._actors.get(actor_key)
                if actor is not None:
                    return actor
                now = _time.monotonic()
                if actor_key in self._actors_creating:
                    seen_creating = True
                    if now > deadline:
                        return None
                    self._actors_creating_cond.wait(
                        min(1.0, deadline - now))
                else:
                    # Creation finished without hosting the actor
                    # (busy/err): bounce immediately — the driver
                    # resends once its creation settles elsewhere.
                    if seen_creating or now > grace_deadline:
                        return None
                    self._actors_creating_cond.wait(0.05)

    def _warm_factory_once(self) -> None:
        """First-work trigger: warm the fork-server template in the
        background so the spawn that follows pays only the remaining
        boot time (reference: worker_pool.h prestarts workers ahead of
        demand). NOT at daemon start — a 100-daemon single-box cluster
        would stampede 100 factory boots onto the cores before any
        work arrives (nodes that never execute should never fork)."""
        if getattr(self, "_factory_warmed", False) \
                or os.environ.get("RAY_TPU_WORKER_FACTORY_DISABLE"):
            return
        self._factory_warmed = True

        def _warm():
            try:
                from ray_tpu._private.worker_pool import _get_factory

                _get_factory()
            except Exception:  # noqa: BLE001 — spawns fall back
                pass

        threading.Thread(target=_warm, daemon=True,
                         name="factory-prewarm").start()

    def _take_standby(self, extra_env: dict | None):
        """Pop a live prestarted worker for this spawn env (None on
        miss) and kick an async refill either way."""
        key = tuple(sorted((extra_env or {}).items()))
        worker = None
        with self._standby_lock:
            pool = self._standby.get(key, [])
            while pool:
                candidate = pool.pop()
                if candidate.alive():
                    worker = candidate
                    break
                candidate.stop()
        self._refill_standby(key, extra_env)
        return worker

    def _refill_standby(self, key: tuple, extra_env: dict | None) -> None:
        with self._standby_lock:
            if key in self._standby_refilling:
                return
            self._standby_refilling.add(key)

        def refill():
            from ray_tpu._private.worker_pool import PoolWorker

            try:
                while not self._stop_event.is_set():
                    with self._standby_lock:
                        if len(self._standby.get(key, [])) >= \
                                self._standby_target:
                            return
                    try:
                        worker = PoolWorker(-1, extra_env=dict(key))
                    except Exception:  # noqa: BLE001 — next take forks
                        return
                    with self._standby_lock:
                        if self._stop_event.is_set():
                            worker.stop()
                            return
                        self._standby.setdefault(key, []).append(worker)
            finally:
                with self._standby_lock:
                    self._standby_refilling.discard(key)

        threading.Thread(target=refill, daemon=True,
                         name="actor-standby-refill").start()

    def actor_kill(self, actor_key: bytes) -> bool:
        return self._reap_actor(actor_key)

    def _reap_actor(self, actor_key: bytes) -> bool:
        with self._actors_lock:
            actor = self._actors.pop(actor_key, None)
        with self._running_lock:
            self._running.pop("actor-" + actor_key.hex(), None)
        self._notify_load()
        if actor is None:
            return False
        actor.kill()
        self._chip_leases.release(actor_key)
        return True

    def _packed_to_blob(self, id_bytes: bytes, packed: tuple):
        """Worker-pipe result descriptor -> framed blob (None for error
        descriptors, which pass through to the driver)."""
        from ray_tpu._private.ids import ObjectID as _OID
        from ray_tpu._private.shm_store import (
            ArenaDescriptor,
            ShmDescriptor,
        )

        kind = packed[0]
        if kind == "inline":
            return packed[1]  # already framed bytes
        if kind == "arena":
            desc = ArenaDescriptor(packed[1], packed[2])
            self._shm_directory.register_arena(_OID(id_bytes), desc)
            value = self._shm_client.get(desc)
            blob = serialization.serialize_framed(value)
            self._shm_directory.free(_OID(id_bytes))
            return blob
        if kind == "shm":
            desc = ShmDescriptor(packed[1], packed[2])
            rid = _OID(id_bytes)
            self._shm_directory.adopt(rid, desc)
            value = self._shm_client.get(desc)
            blob = serialization.serialize_framed(value)
            self._shm_client.close_segment(desc.name)
            self._shm_directory.free(rid)
            return blob
        return None  # ("err", blob)

    def available_resources(self) -> dict[str, float]:
        """Heartbeat piggyback: total minus the demands of running
        tasks (ray_syncer-lite view for dashboards/autoscaler)."""
        avail = dict(self._resources)
        with self._running_lock:
            for demand in self._running.values():
                for key, value in demand.items():
                    avail[key] = avail.get(key, 0.0) - value
        return avail

    # ------------------------------------------------------------- internals

    def _resolve_fetch_args(self, args: tuple, kwargs: dict,
                            to_shm: bool = False):
        """Resolve FetchRef placeholders. ``to_shm=True`` (worker-bound
        paths) maps each pulled framed blob into a shared-memory
        segment ONCE and substitutes an _ShmRef: the worker
        deserializes straight from the mapping — the daemon never pays
        a deserialize + re-serialize + pipe copy of the payload, and
        repeated tasks using the same broadcast arg share one segment
        (reference: plasma is host-shared by design,
        object_manager/plasma/store_runner.h)."""
        from ray_tpu._private.worker_pool import _ShmRef

        def convert(a):
            if not isinstance(a, FetchRef):
                return a
            if to_shm:
                return _ShmRef(self._shm_fetch_blob(a))
            return self._load_object(a)

        return (tuple(convert(a) for a in args),
                {k: convert(v) for k, v in kwargs.items()})

    def _shm_fetch_blob(self, ref: FetchRef):
        """Framed blob of ``ref`` as a shared-memory descriptor
        (single-flight per object; bounded cache, FIFO eviction).
        Remote pulls land straight in the segment; locally-stored
        blobs are copied into one once and reused by every task."""
        key = ref.id_bytes
        with self._shm_args_lock:
            desc = self._shm_directory.lookup(key)
            # Spill protection: this desc is about to ride a worker
            # frame — the spiller must not unlink its segment before
            # the worker attaches.
            self._shm_out_stamp[key] = time.monotonic()
        if desc is not None:
            return desc
        blob = self.store.get(key)
        if blob is not None:
            return self._blob_to_shm(key, blob)
        return self._fetch_remote(ref, to_shm=True)

    def _load_object(self, ref: FetchRef) -> Any:
        blob = self.store.get(ref.id_bytes)
        if blob is None:
            with self._partials_lock:
                part = self._partials.get(ref.id_bytes)
                if part is not None and part.done.is_set() \
                        and part.error is None:
                    try:
                        blob = bytes(part.buf)
                    except ValueError:
                        blob = None  # view released by eviction
        if blob is None:
            # Peer pull (node-to-node; the driver is never in the path).
            blob = self._fetch_remote(ref)
        return serialization.deserialize_from_buffer(memoryview(blob))

    def _fetch_remote(self, ref: FetchRef, to_shm: bool = False):
        """Pull ``ref`` from the cluster: P2P chunked when the object is
        large enough — the owner hands out a plan (size + holders), this
        node registers partial possession and fetches chunks in parallel
        from every node that has them while relaying its own — plain
        pipelined owner pull otherwise.

        Returns the framed bytes, or (``to_shm=True``) a ShmDescriptor
        whose segment the chunks were pulled STRAIGHT into — the
        worker-bound path never materializes an intermediate copy of
        the whole object."""
        from ray_tpu._private.config import GLOBAL_CONFIG
        from ray_tpu._private.same_host import map_enabled

        owner = self._peers.get(ref.addr)
        try:
            # fetch_plan is an idempotent read: ride the shared retry
            # policy so one dropped frame doesn't fail a pull whose
            # owner is alive (exhausted retries propagate — the caller
            # owns the lost-node fallback).
            from ray_tpu._private.rpc import call_with_retry

            plan = call_with_retry(
                owner.call, "fetch_plan", ref.id_bytes,
                self.advertised_address,
                self.host_id if map_enabled() else None,
                attempts=2, timeout_s=30.0)
        except RpcMethodError:
            plan = None  # owner predates fetch_plan
        map_info = plan[2] if plan is not None and len(plan) > 2 \
            else None
        if plan is not None and len(plan) > 3 and plan[3] \
                and plan[3].get("spilled"):
            # The holder's copy is on its disk tier: no map lease can
            # exist and the first chunk pays the holder's restore.
            self.spilled_plan_hits += 1
        if map_info is not None:
            # Co-hosted holder: map its shared memory (or memcpy out of
            # it) instead of moving the bytes through the transport.
            result = self._try_same_host(ref, map_info, to_shm)
            if result is not None:
                return result
        chunk = _fetch_chunk_bytes()
        n_chunks = (-(-plan[0] // chunk)
                    if plan is not None and plan[0] else 0)
        if plan is None or \
                n_chunks < int(GLOBAL_CONFIG.broadcast_min_p2p_chunks):
            self.chunked_pulls += 1
            blob = fetch_blob(owner, ref.id_bytes)
            if to_shm:
                return self._blob_to_shm(ref.id_bytes, blob)
            self.store.put(ref.id_bytes, blob, cached=True)
            return blob
        total, holders = plan[0], plan[1]
        # Single-flight per object: concurrent tasks needing the same
        # arg share one pull instead of racing duplicate transfers.
        with self._partials_lock:
            part = self._partials.get(ref.id_bytes)
            leader = part is None or (part.done.is_set()
                                      and part.error is not None)
            if leader:
                seg = None
                if to_shm:
                    from multiprocessing import shared_memory

                    seg = shared_memory.SharedMemory(
                        create=True, size=max(total, 1))
                    part = _PartialBlob(total, chunk,
                                        buf=memoryview(seg.buf))
                else:
                    part = _PartialBlob(total, chunk)
                self._partials[ref.id_bytes] = part
        if not leader:
            part.done.wait()
            if part.error is None:
                if to_shm:
                    return self._blob_to_shm(ref.id_bytes, None,
                                             part=part)
                with part.lock:
                    return bytes(part.buf)
            # Leader failed; retry as a plain owner pull.
            self.chunked_pulls += 1
            blob = fetch_blob(owner, ref.id_bytes)
            if to_shm:
                return self._blob_to_shm(ref.id_bytes, blob)
            self.store.put(ref.id_bytes, blob, cached=True)
            return blob
        self.chunked_pulls += 1
        try:
            self._pull_chunks(ref, part, holders)
        except BaseException as exc:  # noqa: BLE001 — release waiters
            with self._partials_lock:
                if self._partials.get(ref.id_bytes) is part:
                    del self._partials[ref.id_bytes]
            part.fail(exc)
            if seg is not None:
                try:
                    part.buf.release()
                    seg.unlink()
                    seg.close()
                except (OSError, BufferError):
                    pass  # partial already unusable; raising below
            raise
        if to_shm:
            # The segment is the final copy: register it (workers map
            # it) BEFORE waking waiters, then keep the partial as the
            # relay-serving view.
            desc = self._register_shm_arg(ref.id_bytes, seg, total)
            part.finish()
            self._trim_relays()
            return desc
        blob = part.finish()
        self.store.put(ref.id_bytes, blob, cached=True)
        # Keep serving as a relay while peers are mid-pull — unless the
        # store's pull cache retained the blob (then it serves).
        if self.store.size(ref.id_bytes) is not None:
            with self._partials_lock:
                if self._partials.get(ref.id_bytes) is part:
                    del self._partials[ref.id_bytes]
        else:
            self._trim_relays()
        return blob

    def _try_same_host(self, ref: FetchRef, info: dict, to_shm: bool):
        """Consume a granted same-host map lease: attach the holder's
        segment (zero-copy hand-off to workers) or its arena (cross-
        arena descriptor / single memcpy). Returns a descriptor
        (``to_shm``) or the framed bytes, or None to fall back to the
        chunked path — any failure releases the lease first."""
        key = ref.id_bytes
        token = info.get("token")
        owner_addr = ref.addr
        try:
            if info.get("host") != self.host_id or not token:
                if token:
                    self._unpin_at(owner_addr, token)
                return None
            size = int(info.get("size", 0))
            if info.get("kind") == "seg":
                from ray_tpu._private.same_host import attach_segment
                from ray_tpu._private.shm_store import ShmDescriptor

                try:
                    seg = attach_segment(info["name"])
                except (OSError, ValueError):
                    self._unpin_at(owner_addr, token)
                    return None  # holder freed it: chunked decides
                if to_shm:
                    desc = self._register_shm_arg(
                        key, seg, size,
                        desc=ShmDescriptor(info["name"], size),
                        attached=(owner_addr, token))
                    self.same_host_map_hits += 1
                    return desc
                try:
                    blob = bytes(seg.buf[:size])
                finally:
                    try:
                        seg.close()
                    except (BufferError, OSError):
                        pass  # peer may hold exports; tracker reaps
                self._unpin_at(owner_addr, token)
                self.same_host_copy_hits += 1
                self.store.put(key, blob, cached=True)
                return blob
            if info.get("kind") == "arena":
                view = self._peer_arenas.view(info["name"], info["key"])
                if view is None:
                    self._unpin_at(owner_addr, token)
                    return None
                if to_shm:
                    from ray_tpu._private.shm_store import (
                        PeerArenaDescriptor,
                    )

                    desc = self._register_shm_arg(
                        key, None, size,
                        desc=PeerArenaDescriptor(
                            info["name"], info["key"], size),
                        attached=(owner_addr, token))
                    self.same_host_map_hits += 1
                    return desc
                blob = bytes(view[:size])
                self._unpin_at(owner_addr, token)
                self.same_host_copy_hits += 1
                self.store.put(key, blob, cached=True)
                return blob
            self._unpin_at(owner_addr, token)
            return None
        except Exception:  # noqa: BLE001 — any failure: chunked path
            if token:
                self._unpin_at(owner_addr, token)
            return None

    def _blob_to_shm(self, key: bytes, blob: bytes | None, part=None):
        """Assembled-bytes fallback into a shared segment (small
        objects, plain pulls, non-leader waiters)."""
        from multiprocessing import shared_memory

        with self._shm_args_lock:
            existing = self._shm_directory.lookup(key)
        if existing is not None:
            return existing
        if blob is None:
            with part.lock:
                blob = bytes(part.buf)
        seg = shared_memory.SharedMemory(create=True,
                                         size=max(len(blob), 1))
        seg.buf[:len(blob)] = blob
        return self._register_shm_arg(key, seg, len(blob))

    def _register_shm_arg(self, key: bytes, seg, size: int,
                          desc=None, attached: tuple | None = None):
        """Record a worker-mappable descriptor in the node's shm
        directory (FIFO-bounded; loser of a concurrent promote race
        discards its segment).

        Owned segments (``attached is None``) are also advertised as
        same-host map sources. ``attached=(owner_addr, token)`` records
        a PEER-owned mapping instead: never advertised, never
        unlinked, and its lease is unpinned at the owner when the entry
        is dropped."""
        from ray_tpu._private.config import GLOBAL_CONFIG
        from ray_tpu._private.shm_store import ShmDescriptor

        if desc is None:
            desc = ShmDescriptor(seg.name, size)
        evict: list = []
        redundant_lease = None
        with self._shm_args_lock:
            existing = self._shm_directory.lookup(key)
            if existing is not None:
                # Concurrent promote won (no partial references OUR
                # segment here — leaders are single-flight): discard,
                # and release a now-redundant lease AFTER the lock
                # (the unpin call may connect a socket).
                if seg is not None:
                    try:
                        if attached is None:
                            seg.unlink()
                        seg.close()
                    except (OSError, BufferError):
                        pass  # peer may hold exports; tracker reaps
                if attached is not None:
                    redundant_lease = attached
            else:
                self._shm_directory.register(
                    key, desc, seg if attached is None else None)
                if attached is not None:
                    self._attached[key] = (attached[0], attached[1], seg)
                elif seg is not None:
                    self._map_sources[key] = ("seg", seg.name, size)
                self._shm_args_order.append((key, size))
                self._shm_args_bytes += size
                limit = int(GLOBAL_CONFIG.node_pull_cache_mb) \
                    * 1024 * 1024
                while self._shm_args_bytes > limit \
                        and len(self._shm_args_order) > 1:
                    old_key, old_size = self._shm_args_order.pop(0)
                    self._shm_args_bytes -= old_size
                    evict.append(old_key)
        if redundant_lease is not None:
            self._unpin_at(redundant_lease[0], redundant_lease[1])
            return existing
        if existing is not None:
            return existing
        for old_key in evict:
            # Relay partials viewing the evicted segment must release
            # their buffer before the unlink (exported-view safety).
            with self._partials_lock:
                old_part = self._partials.pop(old_key, None)
            if old_part is not None and old_part.external:
                with old_part.lock:
                    try:
                        old_part.buf.release()
                    except BufferError:
                        pass
            self._release_plane_state(old_key)
            self._shm_directory.free(old_key)
        return desc

    def _release_plane_state(self, key: bytes) -> None:
        """Same-host plane GC for one object: drop its owner-side map
        source (+ any leases peers hold on it) and, if this daemon
        holds a PEER's mapping for it, close that and unpin at the
        owner."""
        with self._shm_args_lock:
            self._map_sources.pop(key, None)
            attached = self._attached.pop(key, None)
        self.leases.release_object(key)
        if attached is not None:
            owner_addr, token, seg = attached
            if seg is not None:
                try:
                    seg.close()
                except (BufferError, OSError):
                    pass  # peer may hold exports; tracker reaps
            self._unpin_at(owner_addr, token)

    def _unpin_at(self, owner_addr: str, token: str) -> None:
        """Fire-and-forget lease release at the owner (its TTL sweep
        is the backstop when this RPC is lost)."""
        try:
            self._peers.get(owner_addr).call_async("unpin_object", token)
        except Exception:  # noqa: BLE001 — owner gone: nothing to unpin
            pass

    def _pull_chunks(self, ref: FetchRef, part: _PartialBlob,
                     holders: list[str]) -> None:
        """Sliding-window parallel chunk fetch across owner + peers.

        Chunk order is rotated by a stable hash of this node's address,
        so concurrent receivers start in different regions — the owner
        seeds distinct chunks round-robin and receivers exchange the
        rest among themselves. Routing is REGION-AWARE: every receiver
        derives its peers' start offsets from the same hash, so a chunk
        is requested from the peer that began pulling its region
        earliest (highest hit probability); misses re-issue to the
        owner asynchronously — never a window stall.

        Node-death hardening: a peer that DIES mid-chunk (transport
        failure, not a mere chunk miss) is blacklisted for the rest of
        the pull; when the OWNER dies, the pull re-plans against a
        surviving full holder (any daemon answering ``fetch_plan`` for
        the object) and continues from there — a 1->N broadcast
        survives the producer's crash once one receiver finished."""
        import zlib
        from collections import deque

        from ray_tpu._private.config import GLOBAL_CONFIG

        owner_addr = ref.addr
        fanout = max(0, int(GLOBAL_CONFIG.broadcast_chunk_fanout))
        n_chunks = part.n_chunks()
        my_addr = self.advertised_address
        dead: set[str] = set()
        known_holders = [a for a in holders if a and a != my_addr]

        def peer_starts(addrs: list[str]) -> dict[str, int]:
            return {a: zlib.crc32(a.encode()) % n_chunks
                    for a in dict.fromkeys(addrs)
                    if a and a != my_addr and a not in dead}

        starts = peer_starts(holders[:fanout])
        start = zlib.crc32(my_addr.encode()) % n_chunks
        order = list(range(start, n_chunks)) + list(range(start))
        owner = self._peers.get(owner_addr)
        depth = _pipeline_depth()
        pending: deque = deque()
        completed = 0

        def pick_source(idx: int) -> str:
            # The peer whose rotated start is closest BEHIND idx pulled
            # that region first; beyond half a revolution the owner is
            # the better bet (the peer likely hasn't reached it).
            best, bestd = owner_addr, n_chunks // 2
            for src, s in starts.items():
                d = (idx - s) % n_chunks
                if d < bestd:
                    best, bestd = src, d
            return best

        def issue(idx: int, src: str, attempts: int):
            nonlocal owner_addr, owner
            length = min(part.chunk, part.total - idx * part.chunk)
            while True:
                try:
                    slot = self._peers.get(src).call_async(
                        "fetch_object", ref.id_bytes,
                        idx * part.chunk, length)
                except (RpcError, RpcMethodError, OSError):
                    # Connect-time death (the async path surfaces a
                    # dead peer synchronously): same failover as a
                    # failed in-flight chunk.
                    blacklist(src)
                    if src == owner_addr:
                        survivor = replan_owner()
                        if survivor is None:
                            raise KeyError(
                                f"object {ref.id_bytes.hex()}: owner "
                                f"{owner_addr} unreachable and no "
                                f"surviving holder has a full copy")
                        owner_addr = survivor
                        owner = self._peers.get(owner_addr)
                    attempts += 1
                    if attempts > 3:
                        raise KeyError(
                            f"object {ref.id_bytes.hex()} unreachable "
                            f"on every source")
                    src = owner_addr
                    continue
                pending.append((idx, src, slot, attempts))
                return

        def blacklist(src: str) -> None:
            if src not in dead:
                dead.add(src)
                starts.pop(src, None)
                self.peer_blacklists += 1

        def replan_owner() -> str | None:
            # The authoritative owner died mid-pull: any surviving
            # holder with the FULL object (its fetch_plan reports the
            # total) can serve as the new authority for re-issues and
            # holder refreshes. Partial relays stay chunk sources but
            # cannot anchor retries — a miss there must escalate
            # somewhere that provably has the byte range.
            for addr in dict.fromkeys(list(starts) + known_holders):
                if addr in dead or addr == my_addr:
                    continue
                try:
                    plan = self._peers.get(addr).call(
                        "fetch_plan", ref.id_bytes, my_addr,
                        timeout_s=5.0)
                except (RpcError, RpcMethodError, OSError):
                    blacklist(addr)
                    continue
                if plan is not None and plan[0] == part.total \
                        and self._peers.get(addr).call(
                            "fetch_object", ref.id_bytes, 0, 1,
                            timeout_s=5.0) is not None:
                    return addr
            return None

        it = iter(order)
        exhausted = False
        while pending or not exhausted:
            while not exhausted and len(pending) < depth:
                try:
                    idx = next(it)
                except StopIteration:
                    exhausted = True
                    break
                issue(idx, pick_source(idx), 0)
            if not pending:
                continue
            idx, src, slot, attempts = pending.popleft()
            transport_dead = False
            try:
                reply = slot.result()
            except (RpcError, RpcMethodError):
                reply = None
                transport_dead = True
            if reply is None:
                if transport_dead:
                    # The SOURCE died (vs a mere chunk miss: the peer
                    # answered "don't have it" and stays a candidate).
                    blacklist(src)
                    if src == owner_addr:
                        survivor = replan_owner()
                        if survivor is None:
                            raise KeyError(
                                f"object {ref.id_bytes.hex()}: owner "
                                f"{owner_addr} died mid-pull and no "
                                f"surviving holder has a full copy")
                        owner_addr = survivor
                        owner = self._peers.get(owner_addr)
                if attempts >= 3:
                    raise KeyError(
                        f"object {ref.id_bytes.hex()} not present on "
                        f"{owner_addr}")
                # Re-issue to the authoritative owner (possibly just
                # re-planned) WITHOUT blocking the window.
                issue(idx, owner_addr, attempts + 1)
                continue
            _, data = reply
            part.write(idx, data)
            completed += 1
            if completed % 64 == 0:
                # Refresh the holder set: pullers that registered after
                # our plan are fresh relay sources (and this re-leases
                # our own registration with the owner's directory).
                try:
                    plan = owner.call("fetch_plan", ref.id_bytes,
                                      my_addr)
                    if plan is not None:
                        starts = peer_starts(plan[1][:fanout])
                except (RpcError, RpcMethodError):
                    pass

    _RELAY_TTL_S = 180.0

    def _sweep_transfer_plane(self) -> None:
        """Periodic GC for the P2P plane: expired relay copies and
        stale holder registrations."""
        import time as _time

        now = _time.monotonic()
        expired = []
        with self._partials_lock:
            for id_bytes in [
                    i for i, p in self._partials.items()
                    if p.completed_at is not None
                    and now - p.completed_at > self._RELAY_TTL_S]:
                expired.append(self._partials.pop(id_bytes))
        for part in expired:
            if part.external:
                with part.lock:
                    try:
                        part.buf.release()
                    except BufferError:
                        pass
        self.chunk_directory.prune()
        # Same-host pin leases: expire grants that outlived the TTL
        # whose holder stopped answering pings (a SIGKILLed puller must
        # not pin this daemon's memory forever).
        from ray_tpu._private.same_host import pin_ttl_s

        def _probe(addr: str) -> bool:
            probe = RpcClient(addr, timeout_s=2.0, connect_timeout_s=1.0)
            try:
                return probe.call("ping") == "pong"
            finally:
                probe.close()

        self.leases.sweep(pin_ttl_s(), _probe)
        # Puller side: peer-owned mappings whose OWNER died are orphans
        # — the lease backing the pin is gone with the owner, so the
        # attachment is released (segment closed, directory entry
        # dropped; the next consumer re-pulls and falls back to the
        # chunked path / lineage). Two consecutive failed probes
        # required: one transient miss must not drop a live owner's
        # mappings out from under its workers.
        with self._shm_args_lock:
            owners = {addr for addr, _, _ in self._attached.values()}
        for addr in owners:
            alive = False
            try:
                alive = _probe(addr)
            except Exception:  # noqa: BLE001 — unreachable
                alive = False
            if alive:
                self._attached_owner_strikes.pop(addr, None)
                continue
            strikes = self._attached_owner_strikes.get(addr, 0) + 1
            self._attached_owner_strikes[addr] = strikes
            if strikes < 2:
                continue
            self._attached_owner_strikes.pop(addr, None)
            with self._shm_args_lock:
                victims = [k for k, (a, _, _) in self._attached.items()
                           if a == addr]
            for key in victims:
                self._drop_shm_arg(key)
                self.lease_orphans_swept += 1
        # Crashed co-hosted owners' native arena segments have no
        # surviving unlinker; any live daemon reaps them.
        from ray_tpu._private.same_host import sweep_orphan_shm

        self.arena_orphans_swept += sweep_orphan_shm()
        # Same for a SIGKILLed owner's per-pid spill directory: its
        # files back objects whose store died with it — any co-hosted
        # survivor deletes the whole tier (pid-liveness gated).
        from ray_tpu._private import spill_manager as _spill_mod

        if _spill_mod.SPILL_ON:
            swept = _spill_mod.sweep_orphan_spill_dirs()
            if swept and self._spill_mgr is not None:
                with self._spill_mgr._lock:
                    self._spill_mgr.orphan_dirs_swept += swept

    def _trim_relays(self) -> None:
        """Bound completed relay copies by node_relay_cache_mb (oldest
        finished pulls evicted first; in-progress pulls never are)."""
        from ray_tpu._private.config import GLOBAL_CONFIG

        limit = int(GLOBAL_CONFIG.node_relay_cache_mb) * 1024 * 1024
        evicted = []
        with self._partials_lock:
            finished = sorted(
                ((id_bytes, p) for id_bytes, p in self._partials.items()
                 if p.completed_at is not None),
                key=lambda kv: kv[1].completed_at)
            total = sum(p.total for _, p in finished)
            for id_bytes, p in finished:
                if total <= limit:
                    break
                evicted.append(self._partials.pop(id_bytes))
                total -= p.total
        for part in evicted:
            if part.external:
                with part.lock:
                    try:
                        part.buf.release()
                    except BufferError:
                        pass

    def _run(self, func, digest, func_blob, args, kwargs, n_returns,
             runtime_env, resources, task_token=None,
             client_addr=None, trace=None, trace_stages=None) -> list:
        if any(k.startswith("TPU") for k in resources):
            # TPU tasks run in the daemon process, which must then be
            # the one owner of this node's chips (pool workers are
            # pinned to CPU; with a chip leased to an actor child this
            # raises ChipOwnershipError, typed, to the caller). Each
            # runs on its own dispatch thread (mux server), so a long
            # TPU task never blocks the connection loop; concurrency
            # between TPU tasks is bounded by admission (TPU resource
            # units), and JAX dispatch itself is thread-safe — a mutual-
            # exclusion lock here would deadlock nested TPU-task
            # submission (outer holds it while blocked in get()).
            self._chip_leases.claim_in_process("a TPU task on this node")
            if perf.PERF_ON:
                # In-daemon run: this dispatch thread IS the executor,
                # so thread_time here is the task's real cpu-seconds.
                sample = perf.sample_start()
                result = func(*args, **kwargs)
                perf.record_task_resources(*perf.sample_end(
                    getattr(func, "__qualname__", digest[:8]), sample))
            else:
                result = func(*args, **kwargs)
        else:
            from ray_tpu._private.worker_pool import _RemoteTaskError

            args_blob = serialization.serialize_framed((args, kwargs))
            if func_blob is None:
                func_blob = serialization.dumps_function(func)
            return_ids = [ObjectID() for _ in range(max(1, n_returns))]
            with self._func_lock:
                sys_path = self._driver_sys_path or None
            try:
                pairs = self.pool.run_task_blobs(
                    digest, func_blob, args_blob, n_returns, return_ids,
                    runtime_env=runtime_env, task_token=task_token,
                    client_addr=client_addr, sys_path=sys_path,
                    trace=trace, stages_out=trace_stages)
            except _RemoteTaskError as rte:
                rte.cause.__ray_tpu_remote_tb__ = rte.remote_tb
                raise rte.cause from None
            return [value for _, value in pairs]
        if n_returns == 0:
            return []
        if n_returns == 1:
            return [result]
        if not isinstance(result, (tuple, list)) or len(result) != n_returns:
            raise ValueError(
                f"task declared num_returns={n_returns} but returned "
                f"{type(result).__name__}")
        return list(result)


def _exc_blob(exc: BaseException) -> bytes:
    import traceback

    tb = "".join(traceback.format_exception(type(exc), exc,
                                            exc.__traceback__))
    try:
        return serialization.serialize_framed((exc, tb))
    except Exception:  # noqa: BLE001 — unpicklable exception
        return serialization.serialize_framed(
            (RuntimeError(f"{type(exc).__name__}: {exc}"), tb))


# --------------------------------------------------------------------------
# Driver side
# --------------------------------------------------------------------------


class RemoteNodeHandle:
    """Driver-side handle to one worker-node executor.

    All task/actor traffic multiplexes on ONE socket (``self.pool``):
    N in-flight calls are seq-tagged and interleaved, not N sockets
    (reference: async completion queues, src/ray/rpc/client_call.h)."""

    def __init__(self, node_id, address: str):
        self.node_id = node_id
        self.address = address
        # "pool" kept for call-site compatibility: it is one multiplexed
        # connection that behaves like an unbounded pool.
        self.pool = MuxRpcClient(address)
        # Monotonic→driver-clock offset estimate for THIS node, anchored
        # half-RTT on traced execute replies (util/tracing.ClockSync):
        # merged timelines correct the daemon's stage stamps with it.
        from ray_tpu.util import tracing

        self.clock = tracing.ClockSync()
        # Short-timeout client for watcher-thread control calls: a ping
        # to an unreachable address must fail fast, never stall the
        # watcher behind the pool's task-length timeouts.
        self._control = RpcClient(address, timeout_s=5.0,
                                  connect_timeout_s=2.0)
        self._digest_lock = lock_witness.Lock(
            "node_executor.RemoteNodeHandle.digest")
        self.known_digests: set[str] = set()
        self._sys_path_sent = False

    def ping(self) -> bool:
        try:
            return self._control.call("ping") == "pong"
        except (RpcError, OSError):
            return False

    def ensure_sys_path(self) -> None:
        """One-shot: hand the node this driver's import paths so
        by-reference pickles (module-level functions/classes) resolve
        there (one-machine clusters share the filesystem)."""
        if self._sys_path_sent:
            return
        import sys

        from ray_tpu._private.rpc import RpcMethodError

        try:
            self._control.call("adopt_sys_path",
                               [p for p in sys.path if p])
            self._sys_path_sent = True
        except (RpcError, RpcMethodError, OSError):
            pass  # best-effort; retried on the next execute

    def execute(self, digest: str, func_blob: bytes, args_blob: bytes,
                n_returns: int, return_keys: list[bytes],
                runtime_env: dict | None,
                resources: dict[str, float],
                task_token: str | None = None,
                client_addr: str | None = None,
                trace_ctx: tuple | None = None,
                deadline: float | None = None) -> tuple:
        """Lease + push + reply. Ships the function blob only the first
        time this node sees its digest. Returns ``(results, trace)``
        where ``trace`` is the daemon's piggybacked trace payload
        (stage stamps + spans + wall clock) or None. Raises
        TaskDeadlineExpired / NodeOverloadedError when the daemon
        refused the lease (deadline dead on arrival / admission shed).
        """
        self.ensure_sys_path()
        with self._digest_lock:
            known = digest in self.known_digests
        # Tracing/deadlines ride as RPC kwargs only when armed: the
        # plain wire shape is byte-identical to before.
        extra = {} if trace_ctx is None else {"trace_ctx": trace_ctx}
        if deadline is not None:
            extra["deadline"] = deadline
        # Coalesced: burst submissions to this node share __batch__
        # frames (one syscall/server wakeup per batch); replies are
        # still per-call, so nothing head-of-line blocks.
        reply = self.pool.call(
            "execute_task", digest, None if known else func_blob,
            args_blob, n_returns, return_keys, runtime_env, resources,
            task_token, client_addr, coalesce=True, **extra)
        if reply[0] == "need_func":
            # Node restarted / cache miss despite our bookkeeping: send
            # the function ALONE — the node stashed the args from the
            # first attempt under a nonce, so they are not re-shipped.
            nonce = reply[1] if len(reply) > 1 else None
            reply = self.pool.call(
                "execute_task", digest, func_blob,
                None if nonce else args_blob, n_returns,
                return_keys, runtime_env, resources, task_token,
                client_addr, nonce, **extra)
            if reply[0] == "stale_args":
                # The stash was evicted between the two calls: full resend.
                reply = self.pool.call(
                    "execute_task", digest, func_blob, args_blob,
                    n_returns, return_keys, runtime_env, resources,
                    task_token, client_addr, **extra)
        if reply[0] == "busy":
            raise NodeBusyError(self.address)
        if reply[0] == "overloaded":
            raise NodeOverloadedError(
                reply[1] if len(reply) > 1 else "admission shed")
        if reply[0] == "timeout":
            raise TaskDeadlineExpired(
                reply[1] if len(reply) > 1 else "admitted")
        if reply[0] == "cancelled":
            raise TaskSpeculationCancelled(self.address)
        with self._digest_lock:
            self.known_digests.add(digest)
        if reply[0] == "err":
            exc, tb = serialization.deserialize_from_buffer(
                memoryview(reply[1]))
            exc.__ray_tpu_remote_tb__ = tb
            raise exc
        return reply[1], (reply[2] if len(reply) > 2 else None)

    def execute_batch(self, entries: list, on_results,
                      on_parked=None, on_resumed=None,
                      client_addr: str | None = None,
                      on_started=None, on_col=None) -> int:
        """One execute_task_batch RPC for a run of tasks leased to this
        node. ``on_results(group)`` fires per streamed completion group
        with [(idx, reply), ...] (execute_task reply shape per task);
        parked/resumed control parts report frames stuck behind a
        blocked lease head; ``on_started(idx)`` marks an entry
        MAYBE-STARTED (its frame reached a worker) — the caller's
        node-death accounting splits unstarted entries (requeued
        invisibly) from started ones (retried under the system-failure
        budget). Returns (replies delivered, fused stats from the
        final ("done", n, stats) reply — {} from a pre-fused daemon);
        the caller fails any missing indexes (stream cut mid-batch).
        Raises RpcError/RpcMethodError like ``execute``."""
        self.ensure_sys_path()
        slot = self.pool.call_streaming(
            "execute_task_batch", entries, client_addr)
        delivered = 0
        while True:
            part = slot.next_part()
            if part is None:
                break
            kind, payload = part
            if kind == "results":
                delivered += len(payload)
                on_results(payload)
            elif kind == "colresults" and on_col is not None:
                # Columnar reply group: (start_idx, [payload…]) — raw
                # inline blobs for the happy path, classic reply
                # tuples for everything else.
                delivered += len(payload[1])
                on_col(payload)
            elif kind == "started" and on_started is not None:
                on_started(payload)
            elif kind == "started_many" and on_started is not None:
                # Fused-run ambiguity window: every member is
                # maybe-started from this part on (one part per window
                # instead of one per task).
                for idx in payload:
                    on_started(idx)
            elif kind == "parked" and on_parked is not None:
                on_parked(payload)
            elif kind == "resumed" and on_resumed is not None:
                on_resumed(payload)
        done = slot.result()  # surfaces transport/method failures
        stats = done[2] if isinstance(done, tuple) and len(done) > 2 \
            else {}
        return delivered, stats

    def fetch(self, id_bytes: bytes) -> bytes:
        return fetch_blob(self.pool, id_bytes)

    def free(self, ids: list[bytes]) -> None:
        self._control.call("free_objects", ids)

    def close(self) -> None:
        self._control.close()
        self.pool.close()
