"""Head (GCS) fault tolerance end-to-end: kill the head mid-workload,
restart it on the same address, and require the cluster to resume.

Reference: the GCS stores its tables in Redis so a restarted gcs_server
rehydrates and the cluster survives (src/ray/gcs/store_client/
redis_store_client.h:33, gcs_redis_failure_detector.h). Here the head's
persistent tables (KV — which carries the named-actor directory and
internal_kv — and the job table) ride a file snapshot
(gcs_server.py:_save_snapshot), node membership rehydrates via
heartbeat-rejection re-registration (node.py: re-register on
``accepted == False``), and driver RPC clients reconnect transparently
(rpc.py). This test fails if any of those tables fails to rehydrate.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private.rpc import RpcClient, RpcError


def _spawn_head(session_dir: str, port: int = 0) -> tuple:
    from ray_tpu._private.node import daemon_child_env

    env = daemon_child_env({"RAY_TPU_SESSION_DIR": session_dir})
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node", "head",
         json.dumps({"port": port, "dashboard_port": None})],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    addr_file = os.path.join(session_dir, "head_address")
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        assert proc.poll() is None, "head died during startup"
        try:
            with open(addr_file) as f:
                addr = f.read().strip()
            if addr:
                # The restarted head rewrites the file; make sure the
                # advertised port is LIVE before handing it out.
                client = RpcClient(addr, timeout_s=2.0)
                try:
                    client.call("list_nodes")
                    return proc, addr
                except (RpcError, OSError):
                    pass
                finally:
                    client.close()
        except OSError:
            pass
        time.sleep(0.2)
    raise TimeoutError("head never advertised a live address")


def _spawn_worker_daemon(gcs_address: str):
    from ray_tpu._private.node import daemon_child_env

    # The "worker" marker resource pins test workloads to these
    # daemons: the head registers an executor node of its own, and
    # anything placed THERE rightly dies with the head.
    return subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.node", "worker",
         json.dumps({"gcs_address": gcs_address,
                     "resources": {"CPU": 2.0, "worker": 4.0},
                     "pool_size": 0,
                     "heartbeat_period_s": 0.5})],
        env=daemon_child_env(),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def _alive_nodes(addr: str) -> list[dict]:
    client = RpcClient(addr, timeout_s=5.0)
    try:
        return [n for n in client.call("list_nodes") if n.get("alive")]
    except (RpcError, OSError):
        return []
    finally:
        client.close()


def test_head_kill_with_inflight_batch_and_broadcast_drains(tmp_path):
    """Head killed while worker daemons hold in-flight BATCHED tasks
    and an in-progress driver-export broadcast: the execute/data
    planes are head-free (driver<->daemon RPC + export pulls), so the
    cluster must drain after the restart+re-register with no task lost
    or doubled."""
    import numpy as np

    import ray_tpu

    session = str(tmp_path / "session")
    os.makedirs(session)
    head_proc, addr = _spawn_head(session)
    port = int(addr.rsplit(":", 1)[1])
    workers = [_spawn_worker_daemon(addr) for _ in range(2)]
    runtime = None
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and len(_alive_nodes(addr)) < 3:
            time.sleep(0.3)
        assert len(_alive_nodes(addr)) >= 3

        ray_tpu.shutdown()  # a runtime an earlier file left in this worker
        runtime = ray_tpu.init(address=addr, num_cpus=0)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and \
                ray_tpu.cluster_resources().get("worker", 0) < 8:
            time.sleep(0.2)

        @ray_tpu.remote(num_cpus=1, resources={"worker": 1},
                        max_retries=3)
        def slow_batch(i):
            import time as _t

            _t.sleep(5.0)
            return i

        # Large enough that daemons pull it from the driver's export
        # server (never through the head).
        blob = np.arange(1_000_000, dtype=np.float64)  # ~8 MB
        blob_ref = ray_tpu.put(blob)

        @ray_tpu.remote(num_cpus=1, resources={"worker": 1},
                        max_retries=3)
        def touch(arr, i):
            return (i, float(arr[0]), len(arr))

        refs = [slow_batch.remote(i) for i in range(12)]
        bcast = [touch.remote(blob_ref, i) for i in range(6)]
        time.sleep(1.5)  # batches dispatched; pulls in progress

        # ---- kill the head mid-flight, restart on the same port ----
        head_proc.send_signal(signal.SIGKILL)
        head_proc.wait(timeout=10)
        head_proc, addr2 = _spawn_head(session, port=port)
        assert addr2.rsplit(":", 1)[1] == str(port)

        # Every batched task drains exactly once; the broadcast
        # completes against the driver's export plane.
        results = ray_tpu.get(refs, timeout=180.0)
        assert sorted(results) == list(range(12)), results
        bres = ray_tpu.get(bcast, timeout=180.0)
        assert sorted(bres) == [(i, 0.0, 1_000_000) for i in range(6)]

        # Worker daemons re-registered under the restarted head.
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline and len(_alive_nodes(addr)) < 3:
            time.sleep(0.5)
        assert len(_alive_nodes(addr)) >= 3, (
            "worker daemons did not re-register after head restart")

        # The cluster still executes NEW work after the restart.
        assert ray_tpu.get(slow_batch.remote(99), timeout=120.0) == 99
    finally:
        if runtime is not None:
            ray_tpu.shutdown()
        for proc in [head_proc, *workers]:
            proc.terminate()
        for proc in [head_proc, *workers]:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def test_head_sigkill_mid_mutation_full_state_survives(tmp_path):
    """Head SIGKILLed in the middle of a write burst (no clean stop,
    no final snapshot): every ACKED mutation must rehydrate from the
    snapshot+WAL — node records, a RESTARTING actor with its restart
    count, object-directory entries including a spilled-location mark,
    placement groups, and the KV — with ``wal_records_replayed > 0``
    and a bumped incarnation epoch."""
    session = str(tmp_path / "session")
    os.makedirs(session)
    head_proc, addr = _spawn_head(session)
    port = int(addr.rsplit(":", 1)[1])
    client = RpcClient(addr, timeout_s=10.0)
    try:
        old_epoch = client.call("gcs_epoch")
        assert isinstance(old_epoch, int) and old_epoch >= 1
        node_id = client.call("register_node", "10.3.3.3:17",
                              {"CPU": 4.0}, {"rack": "r9"},
                              "10.3.3.3:900", host_id="hostZ")
        # In-flight object state: directory entries + a spilled mark
        # shipped the production way (heartbeat stats piggyback).
        client.call("object_locations_update", "owner-x",
                    [("ab" * 10, ["n1", "n2"]), ("cd" * 10, "n2")], [],
                    epoch=old_epoch)
        assert client.call(
            "heartbeat", node_id, None,
            {"spill_events": [("owner-x", "cd" * 10, "spilled")]},
            None, epoch=old_epoch) is True
        client.call("actor_update", [{
            "actor_id": b"\x21" * 16, "name": "survivor",
            "namespace": "default", "class_name": "Keeper",
            "state": "RESTARTING", "max_restarts": 4,
            "num_restarts": 3}], epoch=old_epoch)
        client.call("pg_update", "job-x",
                    [{"pg_id": "ee" * 14, "state": "CREATED",
                      "strategy": "PACK", "bundles": []}],
                    epoch=old_epoch)
        # Write burst; the SIGKILL lands mid-stream. Every ACKED put
        # (the call returned) is already WAL-framed on disk.
        acked = []
        for i in range(50):
            client.call("kv_put", f"burst-{i}".encode(), b"v", "t")
            acked.append(i)
            if i == 29:
                head_proc.send_signal(signal.SIGKILL)
            # After the kill the next call fails somewhere mid-burst.
    except (RpcError, OSError):
        pass  # the burst died with the head — expected
    finally:
        client.close()
    head_proc.wait(timeout=10)

    head_proc, addr2 = _spawn_head(session, port=port)
    client = RpcClient(addr2, timeout_s=10.0)
    try:
        stats = client.call("gcs_persist_stats")
        assert stats["wal_records_replayed"] > 0, stats
        assert stats["epoch"] > old_epoch
        # Node table (restored alive — its daemon gets a grace window).
        nodes = {n["address"]: n for n in client.call("list_nodes")}
        assert nodes["10.3.3.3:17"]["alive"]
        assert nodes["10.3.3.3:17"]["labels"] == {"rack": "r9"}
        # Actor registry incl. RESTARTING + num_restarts.
        actors = {a["name"]: a
                  for a in client.call("list_cluster_actors")}
        assert actors["survivor"]["state"] == "RESTARTING"
        assert actors["survivor"]["num_restarts"] == 3
        # Object directory + the spilled mark.
        locs, spilled = client.call("list_object_locations", None, True)
        assert locs["ab" * 10] == ["n1", "n2"]
        assert spilled.get("cd" * 10) == node_id.hex()
        # Placement groups.
        pgs = client.call("list_cluster_placement_groups")
        assert pgs["job-x"][0]["pg_id"] == "ee" * 14
        # Every ACKED KV write survived the SIGKILL.
        missing = [i for i in acked
                   if client.call("kv_get", f"burst-{i}".encode(), "t")
                   != b"v"]
        assert not missing, f"acked writes lost: {missing}"
        # A stale-epoch write is still fenced by the restarted head.
        from ray_tpu._private.gcs import StaleEpochError
        from ray_tpu._private.rpc import RpcMethodError

        try:
            client.call("heartbeat", node_id, None, None, None,
                        epoch=old_epoch)
            raise AssertionError("stale-epoch heartbeat not fenced")
        except RpcMethodError as exc:
            assert isinstance(exc.cause, StaleEpochError)
    finally:
        client.close()
        head_proc.terminate()
        try:
            head_proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            head_proc.kill()


def test_head_kill_restart_cluster_resumes(tmp_path):
    session = str(tmp_path / "session")
    os.makedirs(session)
    head_proc, addr = _spawn_head(session)
    port = int(addr.rsplit(":", 1)[1])
    workers = [_spawn_worker_daemon(addr) for _ in range(2)]
    runtime = None
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and len(_alive_nodes(addr)) < 3:
            time.sleep(0.3)  # head registers itself too -> 3 total
        assert len(_alive_nodes(addr)) >= 3

        ray_tpu.shutdown()  # a runtime an earlier file left in this worker
        runtime = ray_tpu.init(address=addr, num_cpus=0)

        # State that must survive: internal KV, a job record, a named
        # (detached-style) actor living on a WORKER daemon.
        from ray_tpu.experimental import internal_kv

        internal_kv.internal_kv_put(b"durable-key", b"durable-value")

        head_client = RpcClient(addr, timeout_s=10.0)
        submission_id = head_client.call(
            "submit_job", f"{sys.executable} -c 'print(42)'")
        deadline = time.monotonic() + 60
        job = None
        while time.monotonic() < deadline:
            job = head_client.call("job_status", submission_id)
            if job and job.get("status") in ("SUCCEEDED", "FAILED"):
                break
            time.sleep(0.3)
        assert job and job["status"] == "SUCCEEDED"
        head_client.close()

        @ray_tpu.remote(num_cpus=1, resources={"worker": 1})
        class Keeper:
            def __init__(self):
                self.values = {}

            def put(self, k, v):
                self.values[k] = v
                return len(self.values)

            def get(self, k):
                return self.values.get(k)

        keeper = Keeper.options(name="keeper", lifetime="detached").remote()
        assert ray_tpu.get(keeper.put.remote("a", 1), timeout=60) == 1

        # A get() pending ACROSS the restart: the task sleeps through
        # the head's death and completes after it returns.
        @ray_tpu.remote(num_cpus=1, resources={"worker": 1})
        def slow():
            import time as _t

            _t.sleep(8.0)
            return "survived"

        pending = slow.remote()
        time.sleep(1.0)  # ensure it is dispatched and running

        # ---- kill the head, hard ------------------------------------
        head_proc.send_signal(signal.SIGKILL)
        head_proc.wait(timeout=10)

        # ---- restart on the SAME port with the SAME session dir -----
        head_proc, addr2 = _spawn_head(session, port=port)
        assert addr2.rsplit(":", 1)[1] == str(port)

        # The pending get completes (driver RPC reconnects; the task
        # ran on a worker daemon the whole time).
        assert ray_tpu.get(pending, timeout=120.0) == "survived"

        # Worker daemons re-register via heartbeat rejection.
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline and len(_alive_nodes(addr)) < 3:
            time.sleep(0.5)
        assert len(_alive_nodes(addr)) >= 3, (
            "worker daemons did not re-register after head restart")

        # KV (incl. the named-actor directory) rehydrated from snapshot.
        assert internal_kv.internal_kv_get(b"durable-key") == \
            b"durable-value"

        # The job table rehydrated.
        head_client = RpcClient(addr, timeout_s=10.0)
        job = head_client.call("job_status", submission_id)
        head_client.close()
        assert job is not None and job["status"] == "SUCCEEDED", job

        # The named actor survived (its process lives on a worker
        # daemon; the directory entry came back with the KV).
        again = ray_tpu.get_actor("keeper")
        assert ray_tpu.get(again.get.remote("a"), timeout=60) == 1
        assert ray_tpu.get(again.put.remote("b", 2), timeout=60) == 2
    finally:
        if runtime is not None:
            ray_tpu.shutdown()
        for proc in [head_proc, *workers]:
            proc.terminate()
        for proc in [head_proc, *workers]:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
