"""ActorPool, Queue, and runtime_env tests.

Reference intent: python/ray/tests/test_actor_pool.py,
test_queue.py, and the runtime_env env_vars/working_dir tests.
"""

import os

import pytest

import ray_tpu
from ray_tpu.util import ActorPool, Empty, Full, Queue


@pytest.fixture
def ray_start():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    ray_tpu.shutdown()


@ray_tpu.remote
class _PoolWorker:
    def double(self, x):
        return 2 * x

    def slow_double(self, x):
        import time

        time.sleep(0.05 if x % 2 else 0.0)
        return 2 * x


def test_actor_pool_map_ordered(ray_start):
    pool = ActorPool([_PoolWorker.remote() for _ in range(3)])
    out = list(pool.map(lambda a, v: a.double.remote(v), range(10)))
    assert out == [2 * i for i in range(10)]


def test_actor_pool_map_unordered_complete_set(ray_start):
    pool = ActorPool([_PoolWorker.remote() for _ in range(3)])
    out = list(pool.map_unordered(
        lambda a, v: a.slow_double.remote(v), range(8)))
    assert sorted(out) == [2 * i for i in range(8)]


def test_actor_pool_submit_get_next(ray_start):
    pool = ActorPool([_PoolWorker.remote() for _ in range(2)])
    for i in range(5):  # more submits than actors: queueing kicks in
        pool.submit(lambda a, v: a.double.remote(v), i)
    assert [pool.get_next() for _ in range(5)] == [0, 2, 4, 6, 8]
    assert not pool.has_next()
    with pytest.raises(StopIteration):
        pool.get_next()


def test_actor_pool_push_pop_idle(ray_start):
    pool = ActorPool([_PoolWorker.remote()])
    actor = pool.pop_idle()
    assert actor is not None
    assert not pool.has_free()
    pool.push(actor)
    assert pool.has_free()


def test_queue_fifo_and_batches(ray_start):
    q = Queue()
    for i in range(5):
        q.put(i)
    assert q.qsize() == 5 and not q.empty()
    assert [q.get() for _ in range(5)] == [0, 1, 2, 3, 4]
    assert q.empty()
    q.put_nowait_batch([10, 11, 12])
    assert q.get_nowait_batch(3) == [10, 11, 12]
    with pytest.raises(Empty):
        q.get_nowait()
    with pytest.raises(Empty):
        q.get(timeout=0.05)


def test_queue_maxsize_full(ray_start):
    q = Queue(maxsize=2)
    q.put(1)
    q.put(2)
    assert q.full()
    with pytest.raises(Full):
        q.put_nowait(3)
    with pytest.raises(Full):
        q.put(3, timeout=0.05)
    q.get()
    q.put(3)  # space freed


def test_queue_shared_across_tasks(ray_start):
    q = Queue()

    @ray_tpu.remote
    def producer(queue, n):
        for i in range(n):
            queue.put(i)
        return n

    assert ray_tpu.get(producer.remote(q, 4)) == 4
    assert sorted(q.get() for _ in range(4)) == [0, 1, 2, 3]


# ---------------------------------------------------------- runtime_env
def test_runtime_env_env_vars_in_pool_tasks():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, process_workers=2)
    try:
        @ray_tpu.remote
        def read_env():
            return os.environ.get("RT_TEST_VAR")

        assert ray_tpu.get(read_env.options(
            runtime_env={"env_vars": {"RT_TEST_VAR": "42"}}).remote()) \
            == "42"
        # And it does NOT leak into the next task on the same worker.
        assert ray_tpu.get(read_env.remote()) is None
    finally:
        ray_tpu.shutdown()


def test_runtime_env_working_dir_in_pool_tasks(tmp_path):
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, process_workers=2)
    try:
        marker = tmp_path / "marker.txt"
        marker.write_text("found-me")

        @ray_tpu.remote
        def read_marker():
            with open("marker.txt") as f:
                return f.read()

        out = ray_tpu.get(read_marker.options(
            runtime_env={"working_dir": str(tmp_path)}).remote())
        assert out == "found-me"
    finally:
        ray_tpu.shutdown()


def test_runtime_env_process_actor():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    try:
        @ray_tpu.remote
        class EnvActor:
            def read(self):
                return os.environ.get("RT_ACTOR_VAR")

        actor = EnvActor.options(
            process=True,
            runtime_env={"env_vars": {"RT_ACTOR_VAR": "actor-env"}},
        ).remote()
        assert ray_tpu.get(actor.read.remote()) == "actor-env"
        ray_tpu.kill(actor)
    finally:
        ray_tpu.shutdown()


def test_actor_pool_mixed_ordered_unordered(ray_start):
    """get_next after get_next_unordered must skip consumed indices
    instead of waiting forever (regression)."""
    pool = ActorPool([_PoolWorker.remote() for _ in range(3)])
    for i in range(3):
        pool.submit(lambda a, v: a.double.remote(v), i)
    first = pool.get_next_unordered()      # some index, consumed
    remaining = sorted([pool.get_next(), pool.get_next()])
    assert sorted([first] + remaining) == [0, 2, 4]
    assert not pool.has_next()


def test_actor_pool_task_error_surfaces_and_advances(ray_start):
    """A failed task raises from get_next once, then the pool keeps
    working (ADVICE r2: errors used to hang get_next forever)."""

    @ray_tpu.remote
    class Flaky:
        def run(self, v):
            if v == 1:
                raise ValueError("boom-1")
            return v * 10

    pool = ActorPool([Flaky.remote() for _ in range(2)])
    for i in range(4):
        pool.submit(lambda a, v: a.run.remote(v), i)
    assert pool.get_next(timeout=10) == 0
    with pytest.raises(Exception) as exc_info:
        pool.get_next(timeout=10)
    assert "boom-1" in str(exc_info.value)
    assert pool.get_next(timeout=10) == 20
    assert pool.get_next(timeout=10) == 30
    assert not pool.has_next()


def test_tpu_topology_from_gke_env(monkeypatch):
    """GKE-style env metadata yields slice topology + the pod-slice head
    resource on worker 0 only (reference: accelerators/tpu.py:14-44,
    :363-382)."""
    from ray_tpu._private import accelerators

    monkeypatch.delenv("RAY_TPU_SKIP_TPU_DETECTION", raising=False)
    monkeypatch.delenv("RAY_TPU_NUM_TPU_CHIPS", raising=False)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-16")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1,h2,h3")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    # The count is what the host really exposes; the env names the slice.
    monkeypatch.setattr(accelerators, "local_chips", lambda: (4, "v5e"))

    topo = accelerators.detect_tpu_topology()
    assert topo == {"accelerator_type": "v5litepod-16", "worker_id": 0,
                    "num_workers": 4, "chips_per_host": 4}
    res = accelerators.detect_resources()
    assert res["TPU"] == 4.0
    assert res["TPU-v5litepod-16-head"] == 1.0

    # Worker 3 carries chips but NOT the gang-head resource.
    monkeypatch.setenv("TPU_WORKER_ID", "3")
    res3 = accelerators.detect_resources()
    assert res3["TPU"] == 4.0
    assert not any(k.endswith("-head") for k in res3)


def test_tpu_topology_chips_from_accel_type(monkeypatch):
    from ray_tpu._private import accelerators

    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v4-8")
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "a,b")
    monkeypatch.delenv("TPU_CHIPS_PER_HOST_BOUNDS", raising=False)
    topo = accelerators.detect_tpu_topology()
    # v4-8 counts TENSORCORES: 8 cores = 4 chips, over 2 workers.
    assert topo["chips_per_host"] == 2
    assert topo["num_workers"] == 2

    # v5e suffixes count CHIPS directly.
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-8")
    topo = accelerators.detect_tpu_topology()
    assert topo["chips_per_host"] == 4  # 8 chips / 2 workers

    # Corrupt worker-id metadata falls back to 0, not a crash.
    monkeypatch.setenv("TPU_WORKER_ID", "unknown")
    assert accelerators.detect_tpu_topology()["worker_id"] == 0


def test_config_knobs_reach_hot_paths(monkeypatch):
    """The new flag-table keys actually steer behavior (not dead
    config)."""
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu._private.node_executor import (
        NodeObjectStore,
        _fetch_chunk_bytes,
        _inline_reply_bytes,
    )

    GLOBAL_CONFIG.update({"executor_inline_reply_kb": 8,
                          "fetch_chunk_kb": 64,
                          "node_pull_cache_mb": 1})
    try:
        assert _inline_reply_bytes() == 8 * 1024
        assert _fetch_chunk_bytes() == 64 * 1024
        store = NodeObjectStore()
        assert store._cache_limit == 1024 * 1024
    finally:
        GLOBAL_CONFIG.reset()


def test_joblib_backend_runs_batches_as_tasks(ray_start_regular):
    """joblib.Parallel over the ray_tpu backend (reference:
    util/joblib/register_ray)."""
    import joblib

    from ray_tpu.util.joblib_backend import register_ray_tpu

    register_ray_tpu()
    with joblib.parallel_backend("ray_tpu", n_jobs=4):
        out = joblib.Parallel()(
            joblib.delayed(lambda x: x * x)(i) for i in range(20))
    assert out == [i * i for i in range(20)]

    # Errors propagate like any joblib backend.
    def boom(x):
        raise ValueError("joblib-boom")

    with joblib.parallel_backend("ray_tpu", n_jobs=2):
        try:
            joblib.Parallel()(joblib.delayed(boom)(i) for i in range(2))
            raise AssertionError("expected ValueError")
        except ValueError:
            pass
