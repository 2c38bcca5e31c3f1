"""A stream that has fallen behind is delivered a call at a time, not a
chunk at a time (PR 33): the queue takes and gives lists, a streaming
method's ``<name>_batches`` sibling is what a replica iterates, and the
consumer of the handle sees the same chunks in the same order. A
producer whose consumer is gone stops after the queue's bound."""

import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.util import Empty, Full, Queue


@pytest.fixture
def ray_start():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_queue_gives_what_is_waiting_in_one_call(ray_start):
    q = Queue(maxsize=4)
    with pytest.raises(Empty):
        q.get_available(8, timeout=0.05)
    q.put_batch([1, 2, 3])
    assert q.get_available(2) == [1, 2]
    assert q.get_available(8) == [3]
    # More than the queue holds goes in as the consumer makes room.
    taken = []
    consumer = threading.Thread(
        target=lambda: [taken.extend(q.get_available(8, timeout=5))
                        for _ in iter(lambda: len(taken) < 10, False)])
    consumer.start()
    q.put_batch(list(range(10)), timeout=5)
    consumer.join(10)
    assert taken == list(range(10))
    q.shutdown()


@pytest.mark.parametrize("how", ["put", "put_batch"])
def test_a_producer_without_a_consumer_stops_at_the_bound(ray_start, how):
    q = Queue(maxsize=2, put_timeout_s=0.2)
    q.put_batch([1, 2])
    started = time.monotonic()
    with pytest.raises(Full):
        q.put(3) if how == "put" else q.put_batch([3, 4])
    assert 0.2 <= time.monotonic() - started < 2.0
    # The bound survives the trip to another process's handle.
    import pickle

    assert pickle.loads(pickle.dumps(q)).put_timeout_s == 0.2
    assert Queue(maxsize=2).put_timeout_s is None
    q.shutdown()


class Counts:
    """Streams 0..n-1; its sibling gives them in lists of three."""

    def __init__(self):
        self.calls = {"generate": 0, "generate_batches": 0}

    def generate(self, n: int):
        self.calls["generate"] += 1
        yield from range(n)

    def generate_batches(self, n: int):
        self.calls["generate_batches"] += 1
        for start in range(0, n, 3):
            yield list(range(start, min(n, start + 3)))

    def plain(self, n: int):
        yield from range(n)

    def seen(self):
        return self.calls


def test_a_replica_streams_the_batches_sibling_chunk_by_chunk(ray_start):
    handle = serve.run(serve.deployment(Counts).bind(), name="counts_app",
                       route_prefix="/counts")
    stream = handle.options(stream=True)
    assert list(stream.generate.remote(10)) == list(range(10))
    assert list(stream.generate.remote(600)) == list(range(600))  # > maxsize
    assert list(stream.plain.remote(7)) == list(range(7))   # no sibling
    assert handle.seen.remote().result(timeout_s=30) == \
        {"generate": 0, "generate_batches": 2}


def test_the_engine_gives_waiting_tokens_in_lists():
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LLMEngine, LLMEngineServer

    config = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    engine = LLMEngine(config, max_batch_size=2, max_seq_len=64,
                       block_size=8, prefill_chunk=8, seed=1)
    try:
        one = engine.submit([1, 2, 3], max_new_tokens=9, stream=True)
        want = list(engine.stream_tokens(one))
        again = engine.submit([1, 2, 3], max_new_tokens=9, stream=True)
        engine.result(again, timeout_s=120)    # all nine are waiting now
        batches = list(engine.stream_token_batches(again))
        assert [t for batch in batches for t in batch] == want
        assert len(batches) == 1 and all(batches)
        # A consumer that keeps up gets them as they come.
        third = engine.submit([1, 2, 3], max_new_tokens=9, stream=True)
        batches = list(engine.stream_token_batches(third))
        assert [t for batch in batches for t in batch] == want
    finally:
        engine.shutdown()
    assert hasattr(LLMEngineServer, "generate_batches")


def test_a_waiting_get_is_woken_by_the_put(ray_start):
    """A ``waiting_get`` queue's consumer waits inside the actor for the
    producer's put: one call a delivery, and no 10 ms poll to add to a
    chunk's way."""
    import pickle

    q = Queue(maxsize=8, waiting_get=True)
    assert pickle.loads(pickle.dumps(q)).waiting_get is True
    assert Queue(maxsize=8).waiting_get is False
    with pytest.raises(Empty):
        q.get_available(4, timeout=0.1)
    got = []

    def consume():
        started = time.monotonic()
        got.append((q.get_available(4, timeout=5),
                    time.monotonic() - started))

    consumer = threading.Thread(target=consume)
    consumer.start()
    time.sleep(0.3)                 # the consumer is waiting in the actor
    put_at = time.monotonic()
    q.put("x")
    consumer.join(5)
    items, waited = got[0]
    assert items == ["x"] and waited >= 0.3
    assert time.monotonic() - put_at < 0.2
    q.put_batch(["y", "z"])
    assert q.get_available(4, timeout=1) == ["y", "z"]
    q.shutdown()


def test_only_an_engine_of_many_rows_shortens_the_switch_interval():
    """At 32 rows the engine thread waited whole 5 ms switch intervals
    for the interpreter inside each program call; at 16 the shorter
    interval cost 8% of the throughput (my chip runs, PR 33)."""
    import sys

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LLMEngine, engine as engine_module

    before = sys.getswitchinterval()
    try:
        sys.setswitchinterval(0.005)
        small = LLMEngine(llama.LlamaConfig.tiny(), max_batch_size=4,
                          max_seq_len=32, block_size=8, prefill_chunk=8)
        small.shutdown()
        assert sys.getswitchinterval() == pytest.approx(0.005)
        many = LLMEngine(llama.LlamaConfig.tiny(),
                         max_batch_size=engine_module._MANY_ROWS,
                         max_seq_len=32, block_size=8, prefill_chunk=8)
        many.shutdown()
        assert sys.getswitchinterval() == pytest.approx(
            engine_module._SWITCH_INTERVAL_S)
    finally:
        sys.setswitchinterval(before)
