"""The collector's pauses on the record (PR 57): the ``gc.callbacks``
entry of ``ray_tpu/util/tracing.py`` sums every collection's wall time
and counts the full ones whether a sink is live or not, opens the span
``runtime.gc`` only while one is, and is in the list once, from the
first engine of the process to the last one's shutdown. Nothing here
is a measurement."""

import gc
import types
import weakref

import pytest

from ray_tpu.util import tracing


class User:
    """What an engine is to ``record_gc``: something that lives."""


@pytest.fixture
def hook(monkeypatch):
    """The callback as this test alone installs it: other engines of
    the worker's process, live or leaked, are set aside meanwhile."""
    installed = tracing._on_gc in gc.callbacks
    if installed:
        gc.callbacks.remove(tracing._on_gc)
    monkeypatch.setattr(tracing, "_gc_users", weakref.WeakSet())
    was_on = tracing.is_enabled()
    tracing.disable()
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear()
    if was_on:
        tracing.enable()
    while tracing._on_gc in gc.callbacks:
        gc.callbacks.remove(tracing._on_gc)
    if installed:
        gc.callbacks.append(tracing._on_gc)


def collections():
    return [s for s in tracing.get_spans() if s.name == "runtime.gc"]


def test_a_full_collection_under_an_armed_tracer_is_one_span(hook):
    user = User()
    tracing.record_gc(user)
    before = tracing.gc_counters()
    assert set(before) == {"gc_pause_us", "gc_full_collections"}
    tracing.enable()
    gc.collect()
    after = tracing.gc_counters()
    (span,) = collections()
    assert span.attributes["generation"] == 2
    assert span.attributes["collected"] >= 0
    assert span.attributes["pause_us"] >= 0
    assert span.end_time >= span.start_time
    assert after["gc_full_collections"] - before["gc_full_collections"] == 1
    assert after["gc_pause_us"] >= before["gc_pause_us"] \
        + span.attributes["pause_us"]
    # A collection of the middle generation is a span too and no full
    # one; the youngest, thousands a second in a serving process, never.
    tracing.clear()
    gc.collect(1)
    gc.collect(0)
    assert [s.attributes["generation"] for s in collections()] == [1]
    assert tracing.gc_counters()["gc_full_collections"] \
        == after["gc_full_collections"]
    assert tracing._GC_SPAN_FROM == 1
    # The span nests under what its thread had open: the phase the
    # collection fell in.
    tracing.clear()
    with tracing.phase("engine.decode.emit") as emit:
        gc.collect()
    (span,) = collections()
    assert span.parent_id == emit.span.span_id


def test_with_no_sink_live_the_counters_rise_and_no_span_clock_is_read(
        hook, monkeypatch):
    user = User()
    tracing.record_gc(user)
    assert not tracing.live()
    read = []

    def span_clock(*_):
        read.append(1)
        return 0.0

    import time

    # ``time.time`` is the clock a ``Span`` starts and ends on, and
    # ``thread_time_ns`` a ``cpu=True`` phase's: neither is the
    # counters', which take ``monotonic_ns`` twice a collection.
    ticks = []
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        time=span_clock, thread_time_ns=span_clock,
        monotonic_ns=lambda: ticks.append(1) or time.monotonic_ns()))
    before = tracing.gc_counters()
    gc.collect()
    gc.collect(0)
    after = tracing.gc_counters()
    assert not read and len(ticks) == 4
    assert collections() == []
    assert after["gc_full_collections"] - before["gc_full_collections"] == 1
    assert after["gc_pause_us"] >= before["gc_pause_us"]
    assert tracing._gc_phase is None


def test_the_counters_are_monotonic_and_the_processes():
    first = tracing.gc_counters()
    second = tracing.gc_counters()
    assert all(isinstance(v, int) for v in first.values())
    assert all(second[k] >= first[k] for k in first)


def test_the_hook_is_in_the_list_once_and_goes_with_the_last_user(hook):
    assert tracing._on_gc not in gc.callbacks
    users = [User() for _ in range(3)]
    for user in users:
        tracing.record_gc(user)
        tracing.record_gc(user)  # twice is once
    assert gc.callbacks.count(tracing._on_gc) == 1
    tracing.forget_gc(users[0])
    tracing.forget_gc(users[0])
    assert gc.callbacks.count(tracing._on_gc) == 1
    # One that died without a word is no user any more.
    del user
    users.pop()
    tracing.forget_gc(users[1])
    assert tracing._on_gc not in gc.callbacks
    # A "stop" whose "start" came before the callback went in is
    # dropped, not clocked against nothing.
    before = tracing.gc_counters()
    tracing._on_gc("stop", {"generation": 2, "collected": 0})
    assert tracing.gc_counters() == before


def test_an_engine_installs_it_and_its_shutdown_removes_it(hook):
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LLMEngine
    from ray_tpu.serve.llm_engine.engine import merged_engine_stats

    config = dataclasses.replace(llama.LlamaConfig.tiny(),
                                 dtype=jnp.float32)
    engine = LLMEngine(config, max_batch_size=2, max_seq_len=32,
                       block_size=8, prefill_chunk=8, seed=0)
    try:
        assert gc.callbacks.count(tracing._on_gc) == 1
        assert engine in tracing._gc_users
        before = engine.engine_stats()
        gc.collect()
        after = engine.engine_stats()
        assert after["gc_full_collections"] \
            - before["gc_full_collections"] == 1
        assert after["gc_pause_us"] >= before["gc_pause_us"]
        # The process's, not an engine's: once, whatever the engines.
        merged = merged_engine_stats()
        assert merged["gc_full_collections"] \
            >= after["gc_full_collections"]
        assert merged["gc_full_collections"] \
            <= tracing.gc_counters()["gc_full_collections"]
    finally:
        engine.shutdown()
    assert engine not in tracing._gc_users
    assert tracing._on_gc not in gc.callbacks
