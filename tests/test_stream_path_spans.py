"""The stream path on the record (PR 37): every hand-off of a request
on its way in and of a token on its way out is ONE span on the thread
that receives the work, with the request's id, how long the oldest item
it took had waited (``age_us``) and, for the hops a request makes once,
the thread's CPU time (``cpu_us``); in both sinks (``Span`` while ``TRACE_ON``, a TraceMe inside a profiler
session), and nothing while neither is live: no stamp, no clock read."""

import dataclasses
import glob
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.util import Queue, tracing

# Thread that receives the work, in the order one request meets them.
HOPS = ("serve.handle.send", "serve.replica.admit", "llm.stream.take",
        "serve.stream.put", "serve.stream.get")
RUNTIME = ("runtime.actor.submit", "runtime.actor.run", "runtime.get")
WITH_AGE = ("serve.replica.admit", "llm.stream.take", "serve.stream.get",
            "runtime.actor.run", "runtime.get")
# Once a request: a thread's CPU clock costs 6 us a read on the chip's
# machine, too dear for a hop a token (PERF.md section 6, PR 37).
WITH_CPU = ("serve.handle.send", "serve.replica.admit")
REQUEST = {"tokens": list(range(1, 12)), "max_new_tokens": 6}


@pytest.fixture(scope="module")
def llm_handle():
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LLMEngineServer

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    config = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    handle = serve.run(
        serve.deployment(LLMEngineServer).options(name="llm").bind(
            config, None, max_batch_size=4, max_seq_len=64, block_size=8,
            prefill_chunk=8, seed=3),
        name="llm_app", route_prefix="/llm")
    # The programs compile, the queue path warms.
    assert len(stream(handle)) == REQUEST["max_new_tokens"]
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()


def stream(handle) -> list:
    return list(handle.options(stream=True).generate.remote(dict(REQUEST)))


@pytest.fixture
def traced():
    tracing.clear()
    tracing.enable()
    yield
    tracing.disable()
    tracing.clear()


def test_one_request_id_on_every_hop_in_causal_order(llm_handle, traced):
    tokens = stream(llm_handle)
    # No chunk a caller receives changed shape: plain token ids.
    assert len(tokens) == 6 and all(type(t) is int for t in tokens)
    time.sleep(0.2)  # the replica's call ends after the stream's end
    tracing.disable()
    spans = tracing.get_spans()
    by_name: dict = {}
    for span in sorted(spans, key=lambda s: s.start_time):
        by_name.setdefault(span.name, []).append(span)
    assert set(HOPS) | set(RUNTIME) | {
        "engine.prefill.first_token", "llm.request", "llm.queue",
        "llm.prefill", "llm.decode"} <= set(by_name)
    # ONE id, from the send to the last get and on the engine's own
    # per-request spans.
    tied = [s for s in spans if "request" in s.attributes]
    assert {s.name for s in tied} == set(HOPS) | {
        "engine.prefill.first_token", "llm.request", "llm.queue",
        "llm.prefill", "llm.decode"}
    (request_id,) = {s.attributes["request"] for s in tied}
    assert isinstance(request_id, str) and len(request_id) == 16
    for name in HOPS:
        assert all(s.attributes.get("request") == request_id
                   for s in by_name[name] if "error" not in s.attributes)
    # One way in; the way out carries every token exactly once.
    assert len(by_name["serve.handle.send"]) == 1
    assert len(by_name["serve.replica.admit"]) == 1
    for name in ("llm.stream.take", "serve.stream.put", "serve.stream.get"):
        assert sum(s.attributes.get("tokens", 0)
                   for s in by_name[name]) == 6
    # Causal order: each hop of the way in ends before the next does,
    # the first token leaves the engine before it is taken, put and got.
    send, admit = by_name["serve.handle.send"][0], \
        by_name["serve.replica.admit"][0]
    first_token = by_name["engine.prefill.first_token"][0]

    def first_with_tokens(name):
        return next(s for s in by_name[name] if s.attributes.get("tokens"))

    take, put, got = (first_with_tokens(n) for n in HOPS[2:])
    assert send.start_time <= admit.start_time <= admit.end_time
    assert send.end_time <= admit.end_time <= first_token.start_time
    assert first_token.end_time <= take.end_time <= put.start_time
    assert put.start_time <= got.end_time
    assert all(t.end_time <= p.start_time for t, p in zip(
        by_name["llm.stream.take"], by_name["serve.stream.put"]))
    # The actor calls between the hops are on the record, and each run
    # is the child of the submit that caused it, on another thread.
    methods = {s.attributes["method"] for s in by_name["runtime.actor.run"]}
    assert {"Replica.handle_request_streaming", "_QueueActor.put_nowait",
            "_QueueActor.get_available"} <= methods
    submits = {s.span_id: s for s in by_name["runtime.actor.submit"]}
    for run in by_name["runtime.actor.run"]:
        if run.attributes["method"].startswith(("_QueueActor", "Replica")):
            cause = submits[run.parent_id]
            assert cause.thread != run.thread
            assert cause.trace_id == run.trace_id
            assert cause.attributes["method"] == \
                run.attributes["method"].split(".")[1]
    # The replica's hops hang under its call; the engine's request under
    # the admission that submitted it.
    (call,) = [s for s in by_name["runtime.actor.run"]
               if s.attributes["method"].startswith("Replica.handle_req")]
    assert admit.parent_id == call.span_id
    assert all(s.parent_id == call.span_id for s in
               by_name["llm.stream.take"] + by_name["serve.stream.put"])
    assert by_name["llm.request"][0].parent_id == admit.span_id
    # Waits and CPU times: never negative, the CPU never over the wall.
    for span in spans:
        if span.name in WITH_CPU:
            wall_us = span.duration_s() * 1e6
            assert 0 <= span.attributes["cpu_us"] <= wall_us + 50, span
        else:
            assert "cpu_us" not in span.attributes, span
        if "age_us" in span.attributes:
            assert span.name in WITH_AGE and span.attributes["age_us"] >= 0
    for name in WITH_AGE:
        assert any("age_us" in s.attributes for s in by_name[name]), name
    # A take's wait is no longer than the time since the first token.
    assert take.attributes["age_us"] <= \
        (take.start_time - first_token.start_time) * 1e6 + 50


def test_the_profiler_sink_shows_the_same_hops(llm_handle, tmp_path):
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert len(stream(llm_handle)) == 6
        time.sleep(0.1)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.live()
    found = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(found[0]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    by_name: dict = {}
    for event in events:
        by_name.setdefault(event.name, []).append(dict(event.stats))
    assert set(HOPS) | set(RUNTIME) <= set(by_name)
    (request_id,) = {stats["request"] for name in HOPS + (
        "engine.prefill.first_token",) for stats in by_name[name]
        if "request" in stats}
    assert len(request_id) == 16
    for name in HOPS + RUNTIME:
        assert all((stats["cpu_us"] >= 0) if name in WITH_CPU
                   else ("cpu_us" not in stats)
                   for stats in by_name[name]), name
    for name in WITH_AGE:
        assert any(stats.get("age_us", -1) >= 0
                   for stats in by_name[name]), name
    assert sum(stats.get("tokens", 0)
               for stats in by_name["serve.stream.get"]) == 6
    # The benchmark's readers find them there: one request, both pairs.
    from benchmark.readers import trace_span_attr, trace_span_pair

    spans = trace_span_attr.attributed_spans(found[0])
    way_in = trace_span_pair.pairs_ns(spans, {
        "from_spans": r"^serve\.handle\.send$", "from_edge": "start",
        "to_spans": r"^serve\.replica\.admit$", "to_edge": "end"})
    way_out = trace_span_pair.pairs_ns(spans, {
        "from_spans": r"^engine\.prefill\.first_token$", "from_edge": "end",
        "to_spans": r"^serve\.stream\.get$", "to_edge": "end",
        "to_carrying": "tokens"})
    assert len(way_in) == len(way_out) == 1
    assert 0 < way_in[0] < 5e9 and 0 < way_out[0] < 5e9
    assert trace_span_attr.values(spans, r"^runtime\.get$", "age_us")


class Clocks:
    """``time.monotonic_ns`` and ``time.thread_time_ns``, counted on
    every thread but an engine's loop, which times its own passes
    whatever the sinks."""

    def __init__(self, monkeypatch):
        self.calls = {"monotonic_ns": 0, "thread_time_ns": 0}
        for name in self.calls:
            monkeypatch.setattr(time, name, self.counted(name,
                                                         getattr(time, name)))

    def counted(self, name, clock):
        def read():
            if threading.current_thread().name != "llm-paged-engine":
                self.calls[name] += 1
            return clock()
        return read


def test_with_no_sink_live_a_hop_reads_no_clock(llm_handle, monkeypatch):
    """An actor call, a queue's put and waiting get and a blocking
    ``get`` with neither sink live: no stamp is taken, no thread's CPU
    time is read, nothing is recorded; with a sink live the same calls
    read both. (Nothing else in the runtime reads either clock.)"""
    assert not tracing.live() and tracing.stamp_ns() == 0
    assert tracing.age_us(0) is None
    tracing.clear()
    queue = Queue(maxsize=8, waiting_get=True)
    queue.put("warm")
    assert queue.get_available(4, timeout=5) == ["warm"]

    def round_trip():
        queue.put_batch(["a", "b"])
        queue.put("c")
        with tracing.phase("serve.handle.send", cpu=True) as hop:
            assert hop.live == tracing.live()
        return queue.get_available(8, timeout=5)

    clocks = Clocks(monkeypatch)
    assert round_trip() == ["a", "b", "c"]
    assert queue.oldest_landed_ns == 0
    assert clocks.calls == {"monotonic_ns": 0, "thread_time_ns": 0}
    assert tracing.get_spans() == []
    tracing.enable()
    try:
        assert round_trip() == ["a", "b", "c"]
    finally:
        tracing.disable()
    assert queue.oldest_landed_ns > 0
    assert clocks.calls["monotonic_ns"] >= 6  # stamps and their ages
    assert clocks.calls["thread_time_ns"] == 2  # the one span that asks
    names = {s.name for s in tracing.get_spans()}
    assert set(RUNTIME) | {"serve.handle.send"} <= names
    tracing.clear()
    queue.shutdown()
