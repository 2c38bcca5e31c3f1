"""A launch that found the device drained, and the engine's lock apart
from the interpreter (PR 57): ``launches_starved`` rises for a decode
step or a prefill chunk launched onto a device that had finished
everything before it and not for one behind a program still running
(the probe ``LLMEngine._drained`` stubbed, so this CPU's timing decides
nothing); ``starved`` and ``lock_wait_us`` go on live spans only; the
three new counters are ``ENGINE_STAT_KEYS``. Nothing here is a
measurement."""

import dataclasses

import pytest

from ray_tpu.util import tracing

NEW_KEYS = ("launches_starved", "gc_pause_us", "gc_full_collections")
LOCKED_LEAVES = ("engine.sweep", "engine.prefill.schedule",
                 "engine.prefill.first_token", "engine.decode.schedule",
                 "engine.decode.emit")
LAUNCHES = ("engine.prefill.launch", "engine.decode.launch")


@pytest.fixture(scope="module")
def engine():
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LLMEngine

    config = dataclasses.replace(llama.LlamaConfig.tiny(),
                                 dtype=jnp.float32)
    engine = LLMEngine(config, max_batch_size=2, max_seq_len=64,
                       block_size=8, prefill_chunk=8, seed=0)
    yield engine
    engine.shutdown()


def served(engine, prompt=(5, 9, 2, 7, 1, 8, 3, 4, 6, 2), new=6) -> dict:
    """One request through the engine; the counters' deltas over it."""
    before = engine.engine_stats()
    engine.result(engine.submit(list(prompt), max_new_tokens=new),
                  timeout_s=120)
    after = engine.engine_stats()
    return {key: after[key] - before[key] for key in after}


def test_the_three_new_counters_are_engine_stat_keys(engine):
    from ray_tpu.serve.llm_engine import ENGINE_STAT_KEYS

    assert set(NEW_KEYS) <= set(ENGINE_STAT_KEYS)
    assert len(set(ENGINE_STAT_KEYS)) == len(ENGINE_STAT_KEYS)
    stats = engine.engine_stats()
    assert all(isinstance(stats[key], int) for key in NEW_KEYS)


@pytest.mark.parametrize("drained, share", [(1, 1.0), (0, 0.0)])
def test_a_launch_counts_as_starved_where_the_device_was_drained(
        engine, monkeypatch, drained, share):
    monkeypatch.setattr(engine, "_drained", lambda: drained)
    delta = served(engine)
    launches = delta["decode_steps"] + delta["prefill_chunks"]
    # Ten prompt tokens in chunks of eight, then the steps of five
    # more tokens (the first came from prefill).
    assert delta["prefill_chunks"] == 2 and delta["decode_steps"] >= 5
    assert delta["launches_starved"] == share * launches


def test_only_the_launches_that_found_it_drained_count(engine, monkeypatch):
    """Behind a step still running a launch is not starved, whatever
    came before: the probe answers launch by launch."""
    answers = []

    def probe():
        # The chunks and the first step find the device drained (the
        # first token's read has just waited for it); the steps after
        # ride behind the step before them.
        answers.append(int(len(answers) < 3))
        return answers[-1]

    monkeypatch.setattr(engine, "_drained", probe)
    delta = served(engine)
    assert len(answers) == delta["decode_steps"] + delta["prefill_chunks"]
    assert delta["launches_starved"] == 3 == sum(answers)
    # ``ahead`` does not see it: every step but the first was launched
    # on an unread one, starved or not.
    assert delta["decode_steps_ahead"] == delta["decode_steps"] - 1


def test_the_probe_asks_the_cache_and_does_not_wait(engine):
    # Nothing in flight: everything launched has finished.
    assert engine._drained() == 1
    assert isinstance(engine._drained(), int)
    first = next(iter(engine._pool.values()))
    assert first.is_ready()


def test_starved_and_lock_wait_go_on_live_spans(engine, monkeypatch):
    monkeypatch.setattr(engine, "_drained", lambda: 1)
    tracing.clear()
    tracing.enable()
    try:
        served(engine)
    finally:
        tracing.disable()
    spans = [s for s in tracing.get_spans() if s.name.startswith("engine.")]
    tracing.clear()
    by_name = {}
    for span in spans:
        by_name.setdefault(span.name, []).append(span.attributes)
    for name in LAUNCHES:
        assert by_name[name] and all(a["starved"] == 1
                                     for a in by_name[name]), name
        assert all("lock_wait_us" not in a for a in by_name[name])
    for name in LOCKED_LEAVES:
        assert by_name[name], name
        assert all(isinstance(a["lock_wait_us"], int)
                   and a["lock_wait_us"] >= 0 for a in by_name[name]), name
    assert all("lock_wait_us" not in a
               for a in by_name["engine.decode.fetch"])
    # The other attributes are as they were.
    assert by_name["engine.prefill.launch"][0]["tokens"] == 8
    assert "rows" in by_name["engine.decode.launch"][0]
    assert "finished" in by_name["engine.decode.emit"][-1]
    # With no sink live: the counter still rises, nothing is recorded.
    assert not tracing.live()
    delta = served(engine)
    assert delta["launches_starved"] > 0
    assert tracing.get_spans() == []


def test_the_lock_is_clocked_only_for_a_live_span():
    from ray_tpu.serve.llm_engine.engine import _Held

    class Lock:
        held = 0

        def acquire(self):
            self.held += 1

        def release(self):
            self.held -= 1

    class Span:
        def __init__(self, live):
            self.live, self.said = live, {}

        def set(self, **attrs):
            self.said.update(attrs)

    lock, off, on = Lock(), Span(False), Span(True)
    with _Held(lock, off):
        assert lock.held == 1
    with _Held(lock, on):
        assert lock.held == 1
    assert lock.held == 0
    assert off.said == {} and set(on.said) == {"lock_wait_us"}
    assert on.said["lock_wait_us"] >= 0
    # An exception inside lets go of it all the same.
    with pytest.raises(KeyError):
        with _Held(lock, off):
            raise KeyError("x")
    assert lock.held == 0
    # The real thing: an inert phase reads no clock for it.
    with tracing.phase("engine.sweep") as sweep:
        assert not sweep.live
        with _Held(lock, sweep):
            pass
    assert lock.held == 0
