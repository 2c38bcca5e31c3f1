"""LLM inference engine: paged KV cache, prefill/decode scheduling,
preemption, deadlines, autoscale policy, batcher hardening (ISSUE 14).

The jax-heavy tests share one float32 tiny-config engine where
possible (each engine compiles one prefill + one decode program).
"""

import dataclasses
import threading
import time

import prefill_chunk_cases
import pytest

import ray_tpu
from ray_tpu.exceptions import (
    CacheExhaustedError,
    SystemOverloadedError,
    TaskTimeoutError,
)


def _f32_tiny():
    import jax.numpy as jnp

    from ray_tpu.models import llama

    return dataclasses.replace(llama.LlamaConfig.tiny(),
                               dtype=jnp.float32)


# ---------------------------------------------------------------- kv cache


def test_paged_cache_alloc_free_exhaustion():
    from ray_tpu.serve.llm_engine import PagedKVCache

    cache = PagedKVCache(num_blocks=5, block_size=8, max_blocks_per_seq=4)
    assert cache.free_blocks == 4  # block 0 is reserved scratch
    table: list = []
    assert cache.grow(table, 1) is True
    assert cache.grow(table, 8) is False  # same block covers 8 tokens
    assert cache.grow(table, 9) is True
    assert len(table) == 2 and 0 not in table
    other: list = []
    cache.grow(other, 16)
    assert cache.free_blocks == 0
    with pytest.raises(CacheExhaustedError):
        cache.grow(table, 17)
    cache.release(other)
    assert cache.free_blocks == 2 and other == []
    cache.grow(table, 17)
    assert cache.blocks_allocated == 5 and cache.blocks_freed == 2
    # Per-sequence table cap raises even with free blocks around.
    with pytest.raises(CacheExhaustedError):
        cache.grow(table, 8 * 4 + 1)
    assert cache.fits_ever(32) and not cache.fits_ever(33)


def test_scheduler_preempts_lowest_progress():
    from ray_tpu.serve.llm_engine import PagedKVCache
    from ray_tpu.serve.llm_engine.scheduler import (
        EngineRequest,
        Scheduler,
    )

    cache = PagedKVCache(num_blocks=9, block_size=8, max_blocks_per_seq=8)
    sched = Scheduler(cache, max_batch=4, max_waiting=4,
                      max_tokens_per_seq=64)
    reqs = []
    for i, progress in enumerate([5, 2, 9]):
        req = EngineRequest([1, 2, 3], 16, 0.0)
        req.output = list(range(progress))
        sched.active.append(req)
        reqs.append(req)
    assert sched.pick_victim() is reqs[1]  # fewest generated tokens
    cache.grow(reqs[1].block_table, 16)
    sched.preempt(reqs[1])
    assert reqs[1] not in sched.active
    assert sched.waiting[0] is reqs[1]  # front of the queue
    assert reqs[1].block_table == [] and cache.free_blocks == 8
    # Resume recomputes prompt + output[:-1] and skips first-sample.
    claimed = sched.claim_prefill()
    assert claimed is reqs[1]
    assert claimed.context == reqs[1].tokens + reqs[1].output[:-1]
    assert claimed.sample_first is False


def test_scheduler_bounded_queue_and_never_fits():
    from ray_tpu.serve.llm_engine import PagedKVCache
    from ray_tpu.serve.llm_engine.scheduler import (
        EngineRequest,
        Scheduler,
    )

    cache = PagedKVCache(num_blocks=3, block_size=8, max_blocks_per_seq=8)
    sched = Scheduler(cache, max_batch=2, max_waiting=1,
                      max_tokens_per_seq=64)
    sched.try_enqueue(EngineRequest([1], 4, 0.0))
    with pytest.raises(CacheExhaustedError):
        sched.try_enqueue(EngineRequest([1], 4, 0.0))  # queue full
    sched.waiting.clear()
    with pytest.raises(CacheExhaustedError):
        # 2 usable blocks = 16 tokens; 20-token need can never fit.
        sched.try_enqueue(EngineRequest(list(range(10)), 10, 0.0))


def test_scheduler_deadline_sweep_stages():
    from ray_tpu.serve.llm_engine import PagedKVCache
    from ray_tpu.serve.llm_engine.scheduler import (
        DECODE,
        EngineRequest,
        Scheduler,
    )

    cache = PagedKVCache(num_blocks=5, block_size=8, max_blocks_per_seq=4)
    sched = Scheduler(cache, max_batch=2, max_waiting=4,
                      max_tokens_per_seq=32)
    waiting = EngineRequest([1], 4, 0.0, deadline=time.time() - 1)
    decoding = EngineRequest([1], 4, 0.0, deadline=time.time() - 1)
    decoding.state = DECODE
    cache.grow(decoding.block_table, 8)
    live = EngineRequest([1], 4, 0.0, deadline=time.time() + 60)
    sched.waiting.extend([waiting, live])
    sched.active.append(decoding)
    expired = sched.sweep_expired()
    assert set(expired) == {waiting, decoding}
    assert live in sched.waiting and decoding not in sched.active
    assert cache.free_blocks == 4  # expired blocks reclaimed
    assert sched.expired_error(waiting).stage == "llm_queue"
    assert sched.expired_error(decoding).stage == "llm_decode"


# ------------------------------------------------------------- the engine


@pytest.fixture(scope="module")
def paged_engine():
    from ray_tpu.serve.llm_engine import LLMEngine

    engine = LLMEngine(_f32_tiny(), max_batch_size=4, max_seq_len=64,
                       block_size=8, prefill_chunk=8, seed=0)
    yield engine
    engine.shutdown()


def test_paged_decode_matches_full_forward(paged_engine):
    """Greedy paged decode == full-context greedy decode (f32; the
    gather-by-block-table step must be numerically the dense path)."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = paged_engine.config
    prompt = [5, 9, 2, 7]
    req = paged_engine.submit(prompt, max_new_tokens=6)
    out = paged_engine.result(req, timeout_s=120)

    toks = list(prompt)
    expected = []
    for _ in range(6):
        logits = llama.forward(
            paged_engine.params, jnp.asarray([toks], dtype=jnp.int32),
            cfg)
        nxt = int(jnp.argmax(logits[0, -1]))
        expected.append(nxt)
        toks.append(nxt)
    assert out == expected


@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
def test_paged_steps_with_grouped_heads_match_full_forward(dtype_name):
    """The two jitted steps on a GQA configuration (8 query heads on 2
    key-value heads), driven as the engine drives them: shuffled,
    interleaved block tables, ragged prompts chunked with a padded last
    chunk, then batched decode with one inactive row. Held to
    ``llama.forward`` on the same weights; padding and the inactive row
    may write the scratch block and nothing else."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import PagedKVCache
    from ray_tpu.serve.llm_engine import model as paged_model

    dtype = jnp.dtype(dtype_name)
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=3, num_heads=8, num_kv_heads=2, head_dim=8,
        max_seq_len=32, remat=False, dtype=dtype)
    num_blocks, block, chunk, width, steps = 40, 4, 4, 8, 6
    prompts = [[7, 3, 11, 200, 5], list(range(20, 31)), [9, 1, 4],
               list(range(100, 114))]
    params = paged_model.serving_params(cfg, None, seed=3)
    rng = np.random.default_rng(0)
    # Every request will hold ceil((prompt + steps) / block) blocks,
    # dealt from one shuffled deck so no table is contiguous.
    deck = [int(b) for b in rng.permutation(np.arange(1, num_blocks))]
    need = [-(-(len(p) + steps) // block) for p in prompts]
    tables = [[] for _ in prompts]
    for turn in range(max(need)):
        for i, n in enumerate(need):
            if turn < n:
                tables[i].append(deck.pop())
    bt = np.zeros((len(prompts) + 1, width), np.int32)  # last row inactive
    for i, table in enumerate(tables):
        bt[i, :len(table)] = table
    pool = PagedKVCache.init_pool(cfg, num_blocks, block)
    prefill = paged_model.make_prefill_chunk(cfg, block)
    decode = paged_model.make_decode_step(cfg, block)

    first_logits = []
    for i, prompt in enumerate(prompts):
        for start in range(0, len(prompt), chunk):
            n = min(chunk, len(prompt) - start)
            tokens = np.zeros((1, chunk), np.int32)
            tokens[0, :n] = prompt[start:start + n]
            positions = np.zeros((1, chunk), np.int32)
            positions[0, :n] = np.arange(start, start + n)
            logits, pool, _ = prefill(
                params, pool, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(bt[i:i + 1]), np.int32(n), np.int32(n - 1))
        first_logits.append(np.asarray(logits, np.float32))
    assert any(len(p) % chunk for p in prompts)  # a padded last chunk ran

    generated = [[int(row.argmax())] for row in first_logits]
    lengths = [len(p) for p in prompts]
    for _ in range(steps - 1):
        last = np.zeros((len(bt), 1), np.int32)
        last[:len(prompts), 0] = [g[-1] for g in generated]
        nxt, pool, _ = decode(
            params, pool, jnp.asarray(last),
            jnp.asarray(lengths + [0], dtype=jnp.int32), jnp.asarray(bt),
            jax.random.PRNGKey(0), jnp.zeros((len(bt),), jnp.float32))
        for g, token in zip(generated, np.asarray(nxt)):
            g.append(int(token))
        lengths = [n + 1 for n in lengths]

    # The reference: teacher-forced full-context logits, same weights.
    for i, prompt in enumerate(prompts):
        row = prompt + generated[i][:-1]
        ref = np.asarray(llama.forward(
            params, jnp.asarray([row], dtype=jnp.int32), cfg)[0],
            np.float32)[len(prompt) - 1:]
        if dtype == jnp.float32:
            np.testing.assert_allclose(first_logits[i], ref[0], atol=1e-4)
            assert generated[i] == [int(r.argmax()) for r in ref]
        else:
            # The benchmark's near-tie rule: 8e-2 on logits of std 1.0.
            gaps = [float(r.max() - r[t]) for r, t in zip(ref, generated[i])]
            assert max(gaps) <= 8e-2 * ref.std(), (gaps, ref.std())

    # Written: the positions each table covers, and the scratch block.
    written = np.zeros((num_blocks, block), bool)
    written[0, 0] = True
    for table, n in zip(tables, lengths):
        for p in range(n):
            written[table[p // block], p % block] = True
    for name in ("k", "v"):
        touched = np.asarray(pool[name].astype(jnp.float32) != 0).any(
            axis=(0, 3, 4))
        np.testing.assert_array_equal(touched, written)


def test_concurrent_ragged_requests_batch(paged_engine):
    """Ragged concurrent requests share the fixed decode batch
    (batched_decode_steps counts steps with >= 2 active rows)."""
    before = paged_engine.engine_stats()["batched_decode_steps"]
    results = {}
    lock = threading.Lock()

    def gen(i):
        req = paged_engine.submit([1 + i] * (2 * i + 1),
                                  max_new_tokens=8)
        out = paged_engine.result(req, timeout_s=120)
        with lock:
            results[i] = out

    threads = [threading.Thread(target=gen, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 6
    assert all(len(v) == 8 for v in results.values())
    assert paged_engine.engine_stats()["batched_decode_steps"] > before


def test_streaming_tokens_overlap_decode(paged_engine):
    """stream_tokens yields while the engine still decodes (the TTFT
    surface): the first token arrives before the request seals."""
    req = paged_engine.submit([3, 1, 4], max_new_tokens=12, stream=True)
    got = []
    for token in paged_engine.stream_tokens(req):
        got.append(token)
        if len(got) == 1:
            assert not req.done.is_set() or len(req.output) < 12
    assert got == req.output and len(got) == 12


def test_chunked_prefill_interleaves_with_decode(paged_engine):
    """A long prompt prefills in chunks BETWEEN decode steps: the
    in-flight stream keeps emitting while the long prompt loads."""
    a = paged_engine.submit([7, 7, 7], max_new_tokens=24, stream=True)
    a_tokens_ts = []
    collected = threading.Event()

    def consume():
        for _ in paged_engine.stream_tokens(a):
            a_tokens_ts.append(time.monotonic())
        collected.set()

    thread = threading.Thread(target=consume)
    thread.start()
    while len(a_tokens_ts) < 2:  # A is decoding
        time.sleep(0.005)
    # 40-token prompt / chunk 8 => 5 prefill iterations for B.
    submit_ts = time.monotonic()
    b = paged_engine.submit(list(range(1, 41)), max_new_tokens=2)
    b_out = paged_engine.result(b, timeout_s=120)
    b_first_ts = time.monotonic()
    collected.wait(timeout=120)
    thread.join(timeout=10)
    assert len(b_out) == 2
    during = [ts for ts in a_tokens_ts if submit_ts < ts < b_first_ts]
    assert during, (
        "stream A stalled for the whole of B's chunked prefill — the "
        "interleave is broken")


def test_preemption_recompute_on_resume_exact(paged_engine):
    """Cache pressure preempts the lowest-progress stream; on resume
    it re-prefills prompt+generated and continues from the exact token
    — greedy outputs byte-identical to the pressure-free run, each
    request completing exactly once."""
    from ray_tpu.serve.llm_engine import LLMEngine

    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14]]
    reference = {}
    for i, prompt in enumerate(prompts):
        req = paged_engine.submit(prompt, max_new_tokens=12)
        reference[i] = paged_engine.result(req, timeout_s=120)

    # 5 usable blocks of 8 across four 2-3 block sequences: pressure.
    engine = LLMEngine(paged_engine.config, paged_engine.params,
                       max_batch_size=4, max_seq_len=64, block_size=8,
                       prefill_chunk=8, num_blocks=6, seed=0)
    try:
        results = {}
        lock = threading.Lock()

        def gen(i):
            req = engine.submit(prompts[i], max_new_tokens=12)
            out = engine.result(req, timeout_s=120)
            with lock:
                results[i] = out

        threads = [threading.Thread(target=gen, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = engine.engine_stats()
        assert stats["preemptions"] > 0 and stats["resumes"] > 0, stats
        assert stats["finished"] == 4
        for i in range(4):
            assert results[i] == reference[i], (i, stats)
    finally:
        engine.shutdown()


def test_waiting_deadline_seals_typed_llm_queue(paged_engine):
    """A budget dying in the bounded waiting queue seals
    TaskTimeoutError stage llm_queue — typed, exactly once, without
    the request ever reaching the decode batch."""
    from ray_tpu.serve.llm_engine import LLMEngine

    engine = LLMEngine(paged_engine.config, paged_engine.params,
                       max_batch_size=1, max_seq_len=64, block_size=8,
                       prefill_chunk=8, seed=0)
    # Every program exists once the constructor returns, so the hog's 40
    # steps would be over in 11 ms: each waits, and the one row is held
    # well past the parked request's budget.
    engine._maybe_chaos_slow_step = lambda: time.sleep(0.01)
    try:
        hog = engine.submit([1, 2], max_new_tokens=40)
        parked = engine.submit([3, 4], max_new_tokens=4,
                               deadline=time.time() + 0.15)
        with pytest.raises(TaskTimeoutError) as err:
            engine.result(parked, timeout_s=30)
        assert err.value.stage == "llm_queue"
        assert engine.engine_stats()["deadline_expired"] >= 1
        assert len(engine.result(hog, timeout_s=120)) == 40
        assert parked.output == []  # never decoded
    finally:
        engine.shutdown()


def test_queue_full_and_never_fits_shed_typed(paged_engine):
    """Bounded admission sheds through the SystemOverloadedError path:
    queue-full and never-fits both raise CacheExhaustedError (a
    SystemOverloadedError subclass — the HTTP tier's 503 contract)."""
    from ray_tpu.serve.llm_engine import LLMEngine

    engine = LLMEngine(paged_engine.config, paged_engine.params,
                       max_batch_size=1, max_seq_len=64, block_size=8,
                       prefill_chunk=8, max_waiting=1, num_blocks=5,
                       seed=0)
    try:
        hog = engine.submit([1, 2], max_new_tokens=30)
        deadline = time.monotonic() + 30
        while hog.state == "waiting" and time.monotonic() < deadline:
            time.sleep(0.005)  # wait for the engine to claim it
        engine.submit([3, 4], max_new_tokens=4)   # fills the queue
        with pytest.raises(CacheExhaustedError) as err:
            engine.submit([5, 6], max_new_tokens=4)
        assert isinstance(err.value, SystemOverloadedError)
        stats = engine.engine_stats()
        assert stats["shed_queue_full"] >= 1
    finally:
        engine.shutdown()
    # Never-fits: 2 usable blocks = 16 tokens, request needs 24.
    engine = LLMEngine(paged_engine.config, paged_engine.params,
                       max_batch_size=1, max_seq_len=64, block_size=8,
                       prefill_chunk=8, num_blocks=3, seed=0)
    try:
        with pytest.raises(CacheExhaustedError):
            engine.submit(list(range(12)), max_new_tokens=12)
        assert engine.engine_stats()["shed_cache"] >= 1
    finally:
        engine.shutdown()


def test_engine_stats_keys_contract(paged_engine):
    from ray_tpu.serve.llm_engine import ENGINE_STAT_KEYS

    stats = paged_engine.engine_stats()
    assert set(stats) == set(ENGINE_STAT_KEYS)
    load = paged_engine.engine_load()
    assert set(load) == {"depth", "waiting", "active", "free_blocks"}


TIME_COUNTERS = ("first_tokens", "queue_wait_us", "prefill_us",
                 "loop_wall_us", "loop_cpu_us", "fetch_wait_us",
                 "decode_host_us")


def test_time_counters_account_for_streamed_requests(paged_engine):
    """Where the time went, without a profiler: one first token per
    request, and per loop pass wall >= what is blocked on the device,
    wall >= the thread's own CPU time."""
    before = paged_engine.engine_stats()
    requests = [paged_engine.submit([1 + i] * (3 + 5 * i), max_new_tokens=6,
                                    stream=True) for i in range(5)]
    snapshots = []
    for req in requests:
        assert len(list(paged_engine.stream_tokens(req))) == 6
        snapshots.append(paged_engine.engine_stats())
    after = snapshots[-1]
    assert after["first_tokens"] - before["first_tokens"] == 5
    for earlier, later in zip([before] + snapshots, snapshots):
        for key in TIME_COUNTERS:
            assert later[key] >= earlier[key], key  # monotonic
    delta = {k: after[k] - before[k] for k in TIME_COUNTERS}
    assert all(isinstance(after[k], int) for k in TIME_COUNTERS)
    assert delta["loop_wall_us"] > 0
    assert delta["loop_cpu_us"] <= delta["loop_wall_us"]
    assert delta["fetch_wait_us"] <= delta["loop_wall_us"]
    assert 0 < delta["decode_host_us"] <= delta["loop_wall_us"]
    # Four of the five queued behind a prefill; every prefill took time.
    assert delta["queue_wait_us"] > 0 and delta["prefill_us"] > 0
    for req in requests:
        assert req.submitted_ns <= req.claimed_ns <= req.first_token_ns \
            <= req.sealed_ns


def test_time_counter_names_clash_with_no_engine_argument():
    """The benchmark lays the engine's arguments over the counters."""
    import inspect

    from ray_tpu.serve.llm_engine import ENGINE_STAT_KEYS, LLMEngine

    arguments = set(inspect.signature(LLMEngine.__init__).parameters)
    assert not arguments & set(ENGINE_STAT_KEYS)
    assert set(TIME_COUNTERS) <= set(ENGINE_STAT_KEYS)


ENGINE_SPANS = (
    "engine.iteration", "engine.sweep", "engine.prefill.schedule",
    "engine.prefill.launch", "engine.prefill.first_token",
    "engine.decode.schedule", "engine.decode.launch",
    "engine.decode.fetch", "engine.decode.emit", "engine.idle")


def test_phase_spans_reach_the_profilers_host_plane(paged_engine, tmp_path):
    """Under a profiler session every phase of the engine loop and the
    replica's per-chunk put are host events of the trace (on a chip:
    on the device trace's clock), nested as the loop nests them."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from ray_tpu.serve.replica import Replica

    class Streams:
        def generate(self, request):
            req = paged_engine.submit(request["tokens"], max_new_tokens=4,
                                      stream=True)
            yield from paged_engine.stream_tokens(req)

    class Chunks(list):
        put = list.append

    replica = Replica("llm", "llm#0", Streams(), (), {})
    chunks = Chunks()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        time.sleep(0.02)  # the empty engine idles
        replica.handle_request_streaming(
            "generate", ({"tokens": list(range(1, 12))},), {}, chunks)
    finally:
        jax.profiler.stop_trace()
    assert [kind for kind, _ in chunks] == ["chunk"] * 4 + ["end"]
    found = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = [(line, e) for plane in ProfileData.from_file(found[0]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    names = {e.name for _, e in events}
    assert set(ENGINE_SPANS) | {"serve.stream.put"} <= names
    # The key is split inside the decode program: no span times it.
    assert "engine.decode.split_key" not in names
    launch = next(e for _, e in events if e.name == "engine.prefill.launch")
    assert dict(launch.stats)["tokens"] == 8  # prefill_chunk of the engine
    # Leaves lie inside an iteration of the same host line.
    line, fetch = next((line, e) for line, e in events
                       if e.name == "engine.decode.fetch")
    assert any(e.name == "engine.iteration"
               and e.start_ns <= fetch.start_ns
               and fetch.start_ns + fetch.duration_ns
               <= e.start_ns + e.duration_ns for e in line.events)


# ------------------------------- one call into JAX per program and pass

# What the engine of PR 25 (commit a01396e, the parent of the PR that
# moved the split into the decode program) served on this CPU for
# LLMEngine(_f32_tiny(), max_batch_size=4, max_seq_len=64, block_size=8,
# prefill_chunk=8, seed=7) and these three requests, one after another.
SAMPLED_REQUESTS = (([5, 9, 2, 7], 0.8),
                    ([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 1.5), ([8, 8], 0.0))
PARENT_TOKENS = (
    [254, 221, 46, 49, 11, 152, 244, 83, 47, 30],
    [183, 98, 99, 144, 155, 139, 185, 162, 38, 26],
    [4, 133, 245, 214, 217, 85, 121, 35, 47, 206])


def _turnover(engine, rounds=3):
    """Requests of mixed lengths and temperatures come and go."""
    for i in range(rounds):
        requests = [engine.submit([1 + i, 2, 3 + j], max_new_tokens=4 + j,
                                  temperature=0.5 * (j % 2))
                    for j in range(3)]
        for j, req in enumerate(requests):
            assert len(engine.result(req, timeout_s=120)) == 4 + j


@pytest.mark.parametrize("run", ["first", "again"])
def test_sampled_tokens_are_the_parents_for_a_seed(run):
    """The split is the same function on the same words in the same
    order, on the device now: a seed gives the tokens it gave when the
    host split the key, in every fresh engine."""
    from ray_tpu.serve.llm_engine import LLMEngine

    engine = LLMEngine(_f32_tiny(), max_batch_size=4, max_seq_len=64,
                       block_size=8, prefill_chunk=8, seed=7)
    try:
        for (prompt, temperature), want in zip(SAMPLED_REQUESTS,
                                               PARENT_TOKENS):
            req = engine.submit(prompt, max_new_tokens=10,
                                temperature=temperature)
            assert engine.result(req, timeout_s=120) == want
    finally:
        engine.shutdown()


def test_host_calls_are_two_a_decode_step(paged_engine):
    """``host_calls`` counts the engine thread's calls into JAX: the
    decode program with its one array and the read of its tokens (the
    loop before it made nine: two programs of the split, five
    transfers, the dispatch, the read), one per prefill chunk, and a
    greedy first token's argmax and read."""
    before = paged_engine.engine_stats()
    requests = [paged_engine.submit([2 + i] * (5 + 7 * i), max_new_tokens=9)
                for i in range(3)]
    for req in requests:
        assert len(paged_engine.result(req, timeout_s=120)) == 9
    after = paged_engine.engine_stats()
    delta = {k: after[k] - before[k] for k in after}
    assert delta["first_tokens"] == 3 and delta["prefill_chunks"] >= 5
    assert delta["decode_steps"] >= 8
    assert delta["host_calls"] - delta["prefill_chunks"] \
        - 2 * delta["first_tokens"] == 2 * delta["decode_steps"]
    # Decode alone: rows already in the batch, nothing left to prefill.
    req = paged_engine.submit([4, 4], max_new_tokens=30, stream=True)
    tokens = paged_engine.stream_tokens(req)
    next(tokens)
    while True:
        with paged_engine._lock:  # a step's counters move under it
            low = paged_engine.engine_stats()
        if low["first_tokens"] == after["first_tokens"] + 1:
            break
    assert len(list(tokens)) == 29
    high = paged_engine.engine_stats()
    steps = high["decode_steps"] - low["decode_steps"]
    assert steps >= 20 and high["prefill_chunks"] == low["prefill_chunks"]
    assert high["host_calls"] - low["host_calls"] == 2 * steps


@pytest.mark.parametrize("meshed", [False, True], ids=["no_mesh", "mesh"])
def test_decode_program_is_compiled_once_over_turnover(meshed):
    """The key goes into the decode program as it came out of it. Under
    a mesh what comes out is committed to the mesh: a key made on the
    host would compile the program a second time at the second step.
    The program exists once (it reads by row: the whole table alone of
    ``engine.table_widths``), built by the constructor."""
    import jax
    import numpy as np

    from ray_tpu.serve.llm_engine import LLMEngine

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",)) \
        if meshed else None
    engine = LLMEngine(_f32_tiny(), max_batch_size=3, max_seq_len=64,
                       block_size=8, prefill_chunk=8, seed=1, mesh=mesh)
    try:
        assert engine._widths == (2, 4, 8)
        assert engine._step_widths == (8,)
        assert engine._decode_step._cache_size() == 1
        _turnover(engine)
        assert engine.engine_stats()["decode_steps"] >= 12
        # Every call found the program the constructor had built.
        assert engine._decode_step._cache_size() == 1
        assert engine._key.committed == meshed
    finally:
        engine.shutdown()


@pytest.mark.parametrize("fails_at", [
    "the_call", "the_read", "the_chunk",
    "the_call_with_a_step_in_flight", "the_read_with_a_step_in_flight"])
def test_failed_step_leaves_a_usable_key(fails_at):
    """The key is not donated and is replaced only once a step's
    tokens were read: after ``_reset_after_failure`` the engine holds
    the key the failed step was given, and samples with it. A prefill
    program that raises fails its request the same way, and the engine
    serves the next. With a step in flight (the request's third call
    fails, or its second result cannot be read, after its first step
    was read): the step launched and not read is dropped with the
    request, and the key is the one the last step READ returned."""
    import numpy as np

    from ray_tpu.serve.llm_engine import LLMEngine

    class Unreadable:
        """A step's tokens that the host cannot read; the next step,
        launched before the read, is still given the device's."""

        def __init__(self, tokens):
            self.tokens = tokens

        def __array__(self, *args, **kwargs):
            raise RuntimeError("the device lost the step")

    in_flight = fails_at.endswith("_with_a_step_in_flight")
    engine = LLMEngine(_f32_tiny(), max_batch_size=2, max_seq_len=64,
                       block_size=8, prefill_chunk=8, seed=3)
    try:
        warm = engine.submit([1, 2, 3], max_new_tokens=4, temperature=0.9)
        assert len(engine.result(warm, timeout_s=120)) == 4
        step, chunk_step, key_before, failures = engine._decode_step, \
            engine._prefill_step, np.asarray(engine._key), []
        keys = []  # the key each decode call of the doomed request returned
        steps_before = engine.engine_stats()["decode_steps"]

        def failing_chunk(*args):
            if failures:
                return chunk_step(*args)
            failures.append(fails_at)
            raise RuntimeError("the device refused the chunk")

        def failing(params, pool, rows, key, expert_stats, prev):
            if isinstance(prev, Unreadable):
                prev = prev.tokens
            due = len(keys) == (2 if fails_at.startswith("the_call_with")
                                else 1 if in_flight else 0)
            if failures or not due:
                out = step(params, pool, rows, key, expert_stats, prev)
                if not failures:
                    keys.append(np.asarray(out[3]))
                return out
            failures.append(fails_at)
            if fails_at.startswith("the_call"):
                raise RuntimeError("the device refused the step")
            out, pool, expert_stats, key = step(params, pool, rows, key,
                                                expert_stats, prev)
            return Unreadable(out), pool, expert_stats, key

        if fails_at == "the_chunk":
            engine.__dict__["_prefill_step"] = failing_chunk
        else:
            engine.__dict__["_decode_step"] = failing
        doomed = engine.submit([4, 5], max_new_tokens=6)
        with pytest.raises(RuntimeError, match="the device"):
            engine.result(doomed, timeout_s=120)
        assert failures == [fails_at]
        assert engine._unread is None
        if in_flight:
            # Its first step was read, nothing after it.
            key_before = keys[0]
            assert len(doomed.output) == 2
            assert engine.engine_stats()["decode_steps"] == steps_before + 1
        np.testing.assert_array_equal(np.asarray(engine._key), key_before)
        after = engine.submit([6, 7, 8], max_new_tokens=5, temperature=0.9)
        out = engine.result(after, timeout_s=120)
        assert len(out) == 5 and all(
            0 <= t < engine.config.vocab_size for t in out)
        assert (np.asarray(engine._key) != key_before).any()
    finally:
        engine.shutdown()


# ------------------------------------------------------- one step ahead


@pytest.mark.parametrize("chunk", prefill_chunk_cases.WIDTHS,
                         ids=prefill_chunk_cases.WIDTH_IDS)
def test_greedy_tokens_do_not_depend_on_the_chunk_width(chunk):
    """A prompt shorter than a chunk, one past a chunk and no multiple
    of it and one of more than two chunks: the same tokens whether the
    prompt goes in eights, in thirty-twos or in the default's 128."""
    prefill_chunk_cases.same_tokens_at(_f32_tiny(), chunk)


def test_a_preemption_inside_a_wide_chunks_prompt_resumes_exact():
    prefill_chunk_cases.resumes_to_the_same_tokens(_f32_tiny())


@pytest.mark.parametrize("max_tokens, block, want", [
    (2048, 16, 128), (4096, 16, 128), (64, 8, 64), (24, 8, 24),
    (96, 8, 96), (144, 48, 96), (512, 256, 256)])
def test_the_default_chunk_is_clamped_to_the_table(max_tokens, block, want):
    """The knob's 128 tokens, no longer than a row's table, in whole
    paged blocks (so in whole blocks of a diffusion model too)."""
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.serve.llm_engine.engine import default_prefill_chunk

    assert GLOBAL_CONFIG.llm_prefill_chunk == 128
    got = default_prefill_chunk(max_tokens, block)
    assert got == want and got % block == 0 and got <= max_tokens


def test_an_engine_with_a_short_table_takes_the_table_for_its_chunk(
        paged_engine):
    """20 positions asked for are a table of 24: the chunk is the table,
    and a prompt that fills most of it goes in one chunk to the tokens
    the fixture's engine (chunks of 8) serves."""
    from ray_tpu.serve.llm_engine import LLMEngine

    prompt = list(range(1, 18))
    want = paged_engine.result(
        paged_engine.submit(prompt, max_new_tokens=5), timeout_s=120)
    engine = LLMEngine(paged_engine.config, paged_engine.params,
                       max_batch_size=2, max_seq_len=20, block_size=8,
                       seed=0)
    try:
        assert (engine.max_tokens, engine.prefill_chunk_len) == (24, 24)
        before = engine.engine_stats()["prefill_chunks"]
        assert engine.result(engine.submit(prompt, max_new_tokens=5),
                             timeout_s=120) == want
        assert engine.engine_stats()["prefill_chunks"] - before == 1
    finally:
        engine.shutdown()


@pytest.mark.parametrize("kind", ["dense", "olmoe", "hybrid"])
def test_the_prefill_program_makes_the_head_on_one_row(kind):
    """A chunk returns the logits of its last real token alone: the
    lowered program holds no ``[1, chunk, vocabulary]`` (nor ``[chunk,
    vocabulary]``) tensor, in any dtype; the head's product is over the
    one row and the row of zeros beside it."""
    import re

    import jax

    from ray_tpu.serve.llm_engine import model as paged_model

    # A vocabulary no other width of the tiny configurations equals.
    config = dataclasses.replace(_step_ahead_config(kind), vocab_size=250)
    family = paged_model.family(config)
    block, chunk, table, rows = 4, 24, 16, 2
    params = jax.eval_shape(
        lambda: family.init_params(config, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: family.init_cache(
        config, 1 + rows * table, block, rows, chunk))
    text = family.make_engine_prefill_chunk(config, block, chunk).lower(
        params, cache, family.pack_prefill_chunk(chunk, table, [1], 0, [1], 0),
        None).as_text()
    vocabulary = config.vocab_size
    assert re.search(rf"tensor<(1x)?{chunk}x{vocabulary}x", text) is None
    assert re.search(rf"tensor<{vocabulary}x(1x)?{chunk}x", text) is None
    assert re.search(rf"tensor<(1x2x{vocabulary}|{vocabulary}x1x2)xf32>",
                     text) is not None


def _step_ahead_config(kind):
    import jax.numpy as jnp

    from ray_tpu.models import llama, phi4flash

    if kind == "hybrid":
        return phi4flash.Phi4FlashConfig.tiny(dtype=jnp.float32,
                                              max_seq_len=64)
    if kind == "olmoe":  # 8 experts of which 3 a token, QK-norm
        return llama.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=32,
            num_layers=3, num_heads=4, num_kv_heads=4, head_dim=16,
            max_seq_len=64, remat=False, dtype=jnp.float32, num_experts=8,
            experts_per_token=3, qk_norm=True)
    return _f32_tiny()


def _step_ahead_engine(config, params=None, at_once=False, **kwargs):
    """An engine of 4 rows; ``at_once``: one that reads every decode
    step before it schedules the next, as the loop did before it kept a
    step ahead (its family says of every row that its next pass is not
    known early)."""
    from ray_tpu.serve.llm_engine import LLMEngine

    engine = LLMEngine(config, params, max_batch_size=4, max_seq_len=64,
                       block_size=4, prefill_chunk=8, seed=7, **kwargs)
    if at_once:
        engine._family = dataclasses.replace(engine._family,
                                             ahead=lambda req: False)
    return engine


def _serve_together(engine, requests):
    """Submit ``(prompt, new tokens, temperature)`` requests under the
    engine's lock, so that its loop meets them all at once and its
    schedule does not depend on when this thread ran; their tokens."""
    with engine._lock:
        submitted = [engine.submit(prompt, max_new_tokens=new,
                                   temperature=temperature)
                     for prompt, new, temperature in requests]
    return [engine.result(req, timeout_s=300) for req in submitted]


# Rows join mid-run (prompts of one, three and two chunks are claimed in
# turn while the first decodes) and end by count mid-run, the last
# claimed first; every row is claimed before one ends, so a row's slot,
# which its draw depends on, does not hang on when a slot came back.
_JOINING = [(list(range(1, 4)), 18, 0.0), (list(range(5, 25)), 14, 0.8),
            (list(range(30, 41)), 10, 0.0), ([9, 8, 7, 6, 5], 6, 0.6)]
_SECOND_WAVE = [([3, 1, 4, 1, 5], 9, 0.7), (list(range(40, 58)), 12, 0.0),
                ([2, 7], 15, 1.1)]
# More requests than rows: a row that ends is refilled from the queue
# (one step later when a step is ahead, so only greedy tokens compare).
_REFILLING = [([1 + i] * (1 + 3 * i), 3 + (5 * i) % 11, 0.0)
              for i in range(9)]


@pytest.mark.parametrize("kind", ["dense", "olmoe", "hybrid"])
def test_streams_are_those_of_an_engine_that_fetches_at_once(kind):
    """Step N+1 runs on step N's tokens where they lie on the device:
    every request, greedy or sampled from the seed, gets byte for byte
    the tokens of an engine that reads each step before it schedules
    the next, through rows joining and ending mid-run and through a
    batch refilled from a queue; and the counter says the steps were
    launched ahead."""
    config = _step_ahead_config(kind)
    want, got = [], []
    at_once = _step_ahead_engine(config, at_once=True)
    try:
        for wave in (_JOINING, _SECOND_WAVE, _REFILLING):
            want.append(_serve_together(at_once, wave))
        stats_at_once = at_once.engine_stats()
    finally:
        at_once.shutdown()
    engine = _step_ahead_engine(config, at_once.params)
    try:
        for wave in (_JOINING, _SECOND_WAVE, _REFILLING):
            got.append(_serve_together(engine, wave))
        stats = engine.engine_stats()
        assert engine._unread is None  # nothing left in flight
        programs = engine._decode_step._cache_size()
    finally:
        engine.shutdown()
    assert got == want
    assert [len(tokens) for tokens in got[0]] == [18, 14, 10, 6]
    assert stats_at_once["decode_steps_ahead"] == 0
    assert stats["decode_tokens"] == stats_at_once["decode_tokens"]
    # Every step but a wave's first few (no step before them, or only
    # rows that joined since) was launched before the last was read.
    assert stats["decode_steps"] - 12 <= stats["decode_steps_ahead"] \
        < stats["decode_steps"]
    # A refilled row joins a step later: a few steps more, two calls each.
    extra = stats["decode_steps"] - stats_at_once["decode_steps"]
    assert 0 <= extra <= 9
    assert stats["host_calls"] - stats_at_once["host_calls"] == 2 * extra
    # The constructor's: one a width the step can be given.
    assert programs == len(engine._step_widths) == (
        3 if kind == "hybrid" else 1)


def test_preemption_reads_the_step_in_flight_first(paged_engine):
    """``test_preemption_recompute_on_resume_exact``'s case with steps
    ahead: a victim is rebuilt from its ``output``, so no step is in
    flight unread when one is picked, and every request resumes to the
    pressure-free tokens."""
    from ray_tpu.serve.llm_engine import LLMEngine

    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14]]
    reference = [paged_engine.result(paged_engine.submit(
        prompt, max_new_tokens=12), timeout_s=120) for prompt in prompts]
    engine = LLMEngine(paged_engine.config, paged_engine.params,
                       max_batch_size=4, max_seq_len=64, block_size=8,
                       prefill_chunk=8, num_blocks=6, seed=0)
    try:
        preempt, unread_at_preemption = engine._sched.preempt, []

        def preempting(victim):
            unread_at_preemption.append(engine._unread)
            # Every token made for it is in its output: the context it
            # resumes from.
            assert victim.remaining == \
                victim.max_new_tokens - len(victim.output)
            preempt(victim)

        engine._sched.preempt = preempting
        results = _serve_together(
            engine, [(prompt, 12, 0.0) for prompt in prompts])
        stats = engine.engine_stats()
    finally:
        engine.shutdown()
    assert stats["preemptions"] > 0 and stats["resumes"] > 0, stats
    assert unread_at_preemption == [None] * stats["preemptions"]
    assert 0 < stats["decode_steps_ahead"] < stats["decode_steps"]
    assert stats["finished"] == 4 and results == reference


@pytest.mark.parametrize("how", ["expired", "cancelled"])
def test_a_request_sealed_with_its_token_in_flight(paged_engine, how):
    """A request whose budget dies, or that its caller seals, while the
    step that makes its next token is unread: sealed typed, once; its
    token is never emitted; its batchmates' streams are what they are
    without it; and its row serves the next request."""
    others = [([5, 6, 7], 40), ([8, 9], 36)]
    want = [paged_engine.result(paged_engine.submit(
        prompt, max_new_tokens=new), timeout_s=120)
        for prompt, new in others + [([1, 2, 3, 4], 6)]]
    before = paged_engine.engine_stats()
    doomed = paged_engine.submit([3, 3, 3], max_new_tokens=44, stream=True)
    batchmates = [paged_engine.submit(prompt, max_new_tokens=new)
                  for prompt, new in others]
    error = TaskTimeoutError("llm_generate", "llm_decode", 0.0) \
        if how == "cancelled" else None
    for _ in range(100_000):
        with paged_engine._lock:
            unread = paged_engine._unread
            if unread is not None and doomed in unread.active \
                    and len(doomed.output) >= 3:
                emitted = list(doomed.output)
                if how == "expired":
                    doomed.deadline = time.time() - 1.0
                    paged_engine._check_caller_deadline(doomed)
                else:
                    assert paged_engine._seal(doomed, error)
                break
        time.sleep(0.0005)
    else:
        pytest.fail("never saw the request in a step in flight")
    with pytest.raises(TaskTimeoutError) as info:
        paged_engine.result(doomed, timeout_s=120)
    assert info.value.stage == "llm_decode"
    assert [paged_engine.result(req, timeout_s=120)
            for req in batchmates] == want[:2]
    # The token in flight at the seal went nowhere.
    assert doomed.output == emitted and len(emitted) < 44
    assert list(paged_engine.stream_tokens(
        paged_engine.submit([3, 3, 3], max_new_tokens=len(emitted) + 2,
                            stream=True)))[:len(emitted)] == emitted
    tenant = paged_engine.submit([1, 2, 3, 4], max_new_tokens=6)
    assert paged_engine.result(tenant, timeout_s=120) == want[2]
    after = paged_engine.engine_stats()
    assert after["finished"] - before["finished"] == 4
    assert after["deadline_expired"] - before["deadline_expired"] == \
        (how == "expired")
    assert after["blocks_allocated"] - before["blocks_allocated"] == \
        after["blocks_freed"] - before["blocks_freed"]


def test_the_decode_program_takes_the_last_steps_tokens_on_the_device():
    """``prev``: a row whose token column holds ``PREV`` takes its entry
    of the last step's result; the five-argument call, and a step whose
    rows all carry their tokens, read ``rows`` alone."""
    import jax
    import numpy as np

    from ray_tpu.serve.llm_engine import PagedKVCache
    from ray_tpu.serve.llm_engine import model as paged_model

    config = _f32_tiny()
    params = paged_model.serving_params(config, seed=2)
    step = paged_model.make_engine_decode_step(config, 4)
    key = jax.random.PRNGKey(5)

    def pool():
        return PagedKVCache.init_pool(config, 9, 4)

    first = paged_model.pack_decode_rows(
        4, 2, [(17, 3, 0.0, [1, 2]), (40, 1, 0.7, [3]), (5, 6, 0.0, [4, 5])],
        [0, 1, 3])
    out, after_first, _, key_after = step(params, pool(), first, key, None)
    host = np.asarray(out)
    carried = [(int(host[0]), 4, 0.0, [1, 2]), (int(host[1]), 2, 0.7, [3]),
               (23, 2, 0.0, [6])]  # two rows go on, the third is new
    on_device = [(paged_model.PREV, *row[1:]) for row in carried[:2]] \
        + carried[2:]
    assert paged_model.PREV < 0
    packed = paged_model.pack_decode_rows(4, 2, on_device, [0, 1, 2])
    assert packed[:, 0].tolist() == [-1, -1, 23, 0]
    copy = jax.tree.map(jax.numpy.copy, after_first)
    want = step(params, after_first, paged_model.pack_decode_rows(
        4, 2, carried, [0, 1, 2]), key_after, None)
    got = step(params, copy, packed, key_after, None, out)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[1][name]),
                                      np.asarray(want[1][name]))
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))
    # With tokens in every row ``prev`` is not looked at.
    again = step(params, pool(), first, key, None, out)
    np.testing.assert_array_equal(np.asarray(again[0]), host)


def test_engine_stats_ride_executor_stats(paged_engine):
    """Engines co-hosted with a node executor surface as the "engine"
    stats group (the ray_tpu_node_engine heartbeat payload)."""
    from ray_tpu._private.node_executor import NodeExecutorService
    from ray_tpu.serve.llm_engine import ENGINE_STAT_KEYS

    merged = NodeExecutorService._engine_stats()
    assert merged is not None
    assert set(merged) == set(ENGINE_STAT_KEYS)
    assert merged["decode_steps"] >= \
        paged_engine.engine_stats()["decode_steps"]


@pytest.fixture(scope="module")
def engine_server(paged_engine):
    from ray_tpu.serve.llm_engine import LLMEngineServer

    server = LLMEngineServer(paged_engine.config, paged_engine.params,
                             max_batch_size=2, max_seq_len=64,
                             block_size=8, prefill_chunk=8)
    yield server
    server._engine.shutdown()


def test_server_fallback_equivalence(paged_engine, engine_server):
    """``LLMEngineServer.__call__`` and ``generate`` return, for one
    greedy request, the tokens ``LLMEngine.result`` returns."""
    request = {"tokens": [5, 9, 2, 7], "max_new_tokens": 5}
    expected = paged_engine.result(
        paged_engine.submit(request["tokens"], max_new_tokens=5),
        timeout_s=120)
    assert engine_server(request) == {"tokens": expected}
    assert list(engine_server.generate(request)) == expected


@pytest.mark.parametrize("case", [
    "metrics", "healthy",
    pytest.param("loop_died", marks=pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")),
    "own_deadline"])
def test_server_control_path(paged_engine, engine_server, case):
    """What a replica asks of the deployment besides tokens: the load
    gauges the autoscaler reads, the health check, and which deadline
    a request runs under."""
    from ray_tpu._private import request_context
    from ray_tpu.serve.llm_engine import LLMEngineServer

    if case == "metrics":
        engine = engine_server._engine
        idle = engine_server.serve_metrics()
        assert idle["engine_depth"] == 0 and idle["engine_free_blocks"] > 0
        step, release = engine._decode_step, threading.Event()

        def held(*args):
            release.wait(timeout=60)
            return step(*args)

        engine.__dict__["_decode_step"] = held
        try:
            stream = engine_server.generate(
                {"tokens": [1, 2, 3], "max_new_tokens": 4})
            assert isinstance(next(stream), int)  # now a decode row
            busy = engine_server.serve_metrics()
        finally:
            release.set()
            engine.__dict__["_decode_step"] = step
        assert busy["engine_depth"] == 1
        assert busy["engine_free_blocks"] < idle["engine_free_blocks"]
        assert len(list(stream)) == 3
        assert engine_server.serve_metrics() == idle
    elif case == "healthy":
        assert len(engine_server({"tokens": [8], "max_new_tokens": 2})
                   ["tokens"]) == 2
        assert engine_server.check_health() is None
    elif case == "loop_died":
        server = LLMEngineServer(paged_engine.config, paged_engine.params,
                                 max_batch_size=2, max_seq_len=64)
        engine = server._engine

        def broken():
            raise RuntimeError("the loop broke")

        engine.__dict__["_iteration"] = broken
        engine._loop_thread.join(timeout=30)
        assert not engine._loop_thread.is_alive()
        with pytest.raises(RuntimeError, match="loop died"):
            server.check_health()
        engine.shutdown()
        server.check_health()  # a shut-down engine is not a dead one
    else:
        request = {"tokens": [1, 2], "max_new_tokens": 2}
        token = request_context.set_call(time.time() - 1.0)
        try:
            # The call's inherited budget is dead ...
            with pytest.raises(TaskTimeoutError):
                engine_server(request)
            # ... and the request's own beats it.
            out = engine_server({**request, "deadline_s": 60.0})
        finally:
            request_context.reset_call(token)
        assert len(out["tokens"]) == 2


def test_mesh_context_portable(paged_engine):
    """jax_compat.set_mesh: the engine TP path's ambient-mesh context;
    None is a no-op."""
    import numpy as np
    from jax.sharding import Mesh

    import jax
    from ray_tpu._private import jax_compat

    with jax_compat.set_mesh(None):
        pass
    devices = np.array(jax.devices("cpu")[:2])
    mesh = Mesh(devices, ("tp",))
    with jax_compat.set_mesh(mesh):
        ambient = jax_compat.ambient_mesh()
        assert ambient is not None
    assert jax_compat.ambient_mesh() is None


# --------------------------------------------------- deadline inheritance


def test_actor_call_deadline_visible_in_context(ray_start_regular):
    """The PR-7 deadline rides the actor call INTO user code via
    get_runtime_context().get_task_deadline() — what the engine's
    submit() inherits."""

    class Probe:
        def deadline(self):
            from ray_tpu.runtime_context import get_runtime_context

            return get_runtime_context().get_task_deadline()

    actor = ray_tpu.remote(Probe).remote()
    assert ray_tpu.get(actor.deadline.remote()) is None
    armed = ray_tpu.get(
        actor.deadline.options(_deadline_s=30.0).remote())
    assert armed is not None and armed > time.time() + 10


# -------------------------------------------------------- autoscale policy


def _policy_cfg(**overrides):
    from ray_tpu.serve.config import AutoscalingConfig

    defaults = dict(min_replicas=1, max_replicas=8,
                    target_ongoing_requests=2.0, metrics_interval_s=0.5,
                    upscale_delay_s=1.0, downscale_delay_s=4.0,
                    target_p99_s=0.1)
    defaults.update(overrides)
    return AutoscalingConfig(**defaults)


def test_latency_policy_scales_up_on_p99_skew():
    from ray_tpu.serve.llm_engine import LatencyPolicy

    policy = LatencyPolicy(_policy_cfg())
    # 4x p99 violation: multiplicative (capped 2x) within the window.
    assert policy.desired(2, p99_s=0.4, depth=4.0, now=100.0) == 4
    # Cooldown: an immediate second decision holds.
    assert policy.desired(4, p99_s=0.4, depth=4.0, now=100.5) == 4
    # After upscale_delay_s it keeps expanding toward max.
    assert policy.desired(4, p99_s=0.4, depth=4.0, now=101.5) == 8
    # Depth floor: modest violation still covers the standing queue.
    fresh = LatencyPolicy(_policy_cfg())
    assert fresh.desired(1, p99_s=0.12, depth=10.0, now=10.0) == 5


def test_latency_policy_scales_down_to_min_when_idle():
    from ray_tpu.serve.llm_engine import LatencyPolicy

    policy = LatencyPolicy(_policy_cfg(downscale_delay_s=1.0))
    now = 50.0
    current = 4
    for _ in range(8):
        desired = policy.desired(current, p99_s=0.01, depth=0.0,
                                 now=now)
        assert desired in (current, current - 1)
        current = desired
        now += 1.5
    assert current == 1  # min_replicas


def test_latency_policy_damps_flapping_and_stale_feed():
    from ray_tpu.serve.llm_engine import LatencyPolicy

    policy = LatencyPolicy(_policy_cfg(upscale_delay_s=1.0,
                                       downscale_delay_s=5.0))
    assert policy.desired(2, p99_s=0.4, depth=4.0, now=10.0) == 4  # up
    # Direction flip right after: held for the FULL downscale delay
    # even though the up-cooldown elapsed.
    assert policy.desired(4, p99_s=0.01, depth=0.0, now=12.0) == 4
    assert policy.desired(4, p99_s=0.01, depth=0.0, now=14.9) == 4
    assert policy.desired(4, p99_s=0.01, depth=0.0, now=15.5) == 3
    # A stale feed freezes the policy entirely.
    assert policy.desired(3, p99_s=9.9, depth=99.0, now=30.0,
                          feed_age_s=60.0) == 3


# ------------------------------------------------------ batcher hardening


def test_batcher_exception_scatters_to_all_callers():
    """An exception from the wrapped batch fn must reach EVERY waiting
    caller's future — no caller may hang."""
    from ray_tpu.serve.batching import batch

    calls = []

    @batch(max_batch_size=4, batch_wait_timeout_s=0.2)
    def explode(items):
        calls.append(len(items))
        raise ValueError("batch blew up")

    errors = []
    lock = threading.Lock()

    def call(i):
        try:
            explode(i)
        except Exception as exc:  # noqa: BLE001 — collected
            with lock:
                errors.append(exc)

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads), "a caller hung"
    assert len(errors) == 4
    assert all(isinstance(e, ValueError) for e in errors)
    assert calls and calls[0] == 4  # one batched invocation


def test_batcher_shutdown_exits_thread_and_fails_queued():
    """Deployment shutdown stops the batcher thread; queued callers
    fail typed and late submits are refused."""
    from ray_tpu.serve.batching import _Batcher

    release = threading.Event()

    def slow_fn(items):
        release.wait(10)
        return list(items)

    batcher = _Batcher(slow_fn, max_batch_size=1,
                       batch_wait_timeout_s=0.0)
    first = batcher.submit(None, "a")     # occupies the loop
    time.sleep(0.1)
    queued = batcher.submit(None, "b")    # waits behind it
    thread = batcher._thread
    assert thread is not None and thread.is_alive()
    batcher.shutdown(timeout_s=0.5)
    with pytest.raises(RuntimeError):
        queued.result(timeout=5)
    release.set()
    assert first.result(timeout=5) == "a"  # in-flight batch completes
    thread.join(timeout=5)
    assert not thread.is_alive(), "batcher thread survived shutdown"
    with pytest.raises(RuntimeError):
        batcher.submit(None, "c")


def test_replica_shutdown_stops_instance_batchers():
    """Replica.prepare_for_shutdown finds the instance's @serve.batch
    batchers and stops their threads."""
    from ray_tpu.serve.batching import batch, shutdown_batchers

    class Deployment:
        @batch(max_batch_size=8, batch_wait_timeout_s=0.01)
        def __call__(self, items):
            return [x + 1 for x in items]

    dep = Deployment()
    assert dep(41) == 42  # spins the per-instance batcher up
    batcher = type(dep).__call__._serve_batcher_for(dep)
    assert batcher is not None
    assert shutdown_batchers(dep) == 1
    assert batcher._stopped
    with pytest.raises(RuntimeError):
        dep(1)
