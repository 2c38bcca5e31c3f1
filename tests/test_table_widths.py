"""The decode step's table follows the longest live row: the engine hands
each step the narrowest of ``engine.table_widths`` that holds it, a
prefill chunk the narrowest that holds its request's table, and both
programs exist at every width before the first request. Tiny float32
configurations of the autoregressive families (identical layers over a
pool of keys and values, a hybrid's three caches, latent attention over
a pool of one vector a position), blocks of 4: a table of 16 blocks has
the widths 4, 8 and 16 (16, 32 and 64 positions)."""

import dataclasses
import types

import numpy as np
import pytest

FAMILIES = ["paged", "hybrid", "latent"]
BLOCK, CHUNK, ROWS = 4, 8, 4


def tiny(family):
    import jax.numpy as jnp

    from ray_tpu.models import llama, phi4flash, xing

    if family == "hybrid":
        return phi4flash.Phi4FlashConfig.tiny(dtype=jnp.float32)
    if family == "latent":
        return xing.XingConfig.tiny(dtype=jnp.float32)
    return dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)


def make_engine(family, max_seq_len=64, **kwargs):
    from ray_tpu.serve.llm_engine import LLMEngine

    return LLMEngine(tiny(family), max_batch_size=ROWS,
                     max_seq_len=max_seq_len, block_size=BLOCK,
                     prefill_chunk=CHUNK, seed=5, **kwargs)


def step_logits(engine):
    """A decode step's logits ``[rows, vocab]`` on the engine's cache as
    it stands, nothing donated: what the step's program samples from."""
    import jax

    from ray_tpu.serve.llm_engine import hybrid, latent
    from ray_tpu.serve.llm_engine import model as paged_model

    config, block = engine.config, engine.block_size
    if paged_model.family(config) is hybrid.FAMILY:
        def logits(params, cache, rows):
            return hybrid.decode_forward(
                params, cache, rows[:, :1], rows[:, 1], rows[:, 3:], config,
                block)[0][:, 0]
    elif paged_model.family(config) is latent.FAMILY:
        def logits(params, cache, rows):
            return latent.forward(
                params, cache, rows[:, :1], rows[:, 1:2], rows[:, 3:],
                config, block, absorbed=True)[0][:, 0]
    else:
        def logits(params, cache, rows):
            return paged_model._forward_paged(
                params, cache, rows[:, :1], rows[:, 1:2], rows[:, 3:],
                config, block)[0][:, 0]
    return jax.jit(logits)


def record_steps(engine, compare_logits=False):
    """Every decode step the loop runs from now on: its width in blocks,
    its host array and the preemptions counted before it; with
    ``compare_logits`` also how far the logits at the step's width lie
    from the whole width's on the same cache."""
    step, whole = engine._decode_step, engine.blocks_per_seq
    logits = step_logits(engine) if compare_logits else None
    seen = types.SimpleNamespace(widths=[], rows=[], preemptions=[],
                                 compared=0, worst=0.0, program=step)

    def recording(params, pool, rows, key, expert_stats, prev):
        width = rows.shape[1] - 3
        seen.widths.append(width)
        seen.rows.append(rows)
        seen.preemptions.append(engine._counters["preemptions"])
        if logits is not None and width < whole:
            wide = np.zeros((rows.shape[0], 3 + whole), np.int32)
            wide[:, :rows.shape[1]] = rows
            live = rows[:, 1] > 0
            gap = np.abs(np.asarray(logits(params, pool, rows))
                         - np.asarray(logits(params, pool, wide)))[live]
            seen.compared += 1
            seen.worst = max(seen.worst, float(gap.max()))
        return step(params, pool, rows, key, expert_stats, prev)

    engine.__dict__["_decode_step"] = recording
    return seen


def record_chunks(engine) -> list:
    """Every prefill chunk the loop runs from now on: (its width in
    blocks, its first position, its real tokens)."""
    step, seen = engine._prefill_step, []
    head = len(engine._family.pack_prefill_chunk(0, 0, (), 0, (), 0))

    def recording(params, pool, chunk, expert_stats):
        seen.append((len(chunk) - head - 2 * CHUNK,
                     int(chunk[head + CHUNK]), int(chunk[0])))
        return step(params, pool, chunk, expert_stats)

    engine.__dict__["_prefill_step"] = recording
    return seen


def held_blocks(rows) -> int:
    """The longest table among a host array's rows (block 0 is the
    scratch block and the padding, never a request's)."""
    return int((rows[:, 3:] != 0).sum(axis=1).max())


# A long row (27 + 9: it starts past a quarter of the table, crosses a
# half while it generates, and finishes first) and two short ones that
# cross a quarter after it has gone.
REQUESTS = [(list(range(1, 28)), 9), ([7, 8, 9], 27), ([3, 1, 4], 26)]


def serve(engine, requests=REQUESTS):
    submitted = [engine.submit(prompt, max_new_tokens=new)
                 for prompt, new in requests]
    return [engine.result(req, timeout_s=300) for req in submitted]


@pytest.fixture(scope="module", params=FAMILIES)
def served(request):
    """One run through an engine of each family: what it answered, what
    its steps were given, its counters, and the same requests' answers
    from an engine held to the whole width."""
    engine = make_engine(request.param)
    try:
        built = engine._decode_step._cache_size()
        prefill = engine._prefill_step
        prefill_built = prefill._cache_size()
        key_after_building = np.asarray(engine._key)
        seen = record_steps(engine, compare_logits=True)
        chunks = record_chunks(engine)
        tokens = serve(engine)
        stats = engine.engine_stats()
        programs = seen.program._cache_size()
        prefill_programs = prefill._cache_size()
    finally:
        engine.shutdown()
    whole = make_engine(request.param)
    try:
        whole._widths = (whole.blocks_per_seq,)
        whole_seen = record_steps(whole)
        whole_chunks = record_chunks(whole)
        whole_tokens = serve(whole)
        whole_stats = whole.engine_stats()
    finally:
        whole.shutdown()
    return types.SimpleNamespace(
        family=request.param, engine=engine, seen=seen, tokens=tokens,
        stats=stats, built=built, programs=programs, chunks=chunks,
        whole_chunks=whole_chunks, prefill_built=prefill_built,
        prefill_programs=prefill_programs,
        key_after_building=key_after_building, whole_seen=whole_seen,
        whole_tokens=whole_tokens, whole_stats=whole_stats)


@pytest.mark.parametrize("blocks, widths", [
    (128, (32, 64, 128)), (256, (64, 128, 256)), (16, (4, 8, 16)),
    (4, (1, 2, 4)), (5, (2, 3, 5)), (7, (2, 4, 7)), (3, (3,)), (1, (1,))])
def test_the_widths_are_a_quarter_a_half_and_the_whole(blocks, widths):
    from ray_tpu.serve.llm_engine.engine import table_widths

    assert table_widths(blocks) == widths
    assert list(widths) == sorted(set(widths)) and widths[-1] == blocks


def test_answers_do_not_depend_on_the_rung(served):
    """Token for token the whole-width engine's answers, though the steps
    ran at every width, up as a row crossed a rung and down as the long
    row finished; and each step's logits within 1e-5 of the whole
    width's on the same cache."""
    assert served.tokens == served.whole_tokens
    assert [len(t) for t in served.tokens] == [new for _, new in REQUESTS]
    widths = served.seen.widths
    assert set(widths) == {4, 8, 16} == set(served.engine._widths)
    assert set(served.whole_seen.widths) == {16}
    ups = [(a, b) for a, b in zip(widths, widths[1:]) if b > a]
    downs = [(a, b) for a, b in zip(widths, widths[1:]) if b < a]
    assert (8, 16) in ups and (4, 8) in ups     # a half, a quarter crossed
    assert downs and downs[0][0] == 16          # the long row went
    assert served.seen.compared == sum(w < 16 for w in widths) >= 10
    assert served.seen.worst < 1e-5


def test_every_step_has_the_narrowest_width_that_holds_its_rows(served):
    for width, rows in zip(served.seen.widths, served.seen.rows):
        assert rows.shape == (ROWS, 3 + width)
        assert width == next(w for w in served.engine._widths
                             if w >= held_blocks(rows))


def test_every_chunk_has_the_narrowest_width_that_holds_its_table(served):
    """A chunk attends over the rung that holds its request's table as
    far as the chunk reaches: the long prompt's first two chunks (16
    positions) at a quarter of the table, its last two at a half; the
    engine held to the whole width answered the same
    (``test_answers_do_not_depend_on_the_rung``)."""
    assert sum(n for _, _, n in served.chunks) \
        == served.stats["prefill_tokens"] == sum(len(p) for p, _ in REQUESTS)
    for width, start, n in served.chunks:
        assert width == next(w for w in served.engine._widths
                             if w * BLOCK >= start + n)
    assert [w for w, _, _ in served.chunks] == [4, 4, 8, 8, 4, 4]
    assert {w for w, _, _ in served.whole_chunks} == {16}


def positions_read(family, seen) -> int:
    """What ``seen``'s steps read of the pool, from their host arrays:
    rows x the step's width in positions where the step gathers; of a
    latent engine, whose kernel walks each busy row's own pages, the
    whole pages that hold the row's positions before its own, and its
    own (which the step brings with it)."""
    if family != "latent":
        return sum(ROWS * w * BLOCK for w in seen.widths)
    at = np.concatenate([rows[rows[:, 1] > 0, 1] for rows in seen.rows])
    return int((-(-at // BLOCK) * BLOCK + 1).sum())


def test_counters_say_what_the_steps_read(served):
    """``kv_positions_read`` is what the steps read of the pool, summed
    (``positions_read``); ``decode_steps_narrow`` counts the steps under
    the whole width; the live positions are the same whichever width
    read them, and so is what a latent engine reads: under one page a
    row and step over what is live."""
    stats, widths = served.stats, served.seen.widths
    assert stats["decode_steps"] == len(widths)
    assert stats["kv_positions_read"] == positions_read(
        served.family, served.seen)
    assert stats["decode_steps_narrow"] == sum(w < 16 for w in widths) > 0
    whole = served.whole_stats
    assert whole["decode_steps_narrow"] == 0
    assert whole["kv_positions_read"] == positions_read(
        served.family, served.whole_seen)
    live = sum(new - 1 for _, new in REQUESTS)  # the first is a chunk's
    assert stats["decode_tokens"] == whole["decode_tokens"] == live
    assert stats["kv_positions_live"] == whole["kv_positions_live"]
    if served.family == "latent":
        assert stats["kv_positions_read"] == whole["kv_positions_read"]
        over = stats["kv_positions_read"] - stats["kv_positions_live"]
        assert 0 < over < BLOCK * stats["block_rows"]
    else:
        assert whole["kv_positions_read"] \
            == whole["decode_steps"] * ROWS * 64
        assert stats["kv_positions_read"] < whole["kv_positions_read"]


def test_no_program_is_built_after_the_constructor(served):
    """One program a width when the constructor returns, and the same
    count after a run that visited every one of them."""
    assert served.built == served.programs == 3
    assert served.prefill_built == served.prefill_programs == 3
    assert set(served.seen.widths) == {4, 8, 16}


def test_building_the_programs_leaves_the_key_and_the_caches(served):
    """The runs that build the programs advance no key, count nothing
    and, for a hybrid, touch no ring and no state."""
    import jax

    np.testing.assert_array_equal(served.key_after_building,
                                  np.asarray(jax.random.PRNGKey(5 + 1)))
    fresh = make_engine(served.family)
    try:
        stats = fresh.engine_stats()
        # (``process_cpu_us`` is the process's clock, not a count of
        # what this engine did.)
        assert stats.pop("process_cpu_us") > 0
        assert all(value == 0 for value in stats.values()), stats
        for name, array in fresh._pool.items():
            written = np.asarray(array != 0)
            if name in ("k", "v", "latent"):
                # Inactive rows write the scratch block, and only it.
                written = written[:, 1:] \
                    if written.ndim == 5 or name == "latent" \
                    else written[0, 1:]
            assert not written.any(), name
    finally:
        fresh.shutdown()


@pytest.mark.parametrize("family", FAMILIES)
def test_preempting_the_longest_row_lets_the_width_fall(family):
    """A pool of 14 blocks under a table of 32 (widths 8, 16, 32): the
    row with the long prompt has generated least, so pressure preempts
    it, and the next step is as narrow as the row that is left; both
    answers are the pressure-free ones."""
    requests = [([5, 6, 7], 20), (list(range(1, 41)), 10)]
    roomy = make_engine(family, max_seq_len=128)
    try:
        want = [serve(roomy, [request])[0] for request in requests]
    finally:
        roomy.shutdown()
    engine = make_engine(family, max_seq_len=128, num_blocks=15)
    try:
        assert engine._widths == (8, 16, 32)
        seen = record_steps(engine)
        assert serve(engine, requests) == want
        stats = engine.engine_stats()
    finally:
        engine.shutdown()
    assert stats["preemptions"] >= 1 and stats["resumes"] >= 1
    fell = [i for i in range(1, len(seen.widths))
            if seen.preemptions[i] > seen.preemptions[i - 1]
            and seen.widths[i] < seen.widths[i - 1]]
    assert fell, list(zip(seen.widths, seen.preemptions))
    i = fell[0]
    assert (seen.widths[i - 1], seen.widths[i]) == (16, 8)
    assert held_blocks(seen.rows[i - 1]) > 8 >= held_blocks(seen.rows[i])
    assert seen.program._cache_size() == 3


def test_a_fresh_pool_meets_the_programs_every_later_pool_meets():
    """Under a mesh what a step returns is committed to the mesh, and a
    pool made on the host is not: the constructor's runs would each have
    built a program that serving never finds again. The pool is made by
    a program under the mesh, so the three built are the three used,
    also by the pool that replaces a failed step's."""
    import jax

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    engine = make_engine("paged", mesh=mesh)
    try:
        assert engine._pool["k"].committed
        seen = record_steps(engine)
        serve(engine)
        assert set(seen.widths) == {4, 8, 16}
        assert seen.program._cache_size() == 3
        assert engine._prefill_step._cache_size() == 3
        engine._reset_after_failure(RuntimeError("a step failed"))
        serve(engine)
        assert seen.program._cache_size() == 3
        assert engine._prefill_step._cache_size() == 3
    finally:
        engine.shutdown()


def test_the_constructor_compiles_each_width_once():
    """The constructor lowers and compiles a width by name and then
    calls it: the call has to find that program, not build a second."""
    import jax

    built = []

    def on(event, duration, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            built.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(on)
    engine = make_engine("paged", max_seq_len=96)   # a shape of its own
    try:
        assert engine._widths == (6, 12, 24)
        assert sum("decode_step" in str(name) for name in built) == 3
        assert engine._decode_step._cache_size() == 3
        serve(engine, [([1, 2, 3], 30)])
        assert sum("decode_step" in str(name) for name in built) == 3
    finally:
        engine.shutdown()
