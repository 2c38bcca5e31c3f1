"""The table a decode step and a prefill chunk are given, for one family
a file: ``test_table_widths.py`` (identical layers over a pool of keys
and values), ``test_table_widths_hybrid.py`` (a hybrid's three caches),
``test_table_widths_latent.py`` (latent attention over a pool of one
vector a position) and ``test_table_widths_block.py`` (diffusion over
blocks of 4 positions, on the paged family's layers and pool) each name
their ``FAMILY`` and import these cases, so that ``--dist loadfile``
can give each family's engines a worker of their own. A file builds two or three tiny engines: the one ``served``
runs twice, the one whose compiles are counted serves under pressure,
and (a model without experts) one is built and served under a mesh.

The engine hands a prefill chunk the narrowest of ``engine.table_widths``
that holds its request's table and a decode step that gathers (the
hybrid's and the block family's) the narrowest that holds its longest
live row's; a decode step that reads by row (``BY_ROW``: the paged and
the latent family's) has the whole table at every step, and one
program. Every program exists before the first request. Tiny float32
configurations, blocks of 4: a table of 16 blocks has the widths 4, 8
and 16 (16, 32 and 64 positions)."""

import dataclasses
import types

import numpy as np
import pytest

BLOCK, CHUNK, ROWS = 4, 8, 4
#: The families whose decode step reads each row's own pages
#: (``Family.reads_by_row``).
BY_ROW = ("paged", "latent")


def pytest_generate_tests(metafunc):
    """``family`` is the importing file's ``FAMILY``: one case, under
    the id it had when one file ran all three."""
    if "family" in metafunc.fixturenames:
        metafunc.parametrize("family", [metafunc.module.FAMILY],
                             scope="module")


def tiny(family):
    import jax.numpy as jnp

    from ray_tpu.models import llama, phi4flash, xing

    if family == "hybrid":
        return phi4flash.Phi4FlashConfig.tiny(dtype=jnp.float32)
    if family == "latent":
        return xing.XingConfig.tiny(dtype=jnp.float32)
    # ``block``: the same layers under the mask by blocks, a pass of two
    # a block; ``model.family`` finds ``_blockwise(4)``.
    return dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32,
        **({"block_length": BLOCK, "mask_token_id": 255,
            "denoising_steps": 2} if family == "block" else {}))


def make_engine(family, max_seq_len=64, **kwargs):
    from ray_tpu.serve.llm_engine import LLMEngine

    return LLMEngine(tiny(family), max_batch_size=ROWS,
                     max_seq_len=max_seq_len, block_size=BLOCK,
                     prefill_chunk=CHUNK, seed=5, **kwargs)


def step_widths(family, widths):
    """The widths a decode step of ``family`` may be given, of a table's
    ``widths``: all of them where it gathers, the whole where it reads
    by row."""
    return widths[-1:] if family in BY_ROW else widths


def row_head(engine) -> int:
    """The columns of a decode step's host array before a row's table:
    3, or a block pass's 6 and its block."""
    return engine._family.pack_decode_rows(1, 0, ()).shape[1]


def busy_rows(engine, rows):
    """Which rows of a decode step's host array carry a request: a
    position past 0, or a block pass's phase."""
    if getattr(engine.config, "block_length", 0):
        return rows[:, 5] != 0
    return rows[:, 1] > 0


def step_logits(engine):
    """A decode step's logits ``[rows, vocab]`` (a block pass's
    ``[rows, block_length, vocab]``, on the block its host array shows)
    on the engine's cache as it stands, nothing donated: what the
    step's program samples from."""
    import jax

    config, block = engine.config, engine.block_size
    forward = engine._family.forward
    if getattr(config, "block_length", 0):
        size = config.block_length

        def logits(params, cache, rows):
            tokens = rows[:, 6:6 + size]
            return forward(
                params, cache, jax.numpy.where(
                    tokens < 0, config.mask_token_id, tokens),
                rows[:, :1] + jax.numpy.arange(size), rows[:, 6 + size:],
                config, block, busy=rows[:, 5] != 0)[0]
    else:
        def logits(params, cache, rows):
            return forward(params, cache, rows[:, :1], rows[:, 1:2],
                           rows[:, 3:], config, block)[0][:, 0]
    return jax.jit(logits)


def record_steps(engine, compare_logits=False):
    """Every decode step the loop runs from now on: its width in blocks,
    its host array and the preemptions counted before it; with
    ``compare_logits`` also how far the logits at the step's width lie
    from the whole width's on the same cache."""
    step, whole = engine._decode_step, engine.blocks_per_seq
    logits = step_logits(engine) if compare_logits else None
    head = row_head(engine)
    seen = types.SimpleNamespace(widths=[], rows=[], preemptions=[],
                                 compared=0, worst=0.0, program=step,
                                 head=head)

    def recording(params, pool, rows, key, expert_stats, prev):
        width = rows.shape[1] - head
        seen.widths.append(width)
        seen.rows.append(rows)
        seen.preemptions.append(engine._counters["preemptions"])
        if logits is not None and width < whole:
            wide = np.zeros((rows.shape[0], head + whole), np.int32)
            wide[:, :rows.shape[1]] = rows
            live = busy_rows(engine, rows)
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


def held_blocks(rows, head: int = 3) -> int:
    """The longest table among a host array's rows, whose tables start
    at column ``head`` (block 0 is the scratch block and the padding,
    never a request's)."""
    return int((rows[:, head:] != 0).sum(axis=1).max())


def counted(engine) -> dict:
    """The engine's counters that count (not its clocks' readings)."""
    return {k: v for k, v in engine.engine_stats().items()
            if isinstance(v, int) and not isinstance(v, bool)}


# A long row (27 + 9: it starts past a quarter of the table, crosses a
# half while it generates, and finishes first) and two short ones that
# cross a quarter after it has gone.
REQUESTS = [(list(range(1, 28)), 9), ([7, 8, 9], 27), ([3, 1, 4], 26)]


def prefilled(family, prompt) -> int:
    """The positions of ``prompt`` that prefill chunks write: all of
    it, or (diffusion over blocks) its whole blocks, the rest opening
    the first block in flight."""
    return len(prompt) // BLOCK * BLOCK if family == "block" \
        else len(prompt)


def serve(engine, requests=REQUESTS):
    submitted = [engine.submit(prompt, max_new_tokens=new)
                 for prompt, new in requests]
    return [engine.result(req, timeout_s=300) for req in submitted]


@pytest.fixture(scope="module")
def served(family):
    """One engine of the family, twice through the same requests: what
    it was when its constructor returned, what it answered, what its
    steps were given and what it counted; then held to the whole width
    (a ladder of that one rung: the programs are the constructor's),
    what it answered, was given and counted again."""
    engine = make_engine(family)
    try:
        built = engine._decode_step._cache_size()
        step, prefill = engine._decode_step, engine._prefill_step
        prefill_built = prefill._cache_size()
        key_after_building = np.asarray(engine._key)
        stats_after_building = engine.engine_stats()
        written_after_building = {
            name: np.asarray(array != 0)
            for name, array in engine._pool.items()}
        ladder = engine._widths, engine._step_widths
        seen = record_steps(engine, compare_logits=True)
        chunks = record_chunks(engine)
        tokens = serve(engine)
        stats = counted(engine)
        programs = step._cache_size()
        prefill_programs = prefill._cache_size()
        engine._widths = engine._step_widths = ladder[0][-1:]
        engine.__dict__.update(_decode_step=step, _prefill_step=prefill)
        whole_seen = record_steps(engine)
        whole_chunks = record_chunks(engine)
        whole_tokens = serve(engine)
        whole_stats = {k: v - stats[k] for k, v in counted(engine).items()}
        engine._widths, engine._step_widths = ladder
    finally:
        engine.shutdown()
    return types.SimpleNamespace(
        family=family, engine=engine, seen=seen, tokens=tokens,
        prefill=prefill,
        stats=stats, built=built, programs=programs, chunks=chunks,
        whole_chunks=whole_chunks, prefill_built=prefill_built,
        prefill_programs=prefill_programs,
        key_after_building=key_after_building,
        stats_after_building=stats_after_building,
        written_after_building=written_after_building,
        whole_seen=whole_seen, whole_tokens=whole_tokens,
        whole_stats=whole_stats)


def test_answers_do_not_depend_on_the_rung(served):
    """Token for token the answers of the engine held to the whole
    width, chunks and steps. Where the step gathers, the steps ran at
    every width, up as a row crossed a rung and down as the long row
    finished, each step's logits within 1e-5 of the whole width's on
    the same cache; where it reads by row every step had the whole
    table, and the chunks ran at the narrower rungs
    (``test_every_chunk_has_the_narrowest_width_that_holds_its_table``)."""
    assert served.tokens == served.whole_tokens
    assert [len(t) for t in served.tokens] == [new for _, new in REQUESTS]
    widths = served.seen.widths
    assert served.engine._widths == (4, 8, 16)
    assert set(widths) == set(served.engine._step_widths) \
        == set(step_widths(served.family, (4, 8, 16)))
    assert set(served.whole_seen.widths) == {16}
    assert served.seen.compared == sum(w < 16 for w in widths)
    if served.family in BY_ROW:
        assert served.seen.compared == 0 and len(widths) >= 26
        return
    ups = [(a, b) for a, b in zip(widths, widths[1:]) if b > a]
    downs = [(a, b) for a, b in zip(widths, widths[1:]) if b < a]
    assert (8, 16) in ups and (4, 8) in ups     # a half, a quarter crossed
    assert downs and downs[0][0] == 16          # the long row went
    assert served.seen.compared >= 10
    assert served.seen.worst < 1e-5


def test_every_step_has_the_narrowest_width_that_holds_its_rows(served):
    """Of the widths a step of the family may be given: the whole, at
    every step, where it reads by row."""
    head = served.seen.head
    for width, rows in zip(served.seen.widths, served.seen.rows):
        assert rows.shape == (ROWS, head + width)
        assert width == next(w for w in served.engine._step_widths
                             if w >= held_blocks(rows, head))
    assert min(held_blocks(rows, head) for rows in served.seen.rows) <= 4


def test_every_chunk_has_the_narrowest_width_that_holds_its_table(served):
    """A chunk attends over the rung that holds its request's table as
    far as the chunk reaches, in every family: the long prompt's first
    two chunks (16 positions) at a quarter of the table, its last two at
    a half; the engine held to the whole width answered the same
    (``test_answers_do_not_depend_on_the_rung``)."""
    totals = [prefilled(served.family, p) for p, _ in REQUESTS]
    assert sum(n for _, _, n in served.chunks) \
        == served.stats["prefill_tokens"] == sum(totals)
    for width, start, n in served.chunks:
        assert width == next(w for w in served.engine._widths
                             if w * BLOCK >= start + n)
    # (Of a prompt of 27 diffusion over blocks prefills 24, and of one
    # of 3 nothing: three chunks.)
    assert [w for w, _, _ in served.chunks] == [
        next(w for w in (4, 8, 16) if w * BLOCK >= min(start + CHUNK, total))
        for total in totals for start in range(0, total, CHUNK)]
    assert len(served.chunks) == (3 if served.family == "block" else 6)
    assert {w for w, _, _ in served.whole_chunks} == {16}


def positions_read(family, seen) -> int:
    """What ``seen``'s steps read of the pool, from their host arrays:
    rows x the step's width in positions where the step gathers; of
    an engine whose kernel walks each busy row's own pages, the whole
    pages that hold the row's positions before its own, and its own
    (which the step brings with it)."""
    if family not in BY_ROW:
        return sum(ROWS * w * BLOCK for w in seen.widths)
    at = np.concatenate([rows[rows[:, 1] > 0, 1] for rows in seen.rows])
    return int((-(-at // BLOCK) * BLOCK + 1).sum())


def test_counters_say_what_the_steps_read(served):
    """``kv_positions_read`` is what the steps read of the pool, summed
    (``positions_read``); ``decode_steps_narrow`` counts the steps under
    the whole width, none where the step reads by row; the live
    positions are the same whichever width read them, and so is what
    an engine that reads by row reads: under one page a row and step
    over what is live."""
    stats, widths = served.stats, served.seen.widths
    assert stats["decode_steps"] == len(widths)
    assert stats["kv_positions_read"] == positions_read(
        served.family, served.seen)
    assert stats["decode_steps_narrow"] == sum(w < 16 for w in widths)
    whole = served.whole_stats
    assert whole["decode_steps_narrow"] == 0
    assert whole["kv_positions_read"] == positions_read(
        served.family, served.whole_seen)
    # The first is a chunk's, but for diffusion over blocks, whose
    # prefill yields no token.
    live = sum(new - (served.family != "block") for _, new in REQUESTS)
    assert stats["decode_tokens"] == whole["decode_tokens"] == live
    assert stats["kv_positions_live"] == whole["kv_positions_live"]
    if served.family in BY_ROW:
        assert stats["decode_steps_narrow"] == 0
        assert stats["kv_positions_read"] == whole["kv_positions_read"]
        over = stats["kv_positions_read"] - stats["kv_positions_live"]
        assert 0 < over < BLOCK * stats["block_rows"]
    else:
        assert stats["decode_steps_narrow"] > 0
        assert whole["kv_positions_read"] \
            == whole["decode_steps"] * ROWS * 64
        assert stats["kv_positions_read"] < whole["kv_positions_read"]


def test_no_program_is_built_after_the_constructor(served):
    """One program a width it can be given when the constructor returns,
    and the same count after a run that visited every one of them, and
    after the run at the whole width."""
    steps = step_widths(served.family, (4, 8, 16))
    assert served.built == served.programs == len(steps)
    assert served.prefill_built == served.prefill_programs == 3
    assert set(served.seen.widths) == set(steps)
    assert {w for w, _, _ in served.chunks} == {4, 8}
    assert served.seen.program._cache_size() == len(steps)
    assert served.prefill._cache_size() == 3


def test_building_the_programs_leaves_the_key_and_the_caches(served):
    """The runs that build the programs advance no key, count nothing
    and, for a hybrid, touch no ring and no state: the engine as its
    constructor returned it."""
    import jax

    np.testing.assert_array_equal(served.key_after_building,
                                  np.asarray(jax.random.PRNGKey(5 + 1)))
    stats = dict(served.stats_after_building)
    # (``process_cpu_us`` is the process's clock and the collector's
    # two counters the process's too, not counts of what this engine
    # did.)
    assert stats.pop("process_cpu_us") > 0
    assert stats.pop("gc_pause_us") >= 0
    assert stats.pop("gc_full_collections") >= 0
    assert all(value == 0 for value in stats.values()), stats
    assert served.written_after_building
    for name, written in served.written_after_building.items():
        if name in ("k", "v", "latent"):
            # Inactive rows write the scratch block, and only it.
            written = written[:, 1:] \
                if written.ndim == 5 or name == "latent" \
                else written[0, 1:]
        assert not written.any(), name


@pytest.fixture(scope="module")
def pressed(family):
    """An engine of a shape no other has (a pool of 14 blocks under a
    table of 32: widths 8, 16, 32), built while the compiler's events
    are listened to, then served: each of two requests alone, which the
    pool holds without pressure, then both together, which it does not;
    the names of the programs built, as the constructor returned and
    after all of it."""
    import jax

    built = []

    def on(event, duration, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            built.extend(name for name in ("decode_step", "prefill_chunk")
                         if name in str(fun_name))

    requests = [([5, 6, 7], 20), (list(range(1, 41)), 10)]
    jax.monitoring.register_event_duration_secs_listener(on)
    engine = make_engine(family, max_seq_len=128, num_blocks=15)
    try:
        constructed = list(built)
        programs = (engine._decode_step._cache_size(),
                    engine._prefill_step._cache_size())
        want = [serve(engine, [request])[0] for request in requests]
        alone = engine.engine_stats()
        seen = record_steps(engine)
        got = serve(engine, requests)
        stats = engine.engine_stats()
    finally:
        engine.shutdown()
    return types.SimpleNamespace(
        engine=engine, constructed=constructed, built=list(built),
        programs=programs, want=want, alone=alone, seen=seen, got=got,
        stats=stats)


def test_preempting_the_longest_row_lets_the_width_fall(family, pressed):
    """A pool of 14 blocks under a table of 32 (widths 8, 16, 32): the
    row with the long prompt has generated least, so pressure preempts
    it, and the next step holds only the row that is left: as narrow as
    that row where the step gathers, the whole table as ever where it
    reads by row; both answers are the pressure-free ones, which the
    same pool gives each request alone."""
    steps = pressed.engine._step_widths
    assert pressed.engine._widths == (8, 16, 32)
    assert steps == step_widths(family, (8, 16, 32))
    assert pressed.alone["preemptions"] == 0
    assert pressed.got == pressed.want
    stats, seen = pressed.stats, pressed.seen
    assert stats["preemptions"] >= 1 and stats["resumes"] >= 1
    held = [held_blocks(rows, seen.head) for rows in seen.rows]
    fell = [i for i in range(1, len(seen.widths))
            if seen.preemptions[i] > seen.preemptions[i - 1]
            and held[i] < held[i - 1]]
    assert fell, list(zip(seen.widths, seen.preemptions))
    i = fell[0]
    assert held[i - 1] > 8 >= held[i]
    assert (seen.widths[i - 1], seen.widths[i]) == tuple(
        next(w for w in steps if w >= blocks) for blocks in (16, 8))
    assert seen.program._cache_size() == len(steps)


def test_the_constructor_compiles_each_width_once(family, pressed):
    """The constructor lowers and compiles a program by name at each
    width it can be given and then calls it: the call has to find that
    program, not build a second. Three prefill programs in every
    family, three decode programs where the step gathers and one where
    it reads by row, in the order of the widths, the decode program
    before the prefill program of its width; serving, alone and under
    pressure, builds none."""
    steps = step_widths(family, (8, 16, 32))
    assert pressed.constructed == [
        name for width in (8, 16, 32) for name in (
            ["decode_step"] if width in steps else []) + ["prefill_chunk"]]
    assert pressed.programs == (len(steps), 3)
    assert pressed.built == pressed.constructed


def test_a_fresh_pool_meets_the_programs_every_later_pool_meets(family):
    """Under a mesh what a step returns is committed to the mesh, and a
    pool made on the host is not: the constructor's runs would each have
    built a program that serving never finds again. The pool is made by
    a program under the mesh, so the programs built are the programs
    used (a decode program a width a step can be given, three prefill
    programs), also by the pool that replaces a failed step's."""
    import jax

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    engine = make_engine(family, mesh=mesh)
    try:
        assert all(array.committed for array in engine._pool.values())
        steps = step_widths(family, (4, 8, 16))
        seen = record_steps(engine)
        serve(engine)
        assert set(seen.widths) == set(steps)
        assert seen.program._cache_size() == len(steps)
        assert engine._prefill_step._cache_size() == 3
        engine._reset_after_failure(RuntimeError("a step failed"))
        serve(engine)
        assert seen.program._cache_size() == len(steps)
        assert engine._prefill_step._cache_size() == 3
    finally:
        engine.shutdown()
