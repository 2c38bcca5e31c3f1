"""Jamba's layers (``models/jamba.py``) through the paged programs of
``serve/llm_engine/mamba.py``, driven by hand on the CPU, float32 on
both sides, against the plain reference's full pass
(``benchmark/reference/jamba_decoder.py``) on logits; and the
state-space mixer it shares with ``models/phi4flash.py``.
``test_jamba_engine.py`` drives the same programs through ``LLMEngine``."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jamba_tiny import (BLOCK, CHUNK, ROWS, TABLE, contexts_of,
                        reference_logits, tiny)

from ray_tpu.models import jamba
from ray_tpu.models import phi4flash as phi
from ray_tpu.serve.llm_engine import mamba
from ray_tpu.serve.llm_engine import model as paged_model

F32 = jnp.float32


@pytest.fixture(scope="module")
def cfg():
    return tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return jamba.init_params(cfg, jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def programs(cfg):
    """The engine's two programs, as it builds them."""
    family = mamba.FAMILY
    return (family.make_engine_decode_step(cfg, BLOCK),
            family.make_engine_prefill_chunk(cfg, BLOCK, CHUNK))


def tables_of(rows=ROWS, seed=9):
    """Shuffled, non-contiguous tables; block 0 is no row's."""
    deck = np.random.default_rng(seed).permutation(
        np.arange(1, 1 + rows * TABLE))
    return deck.reshape(rows, TABLE).astype(np.int32)


def new_cache(cfg):
    return mamba.init_cache(cfg, 1 + ROWS * TABLE, BLOCK, ROWS, CHUNK)


def prefill(program, params, cache, context, n, table, slot):
    """The first ``n`` tokens of ``context`` in chunks; the last chunk's
    logits (of position ``n - 1``)."""
    logits = None
    for start in range(0, n, CHUNK):
        chunk = mamba.FAMILY.pack_prefill_chunk(
            CHUNK, TABLE, list(context[start:min(start + CHUNK, n)]), start,
            table, slot)
        logits, cache, _ = program(params, cache, jnp.asarray(chunk))
    return logits, cache


@functools.lru_cache(maxsize=None)
def shown(cfg):
    """``mamba.forward`` as the two programs wrap it, showing every
    position's logits, jitted once a configuration."""
    return (jax.jit(lambda p, c, t, at, table, slot, n: mamba.forward(
                p, c, t, at, table, cfg, BLOCK, slot=slot, n_valid=n)[:2]),
            jax.jit(lambda p, c, t, at, tables: mamba.forward(
                p, c, t, at[:, None], tables, cfg, BLOCK)[:2]))


def logits_through_the_cache(cfg, params, contexts, prefilled, tables):
    """Every context prefilled in chunks into its row slot, then all
    decoded together a step at a time through ``mamba.forward``, which
    the two programs wrap: {row: [logits of each position from the last
    prefilled on]}."""
    cache = new_cache(cfg)
    shown_chunk, shown_step = shown(cfg)
    got = {}
    for i, (context, n) in enumerate(zip(contexts, prefilled)):
        for start in range(0, n, CHUNK):
            m = min(CHUNK, n - start)
            tokens = np.zeros((1, CHUNK), np.int32)
            tokens[0, :m] = context[start:start + m]
            positions = np.zeros((1, CHUNK), np.int32)
            positions[0, :m] = np.arange(start, start + m)
            logits, cache = shown_chunk(
                params, cache, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(tables[i:i + 1]), i, m)
        got[i] = [np.asarray(logits[0, m - 1])]
    for step in range(max(len(c) - n for c, n in zip(contexts, prefilled))):
        tokens = np.zeros((ROWS, 1), np.int32)
        positions = np.zeros((ROWS,), np.int32)
        for i, (context, n) in enumerate(zip(contexts, prefilled)):
            if n + step < len(context):
                tokens[i, 0], positions[i] = context[n + step], n + step
        logits, cache = shown_step(
            params, cache, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(tables))
        for i in got:
            if positions[i]:
                got[i].append(np.asarray(logits[i, 0]))
    return got, cache


def test_the_count_of_parameters_is_the_trees_and_the_published(cfg, params):
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params
    published = jamba.JambaConfig()
    assert published.kinds.count("attention") == 2
    assert [i for i, k in enumerate(published.kinds) if k == "attention"] \
        == [7, 21]
    assert (published.mamba_mixer_params, published.attn_mixer_params,
            published.num_params) == (41_241_792, 13_762_560, 3_029_337_472)
    assert cfg.kinds == ("mamba", "mamba", "attention", "mamba") * 2


@pytest.mark.parametrize("bad", [dict(num_layers=6), dict(ffn_experts=2),
                                 dict(attn_layer_offset=4),
                                 dict(mamba_proj_bias=True)])
def test_a_configuration_that_is_not_written_down_is_refused(bad):
    with pytest.raises(ValueError):
        tiny(**bad)


def test_prefill_in_chunks_then_decode_is_the_references_full_pass(
        cfg, params):
    """Four rows at once: a prompt that ends inside a chunk and inside a
    block (13 = 8 + 5: the second chunk half padding, the fourth block a
    position deep), one on a chunk's boundary (16), one shorter than a
    chunk and a block (3), one of several chunks (27); then 9 steps of
    all four together against state and pools, each row's pages read
    where they lie. Logits, every compared position."""
    prefilled = [13, 16, 3, 27]
    contexts = contexts_of([n + 9 for n in prefilled], seed=60)
    got, cache = logits_through_the_cache(cfg, params, contexts, prefilled,
                                          tables_of())
    for i, context in enumerate(contexts):
        want = reference_logits(cfg, params, context)
        assert len(got[i]) == 10
        np.testing.assert_allclose(
            np.stack(got[i]), want[prefilled[i] - 1:], atol=2e-4)
    assert all(bool(jnp.isfinite(v).all()) for v in cache.values())


def test_the_engines_programs_yield_the_references_greedy_tokens(
        cfg, params, programs):
    """The jitted programs on their packed host arrays: the prefill
    program's logits of its last real position, and the decode
    program's token at temperature 0, the reference's argmax, a step
    launched on the one before's token where it lies (``prev``)."""
    step, chunk_program = programs
    context = list(contexts_of([21], seed=61)[0])
    table = tables_of()[2]
    logits, cache = prefill(chunk_program, params, new_cache(cfg), context,
                            len(context), table, 2)
    want = reference_logits(cfg, params, np.asarray(context))
    np.testing.assert_allclose(np.asarray(logits), want[-1], atol=2e-4)
    token, key, prev = int(np.argmax(logits)), jax.random.PRNGKey(0), None
    for _ in range(6):
        context.append(token)
        packed = mamba.FAMILY.pack_decode_rows(
            ROWS, TABLE,
            [(paged_model.PREV if prev is not None else token,
              len(context) - 1, 0.0, table)], slots=[2])
        out, cache, _, key = step(params, cache, jnp.asarray(packed), key,
                                  None, prev)
        prev, token = out, int(out[2])
        assert token == int(reference_logits(
            cfg, params, np.asarray(context))[-1].argmax())


def test_a_row_slots_next_tenant_starts_from_zero(cfg, params, programs):
    """Two requests in ONE row slot, one after the other, over other
    pages: the second's first chunk resets the state and the
    convolution's inputs IN THE PROGRAM, so it is served as on a fresh
    cache, bit for bit; a later chunk resets nothing."""
    _, chunk_program = programs
    first, second = contexts_of([30, 19], seed=62)
    tables = tables_of()
    _, used = prefill(chunk_program, params, new_cache(cfg), first, 30,
                      tables[0], 1)
    assert float(jnp.abs(used["ssm"][:, 1]).max()) > 0
    got, used = prefill(chunk_program, params, used, second, 19,
                        tables[3], 1)
    want, fresh = prefill(chunk_program, params, new_cache(cfg), second, 19,
                          tables[3], 1)
    np.testing.assert_array_equal(got, want)
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(used[name].take(1, axis=-3),
                                      fresh[name].take(1, axis=-3))
    np.testing.assert_allclose(
        np.asarray(got), reference_logits(cfg, params, second)[-1],
        atol=2e-4)


def test_padding_and_inactive_rows_advance_nothing(cfg, params, programs):
    """A decode step leaves an inactive row's state and convolution
    inputs as they were, to the bit, and a chunk's padding advances its
    row no further than its real positions: the state after 13 tokens
    in chunks of 8 (three positions of padding) is the state after the
    same 13 one at a time."""
    step, chunk_program = programs
    context = contexts_of([14], seed=63)[0]
    tables = tables_of()
    _, cache = prefill(chunk_program, params, new_cache(cfg), context, 13,
                       tables[0], 0)
    before = jax.tree.map(np.asarray, cache)
    packed = mamba.FAMILY.pack_decode_rows(
        ROWS, TABLE, [(int(context[13]), 13, 0.0, tables[3])], slots=[3])
    _, after, _, _ = step(params, cache, jnp.asarray(packed),
                          jax.random.PRNGKey(0))
    for name in ("ssm", "conv"):
        rows_axis = -3
        np.testing.assert_array_equal(
            np.asarray(after[name]).take(0, axis=rows_axis),
            before[name].take(0, axis=rows_axis))
    # One position at a time, through the decode program, from zero:
    # position 0 is "inactive" to a step, so the first goes as a chunk.
    _, single = prefill(chunk_program, params, new_cache(cfg), context, 1,
                        tables[0], 0)
    for at in range(1, 13):
        packed = mamba.FAMILY.pack_decode_rows(
            ROWS, TABLE, [(int(context[at]), at, 0.0, tables[0])], slots=[0])
        _, single, _, _ = step(params, single, jnp.asarray(packed),
                               jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(single["ssm"])[:, 0],
                               before["ssm"][:, 0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(single["conv"])[:, :, 0],
                               before["conv"][:, :, 0], atol=1e-5)


@pytest.mark.parametrize("control", ["bfloat16_state", "norms_dropped",
                                     "rotated"])
def test_a_control_moves_the_logits_out_of_the_tolerance(cfg, params,
                                                         control):
    """What the comparison must tell: the state kept in bfloat16 (the
    precision below the one stated), the three inner norms left out,
    queries and keys rotated where the model has no positions. Each
    moves a logit by more than 50 times the sound run's 2e-4."""
    prefilled = [24]
    context = contexts_of([40], seed=64)
    used, weights = cfg, params
    if control == "bfloat16_state":
        used = dataclasses.replace(cfg, state_dtype=jnp.bfloat16)
    elif control == "norms_dropped":
        mixer = {k: v for k, v in params["mamba"]["mixer"].items()
                 if not k.endswith("_norm")}
        weights = {**params, "mamba": {**params["mamba"], "mixer": mixer}}
    else:
        class Rotated(jamba.JambaConfig):
            rotary, rope_theta = True, 1e4

        used = Rotated(**{f.name: getattr(cfg, f.name)
                          for f in dataclasses.fields(cfg)})
    got, _ = logits_through_the_cache(used, weights, context, prefilled,
                                      tables_of())
    want = reference_logits(cfg, params, context[0])
    assert np.abs(np.stack(got[0]) - want[23:]).max() > 1e-2


@pytest.mark.parametrize("state", ["float32", "bfloat16"])
def test_the_first_layers_state_is_the_references_scans(cfg, params, state):
    """What ``chip_smoke.py --paged-logits`` holds on the chip, where
    bfloat16 products hide a state's precision from the logits: the
    cache's ``ssm[0]`` of a row, behind chunks and decode steps, is the
    carry of the reference's position-by-position scan from zero
    (``reference.first_state``) in float32, and is told from it once
    the state is kept in bfloat16."""
    import chip_smoke
    from jamba_tiny import numbers, reference

    context = contexts_of([40], seed=64)
    used = dataclasses.replace(cfg, state_dtype=jnp.dtype(state))
    _, cache = logits_through_the_cache(used, params, context, [24],
                                        tables_of())
    assert cache["ssm"].dtype == jnp.dtype(state)
    error = chip_smoke.first_state_error(
        {0: np.asarray(cache["ssm"][0, 0], np.float32)}, context, reference,
        params, numbers(cfg))
    assert error < 1e-5 if state == "float32" else 1e-3 < error < 0.1


# ------------------------------------------- the mixer Phi and Jamba share


def _old_ssm_inputs(w, u, config):
    """``phi4flash._ssm_inputs`` as it was before PR 60, to the letter."""
    r, n = config.dt_rank, config.d_state
    proj = phi.matmul_f32(u, w["x_proj"])
    dt_r, b, c = proj[..., :r], proj[..., r:r + n], proj[..., r + n:]
    dt = jax.nn.softplus(phi.matmul_f32(dt_r, w["dt_proj"])
                         + w["dt_bias"].astype(F32))
    return dt, b, c, -jnp.exp(w["A_log"].astype(F32))


def _phi_mixer(seed=7):
    config = phi.Phi4FlashConfig.tiny(dtype=F32)
    weights = phi.init_params(config, jax.random.PRNGKey(seed))
    return config, weights["mid_ssm"]["ssm"]


def test_without_the_norms_the_shared_inputs_are_the_old_ones_bit_for_bit():
    config, w = _phi_mixer()
    u = jax.random.normal(jax.random.PRNGKey(1), (3, 11, config.d_inner))
    for new, old in zip(phi._ssm_inputs(w, u, config),
                        _old_ssm_inputs(w, u, config)):
        np.testing.assert_array_equal(new, old)
    # And with them they are not: the norms are read where they stand.
    normed = {**w, "dt_norm": jnp.ones((config.dt_rank,)),
              "b_norm": jnp.ones((config.d_state,)),
              "c_norm": jnp.ones((config.d_state,))}
    both = type("Both", (), {"dt_rank": config.dt_rank,
                             "d_state": config.d_state,
                             "rms_norm_eps": 1e-6})
    dt, b, _, _ = phi._ssm_inputs(normed, u, both)
    np.testing.assert_allclose(jnp.mean(b ** 2, -1), 1.0, rtol=1e-4)
    assert not np.allclose(dt, _old_ssm_inputs(w, u, config)[0])


def test_the_same_weights_through_phis_and_jambas_entry_to_the_recurrence():
    """ONE mixer's weights (Phi's: no inner norms) through
    ``phi4flash.ssm_step`` and ``ssm_chunk`` under Phi's configuration,
    as ``hybrid.py`` calls them, and under a Jamba configuration of the
    same sizes, as ``mamba.py`` does (the convolution's inputs taps
    first): equal outputs and states, to the bit. The next change to
    either model's mixer is seen by the other's tests."""
    config, w = _phi_mixer()
    ours = tiny(hidden_size=config.hidden_size, num_heads=4,
                mamba_d_state=config.d_state, mamba_dt_rank=config.dt_rank)
    assert (ours.d_inner, ours.d_conv) == (config.d_inner, config.d_conv)
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    rows, length = 3, 11
    h = jax.random.normal(keys[0], (rows, config.hidden_size))
    s = jax.random.normal(keys[1], (rows, config.d_inner, config.d_state))
    conv = jax.random.normal(keys[2], (rows, config.d_conv - 1,
                                       config.d_inner))
    active = jnp.asarray([True, False, True])
    out, memory, s1, c1 = phi.ssm_step(w, h, s, conv, active, config)
    ours_out, ours_memory, s2, c2 = phi.ssm_step(
        w, h, s, jnp.moveaxis(conv, 0, 1), active, ours, taps_first=True)
    for a, b in ((out, ours_out), (memory, ours_memory), (s1, s2),
                 (c1, jnp.moveaxis(c2, 0, 1))):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(s1[1], s[1])      # the inactive row
    x = jax.random.normal(keys[3], (length, config.hidden_size))
    for a, b in zip(phi.ssm_chunk(w, x, s[0], conv[0], 9, config),
                    phi.ssm_chunk(w, x, s[0], conv[0], 9, ours)):
        np.testing.assert_array_equal(a, b)


def test_a_chunk_is_its_positions_one_at_a_time_with_the_norms(cfg, params):
    """``ssm_chunk`` (the scan over positions) against ``ssm_step``
    applied a position at a time, on a Jamba mixer (normed dt, B, C)."""
    w = jax.tree.map(lambda t: t[1], params["mamba"]["mixer"])
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(keys[0], (9, cfg.hidden_size))
    s = jax.random.normal(keys[1], (cfg.d_inner, cfg.d_state))
    conv = jax.random.normal(keys[2], (cfg.d_conv - 1, cfg.d_inner))
    out, _, s_chunk, c_chunk = phi.ssm_chunk(w, x, s, conv, 7, cfg)
    outs, state, inputs = [], s[None], conv[:, None]
    for at in range(7):
        o, _, state, inputs = phi.ssm_step(
            w, x[at][None], state, inputs, jnp.asarray([True]), cfg,
            taps_first=True)
        outs.append(o[0])
    np.testing.assert_allclose(out[:7], jnp.stack(outs), atol=1e-5)
    np.testing.assert_allclose(s_chunk, state[0], atol=1e-5)
    np.testing.assert_allclose(c_chunk, inputs[:, 0], atol=1e-6)


def test_the_family_is_looked_up_from_the_configuration(cfg):
    family = paged_model.family(cfg)
    assert family is mamba.FAMILY
    assert family.recurrent and family.reads_by_row
    cache = jax.eval_shape(lambda: new_cache(cfg))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, 1 + ROWS * TABLE, BLOCK, 16),
        "v": (2, 1 + ROWS * TABLE, BLOCK, 16),
        "ssm": (6, ROWS, 160, 4), "conv": (6, 3, ROWS, 160)}
    assert cache["ssm"].dtype == jnp.float32
