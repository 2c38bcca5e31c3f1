"""Kimi-Linear's layers (KDA: a gated delta rule with a decay a channel
over a float32 matrix state a row; latent attention without positions
every fourth layer; sigmoid-routed experts of which a share is held)
through ``models/kimi_linear.py``, ``models/xing.py``'s latent functions
and the paged engine (``serve/llm_engine/linear.py``), held to the plain
float32 reference ``benchmark/reference/kimi_linear_decoder.py`` at a
small size on the CPU. Logits are compared, not tokens, where the
programs are driven by hand; the engine's tokens are then held to the
reference's own greedy continuation, float32 on both sides.

Tolerances. float32 against float32: 2e-4 absolute on logits of
standard deviation about 1 (read 3e-5: only the order of summation
differs, the chunkwise form and the step against the token-by-token
rule, the absorbed attention against the expanded). With the STATE
alone held in bfloat16 the same logits move by 1e-2 and more
(``test_a_bfloat16_state_leaves_the_float32_tolerance``): the tolerance
is tight enough to tell. bfloat16 programs against the float32
reference: 0.7 of a standard deviation in the root mean square, at
this width (``test_bfloat16_programs_stay_near_the_reference`` has the
reason and the readings).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from kimi_tiny import (  # noqa: E402
    BLOCK, CHUNK, ROWS, TABLE, contexts_of, reference_logits, tiny)
from ray_tpu.models import kimi_linear as kimi  # noqa: E402
from ray_tpu.models import xing  # noqa: E402
from ray_tpu.serve.llm_engine import hybrid, linear  # noqa: E402
from ray_tpu.serve.llm_engine import model as paged_model  # noqa: E402

F32_ATOL = 2e-4


@pytest.fixture(scope="module")
def weights():
    made = {}

    def of(cfg, seed=11):
        if (cfg, seed) not in made:
            made[cfg, seed] = paged_model.serving_params(cfg, None, seed)
        return made[cfg, seed]
    return of


_SHOWN = {}


def shown(cfg):
    """The forward the two programs wrap, showing every position's
    logits, the cache donated."""
    if cfg not in _SHOWN:
        chunk = jax.jit(
            lambda params, cache, tokens, positions, table, slot, n_valid:
            linear.forward(params, cache, tokens, positions, table, cfg,
                           BLOCK, slot=slot, n_valid=n_valid),
            donate_argnums=(1,))
        step = jax.jit(
            lambda params, cache, tokens, positions, tables:
            linear.forward(params, cache, tokens, positions[:, None],
                           tables, cfg, BLOCK), donate_argnums=(1,))
        _SHOWN[cfg] = chunk, step
    return _SHOWN[cfg]


def fresh_cache(cfg):
    return linear.init_cache(cfg, 1 + ROWS * TABLE, BLOCK, ROWS, CHUNK)


def tables_for(rows):
    tables = np.zeros((ROWS, TABLE), np.int32)
    deck = list(np.random.default_rng(3).permutation(
        np.arange(1, 1 + ROWS * TABLE)))
    for i in range(rows):
        tables[i] = [int(deck.pop()) for _ in range(TABLE)]
    return tables


def prefill(cfg, params, cache, context, upto, table, slot, got=None):
    chunk, _ = shown(cfg)
    for start in range(0, upto, CHUNK):
        n = min(CHUNK, upto - start)
        logits, cache, counts, _ = chunk(
            params, cache, *chip_smoke.chunk_inputs(context, start, n, CHUNK),
            jnp.asarray(table[None]), np.int32(slot), np.int32(n))
        if got is not None:
            got[start:start + n] = np.asarray(logits[0, :n])
    return cache


def serve(cfg, params, contexts, prefilled):
    """Each context's first ``prefilled`` positions through prefill
    chunks (row ``i`` in row slot ``i``), the rest through batched
    decode steps, as the engine drives its two programs. Returns every
    position's logits per context, and the cache."""
    _, step = shown(cfg)
    cache, tables = fresh_cache(cfg), tables_for(len(contexts))
    got = [np.zeros((len(c), cfg.vocab_size), np.float32) for c in contexts]
    for i, context in enumerate(contexts):
        cache = prefill(cfg, params, cache, context, prefilled[i], tables[i],
                        i, got[i])
    at = list(prefilled)
    while any(at[i] < len(c) for i, c in enumerate(contexts)):
        tokens = np.zeros((ROWS, 1), np.int32)
        positions = np.zeros((ROWS,), np.int32)
        active = [i for i, c in enumerate(contexts) if at[i] < len(c)]
        for i in active:
            tokens[i, 0], positions[i] = contexts[i][at[i]], at[i]
        logits, cache, _, _ = step(
            params, cache, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(np.where(positions[:, None] > 0, tables, 0)))
        for i in active:
            got[i][at[i]] = np.asarray(logits[i, 0])
            at[i] += 1
    return got, cache


# (prefilled, decoded): inside one sub-chunk (4), chunk (8) and block
# (4); across a sub-chunk and a block, short of a chunk; across chunks
# with a padded last one; a chunk's end exactly, decoded across blocks.
RAGGED = [(3, 12), (7, 9), (27, 14), (16, 30)]


# ------------------ (a) the paged programs against the token-by-token rule


def test_paged_logits_and_states_match_the_reference(weights):
    cfg = tiny()
    params = weights(cfg)
    contexts = contexts_of([p + d for p, d in RAGGED])
    got, cache = serve(cfg, params, contexts, [p for p, _ in RAGGED])
    for slot, (context, logits) in enumerate(zip(contexts, got)):
        want, states = reference_logits(cfg, params, context, True)
        assert 0.5 < want.std() < 2.0
        np.testing.assert_allclose(logits, want, atol=F32_ATOL, rtol=0)
        # The state itself, every KDA layer, after the last position.
        assert len(states) == cfg.kda_layers == 7
        for layer, state in enumerate(states):
            np.testing.assert_allclose(cache["kda"][layer, slot], state[0],
                                       atol=F32_ATOL, rtol=0)
    assert cache["kda"].dtype == jnp.float32
    assert cache["latent"].shape[0] == cfg.latent_layers == 2


def test_a_bfloat16_state_leaves_the_float32_tolerance(weights):
    """The same float32 programs with the state ALONE in bfloat16: the
    float32 tolerance tells."""
    cfg = tiny(state_dtype=jnp.bfloat16)
    params = weights(tiny())
    contexts = contexts_of([p + d for p, d in RAGGED[2:]])
    got, cache = serve(cfg, params, contexts, [p for p, _ in RAGGED[2:]])
    assert cache["kda"].dtype == jnp.bfloat16
    worst = max(np.abs(logits - reference_logits(cfg, params, context)).max()
                for context, logits in zip(contexts, got))
    assert worst > 25 * F32_ATOL, worst


def test_bfloat16_programs_stay_near_the_reference(weights):
    """bfloat16 weights and activations, the state float32, against the
    float32 reference on the same (bfloat16) weights: at this width (64)
    nine layers' roundings of 2^-8 each and the expert choices they
    flip (3 of 16, 8 held) move a logit by a third of a standard
    deviation in the root mean square (read 0.27 to 0.48 over two
    seeds), the float32 programs by 1e-5: a wrong path (a state in the
    wrong slot, a dropped convolution input) moves it by one and more.
    What bfloat16 costs at the PUBLISHED widths is the chip's to say
    (the cell's ``logit_atol_why``)."""
    cfg = tiny(dtype=jnp.bfloat16)
    params = weights(cfg)
    contexts = contexts_of([p + d for p, d in RAGGED[1:3]])
    got, cache = serve(cfg, params, contexts, [p for p, _ in RAGGED[1:3]])
    assert cache["kda"].dtype == jnp.float32 \
        and cache["conv"].dtype == jnp.bfloat16
    for context, logits in zip(contexts, got):
        want = reference_logits(cfg, params, context)
        rms = float(np.sqrt(np.mean((logits - want) ** 2)))
        assert 1e-3 < rms < 0.7, rms


# ----------------------------- (b) the chunkwise form against the rule


def rule_inputs(length, heads, d, a_log, seed):
    """q, k, v, g, beta as ``_kda_inputs`` makes them, with the decay
    ``-exp(a_log) * softplus(N(0, 1) + dt_bias)`` of ``init_params``'
    ``dt_bias`` range."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(length, heads, d)) for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (heads, d)))
    bias = step + np.log(-np.expm1(-step))
    g = -np.exp(a_log) * np.logaddexp(
        0, rng.normal(size=(length, heads, d)) + bias)
    beta = 1 / (1 + np.exp(-rng.normal(size=(length, heads))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


@pytest.mark.parametrize("a_log", [0.0, float(np.log(16.0))],
                         ids=["slowest", "fastest"])
def test_the_chunkwise_form_is_the_rule(a_log):
    """Outputs AND final state, at both ends of ``A_log``'s range, from
    a state that is not zero. At the fast end a sub-chunk of 64 decays
    a channel by e^-100 and more: the factored form ``k e^{-G}`` is
    infinite there, this one takes exponents of differences alone."""
    inputs = rule_inputs(64, 2, 16, a_log, seed=7)
    state = jnp.asarray(np.random.default_rng(8).normal(size=(2, 16, 16)),
                        jnp.float32)
    want_o, want_s = kimi.kda_recurrence(*inputs, state)
    for subchunk in (4, 16, 64):
        o, s = kimi.kda_chunkwise(*inputs, state, subchunk)
        np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=0)
        np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=0)
    g = np.asarray(inputs[3])
    if a_log:
        assert np.exp(-np.cumsum(g, axis=0)[63].astype(np.float64)).max() \
            > np.finfo(np.float32).max       # what the factored form takes
    assert np.isfinite(np.asarray(want_o)).all()


def test_a_padded_chunk_is_its_real_positions(weights):
    """``kda_chunk`` on 5 real positions and 3 of padding against
    ``kda_step`` five times: outputs, state, the convolutions' inputs;
    the padding's own tokens change nothing."""
    cfg = tiny()
    w = weights(cfg)["first"][0]["mixer"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(CHUNK, cfg.hidden_size)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(cfg.kda_heads, 16, 16)), jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(3, 3 * cfg.kda_width)), jnp.float32)
    out, s, c = kimi.kda_chunk(w, x, s0, c0, 5, cfg)
    again = kimi.kda_chunk(w, x.at[5:].set(9.0), s0, c0, 5, cfg)
    for a, b in zip((out[:5], s, c), (again[0][:5], *again[1:])):
        np.testing.assert_array_equal(a, b)
    step_s, step_c = s0[None], c0[:, None]
    for i in range(5):
        step_out, step_s, step_c = kimi.kda_step(
            w, x[i:i + 1], step_s, step_c, jnp.ones((1,), bool), cfg)
        np.testing.assert_allclose(out[i], step_out[0], atol=2e-5, rtol=0)
    np.testing.assert_allclose(s, step_s[0], atol=2e-5, rtol=0)
    np.testing.assert_array_equal(c, step_c[:, 0])
    # An inactive row's state and inputs stay as they are, to the bit.
    _, idle_s, idle_c = kimi.kda_step(w, x[:1], s0[None], c0[:, None],
                                      jnp.zeros((1,), bool), cfg)
    np.testing.assert_array_equal(idle_s[0], s0)
    np.testing.assert_array_equal(idle_c[:, 0], c0)


# ------------------------------------ (c) a row slot's second tenant


def test_a_reused_row_slot_starts_from_zero(weights):
    """A second request prefilled into a row slot its first tenant left
    a state, the convolutions' inputs and latents in gives the logits
    of a fresh cache: its first chunk starts from zeros in the
    program."""
    cfg = tiny()
    params = weights(cfg)
    first, second = contexts_of([29, 21], seed=9)
    table = tables_for(1)[0]
    used = prefill(cfg, params, fresh_cache(cfg), first, 29, table, 2)
    assert float(jnp.abs(used["kda"][:, 2]).max()) > 0
    got = np.zeros((21, cfg.vocab_size), np.float32)
    used = prefill(cfg, params, used, second, 21, table, 2, got)
    want = np.zeros_like(got)
    fresh = prefill(cfg, params, fresh_cache(cfg), second, 21, table, 2, want)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(used["kda"][:, 2], fresh["kda"][:, 2])
    np.testing.assert_array_equal(used["conv"][:, :, 2],
                                  fresh["conv"][:, :, 2])
    np.testing.assert_allclose(
        got, reference_logits(cfg, params, second), atol=F32_ATOL, rtol=0)


def test_the_family_follows_from_the_configuration():
    family = paged_model.family(tiny())
    assert family is linear.FAMILY
    # True together for the first time: a state a row, a pool read by row.
    assert family.recurrent and family.reads_by_row
    # The chunk's array carries the row slot, as the hybrid family's.
    assert family.pack_prefill_chunk(4, 3, [5, 6], 8, [1, 2], 3).tolist() \
        == hybrid.FAMILY.pack_prefill_chunk(4, 3, [5, 6], 8, [1, 2],
                                            3).tolist()
    assert family.pack_decode_rows is paged_model.PAGED.pack_decode_rows
    assert family.ahead is paged_model.PAGED.ahead
    assert family.make_engine_decode_step(tiny(), BLOCK).__name__ \
        == "decode_step"
    assert family.make_engine_prefill_chunk(tiny(), BLOCK, CHUNK).__name__ \
        == "prefill_chunk"
    cache = jax.eval_shape(lambda: linear.init_cache(
        kimi.KimiLinearConfig(num_layers=13), 9, 16, 64, 128))
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "latent": ((3, 9, 16, 640), "bfloat16"),
        "kda": ((10, 64, 32, 128, 128), "float32"),
        "conv": ((10, 3, 64, 12288), "bfloat16")}


# --------------- (e) latent attention without a down-projection or rotation


def test_latent_attention_without_rotation_absorbed_is_expanded(weights):
    """``xing``'s latent functions under ``q_lora_rank=None`` and
    ``rotary=False``: one query projection, nothing rotated (the
    positions change no query and no entry), and the absorbed reading
    of the latents gives what the expanded one gives."""
    cfg = tiny()
    w = weights(cfg)["periods"][2]["mixer"]
    assert "wq" in w and "wq_a" not in w
    w = jax.tree.map(lambda x: x[0], w)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 6, cfg.hidden_size)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(6), (2, 6))
    q_nope, q_rope = xing.latent_queries(w, x, positions, cfg)
    entries = xing.latent_entries(w, x, positions, cfg)
    assert entries.shape == (2, 6, 128) and cfg.latent_dim == 40
    np.testing.assert_array_equal(entries[..., 40:], 0)
    far = positions + 1000
    np.testing.assert_array_equal(
        xing.latent_queries(w, x, far, cfg)[1], q_rope)
    np.testing.assert_array_equal(
        xing.latent_entries(w, x, far, cfg), entries)
    q = jnp.einsum("btc,chd->bthd", x, w["wq"])
    np.testing.assert_allclose(q_rope, q[..., cfg.qk_nope_head_dim:],
                               atol=1e-6)
    mask = jnp.tril(jnp.ones((6, 6), bool))[None]
    expanded = xing.attend_expanded(w, q_nope, q_rope, entries, mask, cfg)
    absorbed = xing.attend_absorbed(w, q_nope, q_rope, entries, mask, cfg)
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5, rtol=0)
    assert float(jnp.abs(expanded).max()) > 0.01


def test_the_configuration_reads_the_published_lists():
    cfg = kimi.KimiLinearConfig()
    assert cfg.kinds.count("kda") == 20 and cfg.kinds.count("latent") == 7
    assert [i + 1 for i, k in enumerate(cfg.kinds) if k == "latent"] == \
        [4, 8, 12, 16, 20, 24, 27]
    # 26 layers behind the dense one do not make whole periods of four
    # (the published stack ends latent at 27): the cut to 13 does.
    cut = kimi.KimiLinearConfig(num_layers=13, experts_held=32,
                                vocab_size=20480)
    assert cut.period_kinds == ("kda", "kda", "latent", "kda")
    assert (cut.periods, cut.kda_layers, cut.latent_layers) == (3, 10, 3)
    assert cut.held == (0, 32) and cut.num_experts == 256
    assert cut.num_params == 3_450_547_008
    with pytest.raises(ValueError):
        kimi.KimiLinearConfig(experts_held=32, first_expert=240)
