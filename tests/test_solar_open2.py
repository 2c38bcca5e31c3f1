"""Solar-Open2's layers (KDA whose ``beta`` reaches 2, so the transition's
eigenvalue along the key goes negative; gated softmax attention of
grouped queries without positions every fourth layer from the first,
over key and value pools that only those layers own; sigmoid-routed
experts of which a share is held, in every layer) through
``models/solar_open2.py``, ``kimi_linear``'s KDA functions,
``llm_engine.model.paged_attention`` and the paged engine
(``serve/llm_engine/linear.py``), held to the plain float32 reference
``benchmark/reference/solar_open2_decoder.py`` at a small size on the
CPU. Logits are compared, not tokens; ``test_solar_engine.py`` then holds
the engine's tokens to the reference's own greedy continuation.

Tolerances. float32 against float32: 2e-4 absolute on logits of
standard deviation about 1 (read 3e-5: only the order of summation
differs, the chunkwise form and the step against the token-by-token
rule, the paged gather against one masked softmax). With the STATE alone
held in bfloat16 the same logits move by 5e-3 and more
(``test_a_bfloat16_state_leaves_the_float32_tolerance``): the tolerance
is tight enough to tell. bfloat16 programs against the float32
reference: 0.7 of a standard deviation in the root mean square at this
width (``test_bfloat16_programs_stay_near_the_reference``).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from solar_tiny import (  # noqa: E402
    BLOCK, CHUNK, ROWS, TABLE, contexts_of, numbers, reference,
    reference_logits, tiny)
from ray_tpu.models import kimi_linear as kimi  # noqa: E402
from ray_tpu.models import llama, moe, xing  # noqa: E402
from ray_tpu.models import solar_open2 as solar  # noqa: E402
from ray_tpu.ops.kda_state_update import kda_state_update  # noqa: E402
from ray_tpu.serve.llm_engine import hybrid, linear  # noqa: E402
from ray_tpu.serve.llm_engine import model as paged_model  # noqa: E402

# The drivers of the two programs are the linear family's, whichever
# configuration they are given (row ``i`` in row slot ``i``, the same
# table of 64 positions): ``test_kimi_linear.py``'s, used and not copied.
from test_kimi_linear import (  # noqa: E402,F401 — ``weights`` is a fixture
    F32_ATOL, RAGGED, fresh_cache, prefill, serve, tables_for, weights)


# ------------------ (a) the paged programs against the token-by-token rule


def test_paged_logits_and_states_match_the_reference(weights):
    cfg = tiny()
    params = weights(cfg)
    assert params["first"] == [] and len(params["periods"]) == 4
    contexts = contexts_of([p + d for p, d in RAGGED])
    got, cache = serve(cfg, params, contexts, [p for p, _ in RAGGED])
    for slot, (context, logits) in enumerate(zip(contexts, got)):
        want, states = reference_logits(cfg, params, context, True)
        assert 0.5 < want.std() < 2.0
        np.testing.assert_allclose(logits, want, atol=F32_ATOL, rtol=0)
        # The state itself, every KDA layer, after the last position.
        assert len(states) == cfg.kda_layers == 6
        for layer, state in enumerate(states):
            np.testing.assert_allclose(cache["kda"][layer, slot], state[0],
                                       atol=F32_ATOL, rtol=0)
    assert cache["kda"].dtype == jnp.float32
    # Pools for the layers that ARE full alone: 2 of 8.
    assert cache["k"].shape == cache["v"].shape == \
        (2, 1 + ROWS * TABLE, BLOCK, 2, 16)
    assert set(cache) == {"k", "v", "kda", "conv"}


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
    eight layers' roundings of 2^-8 each and the expert choices they
    flip (3 of 16, 8 held) move a logit by a third of a standard
    deviation in the root mean square, the float32 programs by 1e-5: a
    wrong path (a state in the wrong slot, a dropped gate, a rotated
    key) moves it by one and more. What bfloat16 costs at the PUBLISHED
    widths is the chip's to say (the cell's ``logit_atol_why``)."""
    cfg = tiny(dtype=jnp.bfloat16)
    params = weights(cfg)
    contexts = contexts_of([p + d for p, d in RAGGED[1:3]])
    got, cache = serve(cfg, params, contexts, [p for p, _ in RAGGED[1:3]])
    assert cache["kda"].dtype == jnp.float32 \
        and cache["conv"].dtype == cache["k"].dtype == jnp.bfloat16
    for context, logits in zip(contexts, got):
        want = reference_logits(cfg, params, context)
        rms = float(np.sqrt(np.mean((logits - want) ** 2)))
        assert 1e-3 < rms < 0.7, rms


# ----------------------------- (b) the chunkwise form against the rule


def rule_inputs(length, heads, d, a_log, seed, beta=None):
    """q, k, v, g, beta as ``_kda_inputs`` makes them under
    ``kda_beta_scale`` 2: ``beta = 2 sigmoid(N(0, 2))`` reaches both
    ends of (0, 2), or is pinned; the decay ``-exp(a_log) * softplus(N(0,
    1) + dt_bias)`` of ``init_params``' ``dt_bias`` range."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(length, heads, d)) for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (heads, d)))
    bias = step + np.log(-np.expm1(-step))
    g = -np.exp(a_log) * np.logaddexp(
        0, rng.normal(size=(length, heads, d)) + bias)
    drawn = 2 / (1 + np.exp(-2 * rng.normal(size=(length, heads))))
    beta = drawn if beta is None else np.full_like(drawn, beta)
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


@pytest.mark.parametrize("beta", [None, 1.99], ids=["to-2", "pinned-1.99"])
@pytest.mark.parametrize("a_log", [0.0, float(np.log(16.0))],
                         ids=["slowest", "fastest"])
def test_the_chunkwise_form_is_the_rule_with_beta_to_two(a_log, beta):
    """Outputs AND final state, at both ends of ``A_log``'s range, from
    a state that is not zero, with ``beta`` drawn over (0, 2) and pinned
    at 1.99: the triangular system's entries ``beta_i A_ij`` reach 2
    where Kimi's reached 1, and forward substitution over a sub-chunk
    of 64 amplifies rounding further, most where the decay is slow
    (``A`` stays near ``k_i . k_j``). Read at 64 positions, 16 channels,
    sub-chunks of 64: the outputs within 1.3e-6 and the state within
    5.0e-6 (of entries up to 7.4) at the slow end pinned at 1.99, where
    ``beta`` 0.5 reads 1.3e-7 and 7.3e-7: seven times the rounding, and
    still float32's. Kimi's tolerance of 2e-5 holds with four times of
    room; a wrong term moves an output by 1e-2."""
    inputs = rule_inputs(64, 2, 16, a_log, seed=7, beta=beta)
    assert beta is not None or (float(inputs[4].max()) > 1.9
                                and float(inputs[4].min()) < 0.1)
    state = jnp.asarray(np.random.default_rng(8).normal(size=(2, 16, 16)),
                        jnp.float32)
    want_o, want_s = kimi.kda_recurrence(*inputs, state)
    for subchunk in (4, 16, 64):
        o, s = kimi.kda_chunkwise(*inputs, state, subchunk)
        np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=0)
        np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=0)
    assert np.isfinite(np.asarray(want_o)).all()
    # From a zero state the rule is the reference's token-by-token one.
    o, s = kimi.kda_chunkwise(*inputs, jnp.zeros_like(state), 64)
    ref_o, ref_s = reference.delta_rule_by_token(*(x[None] for x in inputs))
    np.testing.assert_allclose(o, ref_o[0], atol=2e-5, rtol=0)
    np.testing.assert_allclose(s, ref_s[0], atol=2e-5, rtol=0)


def test_a_negative_eigenvalue_flips_a_state_that_beta_under_one_cannot():
    """One key written at ``beta`` 1.9 with no decay and ``v = 0``: the
    state's component along ``k`` changes sign (eigenvalue 1 - beta =
    -0.9); at ``beta`` 0.9 it shrinks and keeps its sign. Both forms."""
    d = 8
    k = jnp.zeros((1, 1, d), jnp.float32).at[0, 0, 0].set(1.0)
    q, v, g = k, jnp.zeros_like(k), jnp.zeros_like(k)
    state = jnp.eye(d, dtype=jnp.float32)[None]
    for beta, factor in ((1.9, -0.9), (0.9, 0.1)):
        b = jnp.full((1, 1), beta, jnp.float32)
        for form in (kimi.kda_recurrence,
                     lambda *a: kimi.kda_chunkwise(*a, 1)):
            o, s = form(q, k, v, g, b, state)
            np.testing.assert_allclose(s[0, 0, 0], factor, atol=1e-6)
            np.testing.assert_allclose(o[0, 0, 0], factor, atol=1e-6)
            np.testing.assert_allclose(s[0, 1, 1], 1.0)


def test_a_padded_chunk_is_its_real_positions(weights):
    """``kda_chunk`` under ``beta = 2 sigmoid(.)`` on 5 real positions
    and 3 of padding against ``kda_step`` five times: outputs, state,
    the convolutions' inputs; the padding's own tokens change nothing."""
    cfg = tiny()
    w = jax.tree.map(lambda x: x[0], weights(cfg)["periods"][1]["mixer"])
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
        np.testing.assert_allclose(out[i], step_out[0], atol=5e-5, rtol=0)
    np.testing.assert_allclose(s, step_s[0], atol=5e-5, rtol=0)
    np.testing.assert_array_equal(c, step_c[:, 0])
    # The configuration's scale reaches the rule: Kimi's (1) on the same
    # weights gives another state.
    plain = dataclasses.replace(cfg, kda_allow_neg_eigval=False)
    assert (cfg.kda_beta_scale, plain.kda_beta_scale) == (2.0, 1.0)
    _, halved, _ = kimi.kda_chunk(w, x, s0, c0, 5, plain)
    assert float(jnp.abs(halved - s).max()) > 1e-2


@pytest.mark.parametrize("rows,heads", [(3, 64), (2, 32)],
                         ids=["64-heads", "32-heads"])
def test_the_state_kernel_is_the_rule_at_64_heads(rows, heads):
    """``ops/kda_state_update.py`` at this model's 64 heads of 128
    (four blocks of 16 heads a row, ``rows * 64`` scalars of ``beta``
    prefetched; 4,096 at the cell's 64 rows) and ``beta`` to 2, on layer
    1 of a stack of 2, against ``kimi_linear.kda_position``; the other
    layer untouched."""
    d = 128
    q, k, v, g, beta = (jnp.stack([x] * 1)[0] for x in rule_inputs(
        rows, heads, d, 0.5, seed=rows))
    state = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, rows, heads, d, d)), jnp.float32)
    want_o, want_s = kimi.kda_position(q, k, v, g, beta, state[1])
    o, after = kda_state_update(state, 1, q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(after[1], want_s, atol=2e-5, rtol=0)
    np.testing.assert_array_equal(after[0], state[0])
    assert float(beta.max()) > 1.5


# ------------------------------------ (c) a row slot's second tenant


def test_a_reused_row_slot_starts_from_zero(weights):
    """A second request prefilled into a row slot its first tenant left
    a state, the convolutions' inputs, keys and values in gives the
    logits of a fresh cache: its first chunk starts from zeros in the
    program, and the causal mask hides what the pools' blocks held."""
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
    assert family is linear.FAMILIES[solar.GQA] is not linear.FAMILY
    # A state a row beside pools that the decode step reads BY ROW, as
    # Kimi-Linear's reads its latent pool: ONE decode program.
    assert family.recurrent and family.reads_by_row
    assert dataclasses.replace(
        family, init_params=kimi.init_params) == linear.FAMILY
    assert family.init_params is solar.init_params
    # The chunk's array carries the row slot, as the hybrid family's.
    assert family.pack_prefill_chunk(4, 3, [5, 6], 8, [1, 2], 3).tolist() \
        == hybrid.FAMILY.pack_prefill_chunk(4, 3, [5, 6], 8, [1, 2],
                                            3).tolist()
    assert family.pack_decode_rows is paged_model.PAGED.pack_decode_rows
    assert family.make_engine_decode_step(tiny(), BLOCK).__name__ \
        == "decode_step"
    assert family.make_engine_prefill_chunk(tiny(), BLOCK, CHUNK).__name__ \
        == "prefill_chunk"
    cache = jax.eval_shape(lambda: linear.init_cache(
        solar.SolarOpen2Config(num_layers=4), 9, 16, 64, 128))
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((1, 9, 16, 8, 128), "bfloat16"),
        "v": ((1, 9, 16, 8, 128), "bfloat16"),
        "kda": ((3, 64, 64, 128, 128), "float32"),
        "conv": ((3, 3, 64, 24576), "bfloat16")}


def _linear_programs(by_row, monkeypatch):
    """The linear family's decode step and prefill chunk as jaxprs, its
    ``reads_by_row`` set to ``by_row``."""
    cfg = tiny()
    monkeypatch.setitem(linear.FAMILIES, solar.GQA, dataclasses.replace(
        linear.FAMILIES[solar.GQA], reads_by_row=by_row))
    params = jax.eval_shape(
        lambda: solar.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: fresh_cache(cfg))
    rows = jnp.ones((ROWS, 1), jnp.int32)
    tables = jnp.zeros((ROWS, TABLE), jnp.int32)
    step = jax.make_jaxpr(lambda p, c: linear.forward(
        p, c, rows, rows, tables, cfg, BLOCK))(params, cache)
    chunk = jax.make_jaxpr(lambda p, c: linear.forward(
        p, c, jnp.ones((1, CHUNK), jnp.int32),
        jnp.arange(CHUNK, dtype=jnp.int32)[None], tables[:1], cfg, BLOCK,
        slot=0, n_valid=jnp.int32(CHUNK), logits_at=jnp.int32(0)))(
            params, cache)
    return by_row, step, chunk


def _engine_programs(block_length):
    """The engine's two programs of identical layers over one pool, as
    jaxprs on their packers' arrays: the paged family's, or with a
    ``block_length`` the family of diffusion over blocks."""
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32,
        block_length=block_length, mask_token_id=255)
    family = paged_model.family(cfg)
    assert family is (paged_model._blockwise(block_length) if block_length
                      else paged_model.PAGED)
    params = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: family.init_cache(
        cfg, 1 + ROWS * TABLE, BLOCK, ROWS, CHUNK))
    key = jax.random.PRNGKey(0)
    step = jax.make_jaxpr(family.make_engine_decode_step(cfg, BLOCK))(
        params, cache, family.pack_decode_rows(ROWS, TABLE, ()), key)
    chunk = jax.make_jaxpr(
        family.make_engine_prefill_chunk(cfg, BLOCK, CHUNK))(
            params, cache, family.pack_prefill_chunk(CHUNK, TABLE, (), 0,
                                                     (), 0))
    return family.reads_by_row, step, chunk


@pytest.mark.parametrize("case", [
    "linear-by-row", "linear-gathers", "paged", "block"])
def test_the_decode_step_reads_as_its_family_says(case, monkeypatch):
    """``Family.reads_by_row`` is the ONE word the engine's decode
    widths, its counters and the step's read hang on: a family that
    says no gathers in its step too (the linear family told so; the
    family of diffusion over blocks, whose pass has ``block_length``
    query rows a row, though it is made of the paged family, which
    reads by row), and none calls the kernel in a prefill chunk."""
    if case.startswith("linear"):
        by_row, step, chunk = _linear_programs(case == "linear-by-row",
                                               monkeypatch)
    else:
        by_row, step, chunk = _engine_programs(4 if case == "block" else 0)
        assert by_row == (case == "paged")
    assert ("paged_kv_attention" in str(step)) == by_row
    assert "paged_kv_attention" not in str(chunk)


# --------------- (e) the full layer: the dense family's block, told more


def attention_case(cfg, seed=1, length=6):
    """Two rows of ``length`` positions prefilled in one call into
    shuffled tables, at positions that do not start at 0."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, length, cfg.hidden_size)),
                    jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(length), (2, length))
    tables = jnp.asarray([[3, 1, 0, 0], [2, 4, 0, 0]], jnp.int32)
    shape = (2, 5, BLOCK, cfg.num_kv_heads, cfg.head_dim)
    return x, positions, tables, jnp.zeros(shape, jnp.float32), \
        jnp.zeros(shape, jnp.float32)


def test_without_gate_and_with_rotation_it_is_the_dense_familys_block():
    """``paged_attention`` under a Solar configuration that rotates and
    has no gate, on a dense layer's weights, gives
    ``_paged_attention_block``'s numbers under the ``LlamaConfig`` of
    the same widths, as Mistral runs it: same keys and values in the
    pool, same output, to the bit."""
    cfg = tiny(use_rope=True, use_gqa_gate=False, rope_theta=500.0)
    dense = dataclasses.replace(llama.LlamaConfig.tiny(), num_kv_heads=2,
                                rope_theta=500.0, dtype=jnp.float32)
    assert (dense.hidden_size, dense.num_heads, dense.head_dim) == \
        (cfg.hidden_size, cfg.num_heads, cfg.head_dim)
    assert cfg.rotary and not hasattr(dense, "rotary")
    w = jax.tree.map(lambda x: x[1], solar.init_params(
        cfg, jax.random.PRNGKey(5))["periods"][0])
    assert "wg" not in w["mixer"]
    layer = {**w["mixer"], "attn_norm": w["mixer_norm"]}
    x, positions, tables, pool_k, pool_v = attention_case(cfg)
    want, want_k, want_v = paged_model._paged_attention_block(
        layer, x, positions, pool_k, pool_v, 1, tables, dense, BLOCK)
    normed = llama.rms_norm(x, w["mixer_norm"], cfg.rms_norm_eps)
    out, k, v = paged_model.paged_attention(
        w["mixer"], normed, positions, pool_k, pool_v, 1, tables, cfg, BLOCK)
    np.testing.assert_array_equal(x + out, want)
    np.testing.assert_array_equal(k, want_k)
    np.testing.assert_array_equal(v, want_v)
    assert float(jnp.abs(k[1]).max()) > 0 and float(jnp.abs(k[0]).max()) == 0


def test_with_the_gate_and_without_rotation_it_is_the_plain_masked_softmax():
    """The published full layer: no position changes a query or a key,
    and the output is ``W_o [(P v) * sigmoid(W_g x)]`` of one causal
    softmax a head, query head ``2 j + r`` on key-value head ``j``."""
    cfg = tiny()
    w = jax.tree.map(lambda x: x[0], solar.init_params(
        cfg, jax.random.PRNGKey(6))["periods"][0]["mixer"])
    assert set(w) == {"wq", "wk", "wv", "wg", "wo"}
    x, positions, tables, pool_k, pool_v = attention_case(cfg, seed=3)
    out, k, _ = paged_model.paged_attention(
        w, x, positions, pool_k, pool_v, 0, tables, cfg, BLOCK)
    # No position enters: a thousand positions on, the same q, k and v.
    for near, far in zip(llama.qkv_of_normed(w, x, positions, cfg),
                         llama.qkv_of_normed(w, x, positions + 1000, cfg)):
        np.testing.assert_array_equal(near, far)
    assert float(jnp.abs(k[0, 3, 0]).max()) > 0   # row 0's first key
    x64 = np.asarray(x, np.float64)
    q = np.einsum("blc,chd->blhd", x64, np.asarray(w["wq"], np.float64))
    keys = np.einsum("blc,cjd->bljd", x64, np.asarray(w["wk"], np.float64))
    values = np.einsum("blc,cjd->bljd", x64, np.asarray(w["wv"], np.float64))
    keys, values = (np.repeat(t, 2, axis=2) for t in (keys, values))
    scores = np.einsum("bqhd,bshd->bhqs", q, keys) / 16 ** 0.5
    scores = np.where(np.tril(np.ones((6, 6), bool)), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    read = np.einsum("bhqs,bshd->bqhd", p, values)
    gate = 1 / (1 + np.exp(-np.einsum(
        "blc,chd->blhd", x64, np.asarray(w["wg"], np.float64))))
    want = np.einsum("blhd,hdc->blc", read * gate,
                     np.asarray(w["wo"], np.float64))
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=0)
    assert float(np.abs(want).max()) > 0.05
    # The reference's full mixer says the same.
    np.testing.assert_allclose(
        reference.gated_attention(x, w, numbers(cfg)), want, atol=2e-5,
        rtol=0)


# ----------------------------------------- (f) the eight shares of a layer


def test_the_eight_shares_add_up_to_the_uncut_references_layer():
    """An expert layer of 40 routed experts of which a chip holds 5 (the
    cell's 40 of 320 at a small size): the routed parts of the eight
    chips that share the layer, each through ``xing.sparse_ffn`` as the
    engine calls it (the chosen experts it HOLDS), with the shared
    expert counted ONCE, add up to the reference's layer given all 40."""
    base = dict(num_experts=40, experts_per_token=8, num_layers=4)
    whole = tiny(experts_held=40, first_expert=0, **base)
    ffn = jax.tree.map(lambda x: x[0], kimi.init_ffn(
        whole, jax.random.PRNGKey(2), True, 1))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 64)),
                    jnp.float32)
    model = {**numbers(whole), "first_expert_held": 0}
    chosen, weights = reference.choose(x, ffn, model)
    want = reference.held_experts(x, ffn, chosen, weights, model)
    shared = moe.shared_ffn(ffn, x, jnp.float32)
    routed, landed = [], 0
    for share in range(8):
        cfg = tiny(experts_held=5, first_expert=5 * share, **base)
        mine = {k: (v[5 * share:5 * share + 5]
                    if k in moe.EXPERT_TENSORS else v)
                for k, v in ffn.items()}
        stacks, rest = moe.split_experts(
            jax.tree.map(lambda a: a[None], mine))
        out, idx = xing.sparse_ffn(jax.tree.map(lambda a: a[0], rest), x,
                                   cfg, stacks, 0)
        np.testing.assert_array_equal(np.sort(idx), np.sort(chosen))
        routed.append(out - shared)
        landed += int(moe.routing_counts(
            idx, jnp.ones(idx.shape[:2], bool), 40, cfg.held)[0])
    np.testing.assert_allclose(sum(routed) + shared, want, atol=3e-5, rtol=0)
    assert landed == 2 * 9 * 8              # every choice lands once
    assert all(float(jnp.abs(part).max()) > 1e-3 for part in routed)
    # ... and the reference given ONE share adds that share alone.
    cfg = tiny(experts_held=5, first_expert=15, **base)
    mine = {k: (v[15:20] if k in moe.EXPERT_TENSORS else v)
            for k, v in ffn.items()}
    one = reference.held_experts(x, mine, chosen, weights,
                                 {**model, "first_expert_held": 15})
    np.testing.assert_allclose(routed[3] + shared, one, atol=3e-5, rtol=0)


# ----------------------------------------------- the configuration's lists


def test_the_configuration_reads_the_published_list():
    cfg = solar.SolarOpen2Config()
    assert cfg.kinds.count("kda") == 36 and cfg.kinds.count("gqa") == 12
    assert [i for i, k in enumerate(cfg.kinds) if k == "gqa"] == \
        list(range(0, 48, 4))
    assert cfg.period_kinds == ("gqa", "kda", "kda", "kda")
    assert (cfg.periods, cfg.first_k_dense) == (12, 0)
    assert cfg.num_params == 250_287_810_304
    # The list is kept as published (12 entries, counted from 0) and
    # the layers below ``num_layers`` built.
    cut = solar.SolarOpen2Config(num_layers=4, experts_held=40,
                                 vocab_size=24576)
    assert len(cut.gqa_layers) == 12
    assert cut.kinds == ("gqa", "kda", "kda", "kda")
    assert (cut.periods, cut.kda_layers, cut.full_layers) == (1, 3, 1)
    assert cut.held == (0, 40) and cut.num_experts == 320
    assert cut.num_params == 3_308_353_344
    assert (cut.kda_mixer_params, cut.full_mixer_params) == \
        (137_732_288, 109_051_904)
    assert cut.kda_beta_scale == 2.0 and not cut.rotary
    with pytest.raises(ValueError):
        solar.SolarOpen2Config(experts_held=40, first_expert=300)
    with pytest.raises(ValueError):
        solar.SolarOpen2Config(kda_use_full_proj=True)
    with pytest.raises(ValueError):
        solar.SolarOpen2Config(gqa_layers=(0, 3, 8))
    # Kimi's configuration is what it was: beta in (0, 1), counted from 1.
    assert kimi.KimiLinearConfig().kda_beta_scale == 1.0
