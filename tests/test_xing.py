"""Xing4.0's layer (latent attention over a pool of one vector a
position, four residual streams under Sinkhorn-projected
hyper-connections, sigmoid-routed experts beside a shared one, behind a
leading dense layer) through ``models/xing.py``, ``models/moe.py`` and
the paged engine (``serve/llm_engine/latent.py``), held to the plain
float32 reference ``benchmark/reference/xing_decoder.py`` at a small
size on the CPU. Logits are compared, not tokens, where the programs
are driven by hand; the engine's tokens are then held to the
reference's own greedy continuation, float32 on both sides.

Tolerances. float32 against float32: 1e-4 absolute on logits of
standard deviation about 1. Only the order of summation differs: the
paged programs gather a table and (decode) score in the latent space,
the reference expands every key over the whole sequence; the Sinkhorn
loop and the mix are float32 on both sides. A changed equation moves
the logits by 1e-2 and more (``test_a_control_leaves_the_tolerance``).
bfloat16 against float32: the configuration's own ``logit_atol``, in
the cell's measure (how far under the reference's best logit the
program's choice lies), which the same programs on weights rounded to
float8's mantissa must leave.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from benchmark import spec  # noqa: E402
from benchmark.reference import xing_decoder as reference  # noqa: E402
from ray_tpu.models import moe, xing  # noqa: E402
from ray_tpu.serve.llm_engine import latent  # noqa: E402
from ray_tpu.serve.llm_engine import model as paged_model  # noqa: E402
from ray_tpu.serve.llm_engine.engine import table_widths  # noqa: E402
from xing_tiny import (  # noqa: E402
    BLOCK, CHUNK, ROWS, TABLE, contexts_of, numbers, reference_logits, tiny)

F32_ATOL = 1e-4
CONTROL_MOVES = 1e-2
CONFIG_FILE = os.path.join(REPO, "benchmark", "configs",
                           "xing4-29b-a4b-serve-1chip.json")


@pytest.fixture(scope="module")
def weights():
    made = {}

    def of(cfg, seed=11):
        key = (cfg, seed)
        if key not in made:
            made[key] = paged_model.serving_params(cfg, None, seed)
        return made[key]
    return of


def shown(cfg):
    """The forward the two programs wrap, showing every position's
    logits: a chunk expanded, a step absorbed, the pool donated."""
    chunk = jax.jit(
        lambda params, cache, tokens, positions, table, n_valid:
        latent.forward(params, cache, tokens, positions, table, cfg, BLOCK,
                       absorbed=False, n_valid=n_valid), donate_argnums=(1,))
    step = jax.jit(
        lambda params, cache, tokens, positions, tables, absorbed=True:
        latent.forward(params, cache, tokens, positions[:, None], tables,
                       cfg, BLOCK, absorbed=absorbed),
        donate_argnums=(1,), static_argnums=(5,))
    return chunk, step


def serve(cfg, params, contexts, prefilled, absorbed=True):
    """Each context's first ``prefilled`` positions through prefill
    chunks, the rest through batched decode steps, as the engine drives
    its two programs: a chunk at the narrowest of the table's three
    widths that holds its row, every step at the whole table. Returns
    every position's logits per context, and the chosen experts."""
    chunk, step = shown(cfg)
    cache = latent.init_cache(cfg, 1 + ROWS * TABLE, BLOCK, ROWS, CHUNK)
    tables = np.zeros((ROWS, TABLE), np.int32)
    deck = list(np.random.default_rng(3).permutation(
        np.arange(1, 1 + ROWS * TABLE)))
    for i in range(len(contexts)):
        tables[i] = [int(deck.pop()) for _ in range(TABLE)]

    def rung(positions):
        return next(w for w in table_widths(TABLE)
                    if w * BLOCK >= positions)

    got = [np.zeros((len(c), cfg.vocab_size), np.float32) for c in contexts]
    for i, context in enumerate(contexts):
        width = rung(prefilled[i])
        for start in range(0, prefilled[i], CHUNK):
            n = min(CHUNK, prefilled[i] - start)
            logits, cache, _, _ = chunk(
                params, cache, *chip_smoke.chunk_inputs(context, start, n,
                                                        CHUNK),
                jnp.asarray(tables[i:i + 1, :width]), np.int32(n))
            got[i][start:start + n] = np.asarray(logits[0, :n])
    at = list(prefilled)
    while any(at[i] < len(c) for i, c in enumerate(contexts)):
        tokens = np.zeros((ROWS, 1), np.int32)
        positions = np.zeros((ROWS,), np.int32)
        active = [i for i, c in enumerate(contexts) if at[i] < len(c)]
        for i in active:
            tokens[i, 0], positions[i] = contexts[i][at[i]], at[i]
        step_tables = np.where(positions[:, None] > 0, tables, 0)
        logits, cache, _, _ = step(
            params, cache, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(step_tables), absorbed)
        for i in active:
            got[i][at[i]] = np.asarray(logits[i, 0])
            at[i] += 1
    return got


# (prefilled, decoded): inside one chunk and block; across a block and
# a chunk; prefilled past the quarter width (16) and decoded past the
# half (32); decoded across the quarter and the half.
RAGGED = [(3, 12), (9, 9), (27, 14), (11, 30)]


# ---------------------- (1) the paged programs against the full forward


def test_paged_logits_match_the_reference_for_ragged_rows(weights):
    cfg = tiny()
    params = weights(cfg)
    contexts = contexts_of([p + d for p, d in RAGGED])
    got = serve(cfg, params, contexts, [p for p, _ in RAGGED])
    for context, logits in zip(contexts, got):
        want = reference_logits(cfg, params, context)
        assert 0.5 < want.std() < 2.0
        np.testing.assert_allclose(logits, want, atol=F32_ATOL, rtol=0)


def _route_weighing_the_bias(x, w_router, k, norm, *, scoring, bias, scale,
                             _real=moe.route):
    scores, idx, _ = _real(x, w_router, k, norm, scoring=scoring, bias=bias)
    weights = jnp.take_along_axis(scores + bias, idx, axis=-1)
    return scores, idx, scale * weights / weights.sum(-1, keepdims=True)


CONTROLS = {
    "no-rotary": lambda m: m.setattr(
        xing, "rope", lambda x, positions, config: x),
    "softmax-scale-without-yarn": lambda m: m.setattr(
        xing.XingConfig, "softmax_scale",
        property(lambda self: self.qk_head_dim ** -0.5)),
    "one-sinkhorn-round": lambda m: m.setattr(
        xing, "sinkhorn",
        lambda logits, iters, eps, _real=xing.sinkhorn: _real(logits, 1,
                                                              eps)),
    "bias-in-the-weight": lambda m: m.setattr(
        moe, "route", _route_weighing_the_bias),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_leaves_the_tolerance(control, monkeypatch, weights):
    cfg = tiny()
    params = weights(cfg)
    contexts = contexts_of([21, 18], seed=9)
    want = [reference_logits(cfg, params, c) for c in contexts]
    CONTROLS[control](monkeypatch)
    got = serve(cfg, params, contexts, [11, 9])
    worst = max(np.abs(g - w).max() for g, w in zip(got, want))
    assert worst > CONTROL_MOVES, worst


# ----------------------------- (2) absorbed and expanded, the same latents


def test_absorbed_equals_expanded_on_the_same_latents(weights):
    cfg = tiny()
    w = jax.tree.map(lambda a: a[0], weights(cfg)["sparse"])
    rng = np.random.default_rng(2)
    B, T, S = 3, 5, 24
    q_nope = jnp.asarray(rng.standard_normal(
        (B, T, cfg.num_heads, cfg.qk_nope_head_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal(
        (B, T, cfg.num_heads, cfg.qk_rope_head_dim)), jnp.float32)
    latents = jnp.asarray(rng.standard_normal((B, S, cfg.pool_lanes)),
                          jnp.float32)
    latents = latents.at[..., cfg.latent_dim:].set(0.0)  # as the pool's
    mask = jnp.asarray(np.arange(S)[None, None, :]
                       <= rng.integers(0, S, (B, T))[:, :, None])
    expanded = xing.attend_expanded(w, q_nope, q_rope, latents, mask, cfg)
    absorbed = xing.attend_absorbed(w, q_nope, q_rope, latents, mask, cfg)
    assert float(jnp.abs(expanded).max()) > 0.1
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5, rtol=0)


def test_a_decode_step_reads_the_pool_either_way(weights):
    cfg = tiny()
    params = weights(cfg)
    contexts = contexts_of([25, 14], seed=6)
    absorbed = serve(cfg, params, contexts, [9, 5])
    expanded = serve(cfg, params, contexts, [9, 5], absorbed=False)
    for a, e in zip(absorbed, expanded):
        np.testing.assert_allclose(a, e, atol=F32_ATOL, rtol=0)


# ------------------------- (3) bfloat16, and the precision below it


def argmax_gap(got, want) -> float:
    """The cell's measure: how far under the float32 reference's best
    logit the program's own choice lies, worst over the positions."""
    chosen = got.argmax(axis=-1)
    return float((want.max(axis=-1)
                  - want[np.arange(len(want)), chosen]).max())


@pytest.mark.parametrize("rounded", [None, "float8_e4m3fn"],
                         ids=["bfloat16", "float8-weights"])
def test_bfloat16_stays_inside_the_limit_float8_weights_do_not(rounded):
    with open(CONFIG_FILE) as f:
        atol = spec.rehearsed(json.load(f), True)["probes"]["logit_atol"]
    cfg = tiny(dtype=jnp.bfloat16)
    params = paged_model.serving_params(cfg, None, seed=11)
    contexts = contexts_of([60, 52, 44, 36], seed=12)
    want = [reference_logits(cfg, params, c) for c in contexts]
    served = params
    if rounded:
        served = chip_smoke.round_mantissa(
            jax.tree.map(jnp.copy, params), rounded)
    got = serve(cfg, served, contexts, [30, 20, 10, 5])
    gap = max(argmax_gap(g, w) for g, w in zip(got, want))
    if rounded:
        assert gap > atol, gap
    else:
        assert gap <= atol, gap


# --------------------------------------------------- (4) the residual path


def test_h_res_is_doubly_stochastic(weights):
    cfg = tiny()
    w = jax.tree.map(lambda a: a[0], weights(cfg)["sparse"]["hc_attn"])
    streams = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, 7, cfg.hc_mult, cfg.hidden_size)), jnp.float32)
    h, post, res = xing.hyper_mix(w, streams, cfg)
    assert h.shape == (2, 7, cfg.hidden_size)
    assert res.shape == (2, 7, cfg.hc_mult, cfg.hc_mult)
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-3)
    assert float(res.min()) > 0 and float(res.std()) > 0.05  # not uniform
    assert float(post.min()) > 0 and float(post.max()) < 2
    pre, post_ref, res_ref = reference.mix(streams, w, numbers(cfg))
    np.testing.assert_allclose(res, res_ref, atol=1e-6)
    np.testing.assert_allclose(post, post_ref, atol=1e-6)
    # At the published clamp a row of +-30 still sums to one.
    hard = xing.sinkhorn(jnp.asarray([[30.0, -30.0], [-30.0, 30.0]]), 20,
                         cfg.hc_eps)
    np.testing.assert_allclose(hard, np.eye(2), atol=1e-3)


def test_one_stream_with_the_mix_forced_to_one_is_the_plain_stack(weights):
    """``hc_mult`` 1 with H_pre = H_post = H_res = 1 is ``x + F(norm(x))``,
    written here with the reference's sublayers. The ones are forced
    through the parameters, so the mix itself runs: phi 0, and b =
    (30, 0, 30) gives sigmoid(30) = 1, 2 sigmoid(0) = 1 and a Sinkhorn
    of the one entry exp(30), which hc_eps does not move in float32."""
    cfg = tiny(hc_mult=1)
    params = jax.tree.map(lambda a: a, weights(cfg))    # a tree of its own
    for stack in (params["dense"], params["sparse"]):
        for name in ("hc_attn", "hc_ffn"):
            mix = stack[name]
            mix["phi"] = jnp.zeros_like(mix["phi"])
            mix["b"] = jnp.broadcast_to(
                jnp.asarray([30.0, 0.0, 30.0], mix["b"].dtype), mix["b"].shape)
    context = contexts_of([19], seed=8)[0]
    got = serve(cfg, params, [context], [11])[0]
    model = numbers(cfg)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][jnp.asarray(context)[None]]
        for group in ("dense", "sparse"):
            stack = params[group]
            for i in range(stack["attn_norm"].shape[0]):
                w = jax.tree.map(lambda a: a[i], stack)
                x = x + reference.attention(reference.rms_norm(
                    x, w["attn_norm"], cfg.rms_norm_eps), w, model)
                m = reference.rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
                if group == "dense":
                    x = x + reference.swiglu(m, w["w_gate"], w["w_up"],
                                             w["w_down"])
                else:
                    x = x + reference.experts(m, w, *reference.route(
                        m, w, model))
        x = reference.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        want = np.asarray(x @ params["lm_head"])[0]
    np.testing.assert_allclose(got, want, atol=F32_ATOL, rtol=0)
    # And the mix is not idle in the model proper.
    proper = serve(cfg, weights(cfg), [context], [11])[0]
    assert np.abs(proper - want).max() > CONTROL_MOVES


# ------------------------------------------------------------ (5) routing


def test_sigmoid_routing_picks_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 9, 64)), jnp.float32)
    w_router = jnp.asarray(rng.standard_normal((64, 16)) / 8, jnp.float32)
    bias = jnp.asarray(0.3 * rng.standard_normal(16), jnp.float32)
    scores, idx, weights = moe.route(x, w_router, 4, True, scoring="sigmoid",
                                     bias=bias, scale=2.0)
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(w_router)))
    np.testing.assert_allclose(scores, s, atol=1e-6)
    want = np.argsort(-(s + np.asarray(bias)), axis=-1)[..., :4]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want, -1)).all()
    # The bias changes some choices and no weight.
    assert (np.sort(want, -1)
            != np.sort(np.argsort(-s, axis=-1)[..., :4], -1)).any()
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        weights, 2.0 * chosen / chosen.sum(-1, keepdims=True), atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.0, atol=1e-5)


def test_the_shared_expert_is_counted_once(weights):
    cfg = tiny()
    stack = weights(cfg)["sparse"]
    w = jax.tree.map(lambda a: a[0], stack)
    m = jnp.asarray(np.random.default_rng(1).standard_normal((2, 6, 64)),
                    jnp.float32)
    out, idx = xing.sparse_ffn(w, m, cfg, moe.split_experts(stack)[0], 0)
    with jax.default_matmul_precision("highest"):
        ref_idx, ref_weights = reference.route(m, w, numbers(cfg))
        want = reference.experts(m, w, ref_idx, ref_weights)
        routed_only = reference.experts(
            m, {k: v for k, v in w.items() if not k.startswith("shared")},
            ref_idx, ref_weights)
        shared = reference.swiglu(m, w["shared_gate"], w["shared_up"],
                                  w["shared_down"])
    assert (np.sort(idx, -1) == np.sort(ref_idx, -1)).all()
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_allclose(out - routed_only, shared, atol=1e-5)
    assert float(jnp.abs(shared).max()) > 1e-2


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_the_softmax_route_is_bit_equal_to_what_it_was(norm_topk_prob):
    """OLMoE's and SDAR's: ``route`` as it stood before it took a
    scoring, written out."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((3, 7, 64)), jnp.bfloat16)
    w_router = jnp.asarray(rng.standard_normal((64, 32)) / 2, jnp.bfloat16)

    def before(x, w_router, k, norm):
        logits = jnp.einsum("...h,he->...e", x.astype(jnp.float32),
                            w_router.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = lax.top_k(probs, k)
        if norm:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return probs, idx, weights

    for got, want in zip(moe.route(x, w_router, 8, norm_topk_prob),
                         before(x, w_router, 8, norm_topk_prob)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    now = jax.jit(lambda x, w: moe.route(x, w, 8, norm_topk_prob)).lower(
        x, w_router).as_text()
    then = jax.jit(lambda x, w: before(x, w, 8, norm_topk_prob)).lower(
        x, w_router).as_text()
    assert now == then


# --------------------------------------------- (7) what the pool holds


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def test_the_pool_holds_576_values_a_position_and_layer():
    config = published()
    cfg = spec.build_model_config(config)
    rows, positions = (config["engine"][k]
                       for k in ("max_batch_size", "max_seq_len"))
    blocks = 1 + rows * positions // 16
    cache = jax.eval_shape(lambda: latent.init_cache(cfg, blocks, 16, rows,
                                                     128))
    assert set(cache) == {"latent"}
    # 576 values a position and layer, in the 640 lanes the chip tiles
    # them in (XingConfig.pool_lanes); the 64 lanes of the tail are zero.
    assert (cfg.latent_dim, cfg.pool_lanes) == (576, 640)
    assert cache["latent"].shape == (7, blocks, 16, 640)
    assert cache["latent"].dtype == jnp.bfloat16
    positions_held = cache["latent"].size // 640 - 7 * 16  # less the scratch
    assert positions_held == 32 * 8192 * 7
    assert round(positions_held * 576 * 2 / 2 ** 30, 2) == 1.97   # values
    assert round(cache["latent"].size * 2 / 2 ** 30, 2) == 2.19  # on the chip
    # Against keys and values a head: Mistral's 8 x 128 x 2 x 2 bytes.
    assert cfg.latent_dim * 2 == 1152 < 4096
    entry = xing.latent_entries(
        jax.tree.map(lambda a: a[0], paged_model.serving_params(
            tiny(), None, 11)["sparse"]),
        jnp.ones((1, 3, 64), jnp.float32), jnp.arange(3)[None], tiny())
    assert entry.shape == (1, 3, tiny().pool_lanes)
    assert float(jnp.abs(entry[..., tiny().latent_dim:]).max()) == 0.0
    assert float(jnp.abs(entry[..., :tiny().latent_dim]).min()) > 0.0


def test_parameters_at_the_published_widths_count_as_the_issue_counts():
    cfg = spec.build_model_config(published())
    attention = 3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512 \
        + 512 * 32 * 256 + 4096 * 3584
    assert attention == 28_411_136
    mix = 14_336 * 24 + 24 + 3 + 14_336
    assert mix == 358_427
    dense = attention + 3 * 3584 * 9216 + 2 * (mix + 3584)
    sparse = attention + 65 * 11_010_048 + 3584 * 64 + 64 + 2 * (mix + 3584)
    assert (dense, sparse) == (128_225_590, 745_017_718)
    head = 2 * 131_072 * 3584 + 3584
    assert cfg.num_params == head + 2 * dense + 5 * sparse == 4_921_067_450
    # The issue's own cut, one leading dense layer: its 4,792,841,860.
    assert dataclasses.replace(cfg, num_layers=6, first_k_dense=1) \
        .num_params == head + dense + 5 * sparse == 4_792_841_860
    shapes = jax.eval_shape(lambda: xing.init_params(cfg,
                                                     jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == cfg.num_params
    small = tiny()
    assert sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: xing.init_params(small, jax.random.PRNGKey(0))))) \
        == small.num_params
    assert round(cfg.softmax_scale, 6) == round(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2, 6)
