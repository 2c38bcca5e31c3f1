"""Generation by diffusion over blocks (SDAR: a Qwen3-MoE layer under a
mask by blocks, a pass that yields 0 to ``block_length`` tokens a row)
through ``models/llama.py``, ``models/moe.py`` and the paged engine,
held to the plain float32 reference
``benchmark/reference/sdar_decoder.py`` at a small size on the CPU.

Logits are compared, not tokens, where a program is driven by hand
(``Passes``); the engine's tokens are then held to the reference's own
generation, float32 on both sides.

Tolerances. float32 against float32: 1e-4 absolute on logits of
standard deviation about 1 (summation order only: the paged pass
gathers a table and contracts grouped heads, the reference runs a whole
forward; PR 24's bound for the dense step). Each control changes the
mathematics and must leave that bound by an order of magnitude
(``CONTROL_MOVES``). Tokens: with float32 on both sides and the seeds
fixed here no near-tie flips an argmax or an order of confidences; a
flip would show as a failure, not pass silently.
"""

import contextlib
import dataclasses
import functools
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import prefill_chunk_cases
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import sdar_decoder as reference  # noqa: E402
from ray_tpu.models import llama, moe  # noqa: E402
from ray_tpu.serve.llm_engine import LLMEngine, PagedKVCache  # noqa: E402
from ray_tpu.serve.llm_engine import model as paged_model  # noqa: E402
from ray_tpu.serve.llm_engine.scheduler import MASKED  # noqa: E402

F32_ATOL = 1e-4
CONTROL_MOVES = 1e-3
BLOCK, CHUNK, ROWS, SIZE, MASK = 8, 8, 4, 4, 255


def small(**changes) -> llama.LlamaConfig:
    """2 layers, 8 experts of which 3 a token renormalised, 4 heads over
    2 key-value heads, QK-norm a head, blocks of 4."""
    return dataclasses.replace(llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=64,
        remat=False, dtype=jnp.float32, num_experts=8, experts_per_token=3,
        norm_topk_prob=True, qk_norm="head", block_length=SIZE,
        mask_token_id=MASK, denoising_steps=2), **changes)


def hf_keys(cfg: llama.LlamaConfig, **over) -> dict:
    """What the reference is given: the configuration file's numbers."""
    return {"rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.experts_per_token,
            "block_length": cfg.block_length,
            "denoising_steps": cfg.denoising_steps,
            "mask_token_id": cfg.mask_token_id, **over}


def weights(cfg, seed: int = 7) -> dict:
    """Seeded weights with every norm scale scattered around one (all
    ones would hide a scale applied to the wrong axis)."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    layers = dict(params["layers"])
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        scale = 1.0 + 0.2 * rng.standard_normal(layers[name].shape)
        layers[name] = jnp.asarray(scale, layers[name].dtype)
    return {**params, "layers": layers}


def prompt_of(length: int, seed: int = 1) -> list:
    return np.random.default_rng([seed, length]).integers(
        1, MASK, length).tolist()


CFG = small()
PARAMS = weights(CFG)


# ------------------------------------------------------------ the reference


def test_the_reference_masks_by_blocks():
    tokens = np.asarray([prompt_of(12)])
    base = np.asarray(reference.block_forward(PARAMS, tokens, hf_keys(CFG)))
    later = tokens.copy()
    later[0, 9] = 77           # a token of the third block
    moved = np.asarray(reference.block_forward(PARAMS, later, hf_keys(CFG)))
    # The blocks before it see nothing of it; every position of its own
    # block does, those to its left too.
    np.testing.assert_array_equal(moved[0, :8], base[0, :8])
    assert (np.abs(moved[0, 8:] - base[0, 8:]).max(axis=-1) > 1e-3).all()


@pytest.mark.parametrize("steps", [1, 2, 3, 4])
def test_forward_replays_the_logits_generate_recorded(steps):
    model = hf_keys(CFG, denoising_steps=steps)
    prompt, record = prompt_of(8), {}
    out = reference.generate(PARAMS, prompt, 11, model, record=record)
    assert len(out) == 11 and MASK not in out
    row = prompt + out[:-1]                  # as the harness builds it
    padded = np.zeros((1, 32), np.int32)
    padded[0, :len(row)] = row
    replayed = np.asarray(reference.forward(PARAMS, padded, model))[0]
    kept = np.arange(CFG.vocab_size) != MASK
    for j in range(len(out)):
        position = len(prompt) + j
        np.testing.assert_allclose(
            replayed[position - 1][kept], record[position][kept],
            atol=F32_ATOL)
        assert replayed[position - 1].argmax() == out[j]


@pytest.mark.parametrize("steps, counts", [
    (1, [4]), (2, [2, 2]), (3, [2, 1, 1]), (4, [1, 1, 1, 1]),
    (9, [1, 1, 1, 1]), (0, [4])])
def test_the_schedule_spreads_a_block_over_its_passes(steps, counts):
    for fix_count in (paged_model.fix_count, reference.fix_count):
        assert [fix_count(SIZE, steps, t)
                for t in range(len(counts))] == counts


# --------------------------------------------- the passes, driven by hand


class Passes:
    """The engine's two programs' forward on one row's table, driven as
    the engine drives it: whole blocks prefilled chunk by chunk, then
    the block in flight a pass at a time, each pass's logits shown."""

    def __init__(self, cfg, params, width: int = 8):
        self.cfg, self.params = cfg, params
        self.pool = PagedKVCache.init_pool(cfg, 1 + width, BLOCK)
        self.table = np.arange(1, 1 + width, dtype=np.int32)[None]
        self.prefill = paged_model.make_prefill_chunk(cfg, BLOCK)
        self.shown = jax.jit(
            lambda params, pool, tokens, positions, table:
            # A block pass gathers, also under the control's causal
            # configuration, whose own family would read by row.
            paged_model._forward_paged(params, pool, tokens, positions,
                                       table, cfg, BLOCK, by_row=False)[:2],
            donate_argnums=(1,))

    def prefilled(self, context: list) -> "Passes":
        for start in range(0, len(context), CHUNK):
            n = min(CHUNK, len(context) - start)
            tokens = np.zeros((1, CHUNK), np.int32)
            tokens[0, :n] = context[start:start + n]
            positions = np.zeros((1, CHUNK), np.int32)
            positions[0, :n] = np.arange(start, start + n)
            _, self.pool, _ = self.prefill(
                self.params, self.pool, jnp.asarray(tokens),
                jnp.asarray(positions), jnp.asarray(self.table),
                np.int32(n), np.int32(n - 1))
        return self

    def run(self, block: list, start: int) -> np.ndarray:
        """One pass of ``block`` (``None``: masked) at ``start``: the
        logits [block, vocab]; its keys and values are written."""
        tokens = [self.cfg.mask_token_id if t is None else t for t in block]
        logits, self.pool = self.shown(
            self.params, self.pool, jnp.asarray([tokens], jnp.int32),
            jnp.asarray([np.arange(start, start + len(block))], jnp.int32),
            jnp.asarray(self.table))
        return np.asarray(logits[0])


def worst_difference(cfg, params, prompt, new_tokens, steps,
                     finishing: bool = True, reference_params=None) -> float:
    """The largest difference between a hand-driven pass's logits and
    the logits the reference recorded for the positions that pass fixed
    (``sequential``; the reference's tokens are fed back, so one flip
    would not run away). The mask's own logit is left out."""
    model, record = hf_keys(CFG, denoising_steps=steps), {}
    out = reference.generate(reference_params or PARAMS, prompt, new_tokens,
                             model, record=record)
    known = prompt + out
    passes = Passes(cfg, params).prefilled(prompt[:len(prompt) // 4 * 4])
    kept = np.arange(cfg.vocab_size) != MASK
    worst = 0.0
    for start in range(len(prompt) // 4 * 4, len(known), SIZE):
        final = known[start:start + SIZE]
        block = [t if start + i < len(prompt) else None
                 for i, t in enumerate(final)]
        done = 0
        while None in block:
            logits = passes.run(block, start)
            masked = [i for i, t in enumerate(block) if t is None]
            for i in masked[:paged_model.fix_count(SIZE, steps, done)]:
                worst = max(worst, float(np.abs(
                    logits[i] - record[start + i])[kept].max()))
                block[i] = final[i]
            done += 1
        if finishing:
            passes.run(block, start)
    return worst


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("prompt_length", [8, 6, 3, 19])
def test_the_passes_give_the_references_logits(prompt_length, steps):
    """Prompts with and without a remainder modulo the block, shorter
    than a block, and past a chunk; three whole generated blocks."""
    new_tokens = 12 - prompt_length % 4
    assert worst_difference(CFG, PARAMS, prompt_of(prompt_length),
                            new_tokens, steps) < F32_ATOL


def whole_projection_norm(params):
    """The same scales, laid out for a norm over the whole projection."""
    layers = dict(params["layers"])
    layers["q_norm"] = jnp.repeat(layers["q_norm"][:, None], CFG.num_heads, 1)
    layers["k_norm"] = jnp.repeat(layers["k_norm"][:, None],
                                  CFG.num_kv_heads, 1)
    return {**params, "layers": layers}


def bf16_route(x, w_router, experts_per_token, norm_topk_prob=False):
    return ROUTE(x.astype(jnp.bfloat16), w_router.astype(jnp.bfloat16),
                 experts_per_token, norm_topk_prob)


ROUTE = moe.route
CONTROLS = {
    "a-causal-mask": dict(cfg=small(block_length=0)),
    "no-finishing-pass": dict(finishing=False),
    "weights-not-renormalised": dict(cfg=small(norm_topk_prob=False)),
    "qk-norm-over-the-whole-projection": dict(
        cfg=small(qk_norm=True), params=whole_projection_norm(PARAMS)),
    "a-bf16-router": dict(route=bf16_route),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_leaves_the_tolerance(control, monkeypatch):
    """What must fail: each changes one piece of the mathematics in the
    program and keeps the reference as it is."""
    change = dict(CONTROLS[control])
    if "route" in change:
        monkeypatch.setattr(moe, "route", change.pop("route"))
    moved = worst_difference(change.pop("cfg", CFG),
                             change.pop("params", PARAMS), prompt_of(8), 12,
                             steps=2, **change)
    assert moved > CONTROL_MOVES, (control, moved)


# ---------------------------------------------------------- the denoising


def confident(best: list, confidence: list) -> np.ndarray:
    """Logits [1, block, vocab] whose argmax at position i is
    ``best[i]`` with about ``confidence[i]`` of the probability."""
    logits = np.zeros((1, len(best), 16), np.float32)
    for i, (token, c) in enumerate(zip(best, confidence)):
        logits[0, i, token] = np.log(c / (1 - c) * 14)  # 14 others at 0
    return logits


@pytest.mark.parametrize("rule, fix, block, want", [
    ("sequential", 2, [MASKED] * 4, [3, 4, MASKED, MASKED]),
    ("sequential", 2, [9, MASKED, 8, MASKED], [9, 4, 8, 6]),
    ("sequential", 0, [9, 7, 8, 2], [9, 7, 8, 2]),
    ("low_confidence_static", 2, [MASKED] * 4, [MASKED, 4, MASKED, 6]),
    ("low_confidence_static", 1, [MASKED, 1, MASKED, 2], [MASKED, 1, 5, 2]),
    ("low_confidence_dynamic", 1, [MASKED] * 4, [MASKED, 4, MASKED, 6]),
    ("low_confidence_dynamic", 3, [MASKED] * 4, [MASKED, 4, 5, 6]),
    ("low_confidence_dynamic", 1, [MASKED, 1, MASKED, 2], [MASKED, 1, 5, 2]),
])
def test_a_pass_fixes_what_its_rule_says(rule, fix, block, want):
    """Confidences 0.3, 0.95, 0.5, 0.92 for the tokens 3, 4, 5, 6; the
    dynamic rule's threshold is 0.9. Token 15 is the mask here, and the
    most likely at position 0, where it is never taken."""
    logits = confident([3, 4, 5, 6], [0.3, 0.95, 0.5, 0.92])
    logits[0, 0, 15] = 50.0
    got = paged_model.denoise(
        jnp.asarray(logits), jnp.asarray([block]), jnp.asarray([fix]),
        jnp.asarray([paged_model.REMASKING.index(rule)]),
        jnp.asarray([0.9], jnp.float32), jnp.zeros((1,), jnp.float32),
        jax.random.PRNGKey(0), mask_id=15)
    assert np.asarray(got)[0].tolist() == want


def test_a_draw_at_a_temperature_is_never_the_mask():
    logits = jnp.zeros((2, 4, 16)).at[..., 15].set(8.0)
    got = paged_model.denoise(
        logits, jnp.full((2, 4), MASKED), jnp.asarray([4, 4]),
        jnp.asarray([0, 1]), jnp.asarray([0.9, 0.9], jnp.float32),
        jnp.asarray([1.0, 5.0], jnp.float32), jax.random.PRNGKey(3),
        mask_id=15)
    assert ((np.asarray(got) >= 0) & (np.asarray(got) < 15)).all()


# ------------------------------------------------------- through the engine


def make_engine(cfg=CFG, params=PARAMS, **kwargs):
    kwargs = {"max_batch_size": ROWS, "max_seq_len": 64, "block_size": BLOCK,
              "prefill_chunk": CHUNK, "seed": 3, **kwargs}
    return LLMEngine(cfg, params, **kwargs)


@pytest.fixture(scope="module")
def engine():
    engine = make_engine()
    yield engine
    engine.shutdown()


def by_reference(prompt, new_tokens, **schedule):
    return reference.generate(PARAMS, prompt, new_tokens, hf_keys(CFG),
                              **schedule)


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("rule", paged_model.REMASKING)
@pytest.mark.parametrize("prompt_length", [8, 6, 3])
def test_the_engine_serves_what_the_reference_generates(
        engine, prompt_length, rule, steps):
    """Every rule and schedule; prompts with and without a remainder
    modulo the block and shorter than one; an answer that ends inside a
    block (10 tokens, and the remainder shifts the blocks)."""
    prompt = prompt_of(prompt_length, seed=5)
    got = engine.result(engine.submit(
        prompt, max_new_tokens=10, remasking=rule, denoising_steps=steps),
        timeout_s=300)
    assert got == by_reference(prompt, 10, remasking=rule,
                               denoising_steps=steps)
    assert len(got) == 10 and MASK not in got


@pytest.mark.parametrize("chunk", prefill_chunk_cases.WIDTHS,
                         ids=prefill_chunk_cases.WIDTH_IDS)
def test_greedy_tokens_do_not_depend_on_the_chunk_width(chunk):
    """A chunk holds whole blocks at every width (8, 32 and the
    default's 128 are multiples of 4), and a prompt's remainder opens
    the block in flight: the same tokens however the prompt went in."""
    prefill_chunk_cases.same_tokens_at(CFG, chunk)


def test_a_preemption_inside_a_wide_chunks_prompt_resumes_exact():
    prefill_chunk_cases.resumes_to_the_same_tokens(CFG)


def record_passes(engine) -> list:
    """The host array of every pass the loop runs from now on."""
    step, seen = engine._decode_step, []

    def recording(params, pool, rows, key, expert_stats, prev):
        seen.append(np.array(rows))
        return step(params, pool, rows, key, expert_stats, prev)

    engine.__dict__["_decode_step"] = recording
    return seen


SCHEDULES = [(8, 16, "sequential", 2), (5, 14, "low_confidence_static", 4),
             (2, 13, "low_confidence_dynamic", 1), (19, 12, "sequential", 3)]


def test_rows_in_every_phase_share_one_pass():
    engine = make_engine()
    try:
        seen = record_passes(engine)
        requests = [engine.submit(
            prompt_of(n, seed=9), max_new_tokens=new, remasking=rule,
            denoising_steps=steps) for n, new, rule, steps in SCHEDULES]
        got = [engine.result(r, timeout_s=300) for r in requests]
        stats = engine.engine_stats()
    finally:
        engine.shutdown()
    assert got == [by_reference(prompt_of(n, seed=9), new, remasking=rule,
                                denoising_steps=steps)
                   for n, new, rule, steps in SCHEDULES]
    # A row's phase; negative on the block the pass before left.
    phases = [np.abs(rows[:, 5]) for rows in seen]
    assert any({1, 2} <= set(p.tolist()) for p in phases), \
        "no pass mixed the phases"
    assert any((rows[:, 5] < 0).any() and (rows[:, 5] > 0).any()
               for rows in seen), "no pass mixed blocks from both sides"
    rules = [set(rows[p > 0, 3].tolist()) for rows, p in zip(seen, phases)]
    assert any(len(r) > 1 for r in rules), "no pass mixed the rules"
    assert engine._family.make_engine_decode_step \
        is paged_model.make_engine_block_step
    assert stats["decode_tokens"] == sum(s[1] for s in SCHEDULES)
    assert stats["block_rows"] == sum((p > 0).sum() for p in phases)
    assert stats["commit_rows"] == sum((p == 2).sum() for p in phases)


PRESSED_PROMPTS = [prompt_of(n, seed=13) for n in (3, 5, 2, 4)]


@functools.lru_cache(maxsize=None)
def served_under_pressure():
    """Four requests of 14 tokens from four threads through an engine
    of 7 paged blocks, once for the tests that read it: their answers,
    the counters, and of every victim at its preemption whether its
    block was half made and the step that was in flight unread."""
    pressed = make_engine(num_blocks=7)
    victims = []
    preempt = pressed._sched.preempt

    def watching(victim):
        victims.append((victim.passes > 0 or MASKED not in victim.block,
                        pressed._unread))
        preempt(victim)

    pressed._sched.preempt = watching
    try:
        results = {}

        def generate(i):
            results[i] = pressed.result(pressed.submit(
                PRESSED_PROMPTS[i], max_new_tokens=14), timeout_s=300)

        threads = [threading.Thread(target=generate, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = pressed.engine_stats()
    finally:
        pressed.shutdown()
    return [results[i] for i in range(4)], stats, victims


def test_a_row_preempted_mid_block_resumes_to_the_same_tokens(engine):
    """Cache pressure preempts rows whose block is half made: it is
    thrown away, the prompt and the whole blocks emitted are prefilled
    again, and the answer is the unpressed engine's."""
    want = [engine.result(engine.submit(p, max_new_tokens=14),
                          timeout_s=300) for p in PRESSED_PROMPTS]
    got, stats, victims = served_under_pressure()
    assert stats["preemptions"] > 0 and stats["resumes"] > 0, stats
    assert any(half_made for half_made, _ in victims), \
        "no victim was inside a block"
    assert got == want


def test_answers_do_not_depend_on_the_tables_rung():
    """A table of 8 paged blocks has the widths 2, 4 and 8 (16, 32 and
    64 positions): a row that grows through all three answers as one
    held to the whole width, and as the reference."""
    requests = [(prompt_of(6, seed=2), 40), (prompt_of(13, seed=2), 9)]

    def serve(pin_whole: bool):
        engine = make_engine()
        if pin_whole:
            engine._widths = engine._step_widths = (engine.blocks_per_seq,)
        try:
            seen = record_passes(engine)
            submitted = [engine.submit(p, max_new_tokens=n)
                         for p, n in requests]
            got = [engine.result(r, timeout_s=300) for r in submitted]
            return got, {r.shape[1] - 6 - SIZE for r in seen}, \
                engine.engine_stats()
        finally:
            engine.shutdown()

    got, widths, stats = serve(False)
    whole, whole_widths, _ = serve(True)
    assert widths == {2, 4, 8} and whole_widths == {8}
    assert got == whole == [by_reference(p, n) for p, n in requests]
    assert 0 < stats["decode_steps_narrow"] < stats["decode_steps"]
    assert stats["kv_positions_live"] < stats["kv_positions_read"]


def test_the_counters_tell_rows_from_tokens():
    """One request, two passes a block: three blocks of four tokens are
    two denoising passes each and a finishing pass after the first two
    (the last block ends the request, nothing reads its keys)."""
    engine = make_engine()
    try:
        delivered = []
        deliver = engine._deliver_locked
        engine._deliver_locked = lambda req, tokens: (
            delivered.append(list(tokens)), deliver(req, tokens))[1]
        req = engine.submit(prompt_of(8), max_new_tokens=12, stream=True)
        batches = list(engine.stream_token_batches(req))
        stats = engine.engine_stats()
    finally:
        engine.shutdown()
    assert [t for b in batches for t in b] == by_reference(prompt_of(8), 12)
    assert stats["decode_steps"] == stats["block_rows"] == 8
    assert stats["commit_rows"] == 2 and stats["decode_tokens"] == 12
    assert stats["prefill_tokens"] == 8 and stats["first_tokens"] == 1
    assert stats["host_calls"] == 2 * 8 + 1   # a call and a read a pass
    # Prefill yields nothing; a stream sees whole blocks.
    assert [len(d) for d in delivered if d] == [4, 4, 4]
    assert delivered[0] == [] and req.first_token_ns > req.claimed_ns
    # 8 experts on offer a layer, 3 choices a token, 4 tokens a pass.
    assert stats["expert_choices"] == (8 + 8 * 4) * 3 * CFG.num_layers


def test_the_masks_id_is_never_served_and_no_row_waits_on_it():
    """A head that likes the mask's id best at every position: the
    bookkeeping, not a comparison with the id, says what is fixed."""
    params = {**PARAMS, "lm_head": PARAMS["lm_head"].at[:, MASK].set(
        PARAMS["lm_head"][:, 7] * 3)}
    engine = make_engine(params=params)
    try:
        got = [engine.result(engine.submit(
            prompt_of(5), max_new_tokens=9, remasking=rule), timeout_s=120)
            for rule in paged_model.REMASKING]
    finally:
        engine.shutdown()
    assert all(len(g) == 9 and MASK not in g for g in got)
    assert got[0] == reference.generate(params, prompt_of(5), 9,
                                        hf_keys(CFG))


def test_the_family_follows_from_the_configuration():
    family = paged_model.family(CFG)
    assert family is paged_model.family(small(denoising_steps=4))
    assert family is not paged_model.PAGED
    assert family.make_engine_prefill_chunk \
        is paged_model.PAGED.make_engine_prefill_chunk
    assert paged_model.family(llama.LlamaConfig.tiny()) is paged_model.PAGED
    assert family.pack_decode_rows(2, 3, ()).shape == (2, 6 + SIZE + 3)


def test_what_cannot_be_served_is_refused(engine):
    with pytest.raises(ValueError, match="remasking"):
        engine.submit([1, 2], remasking="by_entropy")
    with pytest.raises(ValueError, match="must divide"):
        LLMEngine(small(block_length=3), PARAMS, max_batch_size=2,
                  max_seq_len=32, block_size=8, prefill_chunk=8)


def test_parameters_at_the_published_widths_count_as_the_issue_counts():
    published = llama.LlamaConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=768,
        num_layers=7, num_heads=32, num_kv_heads=4, head_dim=128,
        num_experts=128, experts_per_token=8, norm_topk_prob=True,
        qk_norm="head", block_length=4)
    assert published.num_params == 4_984_176_384
    shapes = jax.eval_shape(
        lambda: llama.init_params(published, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == published.num_params
    assert shapes["layers"]["q_norm"].shape == (7, 128)


def test_a_random_routers_scale_moves_margins_and_weights_not_choices():
    """``router_init_scale``: a random router's logits on a unit-RMS
    input have that standard deviation; the same key draws the same
    router but for the scale, so for the same input the experts chosen
    are the same and only how decided the choice is changes (a later
    layer's input, and so its choice, then differs)."""
    cfg = small(hidden_size=256, num_experts=16, experts_per_token=4)
    routers = [llama.init_params(dataclasses.replace(
        cfg, router_init_scale=scale), jax.random.PRNGKey(2))["layers"]
        ["w_router"][0] for scale in (1.0, 4.0)]
    np.testing.assert_allclose(np.asarray(routers[1]),
                               4.0 * np.asarray(routers[0]), rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 512, 256))
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))
    routed = [moe.route(x, w, 4, True) for w in routers]
    assert float((x[0] @ routers[0]).std()) == pytest.approx(1.0, rel=0.1)
    assert float((x[0] @ routers[1]).std()) == pytest.approx(4.0, rel=0.1)
    np.testing.assert_array_equal(np.asarray(routed[0][1]),
                                  np.asarray(routed[1][1]))
    # The first of four renormalised weights: nearer a quarter at 1.
    assert float(routed[0][2][..., 0].mean()) < 0.45 \
        < float(routed[1][2][..., 0].mean())


# ----------------------- the shared emit path: the others' streams as before

# What each autoregressive family's engine answered on the parent commit
# (seed 5, float32, blocks of 4, chunks of 8): three greedy requests and
# one at temperature 0.8, each alone so that the key's splits are the
# same in every run, then four greedy ones together under cache pressure
# (preempted and resumed).
REQUESTS = [([5, 6, 7], 9, 0.0), (list(range(1, 20)), 6, 0.0), ([9], 1, 0.0),
            ([3, 1, 4, 1, 5], 7, 0.8)]
PRESSED = ([3, 5, 2], [8, 1, 1, 2, 4], [2, 7], [4, 4, 4, 4])
AS_BEFORE = {
    "dense": (
        [[20, 136, 133, 133, 142, 94, 50, 62, 162],
         [220, 16, 62, 0, 62, 224], [212],
         [241, 142, 180, 219, 203, 204, 157]],
        [[15, 229, 229, 136, 146, 93, 229, 136, 146, 7, 44, 136],
         [196, 196, 196, 196, 62, 196, 79, 196, 79, 24, 79, 24],
         [20, 20, 103, 50, 172, 103, 147, 240, 103, 147, 240, 224],
         [62, 133, 142, 62, 224, 142, 32, 142, 172, 142, 27, 32]]),
    "olmoe": (
        [[20, 50, 246, 166, 50, 62, 142, 142, 142],
         [62, 224, 133, 32, 90, 196], [20],
         [254, 142, 180, 32, 203, 204, 157]],
        [[15, 184, 15, 55, 75, 75, 75, 75, 146, 209, 103, 246],
         [128, 103, 240, 72, 96, 62, 255, 65, 240, 255, 255, 209],
         [133, 240, 218, 141, 75, 75, 75, 36, 69, 248, 21, 230],
         [62, 62, 142, 62, 142, 142, 142, 142, 142, 142, 142, 32]]),
    "hybrid": (
        [[11, 193, 191, 157, 10, 80, 50, 237, 125],
         [133, 165, 0, 225, 240, 50], [67],
         [241, 144, 180, 222, 203, 67, 106]],
        [[180, 173, 59, 211, 150, 86, 145, 145, 118, 79, 4, 182],
         [181, 74, 52, 50, 136, 136, 136, 24, 136, 213, 185, 237],
         [19, 200, 198, 228, 147, 125, 183, 224, 39, 136, 248, 174],
         [30, 137, 137, 91, 209, 166, 113, 123, 34, 11, 185, 140]]),
}


def autoregressive(family: str):
    from ray_tpu.models import phi4flash

    if family == "hybrid":
        return phi4flash.Phi4FlashConfig.tiny(dtype=jnp.float32)
    if family == "olmoe":
        return llama.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=32,
            num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
            max_seq_len=64, remat=False, dtype=jnp.float32, num_experts=8,
            experts_per_token=3, qk_norm=True)
    return dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)


@pytest.mark.parametrize("family", sorted(AS_BEFORE))
def test_the_other_families_streams_are_what_they_were(family):
    alone, together = AS_BEFORE[family]
    sizes = dict(max_batch_size=4, max_seq_len=64, block_size=4,
                 prefill_chunk=8, seed=5)
    engine = LLMEngine(autoregressive(family), **sizes)
    try:
        got = [list(engine.stream_tokens(engine.submit(
            prompt, max_new_tokens=new, temperature=temperature,
            stream=True))) for prompt, new, temperature in REQUESTS]
        stats = engine.engine_stats()
    finally:
        engine.shutdown()
    assert got == alone
    # One token a row a step: the new counters say the old thing.
    assert stats["decode_tokens"] == stats["block_rows"] \
        == sum(len(g) - 1 for g in got)
    assert stats["commit_rows"] == 0 and stats["first_tokens"] == 4
    engine = LLMEngine(autoregressive(family), num_blocks=11, **sizes)
    try:
        requests = [engine.submit(p, max_new_tokens=12) for p in PRESSED]
        got = [engine.result(r, timeout_s=300) for r in requests]
        assert engine.engine_stats()["preemptions"] > 0
    finally:
        engine.shutdown()
    assert got == together


@contextlib.contextmanager
def reading_first(engine):
    """The engine as it was before this family kept a pass ahead: its
    family says of every row that its next pass is not known early."""
    family = engine._family
    engine._family = dataclasses.replace(family, ahead=lambda req: False)
    try:
        yield
    finally:
        engine._family = family


def serve_watched(engine, schedules, seed=9):
    """The schedules' streams, served together; the step in flight at
    each launch (None: none); the counters' rise."""
    step, in_flight = engine._decode_step, []

    def watching(*args):
        in_flight.append(engine._unread)
        return step(*args)

    before = engine.engine_stats()
    engine.__dict__["_decode_step"] = watching
    try:
        with engine._lock:     # its loop meets them all at once
            requests = [engine.submit(
                prompt_of(n, seed=seed), max_new_tokens=new, remasking=rule,
                denoising_steps=steps) for n, new, rule, steps in schedules]
        got = [engine.result(r, timeout_s=300) for r in requests]
        assert engine._unread is None  # nothing left in flight
    finally:
        engine.__dict__["_decode_step"] = step
    after = engine.engine_stats()
    return got, in_flight, {k: after[k] - before[k] for k in after}


COUNTED = [(8, 16, "sequential", 2), (5, 14, "low_confidence_static", 4),
           (2, 13, "low_confidence_static", 1), (19, 12, "sequential", 3)]


@pytest.mark.parametrize("case", ["every-rule", "counted", "dynamic",
                                  "pressed"])
def test_the_block_family_keeps_a_pass_ahead(engine, case):
    """What a pass needs of the one before it is the block, which lies
    on the device, and counts the host has: the one loop launches pass
    N+1 before it reads pass N, and the streams are the reference's and
    those of an engine that reads every pass first. A denoising pass
    under the dynamic rule fixes as many positions as pass its
    threshold, so a row on one makes the loop read first; so does cache
    pressure, which rebuilds a victim from what was emitted."""
    if case == "pressed":
        got, stats, victims = served_under_pressure()
        assert got == [by_reference(p, 14) for p in PRESSED_PROMPTS]
        assert len(victims) == stats["preemptions"] > 0
        assert [unread for _, unread in victims] == [None] * len(victims)
        assert 0 < stats["decode_steps_ahead"] < stats["decode_steps"]
        return
    schedules = {"every-rule": SCHEDULES, "counted": COUNTED,
                 "dynamic": [(6, 12, "low_confidence_dynamic", 4),
                             (3, 16, "sequential", 2)]}[case]
    got, in_flight, stats = serve_watched(engine, schedules)
    with reading_first(engine):
        at_once, none_in_flight, stats_at_once = serve_watched(
            engine, schedules)
    assert got == at_once == [
        by_reference(prompt_of(n, seed=9), new, remasking=rule,
                     denoising_steps=steps)
        for n, new, rule, steps in schedules]
    steps, ahead = stats["decode_steps"], stats["decode_steps_ahead"]
    assert steps == len(in_flight) > 10
    assert ahead == sum(step is not None for step in in_flight)
    assert none_in_flight == [None] * stats_at_once["decode_steps"]
    assert stats_at_once["decode_steps_ahead"] == 0
    # The same passes for every row, whoever read them when.
    for key in ("decode_tokens", "block_rows", "commit_rows"):
        assert stats[key] == stats_at_once[key], key
    if case == "counted":
        # Every pass but the first few (no pass before them, or only
        # rows that joined since) went ahead.
        assert steps - 4 <= ahead < steps
    elif case == "dynamic":
        # Four denoising passes a block under the dynamic rule, each
        # read before the next is packed; the finishing passes and the
        # other row's tail go ahead.
        assert 0 < ahead <= steps - 4 * 3
    else:
        assert 0 < ahead < steps


@pytest.mark.parametrize("rule", paged_model.REMASKING[:2])
@pytest.mark.parametrize("steps", [1, 2, 3, 4, 9])
@pytest.mark.parametrize("size", [2, 4, 8])
def test_the_row_one_pass_on_is_known_by_counting(size, steps, rule):
    """No engine: for prompts that leave 0 to ``size - 1`` known
    positions in the first block, answers that end inside a block, at
    its end and at the table's, every pass of the request: the row
    ``block_row_of(req, True)`` tells before the pass is read is the
    row ``block_row_of(req)`` tells after ``advance_block`` on what a
    pass could have made, but for the block, which stays on the device;
    and ``block_lead`` says the row ends where ``_deliver_locked``
    then does, and else where it moved."""
    import types

    from ray_tpu.serve.llm_engine.scheduler import EngineRequest, Scheduler

    max_tokens = 6 * size
    engine = types.SimpleNamespace(max_tokens=max_tokens, _counters={
        "first_tokens": 0, "queue_wait_us": 0, "prefill_us": 0})
    rng = np.random.default_rng([size, steps])
    sched = Scheduler(PagedKVCache(4, size, 6), 1, 4, max_tokens, size)
    passes = 0
    for known in range(size):
        for new in (1, size - known, size - known + 1, 2 * size + 1,
                    max_tokens):
            req = EngineRequest(
                list(range(1, 1 + size + known)), new, 0.0,
                denoising_steps=steps, remasking=rule)
            sched.waiting.append(req)
            assert sched.claim_prefill() is req
            sched.prefilling, req.position = None, len(req.context)
            sched.release(req)
            over = False
            while not over:
                assert paged_model.block_counts(req)
                told = paged_model.block_row_of(req, True)
                lead = paged_model.block_lead(req, max_tokens)
                position = req.position
                # What a pass could have made: ``fix`` of the masked
                # positions fixed, whichever the rule picked.
                block, _, _, fix, *_ = paged_model.block_row_of(req)
                masked = [i for i, t in enumerate(block) if t == MASKED]
                fixed = rng.permutation(masked)[:fix] if rule != "sequential" \
                    else masked[:fix]
                out = [7 if i in fixed else t for i, t in enumerate(block)]
                tokens, committed = paged_model.advance_block(req, out)
                assert committed == (not masked)
                over = LLMEngine._deliver_locked(engine, req, tokens)
                passes += 1
                assert over == (lead is None), (known, new, req.block)
                if over:
                    break
                assert req.position == position + lead
                after = paged_model.block_row_of(req)
                assert told[1:] == after[1:]
                # A fresh block is packed; one a pass left is not.
                assert told[0] == (after[0] if committed else None)
                # Packed, the two differ by the phase's sign and the
                # block's columns alone.
                ahead, read = (paged_model.pack_block_rows(size, 1, 6, [row])
                               for row in (told, after))
                if not committed:
                    assert ahead[0, 5] == -read[0, 5]
                    assert not ahead[0, 6:6 + size].any()
                    ahead[0, 5:6 + size] = read[0, 5:6 + size]
                np.testing.assert_array_equal(ahead, read)
    assert passes > 5 * size


def test_a_row_on_the_block_before_takes_it_from_prev():
    """``pack_block_rows`` marks such a row by a negative phase and
    leaves its block's columns zero; the program runs it on its row of
    ``prev`` and every other row on the array's, and without ``prev``
    (as ``benchmark/sizing_family.py`` lowers it) on the array's alone."""
    table = [1, 2]
    known = ([5, MASKED, 9, MASKED], 8, 0.0, 1, 0, 0.9, table)
    rows = paged_model.pack_block_rows(
        SIZE, 3, 2, [known, (None, 8, 0.0, 1, 0, 0.9, table),
                     (None, 8, 0.0, 0, 0, 0.9, table)])
    assert rows[:, 5].tolist() == [1, -1, -2]
    assert not rows[1:, 6:6 + SIZE].any()
    step = paged_model.make_engine_block_step(CFG, BLOCK)
    key = jax.random.PRNGKey(0)
    prev = jnp.asarray([[1, 2, 3, 4], [5, MASKED, 9, MASKED], [5, 6, 9, 3]])

    def run(rows, *prev):
        pool = PagedKVCache.init_pool(CFG, 3, BLOCK)
        return np.asarray(step(PARAMS, pool, rows, key, None, *prev)[0])

    after = run(rows, prev)
    host = paged_model.pack_block_rows(SIZE, 3, 2, [
        known, known, ([5, 6, 9, 3], 8, 0.0, 0, 0, 0.9, table)])
    np.testing.assert_array_equal(after, run(host))
    np.testing.assert_array_equal(after, run(host, prev))
    assert after[0].tolist() == after[1].tolist() != known[0]
    assert after[2].tolist() == [5, 6, 9, 3]
