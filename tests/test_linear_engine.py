"""Kimi-Linear's layers through the paged engine (``serve/llm_engine/
linear.py`` under ``LLMEngine``): the tokens of the same scheduler,
allocator and stream path as a dense model's, held to the plain float32
reference's own greedy continuation, float32 on both sides
(``benchmark/reference/kimi_linear_decoder.py``); ``test_kimi_linear.py``
drives the two programs by hand. One engine serves the tests that only
read it; the resume has engines of its own (a pool under pressure)."""

import numpy as np
import prefill_chunk_cases
import pytest
from kimi_tiny import BLOCK, CHUNK, ROWS, contexts_of, reference_logits, tiny

from ray_tpu.serve.llm_engine import LLMEngine


@pytest.fixture(scope="module")
def engine():
    engine = LLMEngine(tiny(), max_batch_size=ROWS, max_seq_len=64,
                       block_size=BLOCK, prefill_chunk=CHUNK, seed=11)
    yield engine
    engine.shutdown()


def greedy_by_reference(cfg, params, prompt, new_tokens):
    context = list(prompt)
    for _ in range(new_tokens):
        context.append(int(reference_logits(
            cfg, params, np.asarray(context))[-1].argmax()))
    return context[len(prompt):]


def test_the_engine_serves_the_references_greedy_tokens(engine):
    """``LLMEngine`` with the scheduler, allocator and stream path of
    every family: ragged requests batched, a step launched on the last
    one's tokens before the host read them; then a second round whose
    requests take the row slots the first round left their states in."""
    cfg = engine.config
    before = engine.engine_stats()
    for seed, lengths in ((4, [5, 13, 26]), (6, [9, 3, 18])):
        prompts = contexts_of(lengths, seed=seed)
        requests = [engine.submit(p.tolist(), max_new_tokens=10)
                    for p in prompts]
        for prompt, request in zip(prompts, requests):
            assert engine.result(request, timeout_s=300) == \
                greedy_by_reference(cfg, engine.params, prompt.tolist(), 10)
    stats = {k: v - before[k] for k, v in engine.engine_stats().items()
             if isinstance(v, int) and not isinstance(v, bool)}
    assert stats["decode_steps_ahead"] > 0
    # The step reads by row: one decode program, at the whole table.
    assert engine._widths == (4, 8, 16) and engine._step_widths == (16,)
    assert (engine._decode_step._cache_size(),
            engine._prefill_step._cache_size()) == (1, 3)
    assert stats["decode_steps_narrow"] == 0 < stats["decode_steps"]
    # A state a request: reset on its first chunk, counted.
    assert stats["state_resets"] == stats["first_tokens"] == 6
    # The latent layers' positions: whole pages and the row's own entry.
    assert 0 < stats["kv_positions_live"] < stats["kv_positions_read"] \
        < stats["kv_positions_live"] + BLOCK * stats["block_rows"]
    # The expert counters count the experts HELD: 8 expert layers of 8
    # held of 16, 3 choices a token of which about half land here.
    layer_steps = 8 * (stats["decode_steps"] + stats["prefill_chunks"])
    assert stats["expert_slots"] == 8 * layer_steps
    routed = 8 * 3 * (stats["decode_tokens"] + stats["prefill_tokens"])
    assert 0.3 * routed < stats["expert_choices"] < 0.7 * routed
    assert 0 < stats["experts_touched"] <= stats["expert_slots"]


# ------------------------------------------- (d) preempted and resumed


def test_a_preempted_request_resumes_to_the_same_tokens():
    """Cache pressure preempts with a prompt half prefilled; the request
    prefills again from position 0, its state from zero, over the
    latents' blocks it is dealt anew, and both requests end as they do
    with room. One period, sub-chunks of 64 in chunks of 128."""
    prefill_chunk_cases.resumes_to_the_same_tokens(
        tiny(num_layers=5, kda_subchunk=64))


def test_the_smoke_drives_the_family_at_its_rehearsal_size(capsys):
    """``chip_smoke.py --paged-logits`` on the cell's configuration at
    the file's rehearsal size: every row busy, contexts that end at the
    table's three widths, the chunks in the chunkwise form and the steps
    against the state, logits, expert choices and the state itself by
    layer against the plain reference. It shows that the path holds;
    the chip run holds the first layer's state under ``STATE_ERROR``."""
    import os

    import chip_smoke

    chip_smoke.phase_paged_logits(
        os.path.join(os.path.dirname(chip_smoke.__file__), "benchmark",
                     "configs", "kimi-linear-48b-a3b-serve-1chip.json"),
        2 ** 31 + 7, True, {"platform": "cpu", "kind": "cpu", "count": 1})
    out = capsys.readouterr().out
    assert "smoke[linear] check=" in out
    assert "kimi_linear_decoder" in out and "contexts=[12, 16, 32, 64]" in out
    states = out.split("state_error_by_long_context_and_layer=")[1]
    assert states.count("[") == 4         # three long contexts, by layer
    assert "expert_choices=0 " not in out
