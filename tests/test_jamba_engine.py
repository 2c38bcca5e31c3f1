"""Jamba's layers through the paged engine (``serve/llm_engine/mamba.py``
under ``LLMEngine``): the tokens of the same scheduler, allocator and
stream path as a dense model's, held to the plain float32 reference's
own greedy continuation, float32 on both sides
(``benchmark/reference/jamba_decoder.py``); ``test_jamba.py`` drives the
two programs by hand. One engine serves the tests that only read it; the
resume has engines of its own (a pool under pressure)."""

import os

import numpy as np
import prefill_chunk_cases
import pytest
from jamba_tiny import BLOCK, CHUNK, ROWS, contexts_of, reference_logits, tiny

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
    every family: ragged requests batched (prompts that end inside a
    chunk and a block), a step launched on the last one's tokens before
    the host read them; then a second round whose requests take the row
    slots the first round left their states, keys and values in (a
    reused row gives a fresh engine's tokens)."""
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
    # The step reads BY ROW (``ops/paged_kv_attention.py``): ONE decode
    # program, at the whole table; the prefill chunk keeps the ladder.
    assert engine._family.reads_by_row
    assert engine._widths == (4, 8, 16) and engine._step_widths == (16,)
    assert (engine._decode_step._cache_size(),
            engine._prefill_step._cache_size()) == (1, 3)
    assert stats["decode_steps_narrow"] == 0 < stats["decode_steps"]
    # A state a request: reset on its first chunk, counted.
    assert stats["state_resets"] == stats["first_tokens"] == 6
    # The attention layers' positions: each busy row the whole pages up
    # to its own position, so never a page a row more than what is live.
    over = stats["kv_positions_read"] - stats["kv_positions_live"]
    assert 0 <= over < BLOCK * stats["decode_tokens"]
    # A dense feed-forward: no expert is counted.
    assert stats.get("expert_slots", 0) == 0


def test_a_preempted_request_resumes_to_the_same_tokens():
    """Cache pressure preempts with a prompt half prefilled; the request
    prefills again from position 0, its state from zero, over the keys'
    and values' blocks it is dealt anew, and both requests end as they
    do with room. One period, chunks of 128."""
    prefill_chunk_cases.resumes_to_the_same_tokens(
        tiny(num_layers=4, max_seq_len=384))


def test_the_smoke_drives_the_family_at_its_rehearsal_size(capsys):
    """``chip_smoke.py --paged-logits`` on the cell's configuration at
    the file's rehearsal size: every row busy, prefilled in chunks over
    shuffled tables and decoded together against the state and the
    pools read by row, logits against the plain reference. It shows that
    the path holds; the chip run holds the published widths."""
    import chip_smoke

    chip_smoke.phase_paged_logits(
        os.path.join(os.path.dirname(chip_smoke.__file__), "benchmark",
                     "configs", "jamba2-3b-serve-1chip.json"),
        2 ** 31 + 7, True, {"platform": "cpu", "kind": "cpu", "count": 1})
    out = capsys.readouterr().out
    assert "smoke[hybrid] check=" in out and "jamba_decoder" in out
    # The pools of the two attention layers, a page's positions and the
    # ONE head in one dimension; the state a row and Mamba layer.
    assert '"k": [[2, 65, 4, 16], "bfloat16"]' in out
    assert '"ssm": [[6, 4, 160, 4], "float32"]' in out
    assert '"conv": [[6, 3, 4, 160], "bfloat16"]' in out
    # The first layer's state itself against the reference's scan (a
    # rehearsal reports it; the chip holds it to its bound).
    assert "the first layer's state behind each compared context" in out
    assert "slowest_column=[0.0" in out


def test_the_smokes_second_control_keeps_the_state_in_bfloat16(capsys):
    """``--state-dtype bfloat16``: the programs' state in the precision
    below the one stated. Here it only shows that the option reaches
    the state (the run ends on the check that the control FAILED to
    fail, or passes it: either way the cache says bfloat16); what it
    read on the chip is in the configuration's ``logit_atol_why``."""
    import chip_smoke

    try:
        chip_smoke.phase_paged_logits(
            os.path.join(os.path.dirname(chip_smoke.__file__), "benchmark",
                         "configs", "jamba2-3b-serve-1chip.json"),
            2 ** 31 + 7, True, {"platform": "cpu", "kind": "cpu", "count": 1},
            None, "bfloat16")
    except SystemExit:
        pass
    out = capsys.readouterr().out
    assert '"ssm": [[6, 4, 160, 4], "bfloat16"]' in out
    assert 'control="a state in bfloat16"' in out
