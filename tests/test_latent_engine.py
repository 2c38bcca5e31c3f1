"""Xing4.0's layer through the paged engine (``serve/llm_engine/
latent.py`` under ``LLMEngine``): the tokens of the same scheduler,
allocator and stream path as a dense model's, held to the plain float32
reference's own greedy continuation, float32 on both sides
(``benchmark/reference/xing_decoder.py``); ``test_xing.py`` drives the
two programs by hand. One engine serves the tests that only read it; a
test has an engine of its own only where its arguments differ (a pool
under pressure, a chunk width)."""

import numpy as np
import prefill_chunk_cases
import pytest
from xing_tiny import (BLOCK, CHUNK, ROWS, contexts_of, reference_logits,
                       tiny)

from ray_tpu.serve.llm_engine import LLMEngine
from ray_tpu.serve.llm_engine import latent
from ray_tpu.serve.llm_engine import model as paged_model


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
    """The normal path: ``LLMEngine`` with the same scheduler, allocator
    and stream path as a dense model, ragged requests batched, a step
    launched on the last one's tokens before the host read them, rows
    that pass a quarter and a half of the table: the chunks that
    prefill them follow the table's three widths, the steps have the
    whole table."""
    prompts = contexts_of([5, 13, 26], seed=4)
    before = engine.engine_stats()
    chunks, prefill = [], engine._prefill_step

    def recording(params, pool, chunk, expert_stats):
        chunks.append(len(chunk) - 2 - 2 * CHUNK)   # its table's width
        return prefill(params, pool, chunk, expert_stats)

    engine.__dict__["_prefill_step"] = recording
    requests = [engine.submit(p.tolist(), max_new_tokens=10) for p in prompts]
    for prompt, request in zip(prompts, requests):
        assert engine.result(request, timeout_s=300) == greedy_by_reference(
            engine.config, engine.params, prompt.tolist(), 10)
    stats = {k: v - before[k] for k, v in engine.engine_stats().items()
             if isinstance(v, int) and not isinstance(v, bool)}
    engine.__dict__["_prefill_step"] = prefill
    assert stats["decode_steps_ahead"] > 0
    # A chunk is as wide as its request's table, of the three widths
    # the constructor built; a step reads by row and has the one.
    assert engine._widths == (4, 8, 16) and engine._step_widths == (16,)
    assert len(chunks) == stats["prefill_chunks"] and set(chunks) == {4, 8}
    assert stats["decode_steps_narrow"] == 0 < stats["decode_steps"]
    assert (engine._decode_step._cache_size(),
            prefill._cache_size()) == (1, 3)
    assert stats["decode_tokens"] == 30 - 3  # the first is prefill's
    assert 0 < stats["kv_positions_live"] < stats["kv_positions_read"]
    # The step reads by row (``ops/paged_latent_attention.py``): each
    # busy row's whole pages and its own entry, under a page over what
    # is live.
    assert stats["kv_positions_read"] < stats["kv_positions_live"] \
        + BLOCK * stats["block_rows"]
    # The expert counters: 2 expert layers of 8 experts, 3 a token.
    layer_steps = 2 * (stats["decode_steps"] + stats["prefill_chunks"])
    assert stats["expert_slots"] == 8 * layer_steps
    assert stats["expert_choices"] == 2 * 3 * (
        stats["decode_tokens"] + stats["prefill_tokens"])
    assert 0 < stats["experts_touched"] <= stats["expert_slots"]
    assert stats["expert_peak_choices"] >= stats["expert_choices"]


@pytest.mark.parametrize("prompt, live, read", [
    (7, 8 + 9, (8 + 1) + (8 + 1)),      # positions 7 and 8: two pages
    (8, 9 + 10, (8 + 1) + (12 + 1)),    # 9 starts a third
    (9, 10 + 11, (12 + 1) + (12 + 1))])
def test_a_step_counts_the_pages_its_kernel_fetches(engine, prompt, live,
                                                    read):
    """Two decode steps of one row, by hand: a step at position ``p``
    holds ``p + 1`` live positions and reads ``ceil(p / 4)`` pages of 4
    and the row's own entry (``Family.reads_by_row``; the other
    families count rows x the step's width:
    ``tests/test_table_widths.py``)."""
    before = engine.engine_stats()
    request = engine.submit(list(range(1, prompt + 1)), max_new_tokens=3)
    assert len(engine.result(request, timeout_s=300)) == 3
    after = engine.engine_stats()
    assert after["decode_steps"] - before["decode_steps"] == 2
    assert after["kv_positions_live"] - before["kv_positions_live"] == live
    assert after["kv_positions_read"] - before["kv_positions_read"] == read


def test_a_preempted_request_resumes_to_the_same_tokens():
    """Cache pressure preempts with a prompt half prefilled; the request
    prefills again from position 0 over the latents' blocks it is dealt
    anew, and both requests end as they do with room."""
    prefill_chunk_cases.resumes_to_the_same_tokens(tiny())


@pytest.mark.parametrize("chunk", prefill_chunk_cases.WIDTHS,
                         ids=prefill_chunk_cases.WIDTH_IDS)
def test_greedy_tokens_do_not_depend_on_the_chunk_width(chunk):
    prefill_chunk_cases.same_tokens_at(tiny(), chunk)


def test_the_family_follows_from_the_configuration():
    family = paged_model.family(tiny())
    assert family is latent.FAMILY and not family.recurrent
    # One token a row a pass: the step in flight is the dense model's.
    assert family.ahead is paged_model.PAGED.ahead
    assert family.lead is paged_model.PAGED.lead
    assert family.row_of is paged_model.PAGED.row_of
    assert family.pack_decode_rows is paged_model.PAGED.pack_decode_rows
    # No row owns a cache: the chunk's array carries no row slot.
    assert family.pack_prefill_chunk(4, 3, [5, 6], 8, [1, 2], 3).tolist() \
        == paged_model.PAGED.pack_prefill_chunk(4, 3, [5, 6], 8, [1, 2],
                                                3).tolist()
    # The names the benchmark's readers find the programs by.
    assert family.make_engine_decode_step(tiny(), BLOCK).__name__ \
        == "decode_step"
    assert family.make_engine_prefill_chunk(tiny(), BLOCK, CHUNK).__name__ \
        == "prefill_chunk"
