"""Solar-Open2's layers through the paged engine (``serve/llm_engine/
linear.py`` under ``LLMEngine``): the tokens of the same scheduler,
allocator and stream path as a dense model's, held to the plain float32
reference's own greedy continuation, float32 on both sides
(``benchmark/reference/solar_open2_decoder.py``); ``test_solar_open2.py``
drives the two programs by hand. One engine serves the tests that only
read it; the resume has engines of its own (a pool under pressure)."""

import numpy as np
import prefill_chunk_cases
import pytest
from solar_tiny import BLOCK, CHUNK, ROWS, contexts_of, reference_logits, tiny

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
    requests take the row slots the first round left their states,
    keys and values in (a reused row gives a fresh engine's tokens)."""
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
    # The full layers' positions: each busy row the whole pages up to
    # its own position, so never a page a row more than what is live.
    over = stats["kv_positions_read"] - stats["kv_positions_live"]
    assert 0 <= over < BLOCK * stats["decode_tokens"]
    # The expert counters count the experts HELD: 8 layers of 8 held of
    # 16, 3 choices a token of which about half land here.
    layer_steps = 8 * (stats["decode_steps"] + stats["prefill_chunks"])
    assert stats["expert_slots"] == 8 * layer_steps
    routed = 8 * 3 * (stats["decode_tokens"] + stats["prefill_tokens"])
    assert 0.3 * routed < stats["expert_choices"] < 0.7 * routed
    assert 0 < stats["experts_touched"] <= stats["expert_slots"]


# ------------------------------------------- (d) preempted and resumed


def test_a_preempted_request_resumes_to_the_same_tokens():
    """Cache pressure preempts with a prompt half prefilled; the request
    prefills again from position 0, its state from zero, over the keys'
    and values' blocks it is dealt anew, and both requests end as they
    do with room. One period, sub-chunks of 64 in chunks of 128."""
    prefill_chunk_cases.resumes_to_the_same_tokens(
        tiny(num_layers=4, kda_subchunk=64))


def test_the_smoke_drives_the_family_at_its_rehearsal_size(capsys):
    """``chip_smoke.py --paged-logits`` on the cell's configuration at
    the file's rehearsal size: every row busy, contexts that end at the
    table's three widths, the chunks in the chunkwise form and the steps
    against the state and the pools read by row, logits, expert choices and
    the state itself by layer against the plain reference. It shows
    that the path holds; the chip run holds the first KDA layer's state
    under ``STATE_ERROR``."""
    import os

    import chip_smoke

    chip_smoke.phase_paged_logits(
        os.path.join(os.path.dirname(chip_smoke.__file__), "benchmark",
                     "configs", "solar-open2-250b-serve-1chip.json"),
        2 ** 31 + 7, True, {"platform": "cpu", "kind": "cpu", "count": 1})
    out = capsys.readouterr().out
    assert "smoke[linear] check=" in out
    assert "solar_open2_decoder" in out and "contexts=[12, 16, 32, 64]" in out
    # The pools of the ONE full layer: 4 rows of 4 blocks of 16.
    assert '"k": [[1, 17, 16, 2, 16], "bfloat16"]' in out
    states = out.split("state_error_by_long_context_and_layer=")[1]
    assert states.count("[") == 4         # three long contexts, by layer
    assert "expert_choices=0 " not in out
    # Held to its own file's rehearsal bound, not to a looser shared one.
    assert " bound=0.8 " in out


def test_only_this_rehearsal_says_its_own_bound_of_the_smoke():
    """``chip_smoke.py`` holds a run to ``LATENT_SAME_EXPERTS`` (0.15)
    unless the configuration's ``rehearsal.probes`` says another with
    its readings, and only this configuration's does: Kimi-Linear's and
    Xing's rehearsals stay as tight as they were, and no file loosens
    the chip's run (the key outside a ``rehearsal`` block is not read
    there, so it may not stand there)."""
    import glob
    import json
    import os

    import chip_smoke

    assert chip_smoke.LATENT_SAME_EXPERTS == 0.15
    configs = os.path.join(os.path.dirname(chip_smoke.__file__),
                           "benchmark", "configs")
    own = {}
    for path in sorted(glob.glob(os.path.join(configs, "*.json"))):
        with open(path) as f:
            config = json.load(f)
        assert "smoke_same_experts" not in config.get("probes", {}), path
        probes = config.get("rehearsal", {}).get("probes", {})
        if "smoke_same_experts" in probes:
            own[os.path.basename(path)] = probes
    assert list(own) == ["solar-open2-250b-serve-1chip.json"]
    probes, = own.values()
    assert probes["smoke_same_experts"] == 0.8
    assert "seeds" in probes["smoke_same_experts_why"]
