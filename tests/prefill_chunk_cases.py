"""What a prefill chunk's width must not change, for any family: the
cases ``test_llm_engine.py``, ``test_olmoe.py``, ``test_phi4flash.py``,
``test_sdar.py`` and ``test_latent_engine.py`` each run on their own tiny
configuration. A table of 24 blocks of 16 (384 positions: the rungs 6,
12 and 24), so that the default chunk is the knob's 128 and a prompt can
be longer than two of them."""

import contextlib
import functools

import numpy as np

MAX_SEQ_LEN, BLOCK, ROWS = 384, 16, 2
#: Shorter than the narrowest chunk; past one default chunk and no
#: multiple of any width; more than two default chunks.
PROMPT_LENGTHS = (5, 150, 300)
#: ``resumes_to_the_same_tokens``' two prompts.
RESUMED = (200, 200)
NEW_TOKENS = 12
WIDTHS = (8, 32, None)          # None: the engine's default
WIDTH_IDS = ("8", "32", "default")


def _prompts(config, lengths, seed=38):
    rng = np.random.default_rng(seed)
    top = min(config.vocab_size, 200)   # under any mask's id
    return [rng.integers(1, top, n).tolist() for n in lengths]


def _answers(engine, lengths):
    """Greedy tokens of one request a prompt, submitted together."""
    with engine._lock:
        requests = [engine.submit(prompt, max_new_tokens=NEW_TOKENS)
                    for prompt in _prompts(engine.config, lengths)]
    return [engine.result(req, timeout_s=300) for req in requests]


@contextlib.contextmanager
def _engine(config, params, chunk, **kwargs):
    from ray_tpu.serve.llm_engine import LLMEngine

    engine = LLMEngine(config, params, max_batch_size=ROWS,
                       max_seq_len=MAX_SEQ_LEN, block_size=BLOCK,
                       prefill_chunk=chunk, seed=5, **kwargs)
    try:
        yield engine
    finally:
        engine.shutdown()


def serve(config, params, chunk, lengths=PROMPT_LENGTHS, **kwargs):
    """``_answers`` for ``lengths`` from an engine of chunk width
    ``chunk``; with them its counters, its chunk width and how many
    prefill programs it holds."""
    with _engine(config, params, chunk, **kwargs) as engine:
        return _answers(engine, lengths), engine.engine_stats(), \
            engine.prefill_chunk_len, engine._prefill_step._cache_size()


@functools.lru_cache(maxsize=None)
def _at_sixteen(config):
    """The tokens at a width none of the cases has, for the prompts of
    ``same_tokens_at`` and of ``resumes_to_the_same_tokens``, from ONE
    engine with room for either; the weights."""
    from ray_tpu.serve.llm_engine import model as paged_model

    params = paged_model.serving_params(config, None, seed=5)
    with _engine(config, params, 16) as engine:
        return {lengths: _answers(engine, lengths)
                for lengths in (PROMPT_LENGTHS, RESUMED)}, params


def same_tokens_at(config, chunk):
    """The three prompts at ``chunk`` yield what they yield at 16, in
    the chunks their lengths make of them, through three programs."""
    want, params = _at_sixteen(config)
    got, stats, width, programs = serve(config, params, chunk)
    assert width == (chunk or 128)
    assert got == want[PROMPT_LENGTHS] \
        and [len(t) for t in got] == [NEW_TOKENS] * 3
    span = getattr(config, "block_length", 0) or 1
    prefilled = [n // span * span for n in PROMPT_LENGTHS]
    assert stats["prefill_tokens"] == sum(prefilled)
    assert stats["prefill_chunks"] == sum(-(-n // width) for n in prefilled)
    assert stats["preemptions"] == 0
    assert programs == 3  # one a rung, the constructor's


def resumes_to_the_same_tokens(config):
    """Two prompts of 200 tokens at the default chunk over a pool of 22
    blocks, of which each request needs 14: the second chunk of the
    later prompt finds the pool full, so a request is preempted with a
    prompt half prefilled, and both end as they do with room."""
    want, params = _at_sixteen(config)
    got, stats, width, _ = serve(config, params, None, RESUMED,
                                 num_blocks=23)
    assert width == 128
    assert stats["preemptions"] > 0 and stats["resumes"] > 0, stats
    assert got == want[RESUMED]
