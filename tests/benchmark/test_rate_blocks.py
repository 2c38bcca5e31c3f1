"""``serve_tokens_per_s`` is the median over consecutive blocks of a
traffic file's ``rate_block_tokens`` (256 where the file says nothing)
of the block's rate. Where decode steps run alone or beside a prompt's
chunks, about half and half, a block of 256 tokens lies in one heap or
the other and the median CHOOSES a heap; a block that holds whole
cycles (several prompts' chunks and the decode between them) reads the
window's pace and still shrugs off a standstill, which is what the
median was for (PR 22), on a stream of ONE pace. ``longdoc-closed``'s
window has no one pace (the ramp's prompts come thick at its start),
and on the chip the median of its sixteen blocks was the grain of the
middle few: since PR 54 the file says ``rate_over: window``, the plain
rate over all the window's tokens and seconds, keeps blocks of 4,096
for the ``bench[serve]`` line, and fixes its schedule; every other
traffic file is the parent's byte for byte. The streams here are made
up from a seed: nothing is a measurement."""

import hashlib
import json
import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import serve_cell, spec, traffic_gen  # noqa: E402

WINDOW_S, ROWS = 50.0, 32
STEP_S, CHUNK_S = 0.0126, 0.016   # a decode step alone; a chunk before it
FAST, SLOW = ROWS / STEP_S, ROWS / (STEP_S + CHUNK_S)  # 2,540 and 1,119
# Seconds of decode alone between two prompts: the first leaves MORE
# than half of the tokens in the fast heap, the second less.
RARE_PROMPTS, FREQUENT_PROMPTS = (0.25, 0.75), (0.0, 0.3)


def stream(seed: int, between: tuple, standstill: "tuple | None" = None
           ) -> list:
    """Arrival instants of a closed loop of 32 rows: a decode step
    yields a token a row, takes 12.6 ms alone and 28.6 ms beside one of
    a prompt's 16 to 38 chunks; ``between`` seconds of decode alone lie
    between two prompts; ``standstill`` (at, seconds) stops everything
    once."""
    rng = random.Random(seed)
    t, times, chunks, next_prompt, stood = 0.0, [], 0, 0.0, False
    while t < WINDOW_S:
        if not chunks and t >= next_prompt:
            chunks = rng.randint(16, 38)
        if chunks:
            t += CHUNK_S
            chunks -= 1
            if not chunks:
                next_prompt = t + rng.uniform(*between)
        t += STEP_S
        if standstill and not stood and t >= standstill[0]:
            t, stood = t + standstill[1], True
        times += [t] * ROWS
    return [x for x in times if x <= WINDOW_S]


def reduced(times: list, block: int, over: str = "blocks") -> dict:
    record = serve_cell.Record(traffic_gen.Request(0, 0.0, [1], 4),
                               due=0.0, sent=0.0, arrivals=times)
    return serve_cell.reduce_window([record], 0.0, WINDOW_S, block, over)


SEEDS = [1, 2, 3, 4, 6, 7]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("between, heap", [
    (RARE_PROMPTS, "fast"), (FREQUENT_PROMPTS, "slow")])
def test_a_block_of_256_lands_in_a_heap_and_one_of_4096_reads_the_pace(
        seed, between, heap):
    times = stream(seed, between)
    short, long_ = reduced(times, 256), reduced(times, 4096)
    mean = short["tokens_per_s_mean"]
    assert mean == long_["tokens_per_s_mean"] == len(times) / WINDOW_S
    # 256 tokens are eight steps: the blocks' rates make two heaps, and
    # their median is one heap's rate, far from the window's pace.
    in_heaps = [r for r in short["block_rates"]
                if r > 0.85 * FAST or r < 1.15 * SLOW]
    assert len(in_heaps) > 0.55 * len(short["block_rates"])
    if heap == "fast":
        assert short["tokens_per_s"] > 1.13 * mean
        assert short["tokens_per_s"] > 0.75 * FAST
    else:
        assert short["tokens_per_s"] < 0.88 * mean
        assert short["tokens_per_s"] < 1.1 * SLOW
    # 4,096 tokens are three seconds and two or more prompts: sixteen
    # to twenty blocks a window, their median within 2% of the mean.
    assert 15 <= len(long_["block_rates"]) <= 21
    assert long_["tokens_per_s"] == pytest.approx(mean, rel=0.02)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_step_between_the_heaps_moves_256_by_half_and_4096_by_the_pace(
        seed):
    """The same engine with prompts a little rarer: the window's pace
    rises by a fifth to a quarter; the median over blocks of 256 jumps
    from one heap to the other."""
    rare, frequent = stream(seed, RARE_PROMPTS), stream(seed,
                                                        FREQUENT_PROMPTS)
    pace = len(rare) / len(frequent)
    assert 1.15 < pace < 1.35
    assert reduced(rare, 4096)["tokens_per_s"] / \
        reduced(frequent, 4096)["tokens_per_s"] == pytest.approx(pace,
                                                                 rel=0.04)
    assert reduced(rare, 256)["tokens_per_s"] / \
        reduced(frequent, 256)["tokens_per_s"] > 1.6


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("between", [RARE_PROMPTS, FREQUENT_PROMPTS])
def test_a_standstill_of_five_seconds_moves_the_4096_median_by_under_3_percent(
        seed, between):
    sound = reduced(stream(seed, between), 4096)
    stood = reduced(stream(seed, between, standstill=(20.0, 5.0)), 4096)
    # A tenth of the window is gone: the mean takes it whole ...
    assert stood["tokens_per_s_mean"] < 0.92 * sound["tokens_per_s_mean"]
    # ... and the median over blocks loses one block to it: what moves
    # is where the later blocks' edges fall, by a median of sixteen's
    # own grain (0.0 to 2.8% here; ISSUE 54 hoped for under 2%).
    assert min(stood["block_rates"]) < 0.5 * min(sound["block_rates"])
    assert stood["tokens_per_s"] == pytest.approx(sound["tokens_per_s"],
                                                  rel=0.03)


def ramp_then_steady(seed: int) -> list:
    """A window of two paces, as the Xing cell's is on the chip: 15 s
    in which prompts follow each other at once (the callers that waited
    for a row), then prompts one to two seconds apart."""
    thick = [t for t in stream(seed, (0.0, 0.0)) if t <= 15.0]
    thin = [15.0 + t for t in stream(seed + 100, (1.0, 2.0))
            if t <= WINDOW_S - 15.0]
    return thick + thin


@pytest.mark.parametrize("seed", SEEDS)
def test_rate_over_window_is_all_the_tokens_over_all_the_seconds(seed):
    times = ramp_then_steady(seed)
    plain = reduced(times, 4096, "window")
    blocks = reduced(times, 4096)
    assert plain["tokens_per_s"] == plain["tokens_per_s_mean"] \
        == len(times) / WINDOW_S
    # The blocks' median is still worked out, for the earlier line, and
    # is what a file that says nothing gets.
    assert plain["tokens_per_s_block_median"] == blocks["tokens_per_s"] \
        == blocks["tokens_per_s_block_median"]
    assert plain["block_rates"] == blocks["block_rates"]
    # Two paces: the blocks lie far apart and their median is no pace of
    # the window's; it stands over the plain rate, since a slow block
    # lasts longer than a fast one of as many tokens.
    assert max(blocks["block_rates"]) > 1.5 * min(blocks["block_rates"])
    assert blocks["tokens_per_s"] > 1.03 * plain["tokens_per_s"]
    # A standstill of 4 s shows in the plain rate by 4 / 50 of it.
    stood = [t if t < 30.0 else t + 4.0 for t in times]
    stood = [t for t in stood if t <= WINDOW_S]
    assert reduced(stood, 4096, "window")["tokens_per_s"] == \
        len(stood) / WINDOW_S < 0.94 * plain["tokens_per_s"]


def test_a_file_that_says_nothing_keeps_the_median_over_blocks():
    times = stream(1, RARE_PROMPTS)
    assert reduced(times, 256)["tokens_per_s"] == \
        reduced(times, 256, "blocks")["tokens_per_s"] != \
        reduced(times, 256, "window")["tokens_per_s"]


def test_block_rates_at_both_sizes_by_hand():
    # 32 tokens every 10 ms: 3,200 tokens/s whatever the block.
    times = [0.01 * step for step in range(1, 1001) for _ in range(32)]
    for block in (256, 4096):
        rates = serve_cell.block_rates(times, block)
        assert len(rates) == (len(times) - 1) // block
        assert rates == pytest.approx([3200.0] * len(rates))
    # Fewer than three whole blocks: the plain rate stands in.
    few = reduced(times[:3 * 4096], 4096)
    assert len(few["block_rates"]) == 2
    assert few["tokens_per_s"] == few["tokens_per_s_mean"]


# ------------------------------------------------------ the traffic files

# sha256 of every traffic file at PR 52's commit (65b4760): PR 54 edits
# ``longdoc-closed.json`` alone. PR 67 moved ``chat-steady``'s rate and
# ``rate_why`` and nothing else of it: its sum is PR 67's.
AT_THE_PARENT = {
    "batch16x512":
        "71331401d023b165f0ec769e5882ba0ddaa1901e65080a6d628f99d3fcde94cb",
    "batch2x4096":
        "a89987d7ea9a1c33e5033fafbd847762f9ebbd9dec7d6d48305c109f51fab1e4",
    "batch4x4096":
        "432ba3f577716e5387b64b4b06ede8c6b8e685b887ec929e42fda24aa072d063",
    "blockgen-closed":
        "317966ddfe88808225fdbd6e8b1676a2b2ba483d8063447ceaf10523589dfa1d",
    "chat-steady":
        "8f8ad8b3accb0e4efee8396d0a56ad275024456fb72ab7c7643da29f9f1c9533",
    "longgen-closed":
        "2410eac1ac179a5605cce200a839653ba098f7ac5ae95d90aac4264d280db6d7",
    "reason-closed":
        "50463dc64e183f857b407598b4bd3048e2ce09f8098ea9d1dc046f67cfe34dbd",
    "reason-wide-closed":
        "6e48497ed272f4fe4e3c21d6bc149eb35ccd38922ab16280c1548920ccd7ea61",
}


def traffic_path(name: str) -> str:
    return os.path.join(REPO, "benchmark", "traffic", name + ".json")


def traffic_file(name: str) -> dict:
    with open(traffic_path(name)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(AT_THE_PARENT))
def test_every_other_traffic_file_is_the_parents_byte_for_byte(name):
    with open(traffic_path(name), "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == AT_THE_PARENT[name]
    traffic = traffic_file(name)
    # ... so its statistic is the one it had: blocks of 256 by default.
    for key in ("rate_block_tokens", "rate_over"):
        assert key not in traffic
        assert key not in traffic.get("rehearsal", {})


def test_the_default_block_is_256_and_run_hands_the_files_block_on():
    import inspect

    assert inspect.signature(serve_cell.reduce_window).parameters[
        "block"].default == 256
    assert inspect.signature(serve_cell.reduce_window).parameters[
        "over"].default == "blocks"
    source = inspect.getsource(serve_cell.run)
    assert 'traffic.get("rate_block_tokens", 256)' in source
    assert 'traffic.get("rate_over", "blocks")' in source


def test_longdoc_closed_says_its_block_and_its_schedule():
    traffic = traffic_file("longdoc-closed")
    assert traffic["rate_over"] == "window"
    assert traffic["rate_block_tokens"] == 4096  # the earlier line's
    assert isinstance(traffic["schedule_seed"], int)
    # What the cell measures is what it measured: the ranges, the
    # callers, greedy decoding, the ramp and the traced share.
    assert (traffic["clients"], traffic["requests_per_client"]) == (48, 8)
    assert traffic["prompt"] == {"dist": "uniform", "min": 2048, "max": 4864}
    assert traffic["output"] == {"dist": "uniform", "min": 1024, "max": 3072}
    assert traffic["temperature"] == 0.0
    assert "window_opens_after" not in traffic  # (a) was measured, not built
    cell = spec.load_cell("serve-xing4-longdoc-closed")
    assert spec.rehearsed(cell.traffic, True)["rate_over"] == "window"


@pytest.mark.parametrize("seed", [5, 2147483693, 4010000141])
def test_a_fixed_schedule_deals_the_same_lengths_to_the_same_callers(seed):
    """``--seed`` draws the tokens (and the weights); the lengths, their
    order and their callers come from the file's ``schedule_seed`` and
    are the same in every run, round after round."""
    traffic = traffic_file("longdoc-closed")

    def shape(seed):
        rounds = traffic_gen.ClosedRounds(traffic, seed, 129280)
        return [[(len(r.tokens), r.max_new_tokens) for r in caller]
                for k in (0, 1) for caller in rounds.round(k)]

    def tokens(seed):
        return traffic_gen.ClosedRounds(traffic, seed, 129280) \
            .round(0)[0][0].tokens[:8]

    assert shape(seed) == shape(traffic["schedule_seed"])
    assert tokens(seed) != tokens(seed + 1)
    # Without the key the deal follows the seed, as in every other file.
    free = {k: v for k, v in traffic.items() if k != "schedule_seed"}
    a, b = (traffic_gen.ClosedRounds(free, s, 129280).round(0)
            for s in (seed, seed + 1))
    assert [len(r.tokens) for c in a for r in c] != \
        [len(r.tokens) for c in b for r in c]
    assert sorted(len(r.tokens) for c in a for r in c) == \
        sorted(len(r.tokens) for c in b for r in c)
