"""What PR 36 adds to the benchmark: the share of decode steps launched
while the step before them was unread, as one quantity in two entries
(the closed cells report ``serve_tokens_per_s``, the open one
``token_gap_p95_ms``). Each entry is held to its own file, found and
read through the harness's own loader, from canned counters, once for
every cell it lists. The block-diffusion cell was not among them: when
PR 36 was written the share was 0 there by the family's nature. Since
PR 46 that family keeps a pass ahead too (its block stays on the
device), and PR 59 put it on the list with the Kimi and Solar cells.
Nothing here is a measurement."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402

ENTRIES = {
    # Every closed-loop serving cell since PR 59 (three when PR 36 wrote
    # it, Xing's a copy of its own since PR 44; the SDAR, Kimi and Solar
    # cells waited for a place in a full table).
    "decode_steps_ahead_share": ("serve_tokens_per_s", [
        "serve-longgen-closed", "serve-olmoe-longgen-closed",
        "serve-phi4flash-reason-closed", "serve-sdar-blockgen-closed",
        "serve-xing4-longdoc-closed", "serve-kimi-linear-reason-closed",
        "serve-solar-open2-reason-closed"]),
    "decode_steps_ahead_share.open": ("token_gap_p95_ms",
                                      ["serve-chat-steady"]),
}


def bench_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def entry(name) -> dict:
    (found,) = [m for m in bench_json()["per_layer"] if m["name"] == name]
    return found


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_is_its_files(name):
    moves, cells = ENTRIES[name]
    with open(os.path.join(REPO, "benchmark/metrics", name + ".json")) as f:
        on_file = json.load(f)
    declared = entry(name)
    assert declared == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Engine scheduler and cache",
        "moves": moves, "workloads": cells}
    for key in ("name", "unit", "layer", "moves"):
        assert on_file[key] == declared[key]
    assert on_file["cells"] == cells and on_file["reader"] == "counters"
    assert on_file["formula"] == "100 * decode_steps_ahead / decode_steps"
    # A layer the benchmark already names, and an end-to-end metric
    # every one of the cells reports.
    others = [m for m in bench_json()["per_layer"] if m["name"] != name]
    assert declared["layer"] in {m["layer"] for m in others}
    (moved,) = [m for m in bench_json()["end_to_end"] if m["name"] == moves]
    assert set(cells) <= set(moved["workloads"])


@pytest.mark.parametrize("name, cell", [
    (name, cell) for name in sorted(ENTRIES) for cell in ENTRIES[name][1]])
def test_each_cell_loads_it_and_reads_canned_counters(name, cell):
    loaded = spec.load_cell(cell)
    (metric,) = [m for m in loaded.per_layer if m["name"] == name]
    reader = spec.load_module(loaded.roots, "readers", metric["reader"])
    run = {"trace": None, "memory": {}, "harness": {}, "rehearse": False}
    # 1,000 steps of which the first and four after an empty batch had
    # no step before them.
    counters = {"decode_steps": 1000, "decode_steps_ahead": 995}
    assert reader.read(metric, {**run, "counters": counters}) == 99.5
    # A family that reads every pass at once counts none: 0, a reading.
    counters["decode_steps_ahead"] = 0
    assert reader.read(metric, {**run, "counters": counters}) == 0.0
    # The parent commit's engine has no such counter, and a window with
    # no decode step nothing to divide by: nothing to report, no error.
    assert reader.read(metric, {**run, "counters": {"decode_steps": 7}}) \
        is None
    assert reader.read(metric, {**run, "counters": {
        "decode_steps": 0, "decode_steps_ahead": 0}}) is None


def test_the_engine_counts_what_the_formula_names():
    from ray_tpu.serve.llm_engine.engine import ENGINE_STAT_KEYS

    assert {"decode_steps", "decode_steps_ahead"} <= set(ENGINE_STAT_KEYS)
