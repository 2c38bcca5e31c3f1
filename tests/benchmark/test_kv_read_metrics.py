"""What PR 34 adds to the benchmark: ``kv_read_over_live`` for the three
serve cells that did not report it (``.closed``, ``.moe``, ``.open``),
as new metric files on the ``counters`` reader with the formula of
``kv_read_over_live.phi``. Each is declared for its one cell, is a ratio
of the engine's two counters, and a rehearsed traced run of its cell
reports it. Nothing here is a measurement."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cells import REPO, bench_json, result_line, run_cell  # noqa: E402

if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402

# metric -> (its one cell, the end-to-end metric it moves)
NEW = {
    "kv_read_over_live.closed": ("serve-longgen-closed",
                                 "serve_tokens_per_s"),
    "kv_read_over_live.moe": ("serve-olmoe-longgen-closed",
                              "serve_tokens_per_s"),
    "kv_read_over_live.open": ("serve-chat-steady", "token_gap_p95_ms"),
}


def loaded(name: str) -> dict:
    return {m["name"]: m for m in spec.load_cell(NEW[name][0]).per_layer}[name]


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_is_declared_for_its_one_cell(name):
    cell, moves = NEW[name]
    bench = bench_json()
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry == {
        "name": name, "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "Engine scheduler and cache",
        "moves": moves, "workloads": [cell]}
    # The cell reports the end-to-end metric the new one should move.
    assert moves in {m["name"] for m in spec.load_cell(cell).end_to_end}
    # A file of its own, beside the one whose formula it shares.
    with open(os.path.join(REPO, "benchmark", "metrics", name + ".json")) as f:
        own = json.load(f)
    assert own["name"] == name and own["cells"] == [cell]
    phi = loaded_phi()
    assert (own["reader"], own["formula"], own["layer"], own["unit"]) == \
        (phi["reader"], phi["formula"], phi["layer"], phi["unit"])


def loaded_phi() -> dict:
    cell = spec.load_cell("serve-phi4flash-reason-closed")
    return {m["name"]: m for m in cell.per_layer}["kv_read_over_live.phi"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_is_a_ratio_of_the_two_counters(name):
    metric = loaded(name)
    reader = spec.load_module(spec.load_cell(NEW[name][0]).roots, "readers",
                              metric["reader"])
    # 16 rows: 100 steps at a quarter of 2048 positions, 20 at a half,
    # over contexts of 300 positions.
    read = 16 * (100 * 512 + 20 * 1024)
    live = 16 * 120 * 300
    counters = {"kv_positions_read": read, "kv_positions_live": live,
                "decode_steps": 120, "max_batch_size": 16}
    assert reader.read(metric, {"counters": counters}) == read / live
    assert 1.0 < read / live < 2.0 < 2048 / 300
    # Nothing to read (an engine without the counters; no decode step in
    # the window): None, never an error.
    assert reader.read(metric, {"counters": {"decode_steps": 120}}) is None
    assert reader.read(metric, {"counters": {
        "kv_positions_read": 0, "kv_positions_live": 0}}) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_rehearsed_run_of_the_cell_reports_it(name):
    cell = NEW[name][0]
    done = run_cell("--workload", cell, "--seed", "6", "--seconds", "2",
                    "--trace", "1", "--rehearse")
    out = result_line(done)
    assert out["correct"] is True
    assert out["metrics"][name]["unit"] == "x"
    said = next(json.loads(line.split(" ", 1)[1])
                for line in done.stdout.splitlines()
                if line.startswith("bench[serve] ")
                and "engine_counters" in line)
    counters = said["engine_counters"]
    assert out["metrics"][name]["value"] == pytest.approx(
        counters["kv_positions_read"] / counters["kv_positions_live"])
    # The rehearsal's table is 64 positions in blocks of 16 and its
    # contexts are short: steps ran under the whole width, none compiled
    # in the window, and the step read less than rows x the table.
    engine = spec.rehearsed(spec.load_cell(cell).config, True)["engine"]
    assert said["checks"]["no_compile_in_window"] is True
    assert 0 < counters["decode_steps_narrow"] <= counters["decode_steps"]
    assert counters["kv_positions_live"] < counters["kv_positions_read"] \
        < counters["decode_steps"] * engine["max_batch_size"] \
        * engine["max_seq_len"]
