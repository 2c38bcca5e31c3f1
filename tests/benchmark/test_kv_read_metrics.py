"""``kv_read_over_live``, which PR 34 added for the three serve cells
that did not report it, as the table holds it since PR 59: ONE entry for
the closed-loop cells (``.closed``, whose list names all seven) and one
for the open-loop cell (``.open``: another end-to-end metric). Each is a
ratio of the engine's two counters, and a rehearsed traced run of a cell
reports it with the signature of how that cell's family reads its pool
(``engine.py:965``): BY ROW for the paged family since PR 58 (Mistral,
OLMoE: whole pages of the busy rows, no narrow step), GATHERED at a rung
of the table's width where a family still gathers (the hybrid one: Phi).
Nothing here is a measurement."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cells import (  # noqa: E402
    CLOSED,
    REPO,
    bench_json,
    result_line,
    run_cell,
)

if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402

# metric -> (the cells it lists, the end-to-end metric it moves)
ENTRIES = {
    "kv_read_over_live.closed": (CLOSED, "serve_tokens_per_s"),
    "kv_read_over_live.open": (["serve-chat-steady"], "token_gap_p95_ms"),
}
PAIRS = [(name, cell) for name in sorted(ENTRIES)
         for cell in ENTRIES[name][0]]
# (metric, cell, how the cell's family reads its pool)
REHEARSED = [
    ("kv_read_over_live.closed", "serve-longgen-closed", "by_row"),
    ("kv_read_over_live.closed", "serve-olmoe-longgen-closed", "by_row"),
    ("kv_read_over_live.open", "serve-chat-steady", "by_row"),
    ("kv_read_over_live.closed", "serve-phi4flash-reason-closed",
     "gathered"),
]


def loaded(name: str, cell: str) -> dict:
    return {m["name"]: m for m in spec.load_cell(cell).per_layer}[name]


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_metric_is_declared_for_its_cells(name):
    cells, moves = ENTRIES[name]
    bench = bench_json()
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry == {
        "name": name, "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "Engine scheduler and cache",
        "moves": moves, "workloads": cells}
    # Every cell reports the end-to-end metric the entry should move.
    for cell in cells:
        assert moves in {m["name"] for m in spec.load_cell(cell).end_to_end}
    # A file of its own; the two say the same but for what they move.
    with open(os.path.join(REPO, "benchmark", "metrics", name + ".json")) as f:
        own = json.load(f)
    assert own["name"] == name and own["cells"] == cells
    other = loaded("kv_read_over_live.closed", "serve-phi4flash-reason-closed")
    assert (own["reader"], own["formula"], own["layer"], own["unit"]) == \
        (other["reader"], other["formula"], other["layer"], other["unit"])
    # The copies PR 59 folded into ``.closed`` are gone.
    assert not [m["name"] for m in bench["per_layer"]
                if m["name"].startswith("kv_read_over_live.")
                and m["name"] not in ENTRIES]


@pytest.mark.parametrize("name, cell", PAIRS)
def test_the_metric_is_a_ratio_of_the_two_counters(name, cell):
    metric = loaded(name, cell)
    reader = spec.load_module(spec.load_cell(cell).roots, "readers",
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


@pytest.mark.parametrize("name, cell, reads", REHEARSED)
def test_a_rehearsed_run_of_the_cell_reports_it(name, cell, reads):
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
    live, read = counters["kv_positions_live"], counters["kv_positions_read"]
    assert out["metrics"][name]["value"] == pytest.approx(read / live)
    engine = spec.rehearsed(spec.load_cell(cell).config, True)["engine"]
    assert said["checks"]["no_compile_in_window"] is True
    steps, rows = counters["decode_steps"], engine["max_batch_size"]
    assert steps > 0
    if reads == "by_row":
        # One decode program, no rung of the table's width; a step
        # reads whole pages of its busy rows up to its own position: at
        # most a page a busy row more than what is live.
        block = engine.get("block_size", 16)
        assert counters["decode_steps_narrow"] == 0
        assert live <= read <= live + block * counters["decode_tokens"]
        assert counters["decode_tokens"] <= steps * rows
    else:
        # The rehearsal's table is 64 positions in blocks of 16 and its
        # contexts are short: steps ran under the whole width, and the
        # step read rows x the rung, less than rows x the table.
        assert 0 < counters["decode_steps_narrow"] <= steps
        assert live < read < steps * rows * engine["max_seq_len"]
