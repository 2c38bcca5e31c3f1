"""The per-layer table held by name (PR 53) and, since PR 59, by PAIR of
entry and cell: every entry of ``BENCHMARK.json``'s ``per_layer``
against the metric file of its name and the reader its file names, once
for each cell it lists (a quantity is ONE entry whose list names its
cells: PR 59 folded 60 files that were copies of 15 into those 15); the
table against the contract's limits; no file without an entry; and no
two files that say the same but for ``name``, ``cells`` and ``what``.
An entry is found by its name, never by where it stands or by how long
the table is, so a later PR's cell and entries are held the day they arrive, with no edit
here: the last test appends a made-up cell and its made-up entries to a
copy and puts it through the same checks. These take the place of
the per-PR "appended at the end" assertions. Nothing here is a
measurement."""

import glob
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cells import FOLDED_CELLS, FOLDED_INTO, went  # noqa: E402

from benchmark import spec, trace_reduce  # noqa: E402

BENCHMARK_JSON = os.path.join(REPO, "BENCHMARK.json")
ENTRY_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# The contract's limits.
MOST_PER_LAYER, MOST_CELLS = 128, 24
# A run that saw nothing: no trace, no counter, no memory reading.
NOTHING = {"trace": None, "counters": {}, "memory": {}, "harness": {},
           "clients": {}, "rehearse": False}


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reports(bench: dict, cell: str) -> set:
    """The end-to-end metrics a cell reports."""
    return {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}


def cells_of(bench: dict, entry: dict) -> list:
    """The cells an entry is read in: its list, or with no list every
    cell that reports the end-to-end metric it moves."""
    return entry.get("workloads", sorted(
        w["name"] for w in bench["workloads"]
        if entry["moves"] in reports(bench, w["name"])))


def check_entry(benchmark_json: str, name: str, cell: str,
                monkeypatch) -> None:
    """One entry, found by its name, in one cell of its list: its file,
    its cells, its reader, and what that cell is handed."""
    bench = load(benchmark_json)
    roots = spec.roots_of(benchmark_json, bench)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert ENTRY_KEYS - {"workloads"} <= set(entry) <= ENTRY_KEYS, \
        sorted(entry)
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES
    # A metric file of that name, which says the same of itself.
    file = load(spec._find(roots, "metrics", name + ".json"))
    for key in ("name", "unit", "layer", "moves"):
        assert file[key] == entry[key], key
    assert file.get("cells") == entry.get("workloads")
    assert len(file["what"]) > 20
    # Every cell listed exists and reports the end-to-end metric the
    # entry should move; with no list, every cell that reports it does.
    cells = {w["name"] for w in bench["workloads"]}
    listed = cells_of(bench, entry)
    assert listed and set(listed) <= cells and cell in listed
    assert len(set(listed)) == len(listed)
    assert entry["moves"] in reports(bench, cell), cell
    # What a file says of one cell alone is said by cell, of its cells.
    assert set(file.get("what_by_cell", {})) <= set(listed)
    # THIS cell is handed the entry with its file's content, and the
    # reader its file names is found as the harness finds it and reads
    # nothing from a run that saw nothing: None, never an error and
    # never a 0.
    (metric,) = [m for m in spec.load_cell(cell, benchmark_json).per_layer
                 if m["name"] == name]
    assert metric == {**file, **entry}
    reader = spec.load_module(roots, "readers", file["reader"])
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert reader.read(metric, dict(NOTHING)) is None


def check_table(benchmark_json: str, most: int = MOST_PER_LAYER) -> None:
    """The table whole: the contract's limits (``most`` entries: the
    contract's 128, or a smaller room that a test sets), names that are
    unique, no file without an entry, and cells that have something to
    report."""
    bench = load(benchmark_json)
    held = len(bench["per_layer"])
    assert 1 <= held <= most, (
        f"per_layer holds {held} entries and the limit is {most}: "
        f"{most - held} are free")
    assert 1 <= len(bench["workloads"]) <= MOST_CELLS
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = [c["name"] for c in bench["configs"]]
    assert len(set(configs)) == len(configs)
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    # At most a quarter of the cells, rounded down, take four chips,
    # and the one always may.
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(cells) // 4)
    # No metric file without an entry, no reader that no file names.
    base = os.path.dirname(os.path.abspath(benchmark_json))
    named, read_by = {m["name"] for m in bench["per_layer"]}, set()
    for root in (os.path.join(base, p) for p in bench["paths"]):
        for path in glob.glob(os.path.join(root, "metrics", "*.json")):
            assert os.path.basename(path)[:-len(".json")] in named, path
            read_by.add(load(path)["reader"])
        for path in glob.glob(os.path.join(root, "readers", "*.py")):
            reader = os.path.basename(path)[:-len(".py")]
            assert reader == "__init__" or reader in read_by, path
    # One file a quantity: no two files that say the same but for
    # ``name``, ``cells`` and ``what``. A copy that lists a cell younger
    # than the fold is a later ``model_config`` PR's (it may add files
    # and extend no list) and waits for the next ``benchmark`` PR.
    for copies in same_but_for_the_cells(bench, base).values():
        old = [name for name, cells in copies if set(cells) <= FOLDED_CELLS]
        assert len(old) <= 1, f"one quantity in several files: {old}"
    # Every cell reports set-up, another end-to-end metric and a
    # per-layer metric that moves one it reports.
    for cell in cells:
        said = reports(bench, cell)
        assert "setup_s" in said and len(said) >= 2, cell
        assert any(m["moves"] in said for m in bench["per_layer"]
                   if cell in m.get("workloads", [cell])), cell


SAID_OF_A_CELL = ("name", "cells", "what", "what_by_cell")


def same_but_for_the_cells(bench: dict, base: str) -> dict:
    """The metric files grouped by everything they say but their name,
    their cells and their sentences, their entry's ``better`` and
    ``source`` with it: group -> [(name, cells)]."""
    entries = {m["name"]: m for m in bench["per_layer"]}
    groups = {}
    for root in (os.path.join(base, p) for p in bench["paths"]):
        for path in sorted(glob.glob(os.path.join(root, "metrics",
                                                  "*.json"))):
            file = load(path)
            entry = entries[file["name"]]
            key = json.dumps(
                [{k: v for k, v in file.items() if k not in SAID_OF_A_CELL},
                 entry["better"], entry["source"]], sort_keys=True)
            groups.setdefault(key, []).append(
                (file["name"], cells_of(bench, entry)))
    return groups


_BENCH = load(BENCHMARK_JSON)
ENTRIES = [m["name"] for m in _BENCH["per_layer"]]
# Every pair of an entry and a cell it is read in (169 at PR 59).
PAIRS = [(m["name"], cell) for m in _BENCH["per_layer"]
         for cell in cells_of(_BENCH, m)]


@pytest.mark.parametrize("name, cell", PAIRS)
def test_the_entry_is_its_file_its_cells_and_its_reader(name, cell,
                                                        monkeypatch):
    check_entry(BENCHMARK_JSON, name, cell, monkeypatch)


def test_the_table_keeps_the_contracts_limits():
    check_table(BENCHMARK_JSON)
    # Ten cells, one of them on four chips, as before PR 53.
    bench = load(BENCHMARK_JSON)
    assert len(bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) >= 1
    # A quantity is one entry, so a cell is read in more pairs than the
    # table has entries (PR 59: 82 entries, 169 pairs).
    assert len(PAIRS) == len(set(PAIRS)) >= max(169, len(ENTRIES))


def in_neither_place(names) -> None:
    assert not set(names) & set(ENTRIES)
    for name in names:
        assert not os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", name + ".json")), name


# What PR 53 retired, and where the information lives now (PERF.md 3).
RETIRED = [f"idle_ms_per_step_{part}{kind}"
           for part in ("fetch", "emit", "schedule", "launch", "unattributed")
           for kind in (".open", ".closed")] + [
    "idle_ms_per_step_launch.moe", "idle_ms_per_step_fetch.moe",
    "stream_backlog_rows", "stream_backlog_rows.phi", "hbm_peak_share.512"]


def test_what_pr_53_retired_is_in_neither_place():
    assert len(RETIRED) == 15
    in_neither_place(RETIRED)
    for reader in ("trace_idle_by_span", "stream_backlog"):
        assert not os.path.exists(os.path.join(
            REPO, "benchmark", "readers", reader + ".py")), reader
    # What carries their information is still read, the OLMoE cell's
    # idle share on ``.closed``'s list since PR 59.
    assert {"device_idle_share.open", "device_idle_share.closed",
            "stream_take_age_ms_p95", "stream_get_age_ms_p95",
            "engine_stood_ms_per_step",
            "hbm_peak_share.train"} <= set(ENTRIES)
    (closed,) = [m for m in _BENCH["per_layer"]
                 if m["name"] == "device_idle_share.closed"]
    assert "serve-olmoe-longgen-closed" in closed["workloads"]


# What PR 59 folded (``cells.FOLDED_INTO``: survivor -> the suffixes of
# the names that went and the cells those names and the survivor's old
# list covered between them): 48 names for 45 places, since three
# survivors took a new name; and the one entry it retired (PERF.md 3 has
# the map).
RETIRED_BY_59 = ["kv_gather_time_share.moe"]


def test_what_pr_59_folded_is_in_neither_place_and_no_cell_lost_a_reading():
    gone = [name for survivor in FOLDED_INTO for name in went(survivor)]
    assert len(gone) == 48 and len(set(gone + RETIRED_BY_59)) == 49
    in_neither_place(gone + RETIRED_BY_59)
    # 128 entries gave 46 places and left 82: three of the 48 names are
    # survivors' old ones, and one entry was retired.
    assert len(FOLDED_INTO) == 15
    entries = {m["name"]: m for m in _BENCH["per_layer"]}
    for survivor, (_, covered) in FOLDED_INTO.items():
        # A superset, in the order of ``workloads``: a list may grow.
        listed = entries[survivor]["workloads"]
        assert set(covered) <= set(listed), survivor
        order = [w["name"] for w in _BENCH["workloads"]]
        assert listed == sorted(listed, key=order.index), survivor
    # What the retired entry selected is gone from the step since PR 58
    # (7.5874 -> 0.0495% on the ledger's PR 58 line), and the by-row
    # kernel's share has no selector yet: the next configuration's.
    assert not [name for name in ENTRIES if name.startswith("kv_gather")]


def with_a_made_up_cell(tmp_path, room: int):
    """A later PR's cell beside a copy of ``BENCHMARK.json``: a
    configuration, ``room`` metric files and a reader of its own as new
    files, the entries appended. The last of several entries is a COPY
    of ``decode_step_device_ms.closed`` under the cell's own name, as a
    ``model_config`` PR brings it (it may extend no list)."""
    def dump(obj, *parts):
        path = tmp_path.joinpath(*parts)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(obj if isinstance(obj, str) else json.dumps(obj))

    bench = load(BENCHMARK_JSON)
    cell = "another-serve.longgen-closed"
    config = load(os.path.join(REPO, "benchmark", "configs",
                               "mistral7b-serve-1chip.json"))
    config["name"] = "another-serve"
    dump(config, "added", "configs", "another-serve.json")
    dump("def read(metric, run):\n"
         "    steps = run['counters'].get('decode_steps')\n"
         "    return steps / metric['per'] if steps else None\n",
         "added", "readers", "steps_over.py")
    # The benchmark's own files where they are, the cell's beside them.
    os.symlink(os.path.join(REPO, "benchmark"), tmp_path / "benchmark")
    bench["paths"] = ["benchmark", "added"]
    bench["configs"].append({
        "name": "another-serve", "source": config["source"],
        "file": "added/configs/another-serve.json",
        "reduced": config["reduced"], "why": "a test's"})
    bench["workloads"].append({
        "name": cell, "config": "another-serve", "traffic": "longgen-closed",
        "chips": 1, "why": "a test's"})
    for metric in bench["end_to_end"]:
        if "serve-longgen-closed" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    # All of them made-up counts, but the last of several.
    added = [f"steps_over_{n}.another"
             for n in range(1, room + (room == 1))]
    for n, name in enumerate(added, 1):
        said = {"name": name, "unit": "steps", "layer": "Jitted steps",
                "moves": "serve_tokens_per_s"}
        dump({**said, "what": f"decode steps of the window over {n}",
              "cells": [cell], "reader": "steps_over", "per": n},
             "added", "metrics", name + ".json")
        bench["per_layer"].append({
            **said, "better": "higher", "source": "program_counter",
            "workloads": [cell]})
    if room > 1:
        name = "decode_step_device_ms.another"
        added.append(name)
        copied = load(os.path.join(REPO, "benchmark", "metrics",
                                   "decode_step_device_ms.closed.json"))
        copied.pop("what_by_cell")
        dump({**copied, "name": name, "cells": [cell]},
             "added", "metrics", name + ".json")
        (old,) = [m for m in bench["per_layer"]
                  if m["name"] == "decode_step_device_ms.closed"]
        bench["per_layer"].append({**old, "name": name, "workloads": [cell]})
    dump(bench, "BENCHMARK.json")
    return bench, cell, added, dump


@pytest.mark.parametrize("room", [1, 2, 15])
def test_a_cell_and_its_entries_are_added_with_no_edit(room, tmp_path,
                                                       monkeypatch):
    """With ONE place free, with two and with fifteen (the limit set to
    the table's length and that room, so the case runs however full the
    table is: at 127 of 128 the test failed and at 128 it skipped, until
    PR 59): the same checks hold the old entries and the new ones."""
    if len(_BENCH["workloads"]) >= MOST_CELLS:
        pytest.skip("the benchmark holds its 24 cells: none can be added")
    bench, cell, added, dump = with_a_made_up_cell(tmp_path, room)
    copy, most = str(tmp_path / "BENCHMARK.json"), len(ENTRIES) + room
    check_table(copy, most)
    assert len(bench["per_layer"]) == most
    for name, old_cell in PAIRS[:3] + PAIRS[-3:]:
        check_entry(copy, name, old_cell, monkeypatch)
    for name in added:
        check_entry(copy, name, cell, monkeypatch)
    # The new cell is loaded with its own entries and those alone, and
    # its reader reads.
    loaded = spec.load_cell(cell, copy)
    assert [m["name"] for m in loaded.per_layer] == added
    reader = spec.load_module(loaded.roots, "readers", "steps_over")
    assert reader.read(loaded.per_layer[0],
                       {"counters": {"decode_steps": 30}}) == 30.0
    # A copy that lists a cell the fold knew is one quantity in two
    # files, and shows.
    if room > 1:
        bench["per_layer"][-1]["workloads"] = ["serve-longgen-closed"]
        dump(bench, "BENCHMARK.json")
        with pytest.raises(AssertionError, match="one quantity in several"):
            check_table(copy, most)
        bench["per_layer"][-1]["workloads"] = [cell]
    # A file with no entry shows (before the cell that is left with no
    # per-layer metric, where it was the only one), and so does an entry
    # past the limit.
    last = bench["per_layer"].pop()
    dump(bench, "BENCHMARK.json")
    with pytest.raises(AssertionError, match=last["name"] + ".json"):
        check_table(copy, most)
    bench["per_layer"] += [
        {**last, "name": f"{last['name']}.{n}"}
        for n in range(most + 1 - len(bench["per_layer"]))]
    dump(bench, "BENCHMARK.json")
    with pytest.raises(AssertionError, match="are free"):
        check_table(copy, most)
