"""The per-layer table held by name (PR 53): every entry of
``BENCHMARK.json``'s ``per_layer`` against the metric file of its name,
the cells it lists and the reader its file names; the table against the
contract's limits; and no file without an entry. An entry is found by
its name, never by where it stands or by how long the table is, so a
later PR's cell and entries are held the day they arrive, with no edit
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


def check_entry(benchmark_json: str, name: str, monkeypatch) -> None:
    """One entry, found by its name: its file, its cells, its reader."""
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
    listed = entry.get("workloads", sorted(
        c for c in cells if entry["moves"] in reports(bench, c)))
    assert listed and set(listed) <= cells
    assert len(set(listed)) == len(listed)
    for cell in listed:
        assert entry["moves"] in reports(bench, cell), cell
    # The reader its file names is found as the harness finds it, and
    # reads nothing from a run that saw nothing: None, never an error
    # and never a 0.
    (metric,) = [m for m in spec.load_cell(listed[0], benchmark_json).per_layer
                 if m["name"] == name]
    assert metric == {**file, **entry}
    reader = spec.load_module(roots, "readers", file["reader"])
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert reader.read(metric, dict(NOTHING)) is None


def check_table(benchmark_json: str) -> None:
    """The table whole: the contract's limits, names that are unique,
    cells that have something to report, and no file without an entry."""
    bench = load(benchmark_json)
    held = len(bench["per_layer"])
    assert 1 <= held <= MOST_PER_LAYER, (
        f"per_layer holds {held} entries and the contract allows "
        f"{MOST_PER_LAYER}: {MOST_PER_LAYER - held} are free")
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
    # Every cell reports set-up, another end-to-end metric and a
    # per-layer metric that moves one it reports.
    for cell in cells:
        said = reports(bench, cell)
        assert "setup_s" in said and len(said) >= 2, cell
        assert any(m["moves"] in said for m in bench["per_layer"]
                   if cell in m.get("workloads", [cell])), cell
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


ENTRIES = [m["name"] for m in load(BENCHMARK_JSON)["per_layer"]]


@pytest.mark.parametrize("name", ENTRIES)
def test_the_entry_is_its_file_its_cells_and_its_reader(name, monkeypatch):
    check_entry(BENCHMARK_JSON, name, monkeypatch)


def test_the_table_keeps_the_contracts_limits():
    check_table(BENCHMARK_JSON)
    # Ten cells, one of them on four chips, as before PR 53.
    bench = load(BENCHMARK_JSON)
    assert len(bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) >= 1


# What PR 53 retired, and where the information lives now (PERF.md 3).
RETIRED = [f"idle_ms_per_step_{part}{kind}"
           for part in ("fetch", "emit", "schedule", "launch", "unattributed")
           for kind in (".open", ".closed")] + [
    "idle_ms_per_step_launch.moe", "idle_ms_per_step_fetch.moe",
    "stream_backlog_rows", "stream_backlog_rows.phi", "hbm_peak_share.512"]


def test_what_pr_53_retired_is_in_neither_place():
    assert len(RETIRED) == 15 and not set(RETIRED) & set(ENTRIES)
    for name in RETIRED:
        assert not os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", name + ".json")), name
    for reader in ("trace_idle_by_span", "stream_backlog"):
        assert not os.path.exists(os.path.join(
            REPO, "benchmark", "readers", reader + ".py")), reader
    # What carries their information is still read.
    assert {"device_idle_share.open", "device_idle_share.closed",
            "device_idle_share.moe", "stream_take_age_ms_p95",
            "stream_get_age_ms_p95", "engine_stood_ms_per_step",
            "hbm_peak_share.train"} <= set(ENTRIES)


def test_a_cell_and_its_entries_are_added_with_no_edit(tmp_path, monkeypatch):
    """A later PR's cell: a configuration, fifteen metric files (PR 53
    left room for as many; fewer once later PRs have used it) and a
    reader of its own as new files beside a copy of ``BENCHMARK.json``
    with the entries appended; the same checks hold the old entries and
    the new ones."""
    def dump(obj, *parts):
        path = tmp_path.joinpath(*parts)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(obj if isinstance(obj, str) else json.dumps(obj))

    bench = load(BENCHMARK_JSON)
    room = min(15, MOST_PER_LAYER - len(ENTRIES))
    if room < 1 or len(bench["workloads"]) >= MOST_CELLS:
        pytest.skip("the table is full: nothing can be added to it")
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
    added = [f"steps_over_{n}.another" for n in range(1, room + 1)]
    for n, name in enumerate(added, 1):
        said = {"name": name, "unit": "steps", "layer": "Jitted steps",
                "moves": "serve_tokens_per_s"}
        dump({**said, "what": f"decode steps of the window over {n}",
              "cells": [cell], "reader": "steps_over", "per": n},
             "added", "metrics", name + ".json")
        bench["per_layer"].append({
            **said, "better": "higher", "source": "program_counter",
            "workloads": [cell]})
    dump(bench, "BENCHMARK.json")
    copy = str(tmp_path / "BENCHMARK.json")

    check_table(copy)
    assert len(bench["per_layer"]) == len(ENTRIES) + room <= MOST_PER_LAYER
    for name in ENTRIES[:3] + ENTRIES[-3:] + added:
        check_entry(copy, name, monkeypatch)
    # The new cell is loaded with its own entries and those alone, and
    # its reader reads.
    loaded = spec.load_cell(cell, copy)
    assert [m["name"] for m in loaded.per_layer] == added
    reader = spec.load_module(loaded.roots, "readers", "steps_over")
    assert reader.read(loaded.per_layer[-1],
                       {"counters": {"decode_steps": 30 * room}}) == 30.0
    # A file with no entry shows, and so does an entry past the limit.
    last = bench["per_layer"].pop()
    dump(bench, "BENCHMARK.json")
    with pytest.raises(AssertionError, match=last["name"] + ".json"):
        check_table(copy)
    bench["per_layer"] += [
        {**last, "name": f"{last['name']}.{n}"}
        for n in range(MOST_PER_LAYER + 1 - len(bench["per_layer"]))]
    dump(bench, "BENCHMARK.json")
    with pytest.raises(AssertionError):
        check_table(copy)
