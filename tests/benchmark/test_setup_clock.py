"""``setup_s`` starts its clock where the program starts (PR 54): the
instant ``harness.device_or_refuse`` returns, not the process's first
line. What passes before it (the interpreter, ``import jax``, the TPU
client taking its lease) stays on the ``bench[setup]`` and
``bench[compile]`` lines as information and is in no metric. Driven
through ``harness.main`` with a kind of cell of this file's own, which
does no work: nothing here is a measurement."""

import json
import os
import sys
import time
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402

WORK_S = 0.2  # what the made-up cell's set-up takes


def a_benchmark(tmp_path) -> str:
    """A ``BENCHMARK.json`` with one cell of the kind ``stub``."""
    def dump(obj, *parts):
        path = tmp_path.joinpath(*parts)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))

    dump({"name": "stub-config", "kind": "stub"},
         "bench", "configs", "stub-config.json")
    dump({"generator": "none"}, "bench", "traffic", "none.json")
    dump({"command": ["python3", "bench/run.py"], "paths": ["bench"],
          "run_seconds": 1,
          "configs": [{"name": "stub-config", "source": "a test's",
                       "file": "bench/configs/stub-config.json",
                       "reduced": [], "why": "a test's"}],
          "workloads": [{"name": "stub-cell", "config": "stub-config",
                         "traffic": "none", "chips": 1, "why": "a test's"}],
          "end_to_end": [
              {"name": "work_per_s", "unit": "1/s", "better": "higher",
               "bound": 0.05, "source": "host_clock"},
              {"name": "setup_s", "unit": "s", "better": "lower",
               "bound": 0.1, "source": "host_clock"}],
          "per_layer": []}, "BENCHMARK.json")
    return str(tmp_path / "BENCHMARK.json")


def run_stub(monkeypatch, capsys, tmp_path, before_device_s: float,
             process_age_s: float) -> dict:
    """One run of the made-up cell through ``harness.main``; the search
    for a device takes ``before_device_s`` and the process is
    ``process_age_s`` old when ``main`` is called."""
    seen = {}

    def run(cell, args, started, say, compiles):
        seen["started"] = started
        time.sleep(WORK_S)  # loading, weights, warm-up
        opened = time.perf_counter()
        return {"correct": True, "compared": {"nothing": 0, "limit": 0},
                "attempted": 1, "failed": 0, "setup_s": opened - started,
                "values": {"work_per_s": 1.0},
                "memory": {"peak_bytes_in_use": None}}

    def slow_device(chips, rehearse):
        time.sleep(before_device_s)
        return {"platform": "cpu", "kind": "cpu", "count": 1}

    monkeypatch.setitem(sys.modules, "benchmark.stub_cell",
                        types.SimpleNamespace(run=run))
    monkeypatch.setattr(harness, "device_or_refuse", slow_device)
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR",
                                                     "disabled"))
    born = time.perf_counter() - process_age_s
    code = harness.main(
        ["--workload", "stub-cell", "--seconds", "1", "--rehearse",
         "--benchmark-json", a_benchmark(tmp_path)], started=born)
    assert code == 0
    lines = capsys.readouterr().out.strip().splitlines()
    said = [json.loads(line.split(" ", 1)[1]) for line in lines
            if line.startswith("bench[")]
    seen.update(
        result=json.loads(lines[-1]), born=born,
        found=next(s for s in said if "since_process_start_s" in s),
        compile=next(s for s in said if "before_device_found_s" in s))
    return seen


@pytest.mark.parametrize("before_device_s, process_age_s", [
    (0.0, 0.0),      # a client that starts at once
    (0.3, 0.0),      # the search for the device takes a while
    (0.0, 12.0),     # imports and the lease took 12 s: a slow lease
    (0.3, 24.0),     # ... or 24 (seen once on the chip, PR 48)
])
def test_setup_s_leaves_out_what_passed_before_the_device_was_found(
        monkeypatch, capsys, tmp_path, before_device_s, process_age_s):
    seen = run_stub(monkeypatch, capsys, tmp_path, before_device_s,
                    process_age_s)
    setup_s = seen["result"]["metrics"]["setup_s"]["value"]
    # The same set-up whatever came before the device: the cell's own
    # work and the harness's few lines between the device and the cell
    # (a test process's first pass through them imports the program).
    assert WORK_S <= setup_s < WORK_S + 2.0
    # What came before is said, and is in no metric.
    before = seen["found"]["since_process_start_s"]
    assert before >= before_device_s + process_age_s
    assert process_age_s == 0.0 or setup_s < before / 4
    assert seen["compile"]["before_device_found_s"] == pytest.approx(before)
    assert seen["compile"]["setup_s"] == setup_s
    assert seen["compile"]["wall_s"] >= before + setup_s
    assert set(seen["result"]["metrics"]) == {"work_per_s", "setup_s"}


def test_the_cell_is_handed_the_instant_the_device_was_found(
        monkeypatch, capsys, tmp_path):
    seen = run_stub(monkeypatch, capsys, tmp_path, 0.25, 5.0)
    # ``started``, as the cell's runner gets it, is that instant: after
    # the search for the device ended, not the process's first line.
    assert seen["started"] >= seen["born"] + 5.0 + 0.25
    assert seen["started"] - seen["born"] == pytest.approx(
        seen["found"]["since_process_start_s"])


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_both_kinds_of_cell_count_set_up_from_what_they_are_handed(kind):
    """``serve_cell.run`` and ``train_cell.run`` keep ``opened -
    started``: neither reads a clock of its own for the start."""
    with open(os.path.join(REPO, "benchmark", kind + "_cell.py")) as f:
        text = f.read()
    assert '"setup_s": opened - ' in text
    assert "_STARTED" not in text and "process_time" not in text


def test_the_entry_is_unchanged_and_the_harness_hands_on_the_found_instant():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (setup,) = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert setup == {"name": "setup_s", "unit": "s", "better": "lower",
                     "bound": 0.1, "source": "host_clock"}
    with open(os.path.join(REPO, "benchmark", "harness.py")) as f:
        text = f.read()
    assert "found = time.perf_counter()" in text
    assert "cell_runner.run(cell, args, found, say, compiles)" in text
