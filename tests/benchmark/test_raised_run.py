"""A run that raises once the device was found prints a result's line
that says ``correct`` false and names, under ``compared``, what was
raised and the lines it passed through (PR 54, after its check met
"exited with code 1" in one cell and told no more): of a bare exit code
the next session learns nothing, where the names a run compared reach
the ledger. Before the device is found, and in a directory without the
program, a run still ends with another code than 0 and no result.
Driven through ``harness.main`` with ``test_setup_clock.py``'s made-up
kind of cell: nothing here is a measurement."""

import json
import re
import sys
import types

import pytest

from test_setup_clock import a_benchmark  # noqa: E402

from benchmark import harness  # noqa: E402


def main_with(monkeypatch, tmp_path, run, device=None):
    monkeypatch.setitem(sys.modules, "benchmark.stub_cell",
                        types.SimpleNamespace(run=run))
    monkeypatch.setattr(
        harness, "device_or_refuse", device or (lambda chips, rehearse: {
            "platform": "cpu", "kind": "cpu", "count": 1}))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return harness.main(
        ["--workload", "stub-cell", "--seconds", "1", "--rehearse",
         "--benchmark-json", a_benchmark(tmp_path)], started=0.0)


@pytest.mark.parametrize("exc", [
    RuntimeError("LLM engine loop died"),
    TimeoutError("streaming response stalled past 60s"),
    IndexError("list index out of range"),
    SystemExit("a warm-up request hung"),
], ids=lambda e: type(e).__name__)
def test_a_run_that_raises_says_so_in_its_result_and_where(
        monkeypatch, capsys, tmp_path, exc):
    def run(cell, args, started, say, compiles):
        raise exc

    assert main_with(monkeypatch, tmp_path, run) == 0
    said = capsys.readouterr()
    result = json.loads(said.out.strip().splitlines()[-1])
    assert result["correct"] is False and result["metrics"] == {}
    assert {"correct", "attempted", "failed", "metrics", "device"} \
        <= set(result)
    assert list(result)[-1] == "compared"
    compared = result["compared"]
    assert compared["raised"] == 1 and compared["limit"] == 0
    assert compared["message"] == str(exc)
    (name,) = [k for k in compared if k.startswith("raised.")]
    assert compared[name] == 1
    # What was raised, then the innermost line first: this file's
    # ``raise`` and the harness's call of the cell.
    assert re.fullmatch(r"[A-Za-z0-9_.-]{1,160}", name)
    assert name.startswith(
        f"raised.{type(exc).__name__}.test_raised_run.py.")
    assert ".harness.py." in name
    # The traceback, and last of all the numbers compared.
    assert "Traceback" in said.err or type(exc).__name__ in said.err
    last = said.err.strip().splitlines()[-1]
    assert last.startswith("bench[correct] ")
    assert json.loads(last.split(" ", 1)[1]) == compared


def test_a_runner_that_owes_a_key_is_named_in_the_result(
        monkeypatch, capsys, tmp_path):
    assert main_with(monkeypatch, tmp_path,
                     lambda *a: {"correct": True}) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False
    assert "stub_cell.py:run returned no" in result["compared"]["message"]


def test_no_device_is_still_no_result_and_another_code(
        monkeypatch, capsys, tmp_path):
    def no_device(chips, rehearse):
        raise SystemExit("this cell needs 1 TPU device(s)")

    with pytest.raises(SystemExit, match="needs 1 TPU"):
        main_with(monkeypatch, tmp_path, lambda *a: {}, device=no_device)
    assert not [line for line in capsys.readouterr().out.splitlines()
                if line.startswith("{")]


def test_a_directory_without_the_program_is_still_no_result(
        monkeypatch, capsys, tmp_path):
    monkeypatch.setitem(sys.modules, "ray_tpu", None)  # import raises
    with pytest.raises(ImportError):
        main_with(monkeypatch, tmp_path, lambda *a: {})
    assert not [line for line in capsys.readouterr().out.splitlines()
                if line.startswith("{")]


def test_a_stream_that_ended_with_no_token_is_counted_not_indexed():
    """``reduce_window`` once took ``arrivals[-1]`` of every finished
    record; a stream that ends cleanly before its first token has
    none."""
    from benchmark import serve_cell, traffic_gen

    def record(sent, arrivals):
        return serve_cell.Record(traffic_gen.Request(0, 0.0, [1, 2], 4),
                                 due=sent, sent=sent, arrivals=arrivals,
                                 finished=True)

    seen = serve_cell.reduce_window(
        [record(1.0, []), record(1.5, [2.0, 2.5]), record(0.1, [0.2])],
        opened=1.0, closed=3.0)
    # The empty stream and the one inside the window; not the one that
    # was over before the window opened.
    assert seen["in_flight"] == 2 and seen["completed"] == 1
    assert seen["failed_due"] == 1  # no first token: a failure of ITS
