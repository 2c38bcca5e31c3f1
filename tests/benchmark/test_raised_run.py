"""A run that raises once the device was found prints a result's line
that says ``correct`` false and names, under ``compared``, what was
raised and the lines it passed through (PR 54, after its check met
"exited with code 1" in one cell and told no more): of a bare exit code
the next session learns nothing, where the names a run compared reach
the ledger. Before the device is found, in a directory without the
program, and (PR 59) with a program that lacks the module of the
configuration's model, a run still ends with another code than 0 and no
result.
Driven through ``harness.main`` with ``test_setup_clock.py``'s made-up
kind of cell: nothing here is a measurement."""

import json
import os
import re
import sys
import time
import types

import pytest

from test_setup_clock import a_benchmark  # noqa: E402

from benchmark import harness, spec  # noqa: E402


def main_with(monkeypatch, tmp_path, run, device=None, builder=None):
    monkeypatch.setitem(sys.modules, "benchmark.stub_cell",
                        types.SimpleNamespace(run=run))
    monkeypatch.setattr(
        harness, "device_or_refuse", device or (lambda chips, rehearse: {
            "platform": "cpu", "kind": "cpu", "count": 1}))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    benchmark_json = a_benchmark(tmp_path)
    if builder is not None:  # the made-up configuration builds a model
        path = tmp_path / "bench" / "configs" / "stub-config.json"
        path.write_text(json.dumps({
            **json.loads(path.read_text()),
            "builder": {"path": builder, "from_keys": {}}}))
    return harness.main(
        ["--workload", "stub-cell", "--seconds", "1", "--rehearse",
         "--benchmark-json", benchmark_json], started=0.0)


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


def build_then_run(cell, args, started, say, compiles):
    """As ``serve_cell.run`` and ``train_cell.run`` begin."""
    spec.build_model_config(cell.config)
    raise AssertionError("the builder was to raise")


@pytest.mark.parametrize("builder, missing", [
    ("ray_tpu.models.no_such_model_of_a_later_pr.Config",
     "ray_tpu.models.no_such_model_of_a_later_pr"),
    ("ray_tpu.no_such_package.model.Config", "ray_tpu.no_such_package"),
    ("no_such_top_level_package_59.Config", "no_such_top_level_package_59"),
])
def test_a_program_without_the_models_module_is_no_result_and_another_code(
        monkeypatch, capsys, tmp_path, builder, missing):
    """PR 59: the driver tries a new cell on the parent commit, whose
    program has no module for the new model; the harness itself ends
    that run as it ends one without ``ray_tpu`` (PR 55 needed a file of
    its own for it, ``benchmark/solar_builder.py``)."""
    began = time.perf_counter()
    with pytest.raises(SystemExit) as ended:
        main_with(monkeypatch, tmp_path, build_then_run, builder=builder)
    assert time.perf_counter() - began < 10
    # A message is exit code 1; never 0, never a result's line.
    assert ended.value.code not in (0, None)
    assert f"no module {missing}:" in str(ended.value.code)
    assert "stub-config" in str(ended.value.code)
    assert not [line for line in capsys.readouterr().out.splitlines()
                if line.startswith("{")]


@pytest.mark.parametrize("builder, raised", [
    # The module is there and the builder raises when it is called ...
    ("json.loads", "TypeError"),
    # ... or lacks the attribute ...
    ("ray_tpu.models.llama.NoSuchConfigOfAnyPr", "AttributeError"),
    # ... or imports a module that is not the builder's own and is
    # missing: a program that broke, not a program without the model.
    ("test_raised_run_broken_model.Config", "ModuleNotFoundError"),
])
def test_a_builder_that_is_there_and_raises_is_a_result_as_before(
        monkeypatch, capsys, tmp_path, builder, raised):
    (tmp_path / "test_raised_run_broken_model.py").write_text(
        "import a_module_the_model_needs_and_nobody_has\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    assert main_with(monkeypatch, tmp_path, build_then_run,
                     builder=builder) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False and result["metrics"] == {}
    (name,) = [k for k in result["compared"] if k.startswith("raised.")]
    assert name.startswith(f"raised.{raised}.")


def test_no_configuration_builds_through_a_file_of_the_benchmarks_own():
    """Every configuration's ``builder.path`` names the program's own
    class, so that ``lacks_the_model`` sees the module a program may
    lack; ``solar_builder.py`` is gone."""
    import glob

    assert not os.path.exists(os.path.join(
        spec.HERE, "solar_builder.py"))
    for path in glob.glob(os.path.join(spec.HERE, "configs", "*.json")):
        with open(path) as f:
            config = json.load(f)
        assert config["builder"]["path"].startswith("ray_tpu.models."), path
        assert harness.lacks_the_model(config) is None, path


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
