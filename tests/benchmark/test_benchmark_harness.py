"""The harness around the cells: the traced run's line, refusals, and a
later PR's way of adding a configuration, a traffic mix and a per-layer
metric as new files only."""

import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cells import (  # noqa: E402
    REPO,
    RESULT_KEYS,
    bench_json,
    declared,
    has_result,
    result_line,
    run_cell,
)


@pytest.mark.parametrize("workload, surely", [
    ("serve-chat-steady", {"generator_lateness_p95_ms",
                           "prefill_tokens_per_chunk"}),
    ("train-4k-1chip", set()),  # its readers need a device's trace
    ("serve-longgen-closed", {"decode_batch_occupancy",
                              "engine_stood_ms_per_step"}),
])
def test_traced_run_reports_per_layer_metrics(workload, surely):
    out = result_line(run_cell("--workload", workload, "--seed", "4",
                               "--seconds", "2", "--trace", "1",
                               "--rehearse"))
    assert set(out) - {"breakdown"} == RESULT_KEYS
    assert out["correct"] is True
    want = declared("per_layer", workload)
    got = {k: v["unit"] for k, v in out["metrics"].items()}
    assert surely <= set(got)
    assert got.items() <= want.items()
    assert not os.path.exists(
        os.path.join(REPO, ".bench_trace", workload))  # cleaned up


def test_refuses_to_run_without_a_tpu():
    """JAX is held to the CPU here; without --rehearse that ends the
    run before any phase, with no result."""
    done = run_cell("--workload", "train-4k-1chip", "--seed", "0",
                    "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and not has_result(done)
    assert "only with --rehearse" in done.stderr
    assert "bench[" not in done.stdout


def test_refuses_an_unknown_cell_and_a_bare_directory(tmp_path):
    done = run_cell("--workload", "nope", "--seconds", "1", "--rehearse")
    assert done.returncode != 0 and not has_result(done)
    # Only BENCHMARK.json and the benchmark's own directories: no program.
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bare = run_cell("--workload", "train-4k-1chip", "--seconds", "1",
                    "--rehearse", cwd=tmp_path,
                    command=[sys.executable, "benchmark/run.py"])
    assert bare.returncode != 0 and not has_result(bare)


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """A second configuration, traffic mix and per-layer metric from a
    temporary directory: new files and new entries, no edit of a file
    that is there, and the same harness runs them."""
    def load(*parts):
        with open(os.path.join(REPO, "benchmark", *parts)) as f:
            return json.load(f)

    def dump(obj, *parts):
        path = tmp_path.joinpath(*parts)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))

    config = load("configs", "mistral7b-serve-1chip.json")
    config["name"] = "another-serve"
    config["rehearsal"]["engine"] = {"max_batch_size": 2, "max_seq_len": 64}
    dump(config, "benchmark", "configs", "another-serve.json")
    mix = load("traffic", "chat-steady.json")
    mix["rehearsal"]["rate_per_s"] = 6.0
    dump(mix, "benchmark", "traffic", "chat-busier.json")
    dump({"reader": "counters", "formula": "decode_tokens / decode_steps",
          "what": "rows that carried a request per decode step"},
         "benchmark", "metrics", "decode_rows_per_step.json")
    bench = bench_json()
    cell = "another-serve.chat-busier"
    bench["paths"] = ["benchmark"]
    bench["configs"].append({
        "name": "another-serve", "source": config["source"],
        "file": "benchmark/configs/another-serve.json",
        "reduced": config["reduced"], "why": "a test's"})
    bench["workloads"].append({
        "name": cell, "config": "another-serve", "traffic": "chat-busier",
        "chips": 1, "why": "a test's"})
    for metric in bench["end_to_end"]:
        if "serve-chat-steady" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "decode_rows_per_step", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "Engine scheduler and cache",
        "moves": "token_gap_p95_ms" if any(
            m["name"] == "token_gap_p95_ms" for m in bench["end_to_end"])
        else "token_gap_p50_ms", "workloads": [cell]})
    dump(bench, "BENCHMARK.json")

    common = ("--benchmark-json", str(tmp_path / "BENCHMARK.json"),
              "--workload", cell, "--seed", "5", "--seconds", "2",
              "--rehearse", "--trace-dir", str(tmp_path / "trace"))
    out = result_line(run_cell(*common, "--trace", "1"))
    assert out["correct"] is True
    assert list(out["metrics"]) == ["decode_rows_per_step"]
    assert out["metrics"]["decode_rows_per_step"]["unit"] == "rows"
    assert 1.0 <= out["metrics"]["decode_rows_per_step"]["value"] <= 2.0
    out = result_line(run_cell(*common, "--trace", "0"))
    assert set(out["metrics"]) == set(declared("end_to_end",
                                               "serve-chat-steady"))
    assert out["attempted"] == 12  # 6 requests/s for 2 s: the new mix
