"""Each cell end to end through the benchmark's command, rehearsed on
the CPU with a 2 s window: the last line's keys, the declared names and
units. The numbers are not looked at: a CPU measures nothing."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cells import (  # noqa: E402
    RESULT_KEYS,
    bench_json,
    declared,
    result_line,
    run_cell,
)

CELLS = [w["name"] for w in bench_json()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_end_to_end(workload):
    done = run_cell("--workload", workload, "--seed", "3", "--seconds", "2",
                    "--trace", "0", "--rehearse")
    out = result_line(done)
    # Each number compared, beside its limit, ends standard error too.
    compared = done.stderr.strip().splitlines()[-1]
    assert compared.startswith("bench[correct] {")
    assert ("logit_atol" in compared) != ("loss_rtol" in compared)
    assert set(out) == RESULT_KEYS
    # ... and the result's line, under a key of its own that comes last.
    assert list(out)[-1] == "compared"
    assert out["compared"] == json.loads(compared[len("bench[correct] "):])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = declared("end_to_end", workload)
    assert {k: v["unit"] for k, v in out["metrics"].items()} == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    chips = next(w["chips"] for w in bench_json()["workloads"]
                 if w["name"] == workload)
    assert out["device"]["platform"] == "cpu"  # and says so
    assert out["device"]["count"] == chips
    assert "memory_peak_bytes" in out["device"]
