"""Shared by the two test files that run the benchmark's command as the
driver does: a new process per run (``--rehearse``: the CPU, tiny
sizes, nothing printed is a measurement)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}  # the last: each number compared beside its limit


def bench_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(*args, cwd=REPO, command=None):
    command = command or [sys.executable,
                          os.path.join(REPO, "benchmark", "run.py")]
    return subprocess.run(
        [*command, *args], cwd=cwd, capture_output=True, text=True,
        timeout=300)


def result_line(done) -> dict:
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def has_result(done) -> bool:
    lines = done.stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{") \
        and "metrics" in lines[-1]


def declared(kind: str, workload: str) -> dict:
    """name -> unit of the metrics BENCHMARK.json declares for a cell."""
    return {m["name"]: m["unit"] for m in bench_json()[kind]
            if workload in m.get("workloads", [workload])}
