"""Shared by the two test files that run the benchmark's command as the
driver does: a new process per run (``--rehearse``: the CPU, tiny
sizes, nothing printed is a measurement)."""

import functools
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


# PR 59: a quantity that several cells report is ONE entry whose list
# names them. survivor -> (the suffixes of the copies that went, the
# cells those copies and the survivor's old list covered between them).
MISTRAL, OLMOE, PHI, SDAR, XING, KIMI, SOLAR = CLOSED = [
    "serve-longgen-closed", "serve-olmoe-longgen-closed",
    "serve-phi4flash-reason-closed", "serve-sdar-blockgen-closed",
    "serve-xing4-longdoc-closed", "serve-kimi-linear-reason-closed",
    "serve-solar-open2-reason-closed"]
TRAIN = ["train-4k-1chip", "train-4k-fsdp2tp2", "train-512-1chip"]
SIX = ("moe", "phi", "sdar", "xing", "kimi", "solar")
FOLDED_INTO = {
    "decode_step_device_ms.closed": (SIX, CLOSED),
    "device_idle_share.closed": (SIX, CLOSED),
    "hbm_peak_share.closed": (SIX, CLOSED),
    "engine_host_ms_per_step.closed": (
        ("moe", "phi", "sdar", "xing"), [MISTRAL, OLMOE, PHI, SDAR, XING]),
    "host_calls_per_step.closed": (
        ("moe", "phi", "sdar", "xing"), [MISTRAL, OLMOE, PHI, SDAR, XING]),
    "kv_read_over_live.closed": (
        ("moe", "phi", "sdar", "xing", "solar"),
        [MISTRAL, OLMOE, PHI, SDAR, XING, SOLAR]),
    "decode_batch_occupancy": (
        ("moe", "phi", "xing", "kimi"), [MISTRAL, OLMOE, PHI, XING, KIMI]),
    "decode_steps_ahead_share": (("xing",), [MISTRAL, OLMOE, PHI, XING]),
    "experts_touched_share.closed": (
        ("moe", "sdar", "xing"), [OLMOE, SDAR, XING]),
    "expert_load_max_over_mean.closed": (
        ("moe", "sdar", "xing"), [OLMOE, SDAR, XING]),
    "prefill_chunk_device_ms.closed": (("kimi", "solar"), [KIMI, SOLAR]),
    "train_step_device_ms": (("512",), TRAIN),
    "train_step_mfu": (("512",), TRAIN),
    "device_idle_share.train": (("512",), TRAIN),
    "flash_time_share": (("512",), ["train-4k-1chip", "train-512-1chip"]),
}
# The cells the fold knew: every cell of the benchmark at PR 59.
FOLDED_CELLS = {*CLOSED, *TRAIN, "serve-chat-steady"}


def quantity_of(survivor: str) -> str:
    """``decode_step_device_ms.closed`` -> ``decode_step_device_ms``; a
    survivor that kept a bare name, or ``.train``, is its quantity."""
    stem, _, suffix = survivor.rpartition(".")
    return stem if suffix in ("closed", "train") else survivor


def went(survivor: str) -> list:
    """The names PR 59 folded into ``survivor``."""
    return [f"{quantity_of(survivor)}.{suffix}"
            for suffix in FOLDED_INTO[survivor][0]]


SUFFIX_CELL = {"moe": OLMOE, "phi": PHI, "sdar": SDAR, "xing": XING,
               "kimi": KIMI, "solar": SOLAR, "512": "train-512-1chip"}


@functools.lru_cache(maxsize=None)
def _per_layer() -> dict:
    return {m["name"]: m for m in bench_json()["per_layer"]}


def named(quantity: str, suffix: str) -> str:
    """The entry that reads ``quantity`` in the cell whose entries are
    called ``.<suffix>``: the family's own entry where it has one
    (``decode_batch_occupancy.sdar``, every family share and roofline),
    else the survivor of PR 59's fold that lists the cell."""
    entries = _per_layer()
    own = f"{quantity}.{suffix}"
    for survivor in FOLDED_INTO:
        if own not in entries and quantity_of(survivor) == quantity \
                and SUFFIX_CELL[suffix] in entries[survivor]["workloads"]:
            return survivor
    return own


def renamed(by_old_name: dict) -> dict:
    """A dict keyed by the names before PR 59, keyed by today's."""
    return {named(*old.rsplit(".", 1)): value
            for old, value in by_old_name.items()}
