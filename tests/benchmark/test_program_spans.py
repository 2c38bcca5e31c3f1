"""The per-layer metrics that read the program's own spans and time
counters (PR 23): the span reader's arithmetic on made-up intervals, and
every new metric's file found and read through the harness's own
loader. PR 53 retired the ten ``idle_ms_per_step_*`` of the two Mistral
serve cells (since PR 36 the device is busy under the spans they read:
0.0009 to 0.83 ms of a 12 ms step on the ledger's PR 52 lines) with
their reader ``trace_idle_by_span``; ``find_trace`` and
``program_spans``, which the span readers share, live in
``trace_reduce``. Nothing here is a measurement."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec, trace_reduce  # noqa: E402
from benchmark.readers import trace_span_ms  # noqa: E402

OPEN, CLOSED, TRAIN = "serve-chat-steady", "serve-longgen-closed", \
    "train-4k-1chip"
NEW_METRICS = {
    "ttft_queue_ms_mean": OPEN, "ttft_prefill_ms_mean": OPEN,
    "engine_host_ms_per_step.open": OPEN,
    "engine_host_ms_per_step.closed": CLOSED,
    # (PR 67 retired engine_cpu_share.open: its numerator holds the CPU
    # booked inside the device waits that its denominator leaves out,
    # and no counter of the program says how much that is.)
    "engine_cpu_share.closed": CLOSED,
    # (PR 53 retired idle_ms_per_step_{fetch,emit,schedule,launch,
    # unattributed}.open/.closed: device_idle_share.* and the result
    # line's breakdown.idle_gaps carry what they were for.)
    "stream_put_ms_p50.open": OPEN,
    "flash_fwd_time_share": TRAIN, "flash_dq_time_share": TRAIN,
    "flash_dkdv_time_share": TRAIN,
}

# One pass of the engine loop as the profiler would show it, in ns: the
# iteration encloses its leaves, a replica thread puts a chunk meanwhile.
PASS = [("engine.iteration", 0, 100),
        ("engine.decode.fetch", 10, 40),
        ("engine.decode.emit", 40, 60),
        ("engine.decode.schedule", 62, 70),
        ("serve.stream.put", 45, 50)]


def among(spans, pattern):
    import re

    return [s for s in spans if re.search(pattern, s[0])]


def test_span_ms_reads_a_percentile_and_nothing_without_a_trace(monkeypatch):
    """Loading is kept apart from the arithmetic: here the file and its
    host events are made up."""
    metric = {"span": r"^engine\.decode\.", "percentile": 50,
              "workloads": [OPEN]}
    run = {"trace": None}  # the spans are the host's: no device needed
    # No trace file under .bench_trace/<cell>: nothing to read.
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert trace_reduce.find_trace(metric) is None
    assert trace_span_ms.read(metric, run) is None
    seen = []
    monkeypatch.setattr(trace_reduce, "find_xplane",
                        lambda directory: seen.append(directory) or "made-up")
    monkeypatch.setattr(trace_reduce, "program_spans",
                        lambda path, pattern: among(PASS, pattern))
    # Durations 30, 20, 8: the median, in ms.
    assert trace_span_ms.read(metric, run) == pytest.approx(20.0 / 1e6)
    assert seen == [os.path.join(REPO, ".bench_trace", OPEN)]
    assert trace_span_ms.read({**metric, "percentile": 100}, run) == \
        pytest.approx(30.0 / 1e6)
    # A program without the span (the parent commit): nothing, no error;
    # nor for a metric that lists no cell.
    assert trace_span_ms.read({**metric, "span": r"^llm\."}, run) is None
    assert trace_span_ms.read({**metric, "workloads": []}, run) is None


def test_program_spans_of_a_trace_recorded_on_the_v5e():
    """PR 22's recorded train trace has host planes and none of the
    program's spans: the loader reads it and finds nothing, as it will
    on a parent commit."""
    path = os.path.join(REPO, "tests", "benchmark", "data",
                        "train-4k-1chip.v5e.xplane.pb")
    assert trace_reduce.program_spans(path, r"^(engine|serve|llm)\.") == []
    fences = trace_reduce.program_spans(path, r"^bench\.fence$")
    assert fences and all(end > start for _, start, end in fences)


@pytest.mark.parametrize("name, cell", sorted(NEW_METRICS.items()))
def test_new_metric_file_loads_through_the_cell(name, cell, monkeypatch):
    loaded = spec.load_cell(cell)
    metric = {m["name"]: m for m in loaded.per_layer}[name]
    assert cell in metric["cells"]
    assert metric["cells"] == metric["workloads"]
    reader = spec.load_module(loaded.roots, "readers", metric["reader"])
    # Nothing to read (no trace, no such counter): None, never an error.
    # (Another test's rehearsal may have a trace there at this moment.)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert reader.read(metric, {"trace": None, "counters": {}}) is None


def names_in(pattern: str) -> list:
    """The span names a metric file's ``span`` pattern spells out:
    ``^a\\.(b|c\\.d)$`` -> ``a.b``, ``a.c.d``."""
    import re

    shape = re.fullmatch(
        r"\^((?:[a-z_]|\\\.)*)(?:\(([a-z_|]|\\\.)*\))?((?:[a-z_]|\\\.)*)\$",
        pattern)
    assert shape, f"{pattern!r}: teach names_in this shape of pattern"
    group = re.search(r"\((.*)\)", pattern)
    head, tail = shape.group(1), shape.group(3)
    return [(head + middle + tail).replace("\\.", ".")
            for middle in (group.group(1).split("|") if group else [""])]


def test_every_span_a_metric_names_is_one_the_program_opens():
    """``engine.decode.split_key`` went with the host's split (PR 27)
    and three metric files named it for five PRs more: every name in
    every ``span`` pattern is a ``tracing.phase(...)`` that the engine
    loop or the replica opens."""
    import glob
    import json
    import re

    assert names_in(r"^engine\.(sweep|decode\.schedule)$") == \
        ["engine.sweep", "engine.decode.schedule"]
    assert names_in(r"^serve\.stream\.put$") == ["serve.stream.put"]
    opened = set()
    for source in ("ray_tpu/serve/llm_engine/engine.py",
                   "ray_tpu/serve/replica.py"):
        with open(os.path.join(REPO, source)) as f:
            opened |= set(re.findall(r"\bphase\(\s*\"([a-z_.]+)\"",
                                     f.read()))
    assert {"engine.decode.launch", "serve.stream.put"} <= opened
    files = glob.glob(os.path.join(REPO, "benchmark", "metrics", "*.json"))
    named = {}
    for path in files:
        with open(path) as f:
            pattern = json.load(f).get("span")
        if pattern:
            named[os.path.basename(path)] = names_in(pattern)
    assert len(named) >= 2 and "stream_put_ms_p50.json" in named
    stale = {file: sorted(set(names) - opened)
             for file, names in named.items() if set(names) - opened}
    assert not stale, f"no tracing.phase(...) opens {stale}"


def test_counter_metrics_read_the_engines_time_counters():
    counters = spec.load_module([os.path.join(REPO, "benchmark")],
                                "readers", "counters")
    run = {"counters": {
        "first_tokens": 4, "queue_wait_us": 6_000_000, "prefill_us": 3_200_000,
        "decode_steps": 400, "decode_host_us": 8_800_000,
        "loop_wall_us": 42_000_000, "loop_cpu_us": 4_400_000,
        "fetch_wait_us": 33_200_000, "max_batch_size": 16}}
    cell = {m["name"]: m for m in spec.load_cell(OPEN).per_layer}
    assert counters.read(cell["ttft_queue_ms_mean"], run) == 1500.0
    assert counters.read(cell["ttft_prefill_ms_mean"], run) == 800.0
    assert counters.read(cell["engine_host_ms_per_step.open"], run) == 22.0
    closed = {m["name"]: m for m in spec.load_cell(CLOSED).per_layer}
    assert counters.read(closed["engine_cpu_share.closed"], run) == \
        pytest.approx(50.0)


def test_the_kernel_selectors_match_the_chips_instruction_names():
    """The names the v5e's compiler gives the three custom calls (seen in
    a deviceless compile of the flash gradient, PR 23), each selected by
    its own metric and all three by ``flash_time_share``'s selector."""
    import re

    hlo = {
        "flash_fwd_time_share":
            "%jvp_vmap_flash_fwd__.1 = (bf16[4,16,4096,128]{3,2,1,0}, "
            "f32[4,16,4096,1]{3,2,1,0}) custom-call(%bitcast.21)",
        "flash_dq_time_share":
            "%transpose_jvp_vmap_flash_bwd_dq___.1 = bf16[4,16,4096,128]"
            "{3,2,1,0} custom-call(%bitcast.23)",
        "flash_dkdv_time_share":
            "%transpose_jvp_vmap_flash_bwd_dkv___.1 = (bf16[4,16,4096,128]"
            "{3,2,1,0}, bf16[4,16,4096,128]{3,2,1,0}) custom-call(%bitcast.22)",
    }
    cell = {m["name"]: m for m in spec.load_cell(TRAIN).per_layer}
    for name, text in hlo.items():
        hits = [other for other in hlo
                if re.search(cell[other]["ops"], text)]
        assert hits == [name]
        assert re.search(cell["flash_time_share"]["ops"], text)
        assert not re.search(cell[name]["ops"],
                             "%fusion.3 = bf16[2,4096] fusion(%p0)")
