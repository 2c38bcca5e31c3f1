"""The per-layer metrics that read the program's own spans and time
counters (PR 23): the two readers' arithmetic on made-up intervals, and
every new metric's file found and read through the harness's own
loader. Nothing here is a measurement."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec, trace_reduce  # noqa: E402
from benchmark.readers import trace_idle_by_span, trace_span_ms  # noqa: E402

OPEN, CLOSED, TRAIN = "serve-chat-steady", "serve-longgen-closed", \
    "train-4k-1chip"
NEW_METRICS = {
    "ttft_queue_ms_mean": OPEN, "ttft_prefill_ms_mean": OPEN,
    "engine_host_ms_per_step.open": OPEN,
    "engine_host_ms_per_step.closed": CLOSED,
    "engine_cpu_share.open": OPEN, "engine_cpu_share.closed": CLOSED,
    **{f"idle_ms_per_step_{part}{kind}": cell
       for part in ("fetch", "emit", "schedule", "launch", "unattributed")
       for kind, cell in ((".open", OPEN), (".closed", CLOSED))},
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
LEAVES = r"^engine\.(?!iteration$)"


def among(spans, pattern):
    import re

    return [s for s in spans if re.search(pattern, s[0])]


@pytest.mark.parametrize("gaps, spans, pattern, want", [
    # A gap split over two spans, by overlap and not by its middle.
    ([(30, 50)], among(PASS, LEAVES), r"\.fetch$", 10.0),
    ([(30, 50)], among(PASS, LEAVES), r"\.emit$", 10.0),
    # Nested spans count once, at the innermost: the iteration keeps
    # only what no leaf covers.
    ([(0, 100)], among(PASS, r"^engine\."), r"\.iteration$", 42.0),
    ([(0, 100)], among(PASS, r"^engine\."), r"^engine\.", 100.0),
    # Another thread's span competes only where the metric lets it.
    ([(30, 50)], PASS, r"\.emit$", 5.0),
    ([(30, 50)], PASS, r"^serve\.stream\.put$", 5.0),
    # A gap in no span, whole or in part.
    ([(100, 130)], among(PASS, LEAVES), None, 30.0),
    ([(55, 65), (90, 120)], among(PASS, LEAVES), None, 2.0 + 30.0),
    ([(55, 65)], among(PASS, LEAVES), r"\.(emit|schedule)$", 5.0 + 3.0),
    ([(5, 8)], [], None, 3.0),
    ([], among(PASS, LEAVES), None, 0.0),
])
def test_idle_goes_to_the_innermost_span_by_overlap(gaps, spans, pattern,
                                                    want):
    assert trace_idle_by_span.idle_ns(gaps, spans, pattern) == \
        pytest.approx(want)


def test_every_idle_nanosecond_is_counted_once():
    gaps = [(3, 47), (58, 95), (99, 140)]
    leaves = among(PASS, LEAVES)
    names = sorted({name for name, _, _ in leaves})
    parts = [trace_idle_by_span.idle_ns(gaps, leaves, f"^{name}$".replace(
        ".", r"\.")) for name in names]
    outside = trace_idle_by_span.idle_ns(gaps, leaves, None)
    assert sum(parts) + outside == pytest.approx(
        sum(end - start for start, end in gaps))


def test_spans_that_start_together_nest_by_their_end():
    spans = [("outer", 0, 50), ("inner", 0, 20)]
    assert trace_idle_by_span.innermost(spans) == [
        (0, 20, "inner"), (20, 50, "outer")]


def device_with_gaps():
    def event(name, start, end):
        return trace_reduce.Event(name, float(start), float(end), {})

    ops = [event("fusion.1", 0, 10), event("fusion.2", 40, 62),
           event("fusion.3", 70, 90)]
    modules = [event("jit_decode_step(7)", 0, 10),
               event("jit_decode_step(7)", 40, 62),
               event("jit__argmax(3)", 70, 90)]
    return trace_reduce.Trace({0: trace_reduce.Device(modules, ops)}, [])


def test_readers_per_run_of_the_program_and_nothing_without_a_trace(
        monkeypatch):
    """Loading is kept apart from the arithmetic: here the file and its
    host events are made up, the device is a made-up ``Trace``."""
    metric = {"module": "^jit_decode_step", "among": LEAVES,
              "span": r"^engine\.decode\.(fetch|emit)$", "workloads": [OPEN]}
    run = {"trace": device_with_gaps()}
    # No trace file under .bench_trace/<cell>: nothing to read.
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert trace_idle_by_span.read(metric, run) is None
    assert trace_span_ms.read(
        {"span": "x", "percentile": 50, "workloads": [OPEN]}, run) is None
    seen = []
    monkeypatch.setattr(trace_reduce, "find_xplane",
                        lambda directory: seen.append(directory) or "made-up")
    monkeypatch.setattr(
        trace_idle_by_span, "program_spans",
        lambda path, pattern: among(PASS, pattern))
    # Gaps 10..40 (fetch) and 62..70 (schedule), two decode steps.
    assert trace_idle_by_span.read(metric, run) == \
        pytest.approx(30.0 / 1e6 / 2)
    assert seen == [os.path.join(REPO, ".bench_trace", OPEN)]
    assert trace_idle_by_span.read({**metric, "span": None}, run) == 0.0
    assert trace_idle_by_span.read(
        {**metric, "span": r"^engine\.(sweep|decode\.schedule)$"}, run) == \
        pytest.approx(8.0 / 1e6 / 2)
    # A program without the span (the parent commit): nothing, no error.
    assert trace_idle_by_span.read(
        {**metric, "span": r"^engine\.prefill\."}, run) is None
    # No device plane (a rehearsal), or no run of the program.
    assert trace_idle_by_span.read(metric, {"trace": None}) is None
    assert trace_idle_by_span.read(
        metric, {"trace": trace_reduce.Trace({}, [])}) is None
    assert trace_idle_by_span.read({**metric, "module": "^jit_step"},
                                   run) is None
    # Durations 30, 20, 8: the median put, in ms.
    assert trace_span_ms.read(
        {"span": r"^engine\.decode\.", "percentile": 50,
         "workloads": [OPEN]}, run) == pytest.approx(20.0 / 1e6)
    assert trace_span_ms.read(
        {"span": r"^llm\.", "percentile": 50, "workloads": [OPEN]},
        run) is None


def test_program_spans_of_a_trace_recorded_on_the_v5e():
    """PR 22's recorded train trace has host planes and none of the
    program's spans: the loader reads it and finds nothing, as it will
    on a parent commit."""
    path = os.path.join(REPO, "tests", "benchmark", "data",
                        "train-4k-1chip.v5e.xplane.pb")
    assert trace_idle_by_span.program_spans(
        path, trace_idle_by_span.PROGRAM_SPANS) == []
    fences = trace_idle_by_span.program_spans(path, r"^bench\.fence$")
    assert fences and all(end > start for _, start, end in fences)


@pytest.mark.parametrize("name, cell", sorted(NEW_METRICS.items()))
def test_new_metric_file_loads_through_the_cell(name, cell, monkeypatch):
    loaded = spec.load_cell(cell)
    metric = {m["name"]: m for m in loaded.per_layer}[name]
    assert metric["cells"] == metric["workloads"] == [cell]
    reader = spec.load_module(loaded.roots, "readers", metric["reader"])
    # Nothing to read (no trace, no such counter): None, never an error.
    # (Another test's rehearsal may have a trace there at this moment.)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert reader.read(metric, {"trace": None, "counters": {}}) is None


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
    assert counters.read(cell["engine_cpu_share.open"], run) == \
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
