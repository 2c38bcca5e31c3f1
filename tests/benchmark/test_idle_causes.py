"""What PR 57 adds to the benchmark: the join of the device's idle
gaps with the program's spans (``benchmark/idle_causes.py``), the
reader ``trace_idle_cause`` and two per-layer entries,
``device_idle_gc_share`` and ``launch_starved_share``. The split's
arithmetic on made-up device intervals and spans; the reader on runs
that saw nothing; the entries held to their files, and the ``cause``
pattern to the place in the program that opens the span (what
``test_program_spans.py`` does for the older readers' ``span``).
Nothing here is a measurement."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import idle_causes, spec, trace_reduce  # noqa: E402
from benchmark.idle_causes import GC, OUTSIDE, OWN  # noqa: E402
from benchmark.readers import counters, trace_idle_cause  # noqa: E402
from benchmark.readers import trace_span_attr  # noqa: E402

CLOSED7 = ["serve-longgen-closed", "serve-olmoe-longgen-closed",
           "serve-phi4flash-reason-closed", "serve-sdar-blockgen-closed",
           "serve-xing4-longdoc-closed", "serve-kimi-linear-reason-closed",
           "serve-solar-open2-reason-closed"]
ENGINE, STREAM = ("/host:CPU", 3), ("/host:CPU", 7)
MS = 1e6  # the made-up times below are in ms, a trace's in ns


def device(*programs) -> trace_reduce.Device:
    """A chip that ran ``(name, start, end)``, in ms, one operation a
    program."""
    modules = [trace_reduce.Event(name, start * MS, end * MS, {})
               for name, start, end in programs]
    ops = [trace_reduce.Event("fusion.1", start * MS, end * MS, {})
           for _, start, end in programs]
    return trace_reduce.Device(modules, ops)


def span(name, start, end, thread=ENGINE, **attrs):
    return (name, start * MS, end * MS, attrs, thread)


# Three decode steps and a chunk; the chip stands 10..20, 30..50 and
# 60..64. One pass of the loop covers 8..45, another 46..70.
CHIP = device(("jit_decode_step(1)", 0, 10), ("jit_decode_step(1)", 20, 30),
              ("jit_prefill_chunk(2)", 50, 60), ("jit_decode_step(1)", 64, 70))
LOOP = [
    span("engine.iteration", 8, 45),
    span("engine.decode.emit", 9, 14, lock_wait_us=3000, rows=2),
    span("engine.decode.schedule", 14, 16, lock_wait_us=100),
    span("engine.decode.launch", 16, 19, rows=2, starved=1),
    span("engine.decode.fetch", 19, 31),
    span("engine.decode.emit", 31, 36, lock_wait_us=40),
    span("engine.iteration", 46, 70),
    span("engine.prefill.launch", 47, 49, tokens=128, starved=1),
    span("engine.prefill.first_token", 49, 61),
    span("engine.decode.launch", 62, 63, starved=0),
    # Another thread's spans decide nothing, whatever they cover.
    span("llm.stream.take", 0, 70, thread=STREAM),
    span("serve.stream.put", 10, 20, thread=STREAM),
]
COLLECTION = span(GC, 33, 41, thread=STREAM, generation=2, pause_us=8000)


def seconds(table: dict) -> dict:
    return {cause: pytest.approx(line["seconds"] * 1e3)
            for cause, line in table.items()}


def test_a_gap_is_split_by_overlap_among_the_engines_leaves():
    table = idle_causes.idle_causes(CHIP, LOOP)
    # 10..20: emit 4, schedule 2, launch 3, fetch 1. 30..50: fetch 1,
    # emit 5, the pass itself 36..45, between the passes 45..46, the
    # next pass 46..47, launch 47..49, first_token 49..50. 60..64:
    # first_token 1, the pass 61..62 and 63..64, launch 62..63.
    assert {c: line["seconds"] * 1e3 for c, line in table.items()} == \
        seconds({"engine.decode.emit": {"seconds": 9e-3},
                 "engine.decode.schedule": {"seconds": 2e-3},
                 "engine.decode.launch": {"seconds": 4e-3},
                 "engine.decode.fetch": {"seconds": 2e-3},
                 OWN: {"seconds": 12e-3},
                 OUTSIDE: {"seconds": 1e-3},
                 "engine.prefill.launch": {"seconds": 2e-3},
                 "engine.prefill.first_token": {"seconds": 2e-3}})
    # Every idle second has a cause, and no second two.
    assert sum(line["seconds"] for line in table.values()) == \
        pytest.approx(34e-3)
    emit = table["engine.decode.emit"]
    assert emit["gaps"] == 2 and emit["longest_s"] == pytest.approx(5e-3)
    # The two emits the gaps fell under waited 3000 and 40 us for the
    # engine's lock; each is counted once.
    assert emit["lock_wait_us"] == 3040
    assert table["engine.decode.schedule"]["lock_wait_us"] == 100
    assert table[OWN]["lock_wait_us"] == 0
    # The first gap was ended by the decode launch at 16 (starved), the
    # second by the chunk's at 47 (starved), the third by the launch at
    # 62 (not).
    assert emit["ended_starved"] == 2
    assert table["engine.prefill.first_token"]["ended_starved"] == 1
    assert table["engine.prefill.first_token"]["gaps"] == 2


def test_a_collection_comes_before_the_leaf_it_fell_in():
    table = idle_causes.idle_causes(CHIP, LOOP + [COLLECTION])
    # 33..41 of the second gap: 3 ms that were emit's, 5 the pass's.
    assert table[GC]["seconds"] == pytest.approx(8e-3)
    assert table[GC]["gaps"] == 1 and table[GC]["ended_starved"] == 1
    assert table["engine.decode.emit"]["seconds"] == pytest.approx(6e-3)
    assert table[OWN]["seconds"] == pytest.approx(7e-3)
    assert sum(line["seconds"] for line in table.values()) == \
        pytest.approx(34e-3)
    # Two collections that overlap on two threads count once, and one
    # that ran while the chip was busy counts nothing.
    more = [span(GC, 35, 43, thread=ENGINE, generation=1),
            span(GC, 0, 9, thread=STREAM, generation=2)]
    table = idle_causes.idle_causes(CHIP, LOOP + [COLLECTION] + more)
    assert table[GC]["seconds"] == pytest.approx(10e-3)


def test_a_gap_under_no_span_and_a_chip_with_no_gap():
    # No span of the engine at all: every gap is outside the loop's.
    table = idle_causes.idle_causes(CHIP, [s for s in LOOP
                                           if s[4] == STREAM])
    assert set(table) == {OUTSIDE}
    assert table[OUTSIDE]["seconds"] == pytest.approx(34e-3)
    assert table[OUTSIDE]["gaps"] == 3
    assert table[OUTSIDE]["ended_starved"] == 0
    busy = device(("jit_decode_step(1)", 0, 10),
                  ("jit_decode_step(1)", 10, 20))
    assert idle_causes.idle_causes(busy, LOOP) == {}
    assert idle_causes.idle_causes(trace_reduce.Device([], []), LOOP) == {}


def test_the_engines_thread_is_found_by_its_spans():
    assert idle_causes.engine_thread(LOOP) == ENGINE
    assert idle_causes.engine_thread(
        [s for s in LOOP if s[4] == STREAM]) is None
    # A second, quieter engine in the process: the busier line's.
    other = ("/host:CPU", 9)
    quiet = [span("engine.iteration", 0, 70, thread=other),
             span("engine.idle", 1, 69, thread=other)]
    assert idle_causes.engine_thread(LOOP + quiet) == ENGINE
    table = idle_causes.idle_causes(CHIP, LOOP + quiet)
    assert "engine.idle" not in table
    # A collection's span on the engine's own line is no leaf of it.
    assert idle_causes.engine_thread(
        [span(GC, 0, 1, thread=other)] + LOOP) == ENGINE


def test_innermost_flattens_nested_spans():
    spans = [span("engine.iteration", 0, 10), span("engine.sweep", 1, 2),
             span("engine.decode.emit", 4, 6), span("engine.idle", 12, 13)]
    flat = [(start / MS, end / MS, spans[at][0])
            for start, end, at in idle_causes.innermost(spans)]
    assert flat == [(0, 1, "engine.iteration"), (1, 2, "engine.sweep"),
                    (2, 4, "engine.iteration"),
                    (4, 6, "engine.decode.emit"),
                    (6, 10, "engine.iteration"), (12, 13, "engine.idle")]


def test_launches_starved_is_held_to_the_trace():
    held = idle_causes.starved_held(CHIP, LOOP)
    # Three gaps over 1 ms end at a decode or prefill program; two of
    # the launches that ended them said starved, each of its program's
    # kind; the one launch that said 0 follows a gap all the same.
    assert held == {"gaps_over_ms": 1.0, "gaps": 3, "ended_by_starved": 2,
                    "ended_by_own_kind": 3, "drained_inside_the_launch": 0,
                    "launches_unstarved": 1,
                    "unstarved_after_gap": 1, "launches_starved": 2}
    assert idle_causes.starved_held(CHIP, LOOP, over_ns=15 * MS)["gaps"] == 1
    # A gap that ends at another program (the first token's sampler)
    # is not the launches' to answer for.
    chip = device(("jit_prefill_chunk(2)", 0, 10), ("jit__argmax(3)", 14, 15),
                  ("jit_decode_step(1)", 15, 20))
    assert idle_causes.starved_held(chip, LOOP)["gaps"] == 0


def test_the_device_clocks_lead_is_taken_off_the_spans():
    """On the v5e a program was seen to start 0.46 ms before the launch
    that sent it opened: the planes' clocks differ. A starved launch's
    program cannot start before the launch opens, so the largest such
    lead is the least the device's clock is ahead by."""
    assert idle_causes.clock_lead_ns(CHIP, LOOP) == 0
    # The whole host plane 1.5 ms late: the starved decode launch
    # (16..19, program at 20) now opens at 17.5, still before its
    # program; the chunk's (47..49, program at 50) too. No lead shows.
    late = [s[:1] + (s[1] + 1.5 * MS, s[2] + 1.5 * MS) + s[3:]
            for s in LOOP]
    assert idle_causes.clock_lead_ns(CHIP, late) == 0
    # 5 ms late: the decode launch opens at 21, a ms after its program
    # started, and the chunk's at 52, 2 ms after: the lead is 2 ms at
    # least, and with it off the join is 3 ms from where it was.
    later = [s[:1] + (s[1] + 5 * MS, s[2] + 5 * MS) + s[3:] for s in LOOP]
    assert idle_causes.clock_lead_ns(CHIP, later) == pytest.approx(2 * MS)
    shifted = idle_causes.on_the_devices_clock(CHIP, later)
    assert shifted[0][1] == pytest.approx(11 * MS)
    assert idle_causes.on_the_devices_clock(CHIP, LOOP) is LOOP
    # A launch that does not say starved (the parent's, or one behind a
    # running step) bounds nothing: its program may start much later.
    silent = [s for s in later if not s[3].get("starved")]
    assert idle_causes.clock_lead_ns(CHIP, silent) == 0
    # Held to the trace on the corrected clock: the chunk's launch
    # again ends the second gap, though it opened after the gap's end
    # by the host's own clock. The lead is a lower bound: the last
    # launch (62, now 65) still opens after the program it sent (64).
    held = idle_causes.starved_held(CHIP, later)
    assert held["gaps"] == 3 and held["ended_by_own_kind"] == 2
    assert held["ended_by_starved"] == 3
    uncorrected = idle_causes.Launches(later).ending(50 * MS)
    assert uncorrected[0] == "engine.decode.launch"


def test_the_longest_gaps_say_what_ran_around_them():
    first, second, third = idle_causes.longest(CHIP, LOOP + [COLLECTION])
    assert first["ms"] == pytest.approx(20.0)
    assert first["gap_at_s"] == pytest.approx(0.020)
    assert list(first["causes_ms"])[0] == GC
    assert first["after"] == "jit_decode_step"
    assert first["before"] == "jit_prefill_chunk"
    assert first["ended_by"] == "engine.prefill.launch"
    assert first["starved"] == 1
    assert (second["ms"], third["ms"]) == (pytest.approx(10.0),
                                           pytest.approx(4.0))
    assert third["starved"] == 0
    assert len(idle_causes.longest(CHIP, LOOP, top=2)) == 2


# ----------------------------------------------------- the reader, the files


def traced(monkeypatch, spans):
    """A run whose trace shows ``CHIP`` and ``spans``."""
    monkeypatch.setattr(trace_reduce, "find_xplane",
                        lambda directory: "made-up")
    monkeypatch.setattr(trace_span_attr, "attributed_spans",
                        lambda path: spans)
    return {"trace": trace_reduce.Trace({0: CHIP}, []), "counters": {}}


def metric(name: str) -> dict:
    loaded = spec.load_cell("serve-kimi-linear-reason-closed")
    return {m["name"]: m for m in loaded.per_layer}[name]


def test_the_reader_gives_the_share_of_the_window(monkeypatch):
    gc_share = metric("device_idle_gc_share")
    run = traced(monkeypatch, LOOP + [COLLECTION])
    # 8 ms of a 70 ms window; device_idle_share reads 34 of 70 there.
    assert trace_idle_cause.read(gc_share, run) == \
        pytest.approx(100 * 8 / 70)
    # No collection in the window, in a program that records them: 0.
    assert trace_idle_cause.read(
        gc_share, traced(monkeypatch, LOOP)) == 0.0
    # Any cause can be asked for: a leaf, or what no span covers.
    run = traced(monkeypatch, LOOP + [COLLECTION])
    assert trace_idle_cause.read(
        {**gc_share, "cause": r"^engine\.decode\."}, run) == \
        pytest.approx(100 * 14 / 70)
    assert trace_idle_cause.read(
        {**gc_share, "cause": "^outside"}, run) == pytest.approx(100 / 70)


def test_the_reader_reads_nothing_from_a_run_that_saw_nothing(monkeypatch):
    gc_share = metric("device_idle_gc_share")
    # The parent commit: the engine's spans, no ``runtime.gc`` and no
    # ``starved``. A 0 there would say "no collection ran".
    parent = [s[:3] + ({k: v for k, v in s[3].items() if k != "starved"},)
              + s[4:] for s in LOOP]
    assert not idle_causes.records_causes(parent)
    assert idle_causes.records_causes(LOOP)
    assert idle_causes.records_causes(parent + [COLLECTION])
    assert trace_idle_cause.read(
        gc_share, traced(monkeypatch, parent)) is None
    # No program span at all; a device with no gap; no device.
    assert trace_idle_cause.read(gc_share, traced(monkeypatch, [])) is None
    run = traced(monkeypatch, LOOP)
    run["trace"] = trace_reduce.Trace(
        {0: device(("jit_decode_step(1)", 0, 10))}, [])
    assert trace_idle_cause.read(gc_share, run) is None
    run["trace"] = trace_reduce.Trace({}, [])
    assert trace_idle_cause.read(gc_share, run) is None
    # No trace (an untraced run), or no kept file to find the spans in.
    assert trace_idle_cause.read(gc_share, {"trace": None}) is None
    run = traced(monkeypatch, LOOP)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert trace_idle_cause.read(gc_share, run) is None


def test_the_newest_kept_trace_is_the_runs_own(monkeypatch, tmp_path):
    """Seven cells share the entry; a trace kept from an earlier run of
    a cell listed before this one must not be read for it."""
    gc_share = metric("device_idle_gc_share")
    kept = {}
    for age, cell in enumerate(("serve-longgen-closed",
                                "serve-kimi-linear-reason-closed")):
        path = tmp_path / f"{cell}.xplane.pb"
        path.write_bytes(b"")
        os.utime(path, (1000 + age, 1000 + age))
        kept[os.path.join(REPO, ".bench_trace", cell)] = str(path)
    monkeypatch.setattr(trace_reduce, "find_xplane", kept.get)
    assert trace_reduce.find_trace(gc_share).endswith(
        "serve-longgen-closed.xplane.pb")
    assert trace_idle_cause.kept_trace(gc_share).endswith(
        "serve-kimi-linear-reason-closed.xplane.pb")
    assert trace_idle_cause.kept_trace({**gc_share, "workloads": []}) is None
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert trace_idle_cause.kept_trace(gc_share) is None


def test_the_starved_share_is_a_formula_over_the_window():
    starved = metric("launch_starved_share")
    assert starved["reader"] == "counters"
    run = {"counters": {"launches_starved": 30, "decode_steps": 900,
                        "prefill_chunks": 100, "max_batch_size": 64}}
    assert counters.read(starved, run) == pytest.approx(3.0)
    # The parent commit has no such counter; an empty window no launch.
    del run["counters"]["launches_starved"]
    assert counters.read(starved, run) is None
    assert counters.read(starved, {"counters": {
        "launches_starved": 0, "decode_steps": 0,
        "prefill_chunks": 0}}) is None


@pytest.mark.parametrize("name, layer, source, reader", [
    ("device_idle_gc_share", "Core runtime", "program_span",
     "trace_idle_cause"),
    ("launch_starved_share", "Engine scheduler and cache",
     "program_counter", "counters")])
def test_the_two_entries_and_their_files(name, layer, source, reader):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": source, "layer": layer,
                     "moves": "serve_tokens_per_s", "workloads": CLOSED7}
    # (They filled the table, 126 and the two, until PR 59 folded it by
    # quantity; no place in it and no length is pinned any more.)
    for cell in CLOSED7:
        loaded = {m["name"]: m for m in spec.load_cell(cell).per_layer}
        assert loaded[name]["reader"] == reader
        assert loaded[name]["cells"] == CLOSED7
    # No open cell and no train cell reports them.
    for cell in ("serve-chat-steady", "train-4k-1chip"):
        assert name not in {m["name"]
                            for m in spec.load_cell(cell).per_layer}


def test_the_cause_is_a_span_the_program_opens():
    """The pattern spells one name, and ``util/tracing.py`` opens a
    ``phase`` of that name in its ``gc.callbacks`` entry; the counter
    the formula reads is one the engine serves."""
    pattern = metric("device_idle_gc_share")["cause"]
    assert pattern == r"^runtime\.gc$"
    name = pattern.strip("^$").replace("\\.", ".")
    assert name == idle_causes.GC
    with open(os.path.join(REPO, "ray_tpu", "util", "tracing.py")) as f:
        source = f.read()
    opened = set(re.findall(r"\bphase\(\s*\"([a-z_.]+)\"", source))
    assert name in opened
    assert "gc.callbacks.append(_on_gc)" in source
    # The span reader loads it: its name starts with ``runtime.``.
    assert re.search(trace_span_attr.PROGRAM_SPANS, name)
    # Every leaf the table can name is a phase the engine opens, and
    # the attributes it reads are set there.
    with open(os.path.join(REPO, "ray_tpu", "serve", "llm_engine",
                           "engine.py")) as f:
        engine = f.read()
    opened = set(re.findall(r"\bphase\(\s*\"([a-z_.]+)\"", engine))
    assert {"engine.iteration", "engine.decode.launch",
            "engine.prefill.launch"} <= opened
    assert all(idle_causes.LAUNCH.search(name) for name in
               ("engine.decode.launch", "engine.prefill.launch"))
    assert "set(starved=" in engine and "lock_wait_us=" in engine
    from ray_tpu.serve.llm_engine import ENGINE_STAT_KEYS

    formula = metric("launch_starved_share")["formula"]
    assert set(re.findall(r"[a-z_]+", formula)) <= set(ENGINE_STAT_KEYS)
