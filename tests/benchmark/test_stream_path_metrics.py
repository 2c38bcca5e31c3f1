"""What PR 37 adds to the benchmark: ten per-layer metrics that read
the stream path's and the core runtime's spans and one new counter, two
readers (``trace_span_attr``, ``trace_span_pair``), a table of a kept
trace's spans (``span_table.py``) and their arithmetic on made-up spans; every entry held to its file, found and read through
the harness's own loader; and every span name a new pattern spells held
to a ``tracing.phase(...)`` in the file that opens it (what
``test_program_spans.py`` does for the engine's and the replica's). The
block-diffusion cell is in none of the lists: ``test_sdar_metrics.py``
holds that cell to PR 35's set. Nothing here is a measurement."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec, trace_reduce  # noqa: E402
from benchmark.readers import trace_span_attr, trace_span_pair  # noqa: E402

CLOSED3 = ["serve-longgen-closed", "serve-olmoe-longgen-closed",
           "serve-phi4flash-reason-closed"]
OPEN = ["serve-chat-steady"]
ENGINE, ENTRY, CORE = "Engine scheduler and cache", "Entry points", \
    "Core runtime"
TOKENS, TTFT = "serve_tokens_per_s", "ttft_p90_ms"
# name -> (cells, layer, source, moves, reader)
ENTRIES = {
    "engine_stood_ms_per_step":
        (CLOSED3, ENGINE, "program_counter", TOKENS, "counters"),
    "process_cpu_ms_per_step":
        (CLOSED3, CORE, "program_counter", TOKENS, "counters"),
    "stream_put_ms_p50":
        (CLOSED3, ENTRY, "program_span", TOKENS, "trace_span_ms"),
    "stream_take_age_ms_p95":
        (CLOSED3, ENTRY, "program_span", TOKENS, "trace_span_attr"),
    "stream_get_age_ms_p95":
        (CLOSED3, ENTRY, "program_span", TOKENS, "trace_span_attr"),
    "actor_call_queue_ms_p95":
        (CLOSED3, CORE, "program_span", TOKENS, "trace_span_attr"),
    "actor_call_queue_ms_p95.open":
        (OPEN, CORE, "program_span", TTFT, "trace_span_attr"),
    "actor_call_wake_ms_p95":
        (CLOSED3, CORE, "program_span", TOKENS, "trace_span_attr"),
    "ttft_ingress_ms_mean.open":
        (OPEN, ENTRY, "program_span", TTFT, "trace_span_pair"),
    "ttft_delivery_ms_mean.open":
        (OPEN, ENTRY, "program_span", TTFT, "trace_span_pair"),
}
# Which file opens which span: where a pattern's names are looked for.
OPENED_IN = {
    "serve.handle.send": "ray_tpu/serve/router.py",
    "serve.stream.get": "ray_tpu/serve/router.py",
    "serve.replica.admit": "ray_tpu/serve/replica.py",
    "serve.stream.put": "ray_tpu/serve/replica.py",
    "llm.stream.take": "ray_tpu/serve/llm_engine/engine.py",
    "engine.prefill.first_token": "ray_tpu/serve/llm_engine/engine.py",
    "runtime.actor.submit": "ray_tpu/_private/worker.py",
    "runtime.get": "ray_tpu/_private/worker.py",
    "runtime.actor.run": "ray_tpu/_private/actor_runtime.py",
}


def bench_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def on_file(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark/metrics", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------- the readers' arithmetic

# A decode step's worth of the stream path as the profiler would show
# it, in ns, on three threads: a replica stream thread takes a token and
# puts it (the put holds the actor call's submit and the blocking get),
# the queue actor's thread runs the put, a client thread gets the chunk.
STREAM, ACTOR, CLIENT = ("/host:CPU", 1), ("/host:CPU", 2), ("/host:CPU", 3)
R = {"request": "aaaa"}
STEP = [
    ("llm.stream.take", 100, 140, {**R, "tokens": 1, "age_us": 300,
                                   "cpu_us": 30}, STREAM),
    ("serve.stream.put", 150, 950, {**R, "tokens": 1, "cpu_us": 200},
     STREAM),
    ("runtime.actor.submit", 160, 260, {"cpu_us": 80}, STREAM),
    ("runtime.get", 270, 940, {"age_us": 500, "cpu_us": 70}, STREAM),
    ("runtime.actor.run", 400, 520, {"method": "_QueueActor.put_nowait",
                                     "age_us": 140, "cpu_us": 100}, ACTOR),
    ("serve.stream.get", 50, 1200, {**R, "tokens": 1, "age_us": 700,
                                    "cpu_us": 250}, CLIENT),
    ("runtime.actor.submit", 60, 160, {"cpu_us": 90}, CLIENT),
    ("runtime.get", 170, 1190, {"age_us": 900, "cpu_us": 110}, CLIENT),
    # The engine's own spans carry cpu_us too and match neither pattern.
    ("engine.decode.emit", 0, 90, {"rows": 16, "cpu_us": 60}, ("/host:CPU", 0)),
]
STREAM_SPANS = r"^(serve\.stream|llm\.stream)\."
RUNTIME_SPANS = r"^runtime\."


def own_cpu(spans, pattern):
    """The spans' own CPU by ``span_table.exclusive``, summed over the
    names matching ``pattern``."""
    from benchmark.span_table import exclusive

    own = exclusive(spans, lambda s: s[3].get("cpu_us"))
    return sum(value for span, value in zip(spans, own)
               if value is not None and re.search(pattern, span[0]))


def test_exclusive_takes_the_nested_spans_off_their_parent():
    from benchmark.span_table import exclusive

    # put 200 - (80 + 70); get 250 - (90 + 110); the take has no child.
    assert own_cpu(STEP, STREAM_SPANS) == 30 + 50 + 50
    # The runtime's spans have none nested in them here: unchanged.
    assert own_cpu(STEP, RUNTIME_SPANS) == 80 + 70 + 100 + 90 + 110
    # Nothing is counted twice: the two sums are the three threads' CPU.
    assert own_cpu(STEP, STREAM_SPANS) + own_cpu(STEP, RUNTIME_SPANS) == \
        30 + 200 + 100 + 250
    # A child claiming more than its parent (rounding to whole
    # microseconds) leaves the parent 0, never a negative share.
    skewed = [("outer", 0, 10, {"cpu_us": 3}, STREAM),
              ("inner", 1, 9, {"cpu_us": 4}, STREAM)]
    assert exclusive(skewed, lambda s: s[3].get("cpu_us")) == [0.0, 4.0]
    # Two levels: a grandchild is taken off its parent only; another
    # thread's span is nobody's child.
    deep = [("a", 0, 100, {"cpu_us": 50}, STREAM),
            ("b", 10, 90, {"cpu_us": 30}, STREAM),
            ("c", 20, 80, {"cpu_us": 10}, STREAM),
            ("d", 0, 100, {"cpu_us": 7}, ACTOR)]
    assert exclusive(deep, lambda s: s[3].get("cpu_us")) == \
        [20.0, 20.0, 10.0, 7.0]
    assert exclusive(deep, lambda s: s[2] - s[1]) == \
        [20.0, 20.0, 60.0, 100.0]
    # A span without the attribute (the parent commit's) has no share,
    # and takes none off the span around it.
    bare = [("serve.stream.put", 0, 9, {"cpu_us": 5}, STREAM),
            ("runtime.get", 1, 8, {}, STREAM)]
    assert exclusive(bare, lambda s: s[3].get("cpu_us")) == [5.0, None]


def test_span_attr_reads_a_percentile_of_an_attribute(monkeypatch):
    cell = CLOSED3[0]
    run = {"trace": None}  # the spans are the host's: no device needed
    age = {"spans": r"^runtime\.get$", "attr": "age_us", "percentile": 95,
           "workloads": [cell]}
    # No trace file under .bench_trace/<cell>: nothing to read.
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert trace_span_attr.read(age, run) is None
    seen = []
    monkeypatch.setattr(trace_reduce, "find_xplane",
                        lambda directory: seen.append(directory) or "made-up")
    monkeypatch.setattr(trace_span_attr, "attributed_spans",
                        lambda path: STEP)
    # Ages 500 and 900 us: the 95th percentile, in ms.
    assert trace_span_attr.read(age, run) == pytest.approx(0.880)
    assert seen == [os.path.join(REPO, ".bench_trace", cell)]
    assert trace_span_attr.read({**age, "percentile": 50}, run) == \
        pytest.approx(0.700)
    # The stream path's hand-offs: ages 300 and 700 us.
    assert trace_span_attr.read({**age, "spans": STREAM_SPANS,
                                 "percentile": 100}, run) == \
        pytest.approx(0.700)
    # No such span, no such attribute (the parent commit): nothing,
    # never an error.
    assert trace_span_attr.read({**age, "spans": r"^serve\.router\."},
                                run) is None
    assert trace_span_attr.read({**age, "attr": "wait_us"}, run) is None


# Two requests on their way in and their first tokens on the way out;
# a third whose send fell before the window, a fourth with no token yet.
def span(name, start, end, request, **attrs):
    return (name, start, end, {"request": request, **attrs}, CLIENT)


WAYS = [
    span("serve.handle.send", 1_000, 1_200, "r1"),
    span("serve.replica.admit", 1_500, 1_900, "r1", age_us=1),
    span("engine.prefill.first_token", 9_000, 9_400, "r1"),
    span("serve.stream.get", 2_000, 5_000, "r1"),  # an empty poll
    span("serve.stream.get", 5_100, 11_400, "r1", tokens=1),
    span("serve.stream.get", 11_500, 12_000, "r1", tokens=2),
    span("serve.handle.send", 20_000, 20_100, "r2"),
    span("serve.handle.send", 26_000, 26_100, "r2"),  # a backpressure retry
    span("serve.replica.admit", 20_300, 20_700, "r2"),
    span("serve.replica.admit", 26_300, 26_500, "r2"),
    span("engine.prefill.first_token", 30_000, 30_600, "r2"),
    span("serve.stream.get", 29_000, 31_600, "r2", tokens=1),
    span("serve.replica.admit", 40_000, 40_100, "r3"),
    span("serve.stream.get", 40_500, 41_000, "r3", tokens=1),
    span("serve.handle.send", 50_000, 50_100, "r4"),
    span("engine.prefill.first_token", 51_000, 51_500, "r4"),
    span("runtime.get", 0, 99_000, None),
]
INGRESS = {"from_spans": r"^serve\.handle\.send$", "from_edge": "start",
           "to_spans": r"^serve\.replica\.admit$", "to_edge": "end"}
DELIVERY = {"from_spans": r"^engine\.prefill\.first_token$",
            "from_edge": "end", "to_spans": r"^serve\.stream\.get$",
            "to_edge": "end", "to_carrying": "tokens"}


def test_span_pair_is_a_mean_over_the_requests_that_have_both(monkeypatch):
    # The first send to the first admission that ends after it.
    assert trace_span_pair.pairs_ns(WAYS, INGRESS) == [900, 700]
    # The first token's end to the end of the first get that carried a
    # token and ended no earlier: not the empty poll, not the later get.
    assert trace_span_pair.pairs_ns(WAYS, DELIVERY) == [2_000, 1_000]
    # From the start, to the start: the edges are the file's to name.
    assert trace_span_pair.pairs_ns(
        WAYS, {**INGRESS, "from_edge": "end", "to_edge": "start"}) == \
        [300, 200]
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    metric = {**INGRESS, "workloads": OPEN}
    assert trace_span_pair.read(metric, {"trace": None}) is None
    monkeypatch.setattr(trace_reduce, "find_xplane",
                        lambda directory: "made-up")
    monkeypatch.setattr(trace_span_attr, "attributed_spans",
                        lambda path: WAYS)
    assert trace_span_pair.read(metric, {"trace": None}) == \
        pytest.approx(800 / 1e6)
    assert trace_span_pair.read({**DELIVERY, "workloads": OPEN},
                                {"trace": None}) == pytest.approx(1500 / 1e6)
    # A program without the spans, or a window with no whole pair.
    assert trace_span_pair.read(
        {**metric, "from_spans": r"^serve\.proxy\."}, {"trace": None}) is None
    monkeypatch.setattr(trace_span_attr, "attributed_spans",
                        lambda path: WAYS[-5:])
    assert trace_span_pair.read(metric, {"trace": None}) is None


def test_a_trace_recorded_on_the_v5e_has_none_of_the_spans():
    """PR 22's recorded train trace: the loader reads it and finds no
    program span, as it will on a parent commit."""
    path = os.path.join(REPO, "tests", "benchmark", "data",
                        "train-4k-1chip.v5e.xplane.pb")
    assert trace_span_attr.attributed_spans(path) == []
    assert trace_reduce.program_spans(path, r"^bench\.fence$")


def test_the_span_table_partitions_the_cpu_by_kind_of_thread():
    """``benchmark/span_table.py`` on the made-up step: what a partition
    is read from by hand for a cell no metric lists."""
    from benchmark import span_table

    lines = span_table.table(STEP, steps=2)
    spans = {line["span"]: line for line in lines if "span" in line}
    put = spans["serve.stream.put"]
    assert (put["n"], put["wall_ms"]) == (1, pytest.approx(800 / 1e6))
    # Its own: 200 - 80 - 70 us of CPU in 800 - 100 - 670 ns of wall.
    assert put["own_cpu_ms"] == pytest.approx(0.050)
    assert put["per_step"] == pytest.approx(0.025)
    assert put["own_stood_ms"] == pytest.approx(30 / 1e6 - 0.050)
    got = spans["runtime.get"]
    assert got["n"] == 2 and got["age_ms_p50"] == pytest.approx(0.7)
    assert got["age_ms_max"] == pytest.approx(0.9)
    assert "age_ms_p50" not in put
    kinds = {line["threads"]: line for line in lines if "threads" in line}
    assert {k: (v["n"], round(v["own_cpu_ms"], 3))
            for k, v in kinds.items()} == {
        "engine": (1, 0.06), "stream": (1, 0.23), "client": (1, 0.25),
        "actor": (1, 0.1)}
    # Every microsecond of every thread once.
    assert sum(v["own_cpu_ms"] for v in kinds.values()) == \
        pytest.approx((60 + 30 + 200 + 100 + 250) / 1e3)
    # Gaps between the engine thread's consecutive leaves, from twenty.
    passes = [(name, 100 * i + at, 100 * i + at + 30, {}, ("/host:CPU", 0))
              for i in range(25)
              for name, at in (("engine.decode.emit", 0),
                               ("engine.sweep", 50))]
    gaps = {line["gap"]: line for line in span_table.table(passes)
            if "gap" in line}
    assert gaps["engine.decode.emit -> engine.sweep"]["ms_p50"] == \
        pytest.approx(20 / 1e6)
    assert gaps["engine.sweep -> engine.decode.emit"]["n"] == 24
    assert "per_step" not in span_table.table(STEP)[0]


# ------------------------------------------- the entries and their files


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_is_its_files(name):
    cells, layer, source, moves, reader = ENTRIES[name]
    bench = bench_json()
    (declared,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert declared == {
        "name": name, "unit": "ms", "better": "lower", "source": source,
        "layer": layer, "moves": moves, "workloads": cells}
    file = on_file(name)
    for key in ("name", "unit", "layer", "moves"):
        assert file[key] == declared[key]
    assert file["cells"] == cells and file["reader"] == reader
    assert len(file["what"]) > 60
    # An end-to-end metric every one of the cells reports (the entry is
    # found by its name: where it stands in the list says nothing).
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == moves]
    assert set(cells) <= set(moved["workloads"])
    assert "serve-sdar-blockgen-closed" not in cells


@pytest.mark.parametrize("name, cell", [
    (name, cell) for name in sorted(ENTRIES) for cell in ENTRIES[name][0]])
def test_each_cell_loads_it_and_reads_nothing_from_nothing(name, cell,
                                                           monkeypatch):
    loaded = spec.load_cell(cell)
    (metric,) = [m for m in loaded.per_layer if m["name"] == name]
    assert metric["cells"] == metric["workloads"] == ENTRIES[name][0]
    reader = spec.load_module(loaded.roots, "readers", metric["reader"])
    # No trace, no such counter (the parent commit): None, never an error.
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert reader.read(metric, {"trace": None, "counters": {}}) is None
    assert reader.read(metric, {"trace": None, "counters": {
        "decode_steps": 0, "loop_wall_us": 0, "fetch_wait_us": 0,
        "loop_cpu_us": 0, "process_cpu_us": 0}}) is None


def test_the_counter_metrics_read_the_issues_numbers():
    """The Mistral closed cell by the ledger's PR 36 line: 9.45 ms of
    host a step at a CPU share of 16.2%: 7.9 ms stood."""
    cell = {m["name"]: m for m in spec.load_cell(CLOSED3[0]).per_layer}
    counters = spec.load_module([os.path.join(REPO, "benchmark")],
                                "readers", "counters")
    run = {"counters": {
        "decode_steps": 1000, "loop_wall_us": 14_300_000,
        "fetch_wait_us": 4_850_000, "loop_cpu_us": 1_531_000,
        "process_cpu_us": 12_000_000}}
    assert counters.read(cell["engine_stood_ms_per_step"], run) == \
        pytest.approx(7.919)
    assert counters.read(cell["process_cpu_ms_per_step"], run) == 12.0
    from ray_tpu.serve.llm_engine.engine import ENGINE_STAT_KEYS

    assert {"process_cpu_us", "loop_cpu_us", "loop_wall_us",
            "fetch_wait_us", "decode_steps"} <= set(ENGINE_STAT_KEYS)


# ----------------------------------- every name is one the program opens


def names_in(pattern: str) -> list:
    """The span names a pattern spells out, whole (``^a\\.b$``) or by
    their heads (``^(a\\.b|c\\.d)\\.`` and ``^a\\.``: every opened span
    under one of them)."""
    whole = re.fullmatch(r"\^((?:[a-z_]|\\\.)+)\$", pattern)
    if whole:
        return [whole.group(1).replace("\\.", ".")]
    heads = re.fullmatch(
        r"\^(?:\(((?:[a-z_|]|\\\.)+)\)|((?:[a-z_]|\\\.)+))\\\.", pattern)
    assert heads, f"{pattern!r}: teach names_in this shape of pattern"
    return [head.replace("\\.", ".") + "."
            for head in (heads.group(1) or heads.group(2)).split("|")]


def test_every_span_a_new_pattern_names_is_one_the_program_opens():
    assert names_in(r"^runtime\.actor\.run$") == ["runtime.actor.run"]
    opened = {}
    for name, source in OPENED_IN.items():
        with open(os.path.join(REPO, source)) as f:
            found = re.findall(r"\bphase\(\s*\"([a-z_.]+)\"", f.read())
        assert name in found, f"{source} opens no tracing.phase({name!r})"
        opened[name] = source
    named = {}
    for name in ENTRIES:
        file = on_file(name)
        patterns = [file[key] for key in ("span", "spans", "from_spans",
                                          "to_spans") if key in file]
        assert patterns or file["reader"] == "counters", name
        named[name] = [n for p in patterns for n in names_in(p)]
    assert sum(map(len, named.values())) >= 10
    for name, spelled in named.items():
        for wanted in spelled:
            hits = [o for o in opened if o == wanted or (
                wanted.endswith(".") and o.startswith(wanted))]
            assert hits, f"{name}: no tracing.phase(...) opens {wanted!r}"
    # And the attributes the files read are ones those spans are given.
    for name in ENTRIES:
        file = on_file(name)
        if file["reader"] == "trace_span_attr":
            assert file["attr"] == "age_us"
        if "to_carrying" in file:
            assert file["to_carrying"] == "tokens"
    for source in ("ray_tpu/serve/router.py", "ray_tpu/_private/worker.py",
                   "ray_tpu/_private/actor_runtime.py",
                   "ray_tpu/serve/llm_engine/engine.py"):
        with open(os.path.join(REPO, source)) as f:
            assert "age_us=" in f.read(), source
