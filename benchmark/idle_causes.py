"""A kept trace's device idle gaps, each put down to what the serving
process was doing: ``python3 benchmark/idle_causes.py <trace directory
or .xplane.pb>`` after a run with ``--trace 1 --keep-trace`` (the
directory is ``.bench_trace/<cell>``).

The gaps are the first chip's: the complement of
``trace_reduce.busy_intervals`` between its first and its last
operation, the gaps ``trace_reduce.breakdown`` walks. A gap is split BY
OVERLAP, not put whole under what lay at its middle, among causes in
this order:

1. ``runtime.gc``: the part during which a collection ran on any
   thread of the process (``ray_tpu/util/tracing.py``; every other
   thread stands for as long);
2. else the engine thread's innermost open span at that instant
   (``engine.decode.emit``, ``engine.prefill.first_token``, ...); the
   engine's thread is the line whose spans match ``^engine\\.``
   (``span_table.THREAD_KINDS``; the line with most such spans where
   a process hosts several engines);
3. else ``engine.iteration (own)``: inside a pass of the loop and
   under none of its leaves;
4. else ``outside the loop's spans``.

``idle_causes`` takes what ``trace_reduce.breakdown`` has in hand (the
loaded device) and the program's spans as
``readers/trace_span_attr.attributed_spans`` gives them, so that
``breakdown`` can call it and the ledger's ``idle_gaps`` carry these
names. For each cause: its seconds, the number of gaps it had a part
of, its longest stretch inside one gap, the sum of ``lock_wait_us`` of
the spans those stretches fell under, and how many of those gaps were
ended by a launch that carried ``starved=1`` (the launch that ended a
gap: the last ``engine.decode.launch`` or ``engine.prefill.launch``
opened before the gap's end; with the device drained it is the one the
next program came from).

The two planes' clocks are not quite one: on the v5e a program was
seen to start up to 0.5 ms BEFORE the launch that sent it opened
(PR 57). A launch that says ``starved=1`` found the device drained, so
its program is the first of its kind to start from then on, and cannot
start before the launch opens: the largest such lead is taken off the
spans' times before the join (``clock_lead_ns``; a lower bound of the
offset, 0 for a program that does not say ``starved``).

The command prints one JSON line a cause, one line that holds the
counter ``launches_starved`` to the trace (``starved_held``), then the
ten longest gaps with their instant, length, causes and the programs
before and after them. The reader ``trace_idle_cause`` reads single
numbers from the same split."""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import span_table, trace_reduce  # noqa: E402
from benchmark.readers import trace_span_attr  # noqa: E402

GC = "runtime.gc"
PASS = "engine.iteration"
OWN = "engine.iteration (own)"
OUTSIDE = "outside the loop's spans"
LAUNCH = re.compile(r"^engine\.(decode|prefill)\.launch$")
# Which program a launch span sends, by the span's kind.
PROGRAM_OF = {"decode": "jit_decode_step", "prefill": "jit_prefill_chunk"}


def gaps_of(device) -> list:
    """[start, end] of the first chip's idle gaps, in ns."""
    busy = trace_reduce.busy_intervals(device)
    return [[end, start] for (_, end), (start, _) in zip(busy, busy[1:])]


def records_causes(spans: list) -> bool:
    """Does the program that left these spans put the causes on the
    record at all? One that opens ``runtime.gc`` also says ``starved``
    on every launch; a trace with neither (the parent commit, or no
    launch in the window) has nothing to read."""
    return any(s[0] == GC or "starved" in s[3] for s in spans)


def clock_lead_ns(device, spans: list, within_ns: float = 2e6) -> float:
    """By how much the device's clock leads the host's, at least: over
    the launches that found the device drained, the most by which the
    program they sent (the first of its kind to start no more than
    ``within_ns`` before the launch opened; the one before it started a
    whole program earlier) starts before the launch opens. 0 where none
    does."""
    starts = {kind: sorted(m.start_ns for m in device.modules
                           if m.name.startswith(program))
              for kind, program in PROGRAM_OF.items()}
    lead = 0.0
    for name, opened, _, attrs, _ in spans:
        kind = LAUNCH.search(name)
        if not kind or not attrs.get("starved"):
            continue
        mine = starts[kind.group(1)]
        at = bisect.bisect_left(mine, opened - within_ns)
        if at < len(mine):
            lead = max(lead, opened - mine[at])
    return lead


def on_the_devices_clock(device, spans: list) -> list:
    """The spans with the clocks' lead taken off their times."""
    lead = clock_lead_ns(device, spans)
    if not lead:
        return spans
    return [(name, start - lead, end - lead, attrs, thread)
            for name, start, end, attrs, thread in spans]


def engine_thread(spans: list):
    """The line the engine's loop runs on: of the threads whose spans
    match ``^engine\\.`` the one with most of them; None without one."""
    kinds = span_table.thread_kinds(spans)
    count: dict = {}
    for name, _, _, _, thread in spans:
        if kinds[thread] == "engine" and name.startswith("engine."):
            count[thread] = count.get(thread, 0) + 1
    return max(count, key=count.get) if count else None


def innermost(spans: list) -> list:
    """One thread's spans flattened: sorted, disjoint (start, end, at)
    where ``at`` indexes the innermost span open over the stretch."""
    order = sorted(range(len(spans)),
                   key=lambda at: (spans[at][1], -spans[at][2]))
    out: list = []
    open_: list = []  # indices of the spans open now, outermost first

    def emit(start, end, at):
        if end > start:
            out.append((start, end, at))

    cursor = None
    for at in order:
        start, end = spans[at][1], spans[at][2]
        # Close what ended before this one starts.
        while open_ and spans[open_[-1]][2] <= start:
            closed = open_.pop()
            emit(cursor, spans[closed][2], closed)
            cursor = spans[closed][2]
        if open_:
            emit(cursor, start, open_[-1])
        cursor = start
        open_.append(at)
    while open_:
        closed = open_.pop()
        emit(cursor, spans[closed][2], closed)
        cursor = spans[closed][2]
    return out


def overlaps(stretches: list, starts: list, start: float, end: float):
    """The (from, to, stretch) of the sorted disjoint ``stretches``
    that overlap [start, end]."""
    at = max(0, bisect.bisect_right(starts, start) - 1)
    while at < len(stretches) and stretches[at][0] < end:
        lo, hi = max(stretches[at][0], start), min(stretches[at][1], end)
        if hi > lo:
            yield lo, hi, stretches[at]
        at += 1


def split(gap: list, collections: list, collection_starts: list,
          stretches: list, stretch_starts: list, loop: list) -> list:
    """One gap as (cause, from, to, span index or None), in time order
    and covering it whole."""
    start, end = gap
    parts, cursor = [], start
    pieces = []  # what no collection covers
    for lo, hi, _ in overlaps(collections, collection_starts, start, end):
        if lo > cursor:
            pieces.append((cursor, lo))
        parts.append((GC, lo, hi, None))
        cursor = hi
    if end > cursor:
        pieces.append((cursor, end))
    for piece_start, piece_end in pieces:
        cursor = piece_start
        for lo, hi, (_, _, at) in overlaps(stretches, stretch_starts,
                                           piece_start, piece_end):
            if lo > cursor:
                parts.append((OUTSIDE, cursor, lo, None))
            name = loop[at][0]
            parts.append((OWN if name == PASS else name, lo, hi, at))
            cursor = hi
        if piece_end > cursor:
            parts.append((OUTSIDE, cursor, piece_end, None))
    return sorted(parts, key=lambda part: part[1])


class Launches:
    """The engine's launch spans in the order they were opened."""

    def __init__(self, spans: list):
        self.spans = sorted((s for s in spans if LAUNCH.search(s[0])),
                            key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]

    def ending(self, gap_end: float):
        """The launch that ended a gap: the last one opened before the
        gap's end (None: none was)."""
        at = bisect.bisect_right(self.starts, gap_end)
        return self.spans[at - 1] if at else None


def split_gaps(device, spans: list) -> list:
    """Every gap of the first chip as (gap, parts, the launch that
    ended it or None), ``parts`` as ``split`` gives them; and the
    engine thread's spans, which the parts index."""
    spans = on_the_devices_clock(device, spans)
    thread = engine_thread(spans)
    loop = [s for s in spans
            if s[4] == thread and s[0].startswith("engine.")]
    stretches = innermost(loop)
    stretch_starts = [s[0] for s in stretches]
    collections = [(start, end, None) for start, end in trace_reduce.union(
        (s[1], s[2]) for s in spans if s[0] == GC)]
    collection_starts = [c[0] for c in collections]
    launches = Launches(spans)
    return [(gap, split(gap, collections, collection_starts, stretches,
                        stretch_starts, loop), launches.ending(gap[1]))
            for gap in gaps_of(device)], loop


def idle_causes(device, spans: list, found=None) -> dict:
    """{cause: {"seconds", "gaps", "longest_s", "lock_wait_us",
    "ended_starved"}} over the first chip's idle gaps; empty where the
    device shows no gap. ``found``: what ``split_gaps`` gave for them,
    where the caller has it already."""
    found, loop = found or split_gaps(device, spans)
    out: dict = {}
    under: dict = {}  # cause -> the spans its stretches fell under
    for _, parts, launch in found:
        starved = bool(launch and launch[3].get("starved"))
        for cause in {part[0] for part in parts}:
            mine = [part for part in parts if part[0] == cause]
            line = out.setdefault(cause, {
                "seconds": 0.0, "gaps": 0, "longest_s": 0.0,
                "lock_wait_us": 0.0, "ended_starved": 0})
            line["seconds"] += sum(hi - lo for _, lo, hi, _ in mine) / 1e9
            line["gaps"] += 1
            line["longest_s"] = max(
                line["longest_s"],
                max(hi - lo for _, lo, hi, _ in mine) / 1e9)
            line["ended_starved"] += starved
            under.setdefault(cause, set()).update(
                at for _, _, _, at in mine if at is not None)
    for cause, seen in under.items():
        out[cause]["lock_wait_us"] = sum(
            float(loop[at][3].get("lock_wait_us", 0)) for at in seen)
    return out


def starved_held(device, spans: list, over_ns: float = 1e6) -> dict:
    """The counter ``launches_starved`` held to the trace. Of the gaps
    over ``over_ns`` that end where a decode or prefill program
    starts: how many the launch that ended them marked ``starved=1``,
    and for how many that launch was of the program's own kind. Of the
    launches inside the device's window that said ``starved=0``: how
    many follow such a gap all the same."""
    modules = sorted(device.modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in modules]
    launches = Launches(on_the_devices_clock(device, spans))
    gaps = gaps_of(device)
    long_gaps = said = same_kind = inside = 0
    ended_long = set()
    for start, end in gaps:
        # The program whose first operation ended the gap.
        at = bisect.bisect_right(starts, end) - 1
        after = modules[at] if at >= 0 and modules[at].end_ns > end else None
        if end - start <= over_ns or after is None or not any(
                after.name.startswith(p) for p in PROGRAM_OF.values()):
            continue
        long_gaps += 1
        launch = launches.ending(end)
        if launch is None:
            continue
        ended_long.add(launch[1])
        said += bool(launch[3].get("starved"))
        # Not starved when asked, and dry before the call was through.
        inside += not launch[3].get("starved") and launch[1] <= start
        same_kind += after.name.startswith(
            PROGRAM_OF[LAUNCH.search(launch[0]).group(1)])
    window = (gaps[0][0], gaps[-1][1]) if gaps else (0, 0)
    unstarved = [s for s in launches.spans
                 if window[0] <= s[1] <= window[1]
                 and "starved" in s[3] and not s[3]["starved"]]
    return {"gaps_over_ms": over_ns / 1e6, "gaps": long_gaps,
            "ended_by_starved": said, "ended_by_own_kind": same_kind,
            "drained_inside_the_launch": inside,
            "launches_unstarved": len(unstarved),
            "unstarved_after_gap": sum(s[1] in ended_long
                                       for s in unstarved),
            "launches_starved": sum(
                bool(s[3].get("starved")) for s in launches.spans
                if window[0] <= s[1] <= window[1])}


def longest(device, spans: list, top: int = 10, found=None) -> list:
    """The ``top`` longest gaps as dicts."""
    found, _ = found or split_gaps(device, spans)
    modules = sorted(device.modules, key=lambda m: m.end_ns)
    ends = [m.end_ns for m in modules]
    by_start = sorted(device.modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in by_start]
    origin = found[0][0][0] if found else 0.0
    lines = []
    for (start, end), parts, launch in sorted(
            found, key=lambda f: f[0][0] - f[0][1])[:top]:
        by_cause: dict = {}
        for cause, lo, hi, _ in parts:
            by_cause[cause] = by_cause.get(cause, 0.0) + (hi - lo) / 1e6
        before = bisect.bisect_right(ends, (start + end) / 2)
        after = bisect.bisect_right(starts, end) - 1
        lines.append({
            "gap_at_s": (start - origin) / 1e9, "ms": (end - start) / 1e6,
            "causes_ms": dict(sorted(by_cause.items(),
                                     key=lambda kv: -kv[1])),
            "after": trace_reduce.short_name(modules[before - 1].name)
            if before else None,
            "before": trace_reduce.short_name(by_start[after].name)
            if after >= 0 and by_start[after].end_ns > end else None,
            "ended_by": launch and launch[0],
            "starved": launch and launch[3].get("starved")})
    return lines


def main(argv: list) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    if not path or not os.path.exists(path):
        raise SystemExit(f"no .xplane.pb under {argv[0]}")
    trace = trace_reduce.load(path)
    device = trace_reduce.first_device(trace)
    if device is None:
        raise SystemExit(f"{path} holds no device's plane")
    spans = trace_span_attr.attributed_spans(path)
    busy_s, window_s = trace_reduce.busy_and_window(trace)
    found = split_gaps(device, spans)
    causes = idle_causes(device, spans, found)
    idle_s = sum(line["seconds"] for line in causes.values())
    print(json.dumps({"trace": path, "window_s": window_s,
                      "idle_s": idle_s, "gaps": len(gaps_of(device)),
                      "clock_lead_ms": clock_lead_ns(device, spans) / 1e6,
                      "records_causes": records_causes(spans)}))
    for cause, line in sorted(causes.items(),
                              key=lambda kv: -kv[1]["seconds"]):
        print(json.dumps({
            "cause": cause, **line,
            "share_of_idle": 100 * line["seconds"] / idle_s,
            "share_of_window": 100 * line["seconds"] / window_s}))
    print(json.dumps({"starved_held": starved_held(device, spans)}))
    for line in longest(device, spans, found=found):
        print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
