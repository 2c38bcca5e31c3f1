"""From a profiler trace (``.xplane.pb``) to numbers. Read with
``jax.profiler.ProfileData`` and nothing else.

A TPU's plane is ``/device:TPU:<n>``; its line ``XLA Modules`` has one
event per run of a jitted program (``jit_step(<fingerprint>)``) and its
line ``XLA Ops`` one per HLO operation, named by the operation's whole
HLO text (``%fusion.3 = bf16[..] fusion(..)``), with a ``while``
enclosing the operations of its body on the same line: time by
operation is therefore self time, an event's duration less the events
nested in it. ``Async XLA Ops`` has one event from each ``-start`` to
its ``-done`` (copies, and collectives across chips). Busy time is the
union of the operation intervals. Host planes carry the harness's own
``bench.*`` annotations, on the same clock (seen on the v5e, PR 22).
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re

from benchmark import spec

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HLO = re.compile(r"^%?(\S+) = (.*?) ([\w\-]+)\(")
HARNESS_SPAN = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: dict
    self_ns: float = 0.0

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Device:
    modules: list
    ops: list
    async_ops: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    devices: dict          # chip number -> Device
    host_spans: list       # the harness's own annotations


def find_xplane(trace_dir: str) -> "str | None":
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def find_trace(metric: dict) -> "str | None":
    """The kept trace file of a run of one of the metric's cells.
    ``load`` keeps the harness's ``bench.*`` spans only and ``run``
    carries no path, so a reader of the program's own spans finds the
    file again under ``<checkout>/.bench_trace/<cell>``; with
    ``--trace-dir`` elsewhere there is nothing to read."""
    for cell in metric.get("workloads", []):
        path = find_xplane(os.path.join(spec.ROOT, ".bench_trace", cell))
        if path is not None:
            return path
    return None


@functools.lru_cache(maxsize=2)  # one trace a run, read by several metrics
def program_spans(path: str, among: str) -> list:
    """(name, start_ns, end_ns) of every host event whose name matches:
    the program's spans come from ``ray_tpu.util.tracing.phase`` and lie
    on the device trace's clock."""
    from jax.profiler import ProfileData

    rx = re.compile(among)
    return [(e.name, float(e.start_ns),
             float(e.start_ns) + float(e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if rx.search(e.name)]


def _events(line) -> list:
    out = []
    for e in line.events:
        name, stats = e.name, dict(e.stats)
        parsed = HLO.match(name)
        if parsed:
            # fusion.3 [fusion bf16[2,4096]]: short enough to print, and
            # the whole text is kept for the selectors.
            stats["hlo"] = name
            name = (f"{parsed.group(1)} [{parsed.group(3)} "
                    f"{parsed.group(2)[:48]}]")
        out.append(Event(name, float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns), stats))
    out.sort(key=lambda e: (e.start_ns, -e.end_ns))
    return out


def set_self_times(events: list) -> None:
    """Self time of nested events on one line (sorted by start, longest
    first): a parent loses what its direct children cover."""
    stack: list = []
    for event in events:
        event.self_ns = event.duration_ns
        while stack and stack[-1].end_ns <= event.start_ns:
            stack.pop()
        if stack:
            stack[-1].self_ns -= event.duration_ns
        stack.append(event)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    devices, host_spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            lines = {line.name: line for line in plane.lines}
            device = Device(*(
                _events(lines[name]) if name in lines else []
                for name in (MODULES_LINE, OPS_LINE, ASYNC_LINE)))
            set_self_times(device.ops)
            devices[int(match.group(1))] = device
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [e for e in _events(line)
                               if e.name.startswith(HARNESS_SPAN)]
    return Trace(devices, host_spans)


def union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def busy_intervals(device: Device) -> list:
    events = device.ops or device.modules
    return union((e.start_ns, e.end_ns) for e in events)


def busy_and_window(trace: Trace) -> "tuple[float, float] | None":
    """(seconds an operation ran, averaged over the chips; seconds of
    the traced window, from the first device event to the last over all
    chips). None where no operation ran on a device."""
    per_device = [busy_intervals(d) for d in trace.devices.values()]
    per_device = [b for b in per_device if b]
    if not per_device:
        return None
    window = (max(b[-1][1] for b in per_device)
              - min(b[0][0] for b in per_device))
    busy = sum(sum(e - s for s, e in b) for b in per_device) / len(per_device)
    return busy / 1e9, window / 1e9


def first_device(trace: Trace) -> "Device | None":
    return trace.devices[min(trace.devices)] if trace.devices else None


def module_runs(device: Device, pattern: str) -> list:
    """Durations in ns of the runs of the programs whose name matches."""
    rx = re.compile(pattern)
    return [m.duration_ns for m in device.modules if rx.search(m.name)]


def op_matches(event: Event, pattern) -> bool:
    """By the operation's name or by any of its textual stats (the HLO
    category, the source scope)."""
    return bool(pattern.search(event.name) or any(
        isinstance(v, str) and pattern.search(v)
        for v in event.stats.values()))


def op_self_seconds(device: Device, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(e.self_ns for e in device.ops if op_matches(e, rx)) / 1e9


def op_in_flight_seconds(device: Device, pattern: str) -> float:
    """Seconds in which a matching operation was in flight: the union
    over the synchronous operations and the start-to-done spans of the
    asynchronous ones. It does not say how much was hidden."""
    rx = re.compile(pattern)
    return sum(end - start for start, end in union(
        (e.start_ns, e.end_ns) for e in device.ops + device.async_ops
        if op_matches(e, rx))) / 1e9


def short_name(name: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``; an operation
    keeps its own name (``fusion.12``)."""
    return re.sub(r"\(\d+\)$", "", name)


def breakdown(trace: Trace, top: int = 10) -> "dict | None":
    """The device operations that took most self time on the first
    chip, and its
    longest idle gaps summed by what the harness was doing at their
    middle; a gap inside no ``bench.*`` span is ``unattributed`` and
    says which program ended before it."""
    device = first_device(trace)
    if device is None or not (device.ops or device.modules):
        return None
    by_op: dict = {}
    for e in device.ops:
        by_op[e.name] = by_op.get(e.name, 0.0) + e.self_ns / 1e9
    busy = busy_intervals(device)
    gaps: dict = {}
    modules = sorted(device.modules, key=lambda m: m.end_ns)
    module_ends = [m.end_ns for m in modules]
    for (_, end), (start, _) in zip(busy, busy[1:]):
        middle = (end + start) / 2
        span = next((s.name for s in trace.host_spans
                     if s.start_ns <= middle <= s.end_ns), None)
        if span is None:
            done = bisect.bisect_right(module_ends, middle)
            span = "unattributed" + (
                f" (after {short_name(modules[done - 1].name)})"
                if done else "")
        gaps[span] = gaps.get(span, 0.0) + (start - end) / 1e9

    def largest(table):
        return [[k, v] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": largest(by_op), "idle_gaps": largest(gaps)}
