"""Distributed span tracing — OpenTelemetry-shaped spans over runtime
activity, with cross-process trace-context propagation.

Reference: python/ray/util/tracing/ (tracing_helper.py:36 instruments
task submit/execute with OTel spans) and the gcs_task_manager task-event
subsystem behind ``ray timeline``. Here the tracer is built in:

- ``enable()`` starts collecting; user code opens spans with
  ``with trace_span("name"):`` (nesting gives parent/child links via a
  contextvar, which propagates correctly across threads the runtime
  starts per actor/task);
- task submission/execution is traced automatically: the driver stamps
  a compact trace context ``(trace_id, parent span_id, anchor)`` onto
  every task submit; the context rides the ``execute_task`` /
  ``execute_task_batch`` RPCs and the worker pipe ``task_seq`` frames,
  so spans opened in daemons and pool workers link back to the
  driver-side submit span. Remote spans are buffered locally
  (``buffer_span``) and shipped back piggybacked on existing reply
  frames and heartbeats — no new chatty RPCs;
- per-process clock skew is corrected driver-side: every trace payload
  carries the remote wall clock at send, and ``ClockSync`` keeps the
  minimum-RTT half-RTT offset estimate per peer so merged timelines
  line up;
- ``export_chrome_trace(path)`` writes everything — user spans, remote
  spans, per-stage task lifecycles, fault/chaos instants, and flow
  arrows from submit→execute→seal — as one chrome://tracing / Perfetto
  JSON file with one process lane per node/worker; ``get_spans()``
  returns structured spans for programmatic use.

- ``phase(name, **attrs)`` instruments a phase of host work ONCE for
  two sinks: a ``jax.profiler`` TraceMe, which a profiler session puts
  in the host plane on the device trace's clock (so a device idle gap
  can be read against what the host was doing), and a ``Span`` here
  while tracing is armed. The serving engine's loop uses it.

  The stream path and the core runtime under it use it too: every
  hand-off of a request on its way in and of a token on its way out is
  one ``phase`` on the thread that RECEIVES the work. While a sink is
  live (``live()``: ``TRACE_ON`` or a profiler session) a hand-off
  carries ``age_us``, how long its oldest item waited since the hop
  before let go of it (``stamp_ns()`` there, ``age_us()`` here), and
  ``request``, the id of the streamed request it belongs to; a phase
  made with ``cpu=True`` (the hops a request makes once) also carries
  ``cpu_us``, its thread's CPU time: wall less ``cpu_us`` is how long
  the thread stood.

  The collector's pauses are on the record the same way: while a
  process hosts an engine (``record_gc``) a ``gc.callbacks`` entry sums
  every collection's wall time and counts the full ones
  (``gc_counters()``: ``gc_pause_us``, ``gc_full_collections``), and
  while a sink is live a collection of generation 1 or 2 is one
  ``phase`` too, ``runtime.gc`` (``generation``, ``collected``,
  ``pause_us``), on the thread that tripped it: every other thread
  stands for as long.

Cost discipline: when tracing is disabled every instrumentation site
pays one module-attribute branch (``if tracing.TRACE_ON:``) — the same
contract as ``chaos.ACTIVE``; a ``phase`` also asks whether a profiler
session runs (no TraceMe is built outside one) and reads no clock.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import json
import os
import sys
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Iterator

_current_span: contextvars.ContextVar["Span | None"] = \
    contextvars.ContextVar("ray_tpu_current_span", default=None)

# The ONE production branch: instrumentation sites across the runtime
# (scheduler claim, RPC retry, chaos firings, worker frames) check this
# module attribute and pay nothing else while tracing is off.
TRACE_ON: bool = False

# Canonical pipeline stage order (TaskEvent.stage_ts keys), driver
# clock after offset correction. Used by the exporter to slice a task's
# lifecycle and by tests asserting monotonic ordering.
STAGES = ("submit", "dispatch", "rpc_sent", "admitted", "worker_start",
          "exec_start", "exec_end", "seal")


# Span and trace ids: a random prefix drawn once per process, then a
# counter. A forked child draws its own prefix.
_id_prefix = os.urandom(4).hex()
_id_counter = itertools.count()


def _reseed_ids() -> None:
    global _id_prefix, _id_counter
    _id_prefix = os.urandom(4).hex()
    _id_counter = itertools.count()


os.register_at_fork(after_in_child=_reseed_ids)


def _new_id() -> str:
    return f"{_id_prefix}{next(_id_counter):08x}"


@dataclass
class Span:
    name: str
    span_id: str
    parent_id: str | None
    start_time: float
    end_time: float | None = None
    attributes: dict = field(default_factory=dict)
    thread: str = ""
    trace_id: str = ""
    # Process lane label ("driver", "node:<tag>", "worker:<pid>") for
    # the merged timeline; empty = this process.
    proc: str = ""

    def duration_s(self) -> float | None:
        if self.end_time is None:
            return None
        return self.end_time - self.start_time


def _buffer_cap() -> int:
    try:
        from ray_tpu._private.config import GLOBAL_CONFIG

        return max(1, int(GLOBAL_CONFIG.tracing_buffer_max_spans))
    except Exception:  # noqa: BLE001 — config unavailable mid-teardown
        return 4096


class _Tracer:
    def __init__(self):
        self._lock = threading.Lock()
        self._spans: list[Span] = []
        # Remote-shipping buffer (daemon/worker side): span dicts
        # waiting to piggyback on the next reply frame / heartbeat.
        self._outbox: list[dict] = []
        self.dropped = 0
        self.enabled = False

    def record(self, span: Span) -> None:
        with self._lock:
            if len(self._spans) >= _buffer_cap():
                self.dropped += 1
                return
            self._spans.append(span)

    def buffer(self, span_dict: dict) -> None:
        with self._lock:
            if len(self._outbox) >= _buffer_cap():
                self.dropped += 1
                return
            self._outbox.append(span_dict)

    def drain(self) -> list[dict]:
        with self._lock:
            out, self._outbox = self._outbox, []
            return out

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._outbox.clear()
            self.dropped = 0


_TRACER = _Tracer()


def enable() -> None:
    """Start collecting spans (reference: tracing startup hook)."""
    global TRACE_ON
    _TRACER.enabled = True
    TRACE_ON = True


def disable() -> None:
    global TRACE_ON
    _TRACER.enabled = False
    TRACE_ON = False


def is_enabled() -> bool:
    return _TRACER.enabled


def clear() -> None:
    _TRACER.clear()


def dropped_spans() -> int:
    """Spans discarded because a buffer hit tracing_buffer_max_spans."""
    return _TRACER.dropped


class _OffSpan(Span):
    """What ``trace_span`` yields while tracing is off: no ids, and
    attributes written to it go nowhere."""

    @property
    def attributes(self) -> dict:
        return {}

    @attributes.setter
    def attributes(self, value: dict) -> None:
        pass


_OFF_SPAN = _OffSpan(name="", span_id="", parent_id=None, start_time=0.0)

_annotation_class = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once jax is imported in this
    process (never an import of its own: daemons without jax stay
    without it), else None."""
    global _annotation_class
    if _annotation_class is None:
        try:
            _annotation_class = \
                sys.modules["jax"].profiler.TraceAnnotation
        except (KeyError, AttributeError):  # no jax, or half imported
            return None
    return _annotation_class


def live() -> bool:
    """Is a sink live: spans are recorded, or a profiler session runs?
    What only a span would read (a stamp, a thread's CPU time, an
    attribute's value) is taken only then."""
    if TRACE_ON:
        return True
    annotation = _trace_annotation()
    return annotation is not None and annotation.is_enabled()


def stamp_ns() -> int:
    """``time.monotonic_ns()`` for the hop that lets go of an item,
    kept on the object that crosses to the next thread; 0 (no clock
    read) while no sink is live."""
    return time.monotonic_ns() if live() else 0


def age_us(stamp_ns: int) -> "int | None":
    """Microseconds since ``stamp_ns``; None for an item that was not
    stamped (no sink was live where it was let go of)."""
    if not stamp_ns:
        return None
    return max(0, time.monotonic_ns() - stamp_ns) // 1000


class phase:
    """A phase of host work, instrumented once for two sinks.

    A ``jax.profiler.TraceAnnotation`` whenever jax is already imported
    in this process: inert while no profiler session runs, and inside
    one it lands in the host plane on the device trace's clock. A
    ``Span`` (parented through the contextvar, sharing the enclosing
    span's trace id) while ``TRACE_ON``. While either is live
    (``self.live``) a phase made with ``cpu=True`` carries ``cpu_us``,
    its thread's CPU time between enter and exit (two
    ``time.thread_time_ns()``: 6 us each and in ticks of 10 ms on the
    chip's machine, so for a hop a request makes once, not one a
    token). With neither live a phase costs one lookup and a branch:
    no TraceMe is built, no clock is read."""

    __slots__ = ("_name", "_attrs", "_annotation", "_token", "span",
                 "live", "_cpu", "_cpu0")
    _records_span = True

    def __init__(self, name: str, cpu: bool = False, **attrs):
        self._name = name
        self._attrs = attrs
        self._annotation = None
        self.span: "Span | None" = None
        self.live = False
        self._cpu = cpu

    def __enter__(self) -> "phase":
        annotation = _trace_annotation()
        if annotation is not None and annotation.is_enabled():
            self._annotation = annotation(self._name, **self._attrs)
            self._annotation.__enter__()
            self.live = True
        if TRACE_ON and self._records_span:
            parent = _current_span.get()
            self.span = Span(
                name=self._name,
                span_id=_new_id(),
                parent_id=parent.span_id if parent else None,
                start_time=time.time(),
                attributes=self._attrs,
                thread=threading.current_thread().name,
                trace_id=(parent.trace_id if parent and parent.trace_id
                          else _new_id()),
            )
            self._token = _current_span.set(self.span)
            self.live = True
        if self._cpu and self.live:
            # Inside the span's wall interval: cpu_us <= its duration.
            self._cpu0 = time.thread_time_ns()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only once the phase is under way (one
        that is None is left out)."""
        if not self.live:
            return
        attrs = {k: v for k, v in attrs.items() if v is not None}
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)
        if self.span is not None:
            self.span.attributes.update(attrs)

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._cpu and self.live:
            self.set(cpu_us=(time.thread_time_ns() - self._cpu0) // 1000)
        span = self.span
        if span is not None:
            if exc is not None:
                span.attributes["error"] = f"{exc_type.__name__}: {exc}"
            span.end_time = time.time()
            _current_span.reset(self._token)
            if _TRACER.enabled:
                _TRACER.record(span)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)


class profiler_phase(phase):
    """A ``phase`` for the profiler alone: for the passes of a loop
    that found nothing to do, which would fill the span buffer while
    the process idles."""

    __slots__ = ()
    _records_span = False


# The collector's pauses. A collection runs on the thread that tripped
# it with the interpreter held, so every other thread of the process
# stands until it ends; a full one over a serving process's heap takes
# 0.04 to 0.23 s. The counters are the process's, not an engine's, and
# monotonic; the two globals below are written with the interpreter
# held and by one collection at a time (the collector does not nest).
_GC_SPAN_FROM = 1   # the youngest generation whose collections are spans
_gc_users: "weakref.WeakSet" = weakref.WeakSet()
_gc_pause_ns = 0
_gc_full = 0
_gc_started_ns = 0
_gc_phase: "phase | None" = None


def _on_gc(event: str, info: dict) -> None:
    """The ``gc.callbacks`` entry: two clock reads and two additions a
    collection; a ``phase`` as well for a generation from
    ``_GC_SPAN_FROM`` on, which reads no clock of its own and builds
    nothing while no sink is live (generation 0, 18 a second at 0.7 ms
    in the fullest serve cell's process, is never a span; generation 1
    takes 1.5 to 2.4 ms there and a full one 0.13 to 0.23 s)."""
    global _gc_pause_ns, _gc_full, _gc_started_ns, _gc_phase
    if event == "start":
        if info["generation"] >= _GC_SPAN_FROM and live():
            _gc_phase = phase("runtime.gc", generation=info["generation"])
            _gc_phase.__enter__()
        _gc_started_ns = time.monotonic_ns()
        return
    if not _gc_started_ns:
        return  # the callback went in while this collection ran
    pause_ns = time.monotonic_ns() - _gc_started_ns
    _gc_started_ns = 0
    _gc_pause_ns += pause_ns
    _gc_full += info["generation"] == 2
    opened, _gc_phase = _gc_phase, None
    if opened is not None:
        opened.set(collected=info["collected"], pause_us=pause_ns // 1000)
        opened.__exit__(None, None, None)


def record_gc(user) -> None:
    """Put the collector's pauses on the record for as long as ``user``
    (an engine) lives or until it says ``forget_gc``: one callback a
    process, however many users."""
    _gc_users.add(user)
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def forget_gc(user) -> None:
    """``user`` is done; the callback goes with the last one."""
    global _gc_started_ns, _gc_phase
    _gc_users.discard(user)
    if not _gc_users and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
        # Asked from inside a collection (an engine's finaliser): its
        # "stop" will not come.
        opened, _gc_phase, _gc_started_ns = _gc_phase, None, 0
        if opened is not None:
            opened.__exit__(None, None, None)


def gc_counters() -> dict:
    """``gc_pause_us``: wall time inside collections of every
    generation since the callback went in; ``gc_full_collections``:
    those of generation 2 among them."""
    return {"gc_pause_us": _gc_pause_ns // 1000,
            "gc_full_collections": _gc_full}


@contextlib.contextmanager
def trace_span(name: str, attributes: dict | None = None) -> Iterator[Span]:
    """Open a span; nests under the current span in this context.
    While tracing is off it yields an inert span and records nothing."""
    with phase(name, **(attributes or {})) as opened:
        yield opened.span or _OFF_SPAN


def record_span(name: str, start_time: float, end_time: float,
                trace_id: str, parent_id: str | None = None,
                attributes: dict | None = None) -> str | None:
    """Record a span whose times are already known (a request's stamps,
    read at its seal). Returns its id for its children, or None while
    tracing is off."""
    if not TRACE_ON:
        return None
    span = Span(name=name, span_id=_new_id(), parent_id=parent_id,
                start_time=start_time, end_time=end_time,
                attributes=dict(attributes or {}),
                thread=threading.current_thread().name, trace_id=trace_id)
    _TRACER.record(span)
    return span.span_id


def get_spans() -> list[Span]:
    """All completed spans collected since enable()/clear()."""
    return _TRACER.spans()


# --------------------------------------------------------------------------
# Cross-process trace context
# --------------------------------------------------------------------------
#
# A trace context is a compact picklable tuple riding the existing RPCs:
#     (trace_id, parent_span_id, anchor)
# ``anchor`` is the originating driver's wall clock at creation — remote
# processes never use it for arithmetic directly (skew!), it only tags
# the context's origin for debugging; real merge correction comes from
# ClockSync half-RTT estimation on the reply path.


def make_trace_context(anchor: float | None = None) -> tuple | None:
    """Context for an outgoing task submit: links to the current span
    when one is open, else roots a fresh trace. None when disabled —
    the absence of a context IS the cross-process disable signal (the
    remote side never needs its own tracing flag for runtime spans)."""
    if not TRACE_ON:
        return None
    parent = _current_span.get()
    if parent is not None:
        trace_id = parent.trace_id or _new_id()
        parent_id = parent.span_id
    else:
        trace_id = _new_id()
        parent_id = None
    return (trace_id, parent_id, anchor if anchor is not None
            else time.time())


def attach(ctx: "tuple | None"):
    """Make a trace context that crossed from another thread (an actor
    call's) the current one: spans opened here until ``detach`` are
    children of the span that made it. Returns the token for
    ``detach``; None, and nothing done, for no context."""
    if ctx is None:
        return None
    return _current_span.set(Span(
        name="", span_id=ctx[1], parent_id=None, start_time=ctx[2],
        trace_id=ctx[0]))


def detach(token) -> None:
    if token is not None:
        _current_span.reset(token)


@contextlib.contextmanager
def remote_span(name: str, ctx: tuple | None, proc: str,
                attributes: dict | None = None) -> Iterator[dict]:
    """Daemon/worker-side span linked to a driver trace context.

    The span is recorded as a plain dict into the local outbox
    (``drain_buffered``) so it ships back piggybacked on the next reply
    frame or heartbeat. Timestamps are THIS process's wall clock; the
    driver corrects them with its ClockSync offset at ingest."""
    span = {
        "name": name,
        "span_id": _new_id(),
        "parent_id": ctx[1] if ctx else None,
        "trace_id": ctx[0] if ctx else _new_id(),
        "start_time": time.time(),
        "end_time": None,
        "thread": threading.current_thread().name,
        "proc": proc,
        "attributes": dict(attributes or {}),
    }
    try:
        yield span
    except BaseException as exc:
        span["attributes"]["error"] = f"{type(exc).__name__}: {exc}"
        raise
    finally:
        span["end_time"] = time.time()
        _TRACER.buffer(span)


def buffer_span(span_dict: dict) -> None:
    """Queue one remote span dict for piggyback shipping."""
    _TRACER.buffer(span_dict)


def drain_buffered() -> list[dict]:
    """Pop every span queued for shipping (reply-frame/heartbeat
    piggyback). Returns [] when nothing is buffered — callers attach
    the payload only when non-empty."""
    return _TRACER.drain()


def ingest_spans(span_dicts: list[dict], offset_s: float = 0.0) -> int:
    """Driver-side merge of remote spans: apply the peer's clock offset
    (remote ts + offset ≈ driver ts) and record them as first-class
    spans. Returns the number ingested."""
    n = 0
    for d in span_dicts:
        try:
            end = d.get("end_time")
            span = Span(
                name=d["name"],
                span_id=d.get("span_id", _new_id()),
                parent_id=d.get("parent_id"),
                start_time=float(d["start_time"]) + offset_s,
                end_time=(float(end) + offset_s) if end else None,
                attributes=dict(d.get("attributes") or {}),
                thread=d.get("thread", ""),
                trace_id=d.get("trace_id", ""),
                proc=d.get("proc", ""),
            )
        except (KeyError, TypeError, ValueError):
            continue  # malformed remote span: skip, never poison merge
        _TRACER.record(span)
        n += 1
    return n


def instant(name: str, attributes: dict | None = None,
            proc: str = "") -> None:
    """Record a zero-duration instant event (fault counters, chaos
    firings). Shown as an 'i' pin in the merged timeline. Callers
    gate on ``tracing.TRACE_ON`` so the disabled cost is one branch."""
    if not TRACE_ON:
        return
    span = Span(
        name=name,
        span_id=_new_id(),
        parent_id=None,
        start_time=time.time(),
        end_time=None,
        attributes={**(attributes or {}), "instant": True},
        thread=threading.current_thread().name,
        proc=proc,
    )
    _TRACER.record(span)


def buffer_instant(name: str, proc: str,
                   attributes: dict | None = None) -> None:
    """Remote-process variant of ``instant``: queued for piggyback
    shipping instead of recorded locally."""
    if not TRACE_ON:
        return
    _TRACER.buffer({
        "name": name,
        "span_id": _new_id(),
        "parent_id": None,
        "trace_id": "",
        "start_time": time.time(),
        "end_time": None,
        "thread": threading.current_thread().name,
        "proc": proc,
        "attributes": {**(attributes or {}), "instant": True},
    })


class ClockSync:
    """Per-peer monotonic→driver-clock offset estimation.

    Classic NTP four-timestamp anchoring on existing exchanges (lease
    replies, heartbeats): t0 = local request send, t1 = peer receive
    (the daemon's admission stamp), t2 = peer reply send (the trace
    payload's ``now``), t3 = local reply receive. Server processing
    time (t2−t1) subtracts out of the RTT, so a long-running task
    cannot bias the estimate; the minimum-RTT sample wins — it bounds
    the path-asymmetry error the tightest. ``offset`` is defined so
    that ``driver_time ≈ remote_time + offset``."""

    __slots__ = ("offset", "rtt", "samples", "_lock")

    def __init__(self):
        self.offset = 0.0
        self.rtt = float("inf")
        self.samples = 0
        self._lock = threading.Lock()

    def observe(self, t_send: float, t_recv: float,
                remote_ts: float,
                remote_recv_ts: float | None = None) -> float:
        """One exchange; ``remote_recv_ts`` (t1) defaults to
        ``remote_ts`` (t2) — the degenerate half-RTT form for replies
        that carry only one peer stamp. Returns the current best
        offset."""
        if remote_recv_ts is None:
            remote_recv_ts = remote_ts
        rtt = max(0.0, (t_recv - t_send) - (remote_ts - remote_recv_ts))
        # NTP: θ = ((t1−t0)+(t2−t3))/2 is remote−local; negate for the
        # remote→driver correction.
        offset = -(((remote_recv_ts - t_send)
                    + (remote_ts - t_recv)) / 2.0)
        with self._lock:
            self.samples += 1
            if rtt <= self.rtt:
                self.rtt = rtt
                self.offset = offset
            return self.offset


# --------------------------------------------------------------------------
# Merged timeline export
# --------------------------------------------------------------------------


class _LaneTable:
    """Stable integer pid/tid assignment per process/thread label, plus
    the 'M' metadata events Perfetto needs to group and name lanes
    (string tids violate the chrome trace format and scatter events)."""

    def __init__(self):
        self._pids: dict[str, int] = {}
        self._tids: dict[tuple[int, str], int] = {}
        self.meta: list[dict] = []

    def pid(self, proc: str) -> int:
        proc = proc or "driver"
        got = self._pids.get(proc)
        if got is None:
            got = len(self._pids) + 1
            self._pids[proc] = got
            self.meta.append({
                "name": "process_name", "ph": "M", "pid": got, "tid": 0,
                "args": {"name": proc}})
            self.meta.append({
                "name": "process_sort_index", "ph": "M", "pid": got,
                "tid": 0, "args": {"sort_index": got}})
        return got

    def tid(self, pid: int, thread: str) -> int:
        thread = thread or "main"
        key = (pid, thread)
        got = self._tids.get(key)
        if got is None:
            got = sum(1 for (p, _t) in self._tids if p == pid) + 1
            self._tids[key] = got
            self.meta.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": got,
                "args": {"name": thread}})
        return got


# Stage slice layout for one task: (slice name, from stage, to stage,
# lane). "remote" lanes land in the executing node's process lane.
_STAGE_SLICES = (
    ("stage:submit→dispatch", "submit", "dispatch", "driver"),
    ("stage:dispatch→rpc", "dispatch", "rpc_sent", "driver"),
    ("stage:rpc→admit", "rpc_sent", "admitted", "remote"),
    ("stage:admit→worker", "admitted", "worker_start", "remote"),
    ("stage:worker→exec", "worker_start", "exec_start", "remote"),
    ("stage:execute", "exec_start", "exec_end", "remote"),
    ("stage:exec→seal", "exec_end", "seal", "driver"),
)


def _task_lane(ev) -> str:
    return f"node:{ev.node_id[:8]}" if ev.node_id else "driver"


def build_task_events(runtime, lanes: "_LaneTable | None" = None
                      ) -> list[dict]:
    """Chrome-trace events for the runtime's task lifecycle records:
    per-stage slices (one lane per node) with flow arrows linking the
    driver-side submit to the remote execution and back to the seal.
    Tasks without stage stamps degrade to the single-slice view."""
    own_lanes = lanes is None
    if own_lanes:
        lanes = _LaneTable()
    events: list[dict] = []
    for ev in runtime.gcs.list_task_events():
        stage_ts = getattr(ev, "stage_ts", None) or {}
        present = [s for s in STAGES if s in stage_ts]
        if len(present) >= 2:
            flow_id = ev.task_id.hex()
            prev_lane = None
            for name, a, b, lane_kind in _STAGE_SLICES:
                if a not in stage_ts or b not in stage_ts:
                    continue
                lane = ("driver" if lane_kind == "driver"
                        else _task_lane(ev))
                pid = lanes.pid(lane)
                tid = lanes.tid(pid, "tasks")
                ts = stage_ts[a] * 1e6
                events.append({
                    "name": f"{ev.name} {name}", "cat": "task_stage",
                    "ph": "X", "ts": ts,
                    "dur": max((stage_ts[b] - stage_ts[a]) * 1e6, 1.0),
                    "pid": pid, "tid": tid,
                    "args": {"task_id": flow_id, "state": ev.state},
                })
                if prev_lane is not None and prev_lane != lane:
                    # Cross-lane hop: a flow arrow from the end of the
                    # previous slice to the start of this one.
                    prev_pid = lanes.pid(prev_lane)
                    events.append({
                        "name": "task_flow", "cat": "task_flow",
                        "ph": "s", "id": flow_id, "ts": ts - 1.0,
                        "pid": prev_pid,
                        "tid": lanes.tid(prev_pid, "tasks")})
                    events.append({
                        "name": "task_flow", "cat": "task_flow",
                        "ph": "f", "bp": "e", "id": flow_id, "ts": ts,
                        "pid": pid, "tid": tid})
                prev_lane = lane
            continue
        if not ev.start_time or not ev.end_time:
            continue
        pid = lanes.pid(_task_lane(ev))
        tid = lanes.tid(pid, "tasks")
        events.append({
            "name": ev.name, "cat": "task", "ph": "X",
            "ts": ev.start_time * 1e6,
            "dur": max(ev.end_time - ev.start_time, 1e-6) * 1e6,
            "pid": pid, "tid": tid,
            "args": {"task_id": ev.task_id.hex(), "state": ev.state},
        })
    if own_lanes:
        return lanes.meta + events
    return events


def _span_events(lanes: _LaneTable) -> list[dict]:
    events: list[dict] = []
    for span in _TRACER.spans():
        pid = lanes.pid(span.proc or "driver")
        tid = lanes.tid(pid, span.thread or "main")
        if span.attributes.get("instant") or span.end_time is None:
            events.append({
                "name": span.name,
                "cat": "fault" if span.name.startswith(
                    ("fault:", "chaos:")) else "instant",
                "ph": "i", "s": "p",
                "ts": span.start_time * 1e6,
                "pid": pid, "tid": tid,
                "args": {**span.attributes, "span_id": span.span_id},
            })
            continue
        events.append({
            "name": span.name,
            "cat": "span",
            "ph": "X",
            "ts": span.start_time * 1e6,
            "dur": max(span.end_time - span.start_time, 1e-6) * 1e6,
            "pid": pid, "tid": tid,
            "args": {**span.attributes,
                     "span_id": span.span_id,
                     "trace_id": span.trace_id,
                     "parent_id": span.parent_id},
        })
    return events


def _drain_cluster_spans(runtime) -> None:
    """Pull daemon spans that shipped to the head on heartbeats (the
    piggyback fallback for spans no reply frame carried) into the local
    tracer before exporting. Offsets here are one-way heartbeat
    estimates — coarser than the half-RTT reply path, but these spans
    had no reply to anchor on."""
    if runtime is None or runtime.gcs_client is None:
        return
    try:
        batches = runtime.gcs_client.call("drain_trace_spans",
                                          timeout_s=5.0)
    except Exception:  # noqa: BLE001 — head unreachable: local view only
        return
    for entry in batches or []:
        try:
            spans, offset = entry["spans"], float(entry.get("offset", 0.0))
        except (TypeError, KeyError):
            continue
        ingest_spans(spans, offset)


def export_chrome_trace(path: str) -> int:
    """Write user spans + remote spans + per-stage task lifecycles as
    one merged chrome trace (integer pid/tid + process_name metadata —
    Perfetto groups one lane per node/worker process).

    Returns the number of events written. Open in chrome://tracing or
    https://ui.perfetto.dev.
    """
    from ray_tpu._private.worker import global_runtime

    runtime = global_runtime()
    _drain_cluster_spans(runtime)
    lanes = _LaneTable()
    events = _span_events(lanes)
    if runtime is not None:
        events += build_task_events(runtime, lanes)
    events = lanes.meta + events
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return len(events)
