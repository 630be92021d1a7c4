"""Request-level distributed tracing for the serving stack, and the
phases of the program's own loops (:func:`phase`, at the end).

The serving tier (engine → scheduler → router, PRs 11–15) reports
itself through aggregate counters/gauges/histograms — enough to see
THAT p99 TTFT spiked, never WHICH stage ate the time. This module adds
the per-request timeline those aggregates integrate over: one
:class:`Trace` per request ``uid``, made of :class:`Span` records for
every lifecycle stage (``submit`` → ``route`` → ``queue_wait`` →
``admit`` → ``prefill_chunk``* → ``heartbeat``* / ``draft`` /
``verify`` → ``swap_out`` / ``swap_in`` → terminal ``finish`` /
``expired`` / ``failed``, with ``quarantine`` sub-spans on faults —
the full catalogue is documented in docs/serving.md and pinned by the
span-name lint in tests/L0/test_serving_metrics_lint.py).

Design constraints, in order:

- **Off is free.** ``tracer=None`` (the default everywhere) allocates
  no span objects and changes no tokens — every hook in the serving
  code is a ``if tracer is not None`` guard around pure host-clock
  reads. Pinned bitwise (identical greedy streams, zero new compiled
  programs) by tests/L0/test_tracing.py.
- **No new forced reads.** Span timestamps are host ``perf_counter``
  clocks; device time is attributed from the already-charged
  ``Engine.device_wait_s`` deltas the PR 11 heartbeat split computes
  anyway. The recording methods (:meth:`Tracer.event` and friends)
  are covered by the force-early AST lint — they run inside the
  dispatch-ahead regions' dynamic extent, so they must never call
  ``int()`` / ``np.asarray`` / ``jax.device_get``.
- **Threads are first-class.** The tracer is lock-protected and every
  span records the emitting thread's name, so work the
  ``DraftWorker`` / ``SwapWorker`` daemon threads perform lands in
  the right trace with honest attribution (one Chrome ``tid`` per
  thread). Cross-component context threads two ways: explicitly
  (``trace_id`` captured into worker closures at dispatch) and via
  :meth:`Tracer.bind`, a thread-local binding the scheduler wraps
  around admission so engine-level swap spans — which never see a
  request — attach to the admitting request's trace.
- **Bounded memory.** Completed traces live in a ring of the last
  ``max_traces``; live traces are evicted oldest-first past the same
  bound (a leak-proof default for long-running fleets).

Exporters: :meth:`Tracer.export_chrome_trace` writes Chrome
trace-event JSON (loadable at https://ui.perfetto.dev — one ``pid``
per replica, one ``tid`` per thread) and
:meth:`Tracer.export_jsonl` streams one record per span through the
existing sink machinery (tag ``serving.trace``), which
``python -m apex_tpu.telemetry trace`` summarizes (per-stage
p50/p99, critical-path breakdown, join with ``serving.request``
completion records via their ``trace_id`` field).

**Phases** (:func:`phase`, :data:`phases`) are the other half: not per
request but per region of the program's own loops - the scheduler's beat
(``serve.beat`` > ``serve.admit``, ``serve.decode`` > ``engine.upload``,
``engine.launch``, ``engine.readback`` ...) and the LM recipe's turn
(``train.turn`` > ``train.batch_draw``, ``train.dispatch`` ...). One
primitive marks a host region for both clocks: a
``jax.profiler.TraceAnnotation("apex." + name)``, so that with a
profiler session open the region lands on the host plane of the same
``.xplane.pb`` as the device's operations (read it in Perfetto / XProf
beside the device lanes, or with ``python -m apex_tpu.telemetry
summarize --trace DIR``), and a record ``(name, t0, t1, parent)`` on
``time.perf_counter()`` in :data:`phases`, a bounded process-wide flight
recorder (the last 8192 phases) that is always on: the watchdog's breach
line and the benchmark's per-phase readers take it from there, and the
engine keeps its ``upload_s`` / ``launch_s`` / ``readback_s`` counters at
the same boundaries. ``pyprof.annotate`` and ``telemetry.timed`` are thin
callers.

What "off" costs. There is no off switch for the primitive itself; with
no profiler session a phase is an object, two clock reads, one
``TraceMe`` activity check and one ``deque.append``: 1.6 us a phase on
the v5e machine's host (my chip run, PR 25: ``benchmarks/checks/
probe_phases.py micro``) and 1.7-2.5 us on this repo's sandbox CPU;
12-16 phases a serving beat of 149 ms and 8 a training turn of 153 ms,
under 0.02% of either. ``phases.enabled = False`` stops the recording
and the parent bookkeeping, not the clock reads (0.9 us there, 1.0-1.5
here); with a session open a phase costs 2.6 us there, the annotation
and its encoded arguments.
"""

from __future__ import annotations

import itertools
import json
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

from .sinks import Sink, make_sink

__all__ = ["Span", "Trace", "Tracer", "TRACE_TAG", "PhaseRecord",
           "PhaseRing", "phase", "phases", "PHASE_PREFIX"]

#: ``tag`` of every JSONL record :meth:`Tracer.export_jsonl` writes
TRACE_TAG = "serving.trace"


class Span:
    """One lifecycle stage of one request: a named interval with host
    timestamps (``perf_counter`` seconds), the replica (``pid``) and
    thread (``tid``) it ran on, and a flat dict of annotations
    (chosen replica, bytes moved, drafted/accepted counts, fault
    kind, ...)."""

    __slots__ = ("name", "t0", "dur", "pid", "tid", "args")

    def __init__(self, name, t0, dur, pid, tid, args):
        self.name = name
        self.t0 = t0
        self.dur = dur
        self.pid = pid
        self.tid = tid
        self.args = args

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, t0={self.t0:.6f}, "
                f"dur={self.dur:.6f}, pid={self.pid}, tid={self.tid!r}, "
                f"args={self.args!r})")


class Trace:
    """All spans recorded for one request ``uid`` (the trace id), in
    emission order. ``terminal`` is the name of the trace's single
    terminal span (``finish`` / ``expired`` / ``failed``) once
    :meth:`Tracer.end_trace` sealed it, else None."""

    __slots__ = ("trace_id", "spans", "terminal")

    def __init__(self, trace_id):
        self.trace_id = trace_id
        self.spans: List[Span] = []
        self.terminal: Optional[str] = None

    def by_name(self, name: str) -> List[Span]:
        """The trace's spans named ``name``, in emission order."""
        return [s for s in self.spans if s.name == name]


class _BoundTracer:
    """A :class:`Tracer` view with a fixed default ``pid`` (replica
    index) — what :meth:`Tracer.for_replica` hands each replica's
    scheduler/engine so every span they emit lands under that
    replica's Chrome process without threading ``pid`` through call
    sites."""

    __slots__ = ("_tracer", "pid")

    def __init__(self, tracer: "Tracer", pid: int):
        self._tracer = tracer
        self.pid = pid

    def now(self):
        return self._tracer.now()

    def begin(self, trace_id):
        self._tracer.begin(trace_id)

    def event(self, trace_id, name, *, t0=None, dur=0.0, pid=None,
              **args):
        self._tracer.event(trace_id, name, t0=t0, dur=dur,
                           pid=self.pid if pid is None else pid, **args)

    def event_current(self, name, *, t0=None, dur=0.0, **args):
        self._tracer.event_current(name, t0=t0, dur=dur, **args)

    def end_trace(self, trace_id, name, *, t0=None, dur=0.0, **args):
        self._tracer.end_trace(trace_id, name, t0=t0, dur=dur,
                               pid=self.pid, **args)

    def bind(self, trace_id):
        return self._tracer.bind(trace_id, pid=self.pid)

    def current(self):
        return self._tracer.current()

    def for_replica(self, pid: int) -> "_BoundTracer":
        return self._tracer.for_replica(pid)


class Tracer:
    """Thread-safe span recorder: one :class:`Trace` per request uid,
    a bounded ring of completed traces, exporters.

    Attach with ``Scheduler(tracer=...)`` or ``Router(tracer=...)``;
    the router hands each replica a :meth:`for_replica` view so spans
    carry the replica index as their Chrome ``pid``. The default
    ``tracer=None`` everywhere is the zero-cost off switch — see the
    module docstring's contract.
    """

    def __init__(self, max_traces: int = 1024, clock=time.perf_counter):
        if max_traces < 1:
            raise ValueError("max_traces must be >= 1")
        self.max_traces = max_traces
        self._clock = clock
        self._lock = threading.Lock()
        # live (un-sealed) traces, insertion-ordered for bounded
        # eviction; sealed traces ride the ring + an id index so late
        # worker-thread spans (a swap store completing after its
        # request finished) still find their trace
        self._live: "OrderedDict[Any, Trace]" = OrderedDict()
        self._done: deque = deque()
        self._done_index: Dict[Any, Trace] = {}
        self._local = threading.local()

    # ------------------------------------------------------------ recording
    def now(self) -> float:
        """The tracer's clock (``time.perf_counter`` by default) —
        hooks use it so spans and a custom test clock agree."""
        return self._clock()

    def _get_locked(self, trace_id) -> Trace:
        t = self._live.get(trace_id)
        if t is None:
            t = self._done_index.get(trace_id)
        if t is None:
            t = Trace(trace_id)
            self._live[trace_id] = t
            while len(self._live) > self.max_traces:
                self._live.popitem(last=False)
        return t

    def begin(self, trace_id) -> None:
        """Ensure a live trace exists for ``trace_id`` (idempotent;
        every recording method auto-begins, this just marks intent)."""
        with self._lock:
            self._get_locked(trace_id)

    def event(self, trace_id, name, *, t0=None, dur=0.0, pid=None,
              **args) -> None:
        """Record one span. ``t0`` defaults to now (an instantaneous
        marker); ``dur`` is seconds; ``pid`` is the replica index
        (defaults to the thread's :meth:`bind` binding, else 0); the
        emitting thread's name is recorded as ``tid``; remaining
        keywords become the span's annotations."""
        clock_now = self._clock()
        if pid is None:
            bound = getattr(self._local, "stack", None)
            pid = bound[-1][1] if bound else 0
        span = Span(name, clock_now if t0 is None else t0, dur, pid,
                    threading.current_thread().name, args)
        with self._lock:
            self._get_locked(trace_id).spans.append(span)

    def event_current(self, name, *, t0=None, dur=0.0, **args) -> None:
        """Record a span on the thread's CURRENTLY BOUND trace (see
        :meth:`bind`); a silent no-op when nothing is bound — engine
        internals call this without knowing whether a request context
        exists."""
        bound = getattr(self._local, "stack", None)
        if not bound:
            return
        trace_id, pid = bound[-1]
        self.event(trace_id, name, t0=t0, dur=dur, pid=pid, **args)

    def end_trace(self, trace_id, name, *, t0=None, dur=0.0, pid=None,
                  **args) -> None:
        """Record the TERMINAL span (``finish`` / ``expired`` /
        ``failed``) and seal the trace into the completed ring.
        Sealing twice keeps the first terminal (one terminal per
        trace — the chaos composition pin's invariant)."""
        clock_now = self._clock()
        if pid is None:
            bound = getattr(self._local, "stack", None)
            pid = bound[-1][1] if bound else 0
        span = Span(name, clock_now if t0 is None else t0, dur, pid,
                    threading.current_thread().name, args)
        with self._lock:
            t = self._live.pop(trace_id, None)
            if t is None:
                t = self._done_index.get(trace_id)
                if t is not None:
                    # already sealed: keep the first terminal
                    return
                t = Trace(trace_id)
            t.spans.append(span)
            t.terminal = name
            self._done.append(t)
            self._done_index[trace_id] = t
            while len(self._done) > self.max_traces:
                old = self._done.popleft()
                self._done_index.pop(old.trace_id, None)

    def bind(self, trace_id, pid: int = 0):
        """Context manager binding ``trace_id`` (and default ``pid``)
        to the current thread — the scheduler wraps admission in it so
        engine-level spans (:meth:`event_current` from swap paths,
        which never see a request) land in the admitting request's
        trace. Re-entrant (a stack): swap-outs triggered inside a
        swap-in stay correctly attributed."""
        return _Binding(self._local, trace_id, pid)

    def current(self):
        """The thread's currently bound trace id, or None — captured
        into worker closures at dispatch time so completion spans
        emitted on the worker thread join the right trace."""
        bound = getattr(self._local, "stack", None)
        return bound[-1][0] if bound else None

    def for_replica(self, pid: int) -> _BoundTracer:
        """A view of this tracer whose spans default to Chrome process
        ``pid`` — one per replica, handed out by the router."""
        return _BoundTracer(self, pid)

    # ------------------------------------------------------------ reading
    def traces(self) -> List[Trace]:
        """Snapshot of the COMPLETED traces (oldest first)."""
        with self._lock:
            return list(self._done)

    def live_traces(self) -> List[Trace]:
        """Snapshot of the still-open traces (submitted/unfinished
        requests), oldest first."""
        with self._lock:
            return list(self._live.values())

    def find(self, trace_id) -> Optional[Trace]:
        """The trace for ``trace_id`` (live or completed), or None."""
        with self._lock:
            return self._live.get(trace_id) \
                or self._done_index.get(trace_id)

    def _all_spans(self) -> List[tuple]:
        with self._lock:
            traces = list(self._done) + list(self._live.values())
        out = []
        for t in traces:
            for s in t.spans:
                out.append((t.trace_id, s))
        return out

    # ------------------------------------------------------------ exporters
    def export_chrome_trace(self, path: str) -> int:
        """Write Chrome trace-event JSON (the Perfetto/chrome://tracing
        format): every span becomes a complete (``"ph": "X"``) event
        with microsecond timestamps, ``pid`` = replica index (named
        ``replica<i>`` via process metadata), ``tid`` = a stable
        small integer per emitting thread (named via thread
        metadata), and the span's annotations + ``trace_id`` under
        ``args``. Events are sorted by timestamp within each thread
        lane. Returns the number of span events written."""
        spans = self._all_spans()
        pids = sorted({s.pid for _, s in spans})
        tid_names = sorted({s.tid for _, s in spans})
        tid_of = {name: i + 1 for i, name in enumerate(tid_names)}
        events = []
        for pid in pids:
            events.append({"name": "process_name", "ph": "M",
                           "pid": pid, "tid": 0,
                           "args": {"name": f"replica{pid}"}})
            for name in tid_names:
                events.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": tid_of[name],
                               "args": {"name": name}})
        span_events = []
        for trace_id, s in spans:
            span_events.append({
                "name": s.name, "cat": "serving", "ph": "X",
                "ts": int(round(s.t0 * 1e6)),
                "dur": int(round(s.dur * 1e6)),
                "pid": s.pid, "tid": tid_of[s.tid],
                "args": {"trace_id": trace_id, **s.args},
            })
        span_events.sort(key=lambda e: (e["pid"], e["tid"], e["ts"]))
        events.extend(span_events)
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return len(span_events)

    def export_jsonl(self, spec_or_sink) -> int:
        """Stream one record per span through the sink machinery:
        ``spec_or_sink`` is a :class:`~apex_tpu.telemetry.Sink` or a
        :func:`~apex_tpu.telemetry.make_sink` spec (JSONL path /
        ``"stdout"`` / ``"null"``). Records carry ``tag`` =
        :data:`TRACE_TAG` plus ``trace_id`` / ``span`` / ``ts_s`` /
        ``dur_s`` / ``replica`` / ``thread`` and the span's
        annotations — the shape ``python -m apex_tpu.telemetry
        trace`` consumes. Returns the number of records written; a
        sink this call opened is closed before returning."""
        owns = not isinstance(spec_or_sink, Sink)
        sink = make_sink(spec_or_sink) if owns else spec_or_sink
        n = 0
        try:
            for trace_id, s in self._all_spans():
                sink.emit({"tag": TRACE_TAG, "trace_id": trace_id,
                           "span": s.name, "ts_s": s.t0,
                           "dur_s": s.dur, "replica": s.pid,
                           "thread": s.tid, **s.args})
                n += 1
        finally:
            if owns:
                sink.close()
        return n


class _Binding:
    """The :meth:`Tracer.bind` context manager (tiny and allocation-
    light: one tuple push/pop on a thread-local stack)."""

    __slots__ = ("_local", "_item")

    def __init__(self, local, trace_id, pid):
        self._local = local
        self._item = (trace_id, pid)

    def __enter__(self):
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        stack.append(self._item)
        return self

    def __exit__(self, *exc):
        self._local.stack.pop()
        return False


# ------------------------------------------------------------- phases
#: prefix of every phase's ``TraceAnnotation`` on the profiler's host plane
PHASE_PREFIX = "apex."


class PhaseRecord(NamedTuple):
    """One finished phase as :meth:`PhaseRing.records` hands it out:
    ``(name, t0, t1, parent)`` on ``time.perf_counter()``, then ``id``
    (this phase's own number), ``root`` (the id of the outermost phase
    it ran under: a beat, a loop turn) and ``args`` (the annotations,
    or None). ``parent`` is the id of the enclosing phase of the same
    thread, or None."""

    name: str
    t0: float
    t1: float
    parent: Optional[int]
    id: int
    root: int
    args: Optional[dict]

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class PhaseRing:
    """The process-wide flight recorder of :func:`phase`: the last
    ``maxlen`` finished phases, oldest dropped first. A phase is
    appended when it ENDS, so children precede their parent. Written by
    ``deque.append`` alone (atomic under the interpreter lock: no other
    lock on the beat path); ``enabled = False`` stops the recording,
    not the profiler annotation."""

    def __init__(self, maxlen: int = 8192):
        self.enabled = True
        self._ring: deque = deque(maxlen=maxlen)

    def records(self, name: Optional[str] = None,
                since: Optional[float] = None) -> List[PhaseRecord]:
        """The recorded phases, oldest first: all of them, or those
        called ``name``, or those that ended at or after ``since``
        (``perf_counter`` seconds)."""
        return [PhaseRecord._make(r) for r in list(self._ring)
                if (name is None or r[0] == name)
                and (since is None or r[2] >= since)]

    def self_times(self, records=None) -> Dict[int, float]:
        """``{id: seconds}`` for ``records`` (default: the whole ring):
        each phase's duration less what its direct children cover - a
        layer's own time. A child whose parent has left the ring counts
        for itself only."""
        recs = self.records() if records is None else records
        out = {r[4]: r[2] - r[1] for r in recs}
        for r in recs:
            if r[3] in out:
                out[r[3]] -= r[2] - r[1]
        return out


#: the one ring every :func:`phase` of this process records into
phases = PhaseRing()

_phase_local = threading.local()
_phase_ids = itertools.count(1)


class phase:
    """``with tracing.phase("serve.admit", slot=3): ...`` marks one host
    region, once, for both clocks:

    1. it enters ``jax.profiler.TraceAnnotation("apex." + name, **args)``
       - nothing without a profiler session; with one, the span lands on
       the host plane of the same ``.xplane.pb`` as the device's
       operations. The OUTERMOST phase of a thread (a beat, a loop turn)
       also carries ``pc_ns``, ``time.perf_counter_ns()`` at its entry:
       the profiler rebases its timestamps to the session's start, so
       this stat is what maps ``perf_counter`` onto the trace's clock;
    2. it appends ``(name, t0, t1, parent, ...)`` to :data:`phases`.

    ``t0`` / ``t1`` stay on the object (a caller that keeps a counter
    at the same boundary reads them instead of the clock again), and
    :meth:`note` adds an annotation known only at the end (chunks run,
    tokens emitted). What it costs: the module's docstring."""

    __slots__ = ("name", "args", "t0", "t1", "id", "root", "_parent",
                 "_ann")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self.t0 = self.t1 = 0.0
        self.id = self.root = self._parent = self._ann = None

    def note(self, **args) -> None:
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self):
        if phases.enabled:
            stack = getattr(_phase_local, "stack", None)
            if stack is None:
                stack = _phase_local.stack = []
            self.id = next(_phase_ids)
            if stack:
                up = stack[-1]
                self._parent, self.root = up.id, up.root
                self.t0 = time.perf_counter()
            else:
                self.root = self.id
                ns = time.perf_counter_ns()
                self.args["pc_ns"] = ns
                self.t0 = ns * 1e-9
            stack.append(self)
        else:
            self.t0 = time.perf_counter()
        if TraceAnnotation.is_enabled():    # a profiler session is open
            self._ann = ann = TraceAnnotation(PHASE_PREFIX + self.name,
                                              **self.args)
            ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.t1 = t1 = time.perf_counter()
        if self.id is not None:
            stack = _phase_local.stack
            # a phase left without its exit (a generator dropped
            # mid-block) is still above this one: pop down to it
            while stack and stack.pop() is not self:
                pass
            phases._ring.append((self.name, self.t0, t1, self._parent,
                                 self.id, self.root, self.args or None))
        return False
