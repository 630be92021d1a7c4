"""Request-level distributed tracing for the serving stack.

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
"""

from __future__ import annotations

import json
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

from .sinks import Sink, make_sink

__all__ = ["Span", "Trace", "Tracer", "TRACE_TAG"]

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
