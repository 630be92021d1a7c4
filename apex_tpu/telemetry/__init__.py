"""apex_tpu.telemetry — unified in-jit training telemetry.

The reference apex observes training with NVTX ranges and recipe-level
``print``; this subsystem is the structured counterpart the TPU port
needs before multi-chip runs can be debugged (SURVEY §6): every signal a
jitted train step computes — loss, grad norm, ``found_inf``, the loss-
scale trajectory — streams to the host as it happens, lands in one
process-local :class:`MetricsRegistry`, and fans out to pluggable sinks
(JSONL file, stdout line protocol, in-memory spy, null).

Layers:

- metrics core (:mod:`.core`) — counters, gauges, streaming histograms
  (p50/p95/p99), per-step :data:`StepRecord` ring buffer, sink fan-out.
- sinks (:mod:`.sinks`) — :class:`JsonlSink` / :class:`StdoutSink` /
  :class:`NullSink` / :class:`MemorySink`.
- in-jit emission (:mod:`.emit`) — :func:`emit_metrics`: ONE
  ``jax.debug.callback`` per step bundles all metric scalars; wired into
  ``amp.make_train_step(telemetry=...)``. Enabled-ness is read at trace
  time (same contract as ``pyprof.init``); sinks/registry resolve at
  callback time.
- comm health (:func:`account_collective`) — bytes/calls/leaves counters
  for every ``apex_tpu.comm`` collective and the DDP grad allreduce;
  device latency joins in through the profiler
  (``summarize --trace``).
- request tracing (:mod:`.tracing`) — :class:`Tracer`: span-based
  per-request lifecycle traces for the serving stack (``submit`` →
  ``route`` → ``admit`` → ``prefill_chunk``/``heartbeat`` → terminal),
  Chrome-trace/Perfetto + JSONL exporters; attached via
  ``Scheduler(tracer=)`` / ``Router(tracer=)``, zero-cost when off.
- CLI (:mod:`.__main__`) — ``python -m apex_tpu.telemetry summarize
  run.jsonl [--trace DIR]``: per-metric count/mean/p50/p95/p99 plus the
  device step-time breakdown joined from a ``pyprof.trace`` capture;
  ``python -m apex_tpu.telemetry trace spans.jsonl``: per-stage span
  latency + critical-path breakdown of a request-trace file.

Quick start::

    from apex_tpu import amp, telemetry

    telemetry.start_run("run.jsonl")            # JSONL sink on default reg
    init_fn, step_fn = amp.make_train_step(loss_fn, opt, policy,
                                           telemetry=True)
    ...train...
    telemetry.get_registry().emit_snapshot()    # final aggregate line
    telemetry.get_registry().close()

Then ``python -m apex_tpu.telemetry summarize run.jsonl``.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from typing import List, Optional

from ..log_util import get_logger
from .core import MetricsRegistry, StepRecord, StreamingHistogram
from .emit import (account_collective, collective_bytes, emit_metrics,
                   global_norm)
from .sinks import (JsonlSink, MemorySink, NullSink, Sink, StdoutSink,
                    make_sink)
from . import tracing
from .tracing import Span, Trace, Tracer

__all__ = [
    "MetricsRegistry", "StepRecord", "StreamingHistogram",
    "Sink", "JsonlSink", "StdoutSink", "NullSink", "MemorySink",
    "make_sink",
    "Span", "Trace", "Tracer",
    "emit_metrics", "account_collective", "collective_bytes", "global_norm",
    "enable", "enabled", "get_registry", "set_registry", "configure",
    "start_run", "from_env", "timed", "guard_bench_main",
]

ENV_VAR = "APEX_TPU_TELEMETRY"

_logger = get_logger("telemetry")

_enabled = True
_registry: Optional[MetricsRegistry] = None


def enable(on: bool = True) -> None:
    """Global switch. In-jit emission reads it at TRACE time (flip before
    the first call of a jitted step, or ``jax.clear_caches()``); host-side
    accounting reads it per call."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def get_registry() -> MetricsRegistry:
    """The process-default registry (created lazily, sink-less)."""
    global _registry
    if _registry is None:
        _registry = MetricsRegistry()
    return _registry


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    global _registry
    _registry = registry
    return registry


def configure(sinks: Optional[List[Sink]] = None, ring_size: int = 1024,
              reservoir_size: int = 4096) -> MetricsRegistry:
    """Install a FRESH default registry with the given sinks (the previous
    default, if any, is left for its holders but no longer receives
    emissions routed through the default)."""
    return set_registry(MetricsRegistry(ring_size=ring_size, sinks=sinks,
                                        reservoir_size=reservoir_size))


def start_run(spec: str, **configure_kw) -> MetricsRegistry:
    """One-call run setup: ``spec`` is a JSONL path, ``"stdout"``, or
    ``"null"`` (see :func:`make_sink`); returns the fresh default
    registry."""
    reg = configure(sinks=[make_sink(spec)], **configure_kw)
    _logger.info("telemetry run started (sink=%s)", spec)
    return reg


def from_env(var: str = ENV_VAR) -> Optional[MetricsRegistry]:
    """Opt-in via environment: ``APEX_TPU_TELEMETRY=run.jsonl`` (or
    ``stdout``/``null``) starts a run; unset/empty returns None and
    changes nothing. The bench drivers call this so any bench run can
    stream step telemetry without a flag plumb-through."""
    spec = os.environ.get(var)
    if not spec:
        return None
    return start_run(spec)


@contextlib.contextmanager
def timed(name: str, registry: Optional[MetricsRegistry] = None):
    """Host-side latency observation: wall seconds of the block go into
    histogram ``name`` (+ counter ``name.calls``) — for eager sections
    (checkpoint saves, eval passes) the in-jit path can't time. The
    block is one :func:`tracing.phase`: it shows as ``apex.<name>`` in
    a profiler session and in the flight recorder too."""
    p = tracing.phase(name)
    try:
        with p:
            yield
    finally:
        if _enabled:
            reg = registry if registry is not None else get_registry()
            reg.observe(name, p.t1 - p.t0)
            reg.counter_inc(f"{name}.calls")


# Error-text markers of TRANSIENT infrastructure failures (dropped
# connections, backend races) — worth one retry before the failure line
# erases a canonical perf record. Substring-matched, case-insensitive,
# against ``{type}: {message}``.
_TRANSIENT_MARKERS = (
    "unavailable", "deadline_exceeded",
    "deadline exceeded", "connection reset", "connection refused",
    "broken pipe", "socket closed", "transient", "temporarily",
)


def _is_transient_error(err: str) -> bool:
    low = err.lower()
    return any(m in low for m in _TRANSIENT_MARKERS)


# Backoff before each transient retry: _RETRY_BACKOFF_S * 2**n, capped.
# Module-level so tests (and desperate operators) can zero it.
_RETRY_BACKOFF_S = 0.5
_RETRY_BACKOFF_CAP_S = 8.0


def _env_retries(default: int = 1) -> int:
    """``APEX_TPU_BENCH_RETRIES`` (>= 0), or ``default``. A malformed
    value must degrade to the default, never crash the bench before its
    guard is even armed."""
    raw = os.environ.get("APEX_TPU_BENCH_RETRIES")
    if raw is None or not raw.strip():
        return default
    try:
        return max(0, int(raw))
    except ValueError:
        _logger.warning("APEX_TPU_BENCH_RETRIES=%r is not an integer; "
                        "using %d", raw, default)
        return default


def guard_bench_main(main, metric: str, retries: Optional[int] = None):
    """Run a bench driver's ``main`` so that EVERY outcome ends in a final
    parseable JSON line on stdout.

    Success: ``main`` already printed its metric line — pass through.
    Any failure (backend init, compile, OOM, bad argv): the traceback
    goes to stderr, and the LAST stdout line is
    ``{"metric": ..., "error": "...", "rc": 1, "transient": ...}`` so
    harnesses that parse the final line never record
    ``"parsed": null`` again. Exits 1 on failure; KeyboardInterrupt
    passes through.

    Resilience: an error whose text matches a
    transient-infrastructure marker (UNAVAILABLE, deadline exceeded,
    connection resets — :data:`_TRANSIENT_MARKERS`) gets
    ``retries`` fresh attempts of ``main`` before the failure line is
    emitted, so one infrastructure flake cannot erase the round's
    canonical perf record. The final failure line carries ``"transient": true/false``
    — true means the retries were exhausted on flake-shaped errors and
    the record should be read as infrastructure noise, not a perf
    regression; deterministic failures (bad argv, OOM, real compile
    errors) never retry and tag false.

    A retry re-runs ``main`` FROM SCRATCH, so a multi-row driver
    (bench_schedule.py) that emitted rows before the flake emits them
    again on the retry. Before each retry a marker line
    ``{"metric": ..., "event": "transient_retry", "discard_preceding":
    true, ...}`` is written to stdout so row-aggregating harnesses can
    drop the partial first attempt; final-line parsers are unaffected
    (the marker is never last — a real row or the failure line follows).

    ``retries`` defaults from ``APEX_TPU_BENCH_RETRIES`` (else 1), so a
    flaky round can be re-driven with more attempts without touching
    every bench driver (a single retry can be burned by back-to-back
    resets). Retries sleep a short
    exponential backoff first (0.5 s, 1 s, 2 s, ... capped at 8 s) —
    back-to-back retries land inside the same infrastructure hiccup;
    a beat of patience is what actually clears connection resets.
    """
    import traceback

    if retries is None:
        retries = _env_retries()

    def _fail(err: str):
        # drain in-flight debug callbacks BEFORE writing the line that
        # must be last on stdout — a step that died mid-loop can still
        # have queued emissions (a StdoutSink printing after the JSON
        # line would break the contract). jax may itself be the thing
        # that failed to import/init, so best-effort.
        try:
            import jax

            jax.effects_barrier()
        except BaseException:
            pass
        _logger.error("bench %s failed: %s", metric, err)
        line = json.dumps({"metric": metric, "error": err, "rc": 1,
                           "transient": _is_transient_error(err)})
        sys.stdout.write(line + "\n")
        sys.stdout.flush()
        raise SystemExit(1)

    attempts_left = int(retries)
    while True:
        try:
            return main()
        except KeyboardInterrupt:
            raise
        except SystemExit as e:
            if e.code in (None, 0):
                raise
            traceback.print_exc(file=sys.stderr)
            err = str(e.code) if not isinstance(e.code, int) \
                else f"SystemExit: {e.code}"
        except BaseException as e:  # noqa: BLE001 — the contract is total
            traceback.print_exc(file=sys.stderr)
            err = f"{type(e).__name__}: {e}"
        if attempts_left > 0 and _is_transient_error(err):
            attempts_left -= 1
            n_retried = int(retries) - attempts_left - 1
            delay = min(_RETRY_BACKOFF_CAP_S,
                        _RETRY_BACKOFF_S * (2 ** n_retried))
            _logger.warning("bench %s hit a transient error (%s); "
                            "retrying in %.1fs — %d retry(ies) remain "
                            "after this", metric, err, delay,
                            attempts_left)
            if delay > 0:
                time.sleep(delay)
            # multi-row drivers re-emit their rows on the retry: mark the
            # boundary so row aggregators can discard the partial attempt
            sys.stdout.write(json.dumps({
                "metric": metric, "event": "transient_retry",
                "error": err, "discard_preceding": True}) + "\n")
            sys.stdout.flush()
            continue
        _fail(err)
