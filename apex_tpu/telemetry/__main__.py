"""Run-summary CLI:

    python -m apex_tpu.telemetry summarize [run.jsonl] [--tag T] [--json]
                                                        [--trace DIR]
    python -m apex_tpu.telemetry trace spans.jsonl [--requests RUN]
                                                    [--json]

``summarize`` renders per-metric count/mean/p50/p95/p99 aggregates of a
telemetry JSONL run file; ``--trace`` additionally joins a
``pyprof.trace`` capture into a device step-time breakdown (ms/step per
HLO category, collective-op latency) and the device's idle seconds by
the innermost ``apex.*`` host span (``tracing.phase``) the host was in.

``trace`` summarizes a request-trace JSONL file (what
:meth:`~apex_tpu.telemetry.Tracer.export_jsonl` wrote): per-stage span
latency p50/p99, the critical-path breakdown, and — via ``--requests``
(defaults to the same file, since one sink may carry both streams) —
the join with ``serving.request`` completion records on ``trace_id``.

``--json`` emits the machine form instead of the tables.
"""

from __future__ import annotations

import argparse
import json
import sys

from .summarize import (load_records, phase_idle, render_breakdown,
                        render_phase_idle, render_summary,
                        render_trace_summary, summarize_records,
                        summarize_trace, trace_breakdown)


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.telemetry",
        description="apex_tpu telemetry tools")
    sub = p.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("summarize",
                       help="aggregate a telemetry JSONL run file")
    s.add_argument("run", nargs="?", default=None,
                   help="JSONL file a JsonlSink wrote (may be left out "
                        "with --trace: the capture alone is read)")
    s.add_argument("--tag", default=None,
                   help="only records with this tag (default: all)")
    s.add_argument("--trace", default=None, metavar="DIR",
                   help="join a pyprof.trace capture: device step-time "
                        "breakdown + collective latency")
    s.add_argument("--json", action="store_true",
                   help="machine-readable output instead of tables")
    t = sub.add_parser("trace",
                       help="summarize a serving request-trace JSONL "
                            "file (Tracer.export_jsonl output)")
    t.add_argument("run", help="JSONL file Tracer.export_jsonl wrote")
    t.add_argument("--requests", default=None, metavar="RUN",
                   help="JSONL with serving.request completion records "
                        "to join on trace_id (default: the trace file "
                        "itself)")
    t.add_argument("--json", action="store_true",
                   help="machine-readable output instead of tables")
    args = p.parse_args(argv)

    if args.cmd == "trace":
        return _main_trace(args)
    if args.run is None and not args.trace:
        p.error("summarize needs a run file, --trace DIR, or both")
    records = []
    if args.run is not None:
        try:
            records = load_records(args.run)
        except OSError as e:
            raise SystemExit(str(e))
        if not records:
            raise SystemExit(f"no telemetry records in {args.run!r}")
    summary = summarize_records(records, tag=args.tag)

    breakdown = idle = None
    if args.trace:
        n_steps = max(summary["steps"].values(), default=0) \
            if summary["steps"] else 0
        try:
            breakdown = trace_breakdown(args.trace, n_steps)
            idle = phase_idle(args.trace)
        except FileNotFoundError as e:
            raise SystemExit(str(e))

    if args.json:
        out = dict(summary)
        if breakdown is not None:
            out["device_breakdown"] = breakdown
        if idle is not None:
            out["device_idle_by_phase"] = idle
        print(json.dumps(out))
    else:
        if records:
            print(render_summary(summary))
        if breakdown is not None:
            print()
            print(render_breakdown(breakdown))
        if idle is not None:
            print()
            print(render_phase_idle(idle))
    return 0


def _main_trace(args):
    try:
        records = load_records(args.run)
    except OSError as e:
        raise SystemExit(str(e))
    if not any(r.get("tag") == "serving.trace" for r in records):
        raise SystemExit(f"no serving.trace records in {args.run!r} — "
                         "is this a Tracer.export_jsonl file?")
    if args.requests is None:
        request_records = records
    else:
        try:
            request_records = load_records(args.requests)
        except OSError as e:
            raise SystemExit(str(e))
    summary = summarize_trace(records, request_records)
    if args.json:
        print(json.dumps(summary))
    else:
        print(render_trace_summary(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
