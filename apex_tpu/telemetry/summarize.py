"""Run-file aggregation behind ``python -m apex_tpu.telemetry summarize``.

Consumes the JSONL a :class:`~apex_tpu.telemetry.JsonlSink` wrote (one
record per step + optional snapshot records) and renders per-metric
aggregates — count/mean/p50/p95/p99/min/max, through the same
:class:`~apex_tpu.telemetry.StreamingHistogram` the live registry uses,
so offline and online numbers agree.

With ``--trace DIR`` it joins a ``pyprof.trace`` capture: the device
lanes' per-op spans (``pyprof.analyze``) are grouped by HLO category into
a step-time breakdown (ms/step per category, using the run's step count),
and collective categories are split out as device-side comm latency —
the latency half of the comm-health story whose bytes half lives in the
``comm.*`` counters.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

from .core import META_KEYS, StreamingHistogram

__all__ = ["load_records", "summarize_records", "render_summary",
           "trace_breakdown", "render_breakdown", "phase_idle",
           "render_phase_idle",
           "summarize_trace", "render_trace_summary"]

#: counters read as a share of another: (name, part, whole)
COUNTER_SHARES = (
    ("dispatch-ahead engagement", "serving.heartbeat.dispatched_ahead",
     "serving.decode.steps"),
)

#: hlo_category substrings that identify collective/communication ops
COMM_CATEGORIES = ("all-reduce", "all-gather", "all-to-all",
                   "reduce-scatter", "collective", "copy", "send", "recv")


def load_records(path: str) -> List[Dict[str, Any]]:
    """Parse a telemetry JSONL file; non-JSON and non-dict lines are
    skipped (a crashed run may end mid-write — the contract is that every
    complete line is usable)."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                records.append(rec)
    return records


def _is_snapshot(rec: Dict[str, Any]) -> bool:
    return "counters" in rec or "histograms" in rec


def summarize_records(records: List[Dict[str, Any]],
                      tag: Optional[str] = None) -> Dict[str, Any]:
    """Aggregate step records into per-metric summaries.

    Returns ``{"metrics": {"<tag>.<name>": summary_dict},
    "counters": {...}, "steps": {tag: n}}``. ``step_time_s`` (stamped by
    the registry host-side) aggregates like any other series. Counters
    come from the LAST snapshot record, if the run emitted one."""
    hists: Dict[str, StreamingHistogram] = {}
    steps: Dict[str, int] = {}
    counters: Dict[str, float] = {}
    for rec in records:
        if _is_snapshot(rec):
            counters = dict(rec.get("counters", {}))
            continue
        rtag = rec.get("tag", "train")
        if tag is not None and rtag != tag:
            continue
        steps[rtag] = steps.get(rtag, 0) + 1
        for k, v in rec.items():
            if k in META_KEYS and k != "step_time_s":
                continue
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                continue
            key = f"{rtag}.{k}"
            h = hists.get(key)
            if h is None:
                h = hists[key] = StreamingHistogram()
            h.observe(v)
    return {
        "metrics": {k: hists[k].summary() for k in sorted(hists)},
        "counters": counters,
        "steps": steps,
    }


def render_summary(summary: Dict[str, Any]) -> str:
    """Aligned text table of :func:`summarize_records` output."""
    lines = []
    steps = summary.get("steps", {})
    if steps:
        lines.append("steps: " + ", ".join(
            f"{t}={n}" for t, n in sorted(steps.items())))
        lines.append("")
    hdr = (f"{'metric':<32} {'count':>7} {'mean':>12} {'p50':>12} "
           f"{'p95':>12} {'p99':>12} {'min':>12} {'max':>12}")
    lines += [hdr, "-" * len(hdr)]
    for name, s in summary["metrics"].items():
        if s.get("count", 0) == 0:
            continue
        lines.append(
            f"{name[:32]:<32} {s['count']:>7} {s['mean']:>12.6g} "
            f"{s['p50']:>12.6g} {s['p95']:>12.6g} {s['p99']:>12.6g} "
            f"{s['min']:>12.6g} {s['max']:>12.6g}")
    if summary.get("counters"):
        lines += ["", f"{'counter':<48} {'value':>14}"]
        lines.append("-" * 63)
        for name in sorted(summary["counters"]):
            v = summary["counters"][name]
            lines.append(f"{name[:48]:<48} {v:>14,.0f}")
        for title, part, whole in COUNTER_SHARES:
            c = summary["counters"]
            if c.get(whole):
                lines.append(f"{title + ' (' + part + ' / ' + whole + ')'}"
                             f" {c.get(part, 0.0) / c[whole]:.4f}")
    return "\n".join(lines)


def summarize_trace(records: List[Dict[str, Any]],
                    request_records: Optional[List[Dict[str, Any]]] = None,
                    ) -> Dict[str, Any]:
    """Aggregate ``serving.trace`` span records (the JSONL
    :meth:`~apex_tpu.telemetry.Tracer.export_jsonl` writes) behind
    ``python -m apex_tpu.telemetry trace``.

    Returns::

        {"traces": n, "spans": {name: {count, mean, p50, p95, p99,
                                       min, max}},       # span DURATIONS
         "critical_path": {name: {"total_s", "per_request_s",
                                  "pct"}},               # where time went
         "requests": {...} | None}

    The critical path charges each stage's summed span durations
    against the fleet-wide total (heartbeat spans measure whole beats
    a slot participated in, so stages legitimately overlap — ``pct``
    reads as "fraction of summed stage time", not wall time).

    ``request_records`` (optional) are ``serving.request`` completion
    records (same JSONL or another run file): they join on their
    ``trace_id`` field — the summary then reports how many traces
    matched a completion record and the per-status request counts,
    the cross-check that the trace stream and the metrics stream
    describe the same requests."""
    spans = [r for r in records if r.get("tag") == "serving.trace"]
    hists: Dict[str, StreamingHistogram] = {}
    totals: Dict[str, float] = {}
    trace_ids = set()
    for r in spans:
        name = r.get("span")
        if not isinstance(name, str):
            continue
        trace_ids.add(r.get("trace_id"))
        dur = r.get("dur_s") or 0.0
        h = hists.get(name)
        if h is None:
            h = hists[name] = StreamingHistogram()
        h.observe(dur)
        totals[name] = totals.get(name, 0.0) + float(dur)
    n_traces = len(trace_ids)
    grand = sum(totals.values()) or 1.0
    critical = {
        name: {"total_s": totals[name],
               "per_request_s": totals[name] / max(n_traces, 1),
               "pct": 100.0 * totals[name] / grand}
        for name in sorted(totals, key=lambda k: -totals[k])}
    joined = None
    if request_records is not None:
        reqs = [r for r in request_records
                if r.get("tag") == "serving.request"]
        matched = [r for r in reqs if r.get("trace_id") in trace_ids]
        statuses: Dict[str, int] = {}
        for r in matched:
            s = str(r.get("status"))
            statuses[s] = statuses.get(s, 0) + 1
        joined = {"completion_records": len(reqs),
                  "matched": len(matched),
                  "unmatched_traces": n_traces - len({
                      r.get("trace_id") for r in matched}),
                  "statuses": statuses}
    return {"traces": n_traces,
            "spans": {k: hists[k].summary() for k in sorted(hists)},
            "critical_path": critical,
            "requests": joined}


def render_trace_summary(summary: Dict[str, Any]) -> str:
    """Aligned text tables of :func:`summarize_trace` output: the
    per-stage latency distribution, then the critical-path breakdown,
    then the completion-record join (when requested)."""
    lines = [f"traces: {summary['traces']}", ""]
    hdr = (f"{'span':<18} {'count':>7} {'mean':>12} {'p50':>12} "
           f"{'p95':>12} {'p99':>12} {'max':>12}")
    lines += [hdr, "-" * len(hdr)]
    for name, s in summary["spans"].items():
        if s.get("count", 0) == 0:
            continue
        lines.append(
            f"{name[:18]:<18} {s['count']:>7} {s['mean']:>12.6g} "
            f"{s['p50']:>12.6g} {s['p95']:>12.6g} {s['p99']:>12.6g} "
            f"{s['max']:>12.6g}")
    lines += ["", "critical path (summed stage time; stages overlap):"]
    hdr = f"{'span':<18} {'total_s':>12} {'per_req_s':>12} {'%':>6}"
    lines += [hdr, "-" * len(hdr)]
    for name, c in summary["critical_path"].items():
        lines.append(f"{name[:18]:<18} {c['total_s']:>12.6g} "
                     f"{c['per_request_s']:>12.6g} {c['pct']:>6.1f}")
    joined = summary.get("requests")
    if joined is not None:
        lines += ["", f"completion records: {joined['completion_records']}"
                  f" ({joined['matched']} matched by trace_id, "
                  f"{joined['unmatched_traces']} trace(s) unmatched)"]
        for s in sorted(joined["statuses"]):
            lines.append(f"  status {s}: {joined['statuses'][s]}")
    return "\n".join(lines)


def trace_breakdown(trace_dir: str, n_steps: int) -> Dict[str, Any]:
    """Join a ``pyprof.trace`` capture with a run's step count: device
    time per HLO category (total and ms/step) plus per-op latency stats
    for the collective categories."""
    from apex_tpu import pyprof

    rows = pyprof.analyze(trace_dir)
    by_cat: Dict[str, Dict[str, float]] = {}
    comm_ops = []
    for r in rows:
        cat = r.get("category") or "(uncategorized)"
        c = by_cat.setdefault(cat, {"total_ms": 0.0, "occurrences": 0})
        c["total_ms"] += r["total_ms"]
        c["occurrences"] += r["occurrences"]
        if any(s in cat.lower() or s in r["name"].lower()
               for s in COMM_CATEGORIES):
            comm_ops.append({"name": r["name"], "category": cat,
                             "occurrences": r["occurrences"],
                             "mean_ms": r["mean_ms"],
                             "total_ms": r["total_ms"]})
    total = sum(c["total_ms"] for c in by_cat.values()) or 1.0
    cats = [{"category": k, "total_ms": v["total_ms"],
             "occurrences": v["occurrences"],
             "ms_per_step": v["total_ms"] / max(n_steps, 1),
             "pct": 100.0 * v["total_ms"] / total}
            for k, v in by_cat.items()]
    cats.sort(key=lambda c: -c["total_ms"])
    comm_ops.sort(key=lambda c: -c["total_ms"])
    return {"n_steps": n_steps, "categories": cats, "comm_ops": comm_ops}


def render_breakdown(bd: Dict[str, Any]) -> str:
    lines = [f"device step-time breakdown ({bd['n_steps']} steps):"]
    hdr = (f"{'category':<28} {'n':>7} {'total_ms':>12} "
           f"{'ms/step':>10} {'%':>6}")
    lines += [hdr, "-" * len(hdr)]
    for c in bd["categories"]:
        lines.append(f"{c['category'][:28]:<28} {c['occurrences']:>7} "
                     f"{c['total_ms']:>12.3f} {c['ms_per_step']:>10.4f} "
                     f"{c['pct']:>6.1f}")
    if bd["comm_ops"]:
        lines += ["", "comm op device latency:"]
        hdr = f"{'op':<44} {'n':>7} {'mean_ms':>10} {'total_ms':>12}"
        lines += [hdr, "-" * len(hdr)]
        for c in bd["comm_ops"][:20]:
            lines.append(f"{c['name'][:44]:<44} {c['occurrences']:>7} "
                         f"{c['mean_ms']:>10.4f} {c['total_ms']:>12.3f}")
        if len(bd["comm_ops"]) > 20:
            lines.append(f"(+{len(bd['comm_ops']) - 20} more)")
    return "\n".join(lines)


def _innermost_pieces(spans: List[tuple]) -> List[tuple]:
    """Properly nested ``(start, end, name)`` spans of one thread cut
    into the pieces each has to itself (its interval less its
    children's): sorted, non-overlapping ``(start, end, name)``."""
    out: List[tuple] = []
    stack: List[list] = []              # [end, name, resume-from]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, t = stack.pop()
            if end > t:
                out.append((t, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for start, end, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close(start)
        if stack and start > stack[-1][2]:
            out.append((stack[-1][2], start, stack[-1][1]))
        stack.append([end, name, start])
    close(float("inf"))
    return sorted(out)


def phase_idle(trace_dir: str) -> Optional[Dict[str, Any]]:
    """Where the host was while the device had nothing to run: the idle
    stretches of the busiest device lane (between merged device-op
    intervals, first op to last), cut at the boundaries of the
    ``apex.*`` host spans (:func:`~apex_tpu.telemetry.tracing.phase`)
    and each piece put down to the innermost span that held it. The
    host lane read is the one whose outermost spans (a beat, a loop
    turn: those that carry ``pc_ns``) cover the most time. None when
    the capture has no device lane.

    ``clock`` gives what maps ``time.perf_counter_ns()`` onto the
    trace's clock - the median over the outermost spans of (their start
    on the trace's clock - their ``pc_ns`` stat) - for joining records
    kept on the host clock (the flight recorder, a ``Tracer`` export)
    with the device lanes."""
    import bisect

    from apex_tpu import pyprof
    from .tracing import PHASE_PREFIX

    events = pyprof._load_events(trace_dir)
    ops, file_of = pyprof._device_ops(events)
    if not ops:
        return None
    lanes: Dict[tuple, list] = {}
    for e in ops:
        lanes.setdefault((file_of[id(e)], e.get("pid"), e.get("tid")),
                         []).append((float(e["ts"]),
                                     float(e["ts"]) + float(e["dur"])))
    ivs = sorted(max(lanes.values(),
                     key=lambda v: sum(b - a for a, b in v)))
    gaps, end = [], ivs[0][1]
    for a, b in ivs[1:]:
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    window = (end - ivs[0][0]) / 1e6
    host: Dict[tuple, list] = {}
    outer: Dict[tuple, float] = {}
    offsets = []
    for lane, fi, e in events:
        if lane.startswith("/device:") \
                or not e["name"].startswith(PHASE_PREFIX):
            continue
        key = (fi, e.get("pid"), e.get("tid"))
        host.setdefault(key, []).append(
            (float(e["ts"]), float(e["ts"]) + float(e["dur"]), e["name"]))
        if "pc_ns" in e.get("args", {}):
            outer[key] = outer.get(key, 0.0) + float(e["dur"])
            offsets.append(e["ts"] * 1e3 - float(e["args"]["pc_ns"]))
    pieces = _innermost_pieces(host[max(outer, key=outer.get)]) \
        if outer else []
    # idle microseconds before a moment: whole stretches ended by then
    # and the part of the one it falls in
    starts = [g[0] for g in gaps]
    before = [0.0]
    for a, b in gaps:
        before.append(before[-1] + b - a)

    def idle_until(t):
        k = bisect.bisect_right(starts, t)
        if k == 0:
            return 0.0
        return before[k - 1] + min(t, gaps[k - 1][1]) - gaps[k - 1][0]

    idle: Dict[str, float] = {}
    for a, b, name in pieces:
        idle[name] = idle.get(name, 0.0) + idle_until(b) - idle_until(a)
    total = before[-1]
    idle["(outside any apex.* span)"] = total - sum(idle.values())
    rows = [{"phase": n, "idle_s": t / 1e6,
             "pct_of_window": 100.0 * t / 1e6 / window if window else 0.0}
            for n, t in sorted(idle.items(), key=lambda kv: -kv[1])
            if t > 0]
    clock = None
    if offsets:
        offsets.sort()
        clock = {"trace_minus_perf_counter_ns":
                 offsets[len(offsets) // 2], "spans": len(offsets),
                 "spread_ns": offsets[-1] - offsets[0]}
    return {"window_s": window, "idle_s": total / 1e6, "phases": rows,
            "clock": clock}


def render_phase_idle(pi: Dict[str, Any]) -> str:
    lines = [f"device idle by host phase ({pi['idle_s']:.4f}s idle of "
             f"{pi['window_s']:.4f}s, busiest device):"]
    hdr = f"{'innermost apex.* span':<36} {'idle_s':>10} {'% window':>9}"
    lines += [hdr, "-" * len(hdr)]
    for r in pi["phases"]:
        lines.append(f"{r['phase'][:36]:<36} {r['idle_s']:>10.4f} "
                     f"{r['pct_of_window']:>9.2f}")
    c = pi.get("clock")
    if c:
        lines.append(
            f"clock: trace_ns = perf_counter_ns + "
            f"{c['trace_minus_perf_counter_ns']:.0f} (pc_ns of "
            f"{c['spans']} outermost spans, spread "
            f"{c['spread_ns'] / 1e3:.1f} us)")
    return "\n".join(lines)
