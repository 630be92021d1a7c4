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
``comm.*`` counters. A serving capture also gets the device's idle time
by host phase (:func:`phase_idle`) and the engine's host-device boundary
beat by beat (:func:`boundary_by_beat`).

Under the counter table the summary prints the counters that read as a
share of another (``COUNTER_SHARES``): the dispatch-ahead engagement
(``serving.heartbeat.dispatched_ahead / serving.decode.steps``), the chunk
kernel's walk of the page table (``serving.chunk.pages_live /
pages_tabled``) and, for a model with learned sparse attention, the share
of its live positions a query keeps (``serving.dsa.positions_selected /
serving.dsa.positions_live``: 1 where every context is shorter than the
selection, ``topk / context`` on long ones) and the share of the page
table its kernels walk (``serving.dsa.steps_walked /
serving.dsa.steps_tabled``).
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

from .core import META_KEYS, StreamingHistogram

__all__ = ["load_records", "summarize_records", "render_summary",
           "trace_breakdown", "render_breakdown", "phase_idle",
           "render_phase_idle", "boundary_by_beat", "render_boundary",
           "load_capture",
           "summarize_trace", "render_trace_summary"]

#: counters read as a share of another: (name, part, whole)
COUNTER_SHARES = (
    ("dispatch-ahead engagement", "serving.heartbeat.dispatched_ahead",
     "serving.decode.steps"),
    ("chunk kernel's walk of the table", "serving.chunk.pages_live",
     "serving.chunk.pages_tabled"),
    ("positions a sparse-attention query keeps",
     "serving.dsa.positions_selected", "serving.dsa.positions_live"),
    ("sparse-attention kernels' walk of the table",
     "serving.dsa.steps_walked", "serving.dsa.steps_tabled"),
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


def load_capture(trace_dir: str) -> tuple:
    """One read of a ``pyprof.trace`` capture for every table of
    ``summarize --trace``: ``(events, modules)``, the events as
    ``pyprof._load_events`` gives them, from the newest run's
    ``.xplane.pb`` where it has one (a chrome dump beside it can stop
    short of the capture's end), and the device planes' program lines
    (``{(file_idx, plane): [(start_us, end_us, name)]}``, empty for a
    chrome dump, which keeps none)."""
    from apex_tpu import pyprof

    modules: Dict[tuple, list] = {}
    return pyprof._load_events(trace_dir, xplane=True,
                               modules=modules), modules


def trace_breakdown(trace_dir: str, n_steps: int,
                    events: Optional[List[tuple]] = None) -> Dict[str, Any]:
    """Join a ``pyprof.trace`` capture with a run's step count: device
    time per HLO category (total and ms/step) plus per-op latency stats
    for the collective categories. ``events``: the capture already read
    (:func:`load_capture`)."""
    from apex_tpu import pyprof

    rows = pyprof.analyze(trace_dir, events=events)
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


def phase_idle(trace_dir: str, events: Optional[List[tuple]] = None
               ) -> Optional[Dict[str, Any]]:
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
    with the device lanes. ``events``: the capture already read
    (:func:`load_capture`)."""
    import bisect

    from apex_tpu import pyprof
    from .tracing import PHASE_PREFIX

    if events is None:
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


#: beats the table of :func:`render_boundary` shows (``--json`` has all)
BOUNDARY_ROWS = 40
#: a lag below this is a wrong clock or a wrong pair, not the host span's
#: microsecond of lateness; more than LAG_FLOOR_SHARE of the lags below
#: it and the view reads no lag (the benchmark's ``completion_lag`` rule)
LAG_FLOOR_US = -20.0
LAG_FLOOR_SHARE = 0.01


def _lags(evs: List[dict], progs: List[tuple], program: str) -> tuple:
    """``(lags, not_waited, note)`` over one host thread's phases: the
    launches the ``program`` reads name (``of``, the launch's ``id``)
    paired with the ``program`` runs of the device's program line from
    the last backwards, and for each read whose wait began before its
    program ended, ``{id(read): microseconds}`` from that end to the
    wait's return. ``note`` (and no lag) where a program starts before
    its launch."""
    from .tracing import PHASE_PREFIX

    rd, la = (PHASE_PREFIX + n
              for n in ("engine.readback", "engine.launch"))
    reads = [e for e in evs if e["name"] == rd
             and e["args"].get("program") == program
             and "of" in e["args"] and "wait_s" in e["args"]]
    named = {int(e["args"]["of"]) for e in reads}
    launches = sorted((e for e in evs if e["name"] == la
                       and int(e["args"].get("id", -1)) in named),
                      key=lambda e: e["ts"])
    if not reads or not launches:
        return {}, 0, None
    cut = max(e["ts"] + e["dur"] for e in reads)
    runs = [(s, end) for s, end, name in progs
            if program in name and end <= cut]
    n = min(len(launches), len(runs))
    ran = {}
    for launch, (s, end) in zip(launches[len(launches) - n:],
                                runs[len(runs) - n:]):
        if s < launch["ts"]:
            return {}, 0, (
                f"a {program} program starts {launch['ts'] - s:.1f} us "
                "before its launch: the device's clock is off against the "
                "host's, or launches and programs do not pair; no lag read")
        ran[int(launch["args"]["id"])] = end
    lags, idle = {}, 0
    for e in reads:
        end = ran.get(int(e["args"]["of"]))
        if end is None:
            continue
        if e["ts"] >= end:
            idle += 1
        else:
            lags[id(e)] = e["ts"] + float(e["args"]["wait_s"]) * 1e6 - end
    return lags, idle, None


def boundary_by_beat(trace_dir: str, program: str = "decode",
                     capture: Optional[tuple] = None
                     ) -> Optional[Dict[str, Any]]:
    """The serving engine's host-device boundary beat by beat, from the
    arguments its phases carry on the host plane (a profiler session
    has them recorded) and the device's program line (``XLA Modules``):
    for each ``apex.serve.beat``, the upload's calls (``n`` host arrays
    + ``eager`` computations) and bytes, the reads' ``wait_s`` and copy
    (a read's duration less its wait) in ms and bytes, and for each read
    of ``program`` that waited for its program, the lag in microseconds
    from that program's end to the wait's return. A read names the
    launch of its program (``of``, the launch's ``id``), and those
    launches are paired with the program's runs from the last
    backwards, as the benchmark's ``completion_lag`` pairs them. No lag
    is read, and ``lag_note`` says why, where a program starts before
    its launch or over ``LAG_FLOOR_SHARE`` of the lags lie below
    ``LAG_FLOOR_US``: the device's clock is then off against the
    host's, or a pair is wrong. A host span starts a microsecond or two
    after the phase reads its clock, so a wait's end, and the lag, reads
    late by that much. ``capture``: :func:`load_capture`'s, where read
    already. None where no beat's phases carry the arguments (a program
    that predates them, a beat run without a session)."""
    import bisect

    from .tracing import PHASE_PREFIX

    beat, up, la, rd = (PHASE_PREFIX + n for n in (
        "serve.beat", "engine.upload", "engine.launch", "engine.readback"))
    events, modules = load_capture(trace_dir) if capture is None \
        else capture
    host: Dict[tuple, list] = {}
    for lane, fi, e in events:
        if not lane.startswith("/device:") and e["name"] in (beat, up, la,
                                                              rd):
            host.setdefault((fi, e.get("pid"), e.get("tid")), []).append(
                dict(e, args=e.get("args") or {}))
    lag_of: Dict[int, float] = {}
    idle, note = 0, None
    for (fi, _, _), evs in host.items():
        planes = sorted(k for k in modules if k[0] == fi)
        got, n_idle, why = _lags(evs, sorted(modules[planes[0]])
                                 if planes else [], program)
        note = note or why
        lag_of.update(got)
        idle += n_idle
    low = sum(1 for v in lag_of.values() if v < LAG_FLOOR_US)
    if low > LAG_FLOOR_SHARE * len(lag_of):
        note = note or (f"{low} of {len(lag_of)} lags below "
                        f"{LAG_FLOOR_US:.0f} us: the device's clock is off "
                        "against the host's, or a pair is wrong; no lag read")
    waited = len(lag_of)
    if note:
        lag_of = {}
    rows = []
    for evs in host.values():
        inner = sorted((e for e in evs if e["name"] in (up, rd)),
                       key=lambda e: e["ts"])
        starts = [e["ts"] for e in inner]
        for b in sorted((e for e in evs if e["name"] == beat),
                        key=lambda e: e["ts"]):
            row = {"tick": b["args"].get("tick"), "ts_us": b["ts"],
                   "upload_calls": 0, "upload_bytes": 0, "read_wait_ms": 0.0,
                   "read_copy_ms": 0.0, "read_bytes": 0, "lag_us": []}
            seen = False
            for e in inner[bisect.bisect_left(starts, b["ts"]):
                           bisect.bisect_right(starts, b["ts"] + b["dur"])]:
                a = e["args"]
                if e["name"] == up and "n" in a:
                    row["upload_calls"] += int(a["n"]) + int(a["eager"])
                    row["upload_bytes"] += int(a["bytes"])
                elif e["name"] == rd and "wait_s" in a:
                    wait = float(a["wait_s"]) * 1e3
                    row["read_wait_ms"] += wait
                    row["read_copy_ms"] += float(e["dur"]) / 1e3 - wait
                    row["read_bytes"] += int(a["bytes"])
                    if id(e) in lag_of:
                        row["lag_us"].append(lag_of[id(e)])
                else:
                    continue
                seen = True
            if seen:
                rows.append(row)
    if not rows:
        return None
    rows.sort(key=lambda r: r["ts_us"])
    h = {k: StreamingHistogram() for k in
         ("upload_calls", "upload_bytes", "read_wait_ms", "read_copy_ms",
          "lag_us")}
    for r in rows:
        for k, v in r.items():
            if k in h:
                for x in (v if isinstance(v, list) else [v]):
                    h[k].observe(x)
    return {"program": program, "beats": rows,
            "reads_waited": waited, "reads_not_waited": idle,
            "lag_note": note,
            "summary": {k: v.summary() for k, v in h.items()}}


def render_boundary(bb: Dict[str, Any]) -> str:
    s = bb["summary"]
    lines = [f"serving engine boundary by beat ({len(bb['beats'])} beats; "
             f"{bb['program']} reads that waited {bb['reads_waited']}, "
             f"that did not {bb['reads_not_waited']}):"]
    lines.append(
        f"p50 a beat: upload {s['upload_calls']['p50']:.6g} calls, "
        f"{s['upload_bytes']['p50']:.6g} B; read wait "
        f"{s['read_wait_ms']['p50']:.4f} ms, copy "
        f"{s['read_copy_ms']['p50']:.4f} ms" + (
            f"; lag from a {bb['program']} program's end to the read's "
            f"return p50 {s['lag_us']['p50']:.1f} us, p95 "
            f"{s['lag_us']['p95']:.1f} us" if s["lag_us"].get("count")
            else ""))
    if bb.get("lag_note"):
        lines.append(bb["lag_note"])
    hdr = (f"{'tick':>8} {'upload calls':>12} {'upload B':>10} "
           f"{'wait ms':>9} {'copy ms':>9} {'read B':>8}  lag us")
    lines += [hdr, "-" * len(hdr)]
    for r in bb["beats"][:BOUNDARY_ROWS]:
        lines.append(
            f"{str(r['tick']):>8} {r['upload_calls']:>12} "
            f"{r['upload_bytes']:>10} {r['read_wait_ms']:>9.4f} "
            f"{r['read_copy_ms']:>9.4f} {r['read_bytes']:>8}  "
            + " ".join(f"{v:.1f}" for v in r["lag_us"]))
    if len(bb["beats"]) > BOUNDARY_ROWS:
        lines.append(f"(+{len(bb['beats']) - BOUNDARY_ROWS} more beats; "
                     "--json has every beat)")
    return "\n".join(lines)


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
