"""Reader kinds over the program's own phase records (PR 25), named in a
metric's file as ``program_spans:<fn>``.

The program marks the regions of its serving beat and of its training
loop with ``apex_tpu.telemetry.tracing.phase`` and keeps the last 8192
of them in ``tracing.phases``, on ``time.perf_counter()``. This module
reads that ring in the process that ran the cell, and from the run's
context only the reduced trace's public parts (``spans``, ``ops``,
``w0``, ``w1``). A program without the ring (the parent of the PR that
added it) gives every reader here nothing to read: it returns None and
the metric is left out.

``phase_stat``: a statistic of the summed SELF time (a phase's duration
less what its direct children cover) of the named phases, per beat or
per loop turn. ``idle_in_phase``: the share of the traced window in
which no device operation ran while the host was in one of the named
phases, innermost phase winning. An idle stretch is cut at the phases'
boundaries and each piece goes to the phase that held it: the stretch
between two programs runs from the tail of one beat's readback through
the next beat's upload, and handing all of it to the phase at its
midpoint, as ``Trace.breakdown`` does for the harness's spans, moved
two points of idle from one phase to another between two runs of the
same cell (PERF.md, PR 25). The harness's ``bench.step`` spans
are on the trace's clock and the ring is on the host's: the beats of the
traced phase are found by pairing the two from the last backwards (one
``Scheduler.step`` per ``bench.step``), and the clock offset is the
median of (span start - record start) over the pairs.
"""

from __future__ import annotations

import numpy as np

from . import common

BEAT, TURN, STEP_SPAN = "serve.beat", "train.turn", "bench.step"
TURNS_READ = 256
OFFSET_SPREAD_S = 100e-6
ELSEWHERE = "(no phase)"


def _ring():
    try:
        from apex_tpu.telemetry import tracing
    except ImportError:
        return None
    return getattr(tracing, "phases", None)


def _note(ctx, text):
    ctx.setdefault("notes", []).append(f"program_spans: {text}")


def traced_beats(ring, trace):
    """``[(bench.step start, bench.step end, serve.beat record)]`` for
    the beats of the traced phase, oldest first: the trace's
    ``bench.step`` spans against the ring's last ``serve.beat`` records,
    paired from the last backwards. None where there is nothing to
    pair."""
    steps = sorted((s, e) for s, e, n in trace.spans if n == STEP_SPAN)
    beats = ring.records(name=BEAT)
    if not steps or not beats:
        return None
    n = min(len(steps), len(beats))
    return [(s, e, b) for (s, e), b in zip(steps[-n:], beats[-n:])]


def clock_offset(pairs):
    """``(offset, spread)`` in seconds: what is added to a
    ``perf_counter`` reading to put it on the trace's clock - the median
    over the pairs of (``bench.step`` start - ``serve.beat`` start) -
    and the distance between the pairs' first and last deciles, which is
    some microseconds when the pairing is right and the width of a beat
    when it is off by one."""
    d = sorted(s - b.t0 for s, _, b in pairs)
    return (d[len(d) // 2], common.percentile(d, 90)
            - common.percentile(d, 10))


def self_time_per_root(ring, roots):
    """``{phase name: [seconds of self time under each of roots]}``
    (records of beats or turns, in their order)."""
    ids = {r.id: i for i, r in enumerate(roots)}
    recs = [r for r in ring.records(since=roots[0].t0) if r.root in ids]
    own = ring.self_times(recs)
    out = {}
    for r in recs:
        out.setdefault(r.name, [0.0] * len(roots))[ids[r.root]] += \
            own[r.id]
    return out


def phase_stat(ctx, p):
    """``phases`` (names), ``stat``, ``per`` (``serve.beat``: the beats
    of the traced phase; ``train.turn``: the last 256 turns, which the
    measured window ran). Milliseconds."""
    ring = _ring()
    if ring is None:
        return None
    if p["per"] == BEAT:
        tr = ctx.get("trace")
        pairs = traced_beats(ring, tr) if tr is not None else None
        if not pairs:
            return None
        roots = [b for _, _, b in pairs]
    else:
        roots = ring.records(name=p["per"])[-TURNS_READ:]
        if not roots:
            return None
    by = self_time_per_root(ring, roots)
    vals = [sum(by[n][i] for n in p["phases"] if n in by)
            for i in range(len(roots))]
    return common.stat([v * 1e3 for v in vals], p["stat"])


def _own_segments(ring, roots, offset):
    """The timeline of ``roots`` cut into the pieces each phase has to
    itself (its interval less its children's), on the trace's clock:
    sorted ``starts``, ``ends`` and the phase's name for each piece."""
    ids = {r.id for r in roots}
    recs = [r for r in ring.records(since=roots[0].t0) if r.root in ids]
    kids = {}
    for r in recs:
        kids.setdefault(r.parent, []).append(r)
    pieces = []
    for r in recs:
        t = r.t0
        for c in sorted(kids.get(r.id, ()), key=lambda c: c.t0):
            if c.t0 > t:
                pieces.append((t + offset, c.t0 + offset, r.name))
            t = max(t, c.t1)
        if r.t1 > t:
            pieces.append((t + offset, r.t1 + offset, r.name))
    pieces.sort()
    return (np.array([a for a, _, _ in pieces]),
            np.array([b for _, b, _ in pieces]),
            [n for _, _, n in pieces])


def _idle_gaps(trace):
    """``(starts, ends)`` of the stretches of the traced window in which
    no operation ran on the busiest chip: the complement of the union of
    its operations' intervals, as ``Trace.breakdown`` takes it, with no
    gap left out for being short."""
    w0, w1 = trace.w0, trace.w1
    best, best_busy = None, -1.0
    for dev, ops in trace.ops.items():
        a = np.array([[o[0], o[1]] for o in ops], float).reshape(-1, 2)
        s, e = np.clip(a[:, 0], w0, w1), np.clip(a[:, 1], w0, w1)
        keep = e > s
        s, e = s[keep], e[keep]
        order = np.argsort(s, kind="stable")
        s, e = s[order], np.maximum.accumulate(e[order])
        # merged: a new interval starts where an op begins after every
        # earlier one has ended
        first = np.ones(len(s), bool)
        first[1:] = s[1:] > e[:-1]
        ms = s[first]
        me = np.append(e[:-1][first[1:]], e[-1:]) if len(s) else e
        busy = float(np.sum(me - ms))
        if busy > best_busy:
            best, best_busy = (ms, me), busy
    if best is None:
        return None
    ms, me = best
    gs = np.concatenate([[w0], me])
    ge = np.concatenate([ms, [w1]])
    keep = ge > gs
    return gs[keep], ge[keep]


def idle_by_phase(ctx):
    """``{phase name: seconds}`` of the traced window's idle stretches
    by the innermost phase the host was in meanwhile (``ELSEWHERE``:
    between beats, in the harness), and the window's length. None - with
    a note in the run's log - where the ring, the trace or a trustworthy
    clock offset is missing. Computed once a run."""
    if "_idle_by_phase" in ctx:
        return ctx["_idle_by_phase"]
    out = ctx["_idle_by_phase"] = _idle_by_phase(ctx)
    return out


def _idle_by_phase(ctx):
    ring, tr = _ring(), ctx.get("trace")
    if ring is None or tr is None or not getattr(tr, "ops", None):
        return None
    pairs = traced_beats(ring, tr)
    if not pairs:
        return None
    offset, spread = clock_offset(pairs)
    if spread > OFFSET_SPREAD_S:
        _note(ctx, f"clock offsets of {len(pairs)} paired beats spread by "
              f"{spread * 1e6:.0f} us (limit "
              f"{OFFSET_SPREAD_S * 1e6:.0f}): the bench.step spans and the "
              "serve.beat records do not pair; idle not put down to "
              "phases")
        return None
    gaps = _idle_gaps(tr)
    if gaps is None or tr.w1 <= tr.w0:
        return None
    gs, ge = gaps
    starts, ends, names = _own_segments(ring, [b for _, _, b in pairs],
                                        offset)
    # idle seconds before a moment t: whole stretches that ended by
    # then, and the part of the one t falls in
    before = np.concatenate([[0.0], np.cumsum(ge - gs)])

    def idle_until(t):
        k = np.searchsorted(gs, t, side="right")
        part = np.minimum(t, ge[np.maximum(k - 1, 0)]) \
            - gs[np.maximum(k - 1, 0)]
        return before[np.maximum(k - 1, 0)] + np.where(k > 0, part, 0.0)

    inside = idle_until(ends) - idle_until(starts)
    by = {}
    for n, t in zip(names, inside):
        by[n] = by.get(n, 0.0) + float(t)
    by[ELSEWHERE] = float(before[-1] - np.sum(inside))
    _note(ctx, f"{len(pairs)} beats paired, clock offset spread "
          f"{spread * 1e6:.1f} us; idle seconds by phase: "
          + ", ".join(f"{n} {t:.4f}" for n, t in sorted(
              by.items(), key=lambda kv: -kv[1])))
    return by, tr.w1 - tr.w0


def idle_in_phase(ctx, p):
    """Percent of the traced window that was idle inside the phases
    named by ``phases``, or - with ``except`` - inside every other phase
    and between beats. The metrics that split a cell's idle this way add
    up to its idle share."""
    got = idle_by_phase(ctx)
    if got is None:
        return None
    by, window = got
    if "except" in p:
        t = sum(v for n, v in by.items() if n not in set(p["except"]))
    else:
        t = sum(by.get(n, 0.0) for n in p["phases"])
    return 100.0 * t / window
