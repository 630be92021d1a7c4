"""The readers of the program's own phase records
(``benchmarks/lib/program_spans.py``) against a hand-written trace and
hand-written ring records, whose answers are known exactly, and against
the recording of cell B kept in ``data/`` with records made up from its
``bench.step`` spans."""

import os

import pytest

from apex_tpu.telemetry import tracing
from benchmarks.lib import program_spans as ps
from benchmarks.lib import readers, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e-3
CLOCK = 100.0       # the trace's clock reads 100 s more than the host's


@pytest.fixture
def ring(monkeypatch):
    r = tracing.PhaseRing()
    monkeypatch.setattr(tracing, "phases", r)
    return r


def beat(ring, i, t0, t1, kids=()):
    """One ``serve.beat`` [t0, t1] (host ms) with children
    ``(name, a, b)`` and grandchildren ``(name, a, b, parent_name)``."""
    base = 100 * (i + 1)
    ids = {}
    for k, kid in enumerate(kids):
        name, a, b = kid[:3]
        ids[name] = base + 1 + k
        parent = ids[kid[3]] if len(kid) > 3 else base
        ring._ring.append((name, a * MS, b * MS, parent, base + 1 + k,
                           base, None))
    ring._ring.append(("serve.beat", t0 * MS, t1 * MS, None, base, base,
                       {"tick": i}))


def hand_trace(steps, ops, window=(0.0, 20.0)):
    spans = [(CLOCK + window[0] * MS, CLOCK + window[1] * MS,
              "bench.window")]
    spans += [(CLOCK + a * MS, CLOCK + b * MS, "bench.step")
              for a, b in steps]
    dev = [(CLOCK + a * MS, CLOCK + b * MS, "op", None) for a, b in ops]
    return trace.Trace({"/device:TPU:0": dev}, {}, spans)


def fill(ring):
    """Three beats on the host clock; the trace saw the last two."""
    beat(ring, 0, -9.0, -1.0, [("engine.launch", -8.0, -7.0)])
    beat(ring, 1, 1.0, 9.0, [
        ("serve.admit", 1.0, 2.0),
        ("serve.decode", 2.0, 8.0),
        ("engine.upload", 2.0, 3.0, "serve.decode"),
        ("engine.launch", 3.0, 3.5, "serve.decode"),
        ("engine.readback", 3.5, 8.0, "serve.decode"),
        ("serve.emit", 8.0, 9.0)])
    beat(ring, 2, 11.0, 19.0, [
        ("serve.decode", 12.0, 18.0),
        ("engine.upload", 12.0, 14.0, "serve.decode"),
        ("engine.launch", 14.0, 14.5, "serve.decode"),
        ("engine.readback", 14.5, 17.0, "serve.decode")])


STEPS = [(1.0, 9.0), (11.0, 19.0)]
# idle: 0-2.6 (1 ms before the first beat, admit's 1 ms, 0.6 of upload),
# 7.5-8.3 (it straddles readback, 0.5, and emit, 0.3), 9.5-12.5 (1.5
# between the beats, the beat's own 1 ms before its decode, 0.5 of
# upload), 16-17.5 (1 ms of readback, 0.5 of decode's own), 19.5-20
# (after the last beat)
OPS = [(2.6, 7.5), (8.3, 9.5), (12.5, 16.0), (17.5, 19.5)]


def test_beats_are_paired_from_the_end(ring):
    fill(ring)
    tr = hand_trace(STEPS, OPS)
    pairs = ps.traced_beats(ring, tr)
    assert [b.args["tick"] for _, _, b in pairs] == [1, 2]
    off, spread = ps.clock_offset(pairs)
    assert off == pytest.approx(CLOCK) and spread == pytest.approx(0)
    # fewer records than spans: still from the end
    tr3 = hand_trace([(-30.0, -20.0), (-9.0, -1.0)] + STEPS, OPS)
    assert len(ps.traced_beats(ring, tr3)) == 3


def test_phase_stat_sums_self_time_per_beat(ring):
    fill(ring)
    ctx = {"trace": hand_trace(STEPS, OPS)}
    launch = {"phases": ["engine.upload", "engine.launch"],
              "per": "serve.beat"}
    assert ps.phase_stat(ctx, dict(launch, stat="p50")) == \
        pytest.approx((1.5 + 2.5) / 2)
    assert ps.phase_stat(ctx, dict(launch, stat="max")) == \
        pytest.approx(2.5)
    # a parent named beside its children is not counted twice: decode's
    # own time is what its children leave (0 and 1 ms)
    assert ps.phase_stat(ctx, {
        "phases": ["serve.decode", "engine.upload", "engine.launch",
                   "engine.readback"], "per": "serve.beat",
        "stat": "sum"}) == pytest.approx(6.0 + 6.0)
    assert ps.phase_stat(ctx, {"phases": ["serve.decode"], "stat": "sum",
                               "per": "serve.beat"}) == pytest.approx(1.0)
    # through the route a metric's file takes
    assert readers._by_name("program_spans:phase_stat", readers.KINDS)(
        ctx, dict(launch, stat="p50")) == pytest.approx(2.0)


def test_idle_is_cut_at_phase_boundaries_innermost_phase_first(ring):
    fill(ring)
    ctx = {"trace": hand_trace(STEPS, OPS)}
    by, window = ps.idle_by_phase(ctx)
    assert window == pytest.approx(20 * MS)
    assert {k: round(v / MS, 6) for k, v in by.items() if v > 1e-12} == {
        "serve.admit": 1.0, "engine.upload": 0.6 + 0.5,
        "engine.readback": 0.5 + 1.0, "serve.emit": 0.3,
        "serve.beat": 1.0, "serve.decode": 0.5,
        ps.ELSEWHERE: 1.0 + 1.5 + 0.5}
    launch = ps.idle_in_phase(ctx, {"phases": ["engine.upload",
                                               "engine.launch"]})
    read = ps.idle_in_phase(ctx, {"phases": ["engine.readback"]})
    host = ps.idle_in_phase(ctx, {"except": [
        "engine.upload", "engine.launch", "engine.readback"]})
    assert (launch, read) == (pytest.approx(5.5), pytest.approx(7.5))
    assert host == pytest.approx(100 * (1.0 + 0.3 + 1.0 + 0.5 + 3.0) / 20)
    # the three are all of the window's idle share
    assert launch + read + host == pytest.approx(
        readers.idle_share(ctx, {}))
    assert any("2 beats paired" in n for n in ctx["notes"])


def test_offsets_too_spread_read_nothing_and_say_why(ring):
    fill(ring)
    # the first span starts 2 ms before its beat would: the spans and
    # the records are not the same calls
    ctx = {"trace": hand_trace([(-1.0, 9.0), (11.0, 19.0)], OPS)}
    assert ps.idle_in_phase(ctx, {"phases": ["engine.readback"]}) is None
    assert ps.idle_in_phase(ctx, {"except": []}) is None
    assert sum("do not pair" in n for n in ctx["notes"]) == 1


def test_nothing_to_read_is_none_not_an_error(ring, monkeypatch):
    launch = {"phases": ["engine.launch"], "stat": "p50",
              "per": "serve.beat"}
    ctx = {"trace": hand_trace(STEPS, OPS)}
    assert ps.phase_stat(ctx, launch) is None               # empty ring
    assert ps.idle_in_phase(ctx, {"phases": ["engine.launch"]}) is None
    assert ps.phase_stat({}, dict(launch, per="train.turn")) is None
    fill(ring)
    assert ps.phase_stat({}, launch) is None                # no trace
    # the parent's program has no ring at all
    monkeypatch.delattr(tracing, "phases")
    assert ps.phase_stat(ctx, launch) is None
    assert ps.idle_in_phase(dict(ctx), {"except": []}) is None


def test_turns_are_read_from_the_ring_alone(ring):
    for i in range(300):
        t = float(i)
        ring._ring.append(("train.rng_readback", t, t + 0.1 + i * 1e-3,
                           3 * i + 1, 3 * i + 2, 3 * i, None))
        ring._ring.append(("train.batch_draw", t, t + 0.2 + i * 1e-3,
                           3 * i, 3 * i + 1, 3 * i, None))
        ring._ring.append(("train.turn", t, t + 0.5, None, 3 * i, 3 * i,
                           {"it": i}))
    spec = {"phases": ["train.batch_draw", "train.rng_readback"],
            "per": "train.turn"}
    # the last 256 turns: 44..299, batch draw 0.2 s + i ms
    assert ps.phase_stat({}, dict(spec, stat="p50")) == pytest.approx(
        200 + (44 + 299) / 2)
    assert ps.phase_stat({}, dict(spec, stat="max")) == pytest.approx(499)


def test_recorded_cell_b_with_records_made_from_its_spans(ring):
    """One second of cell B from the chip (PR 24). Its program had no
    phases: records are made up from the ``bench.step`` spans, each beat
    a launch of 1 ms and a readback to 0.3 ms before its end."""
    tr = trace.load(os.path.join(DATA, "cellB_1s.txtpb.gz"))
    steps = sorted((s, e) for s, e, n in tr.spans if n == "bench.step")
    assert len(steps) == 5
    shift = 1234.5
    beat(ring, 0, 0.0, 1.0)                     # one the trace never saw
    for i, (s, e) in enumerate(steps, 1):
        a, b = (s - shift) / MS + 2e-3, (e - shift) / MS - 2e-3
        beat(ring, i, a, b, [("engine.launch", a, a + 1.0),
                             ("engine.readback", a + 1.0, b - 0.3),
                             ("serve.emit", b - 0.3, b)])
    ctx = {"trace": tr}
    parts = [ps.idle_in_phase(ctx, p) for p in (
        {"phases": ["engine.upload", "engine.launch"]},
        {"phases": ["engine.readback"]},
        {"except": ["engine.upload", "engine.launch",
                    "engine.readback"]})]
    assert all(p is not None and p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(100 * tr.idle_share_busiest(),
                                       rel=1e-9)
    assert sum(parts) == pytest.approx(4.2611638, rel=1e-6)
    # the readings, taken once; the same every time
    assert parts == pytest.approx([0.4991231, 3.2189144, 0.5431263],
                                  rel=1e-6)
    assert ps.phase_stat(ctx, {"phases": ["engine.launch"], "stat": "p50",
                               "per": "serve.beat"}) == pytest.approx(1.0)
