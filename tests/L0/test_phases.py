"""``telemetry.tracing.phase`` and the flight recorder ``tracing.phases``:
the one primitive that marks a host region on the profiler's clock and
on ``perf_counter`` at once, and where the serving beat and the LM
recipe's loop use it.

- **The primitive**: nesting and parent links, self time, the bounded
  ring, the off switch, per-thread parent chains, an exception that
  unwinds several phases, what a phase costs with no profiler session.
- **The serving beat**: a sync beat and a pipelined beat at toy size
  each leave exactly one ``serve.beat`` whose descendants come from the
  catalogue and whose self times sum to its duration; the engine's
  ``upload_s + launch_s + readback_s`` is the growth of
  ``device_wait_s``; the registry gains the launch / readback
  histograms; a stalled beat's watchdog line names phases.
- **The recipe**: one ``main()`` at toy size leaves ``train.turn`` with
  ``batch_draw``, ``dispatch`` and ``on_step`` beneath it, and the hook
  still sees ``main()``'s own frame.
- **The profiler's clock**: with a session open the phases land on the
  host plane, the outermost carrying ``pc_ns``; the operator's readers
  (``pyprof.analyze`` / ``device_busy`` / ``summarize --trace``) read an
  ``.xplane.pb`` recording, and put idle gaps down to ``apex.*`` spans.
"""

import gzip
import logging
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import pyprof, telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import Engine, FaultPolicy, Request, Scheduler
from apex_tpu.telemetry import MetricsRegistry, summarize, tracing

pytestmark = [pytest.mark.serving, pytest.mark.telemetry]

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
VOCAB = 101

#: every phase the serving code may leave under a beat
SERVE_CATALOGUE = {
    "serve.expire", "serve.admit", "serve.chunk", "serve.spec",
    "serve.decode", "serve.emit", "engine.grow", "engine.upload",
    "engine.launch", "engine.readback"}


@pytest.fixture(autouse=True)
def _ring_on():
    tracing.phases.enabled = True
    yield
    tracing.phases.enabled = True


def _since(t):
    return tracing.phases.records(since=t)


# ------------------------------------------------------- the primitive
def test_nesting_links_children_to_their_parent_and_root():
    t = time.perf_counter()
    with tracing.phase("outer", tick=7) as o:
        with tracing.phase("mid") as m:
            with tracing.phase("leaf", program="decode"):
                pass
        with tracing.phase("sib"):
            pass
    recs = {r.name: r for r in _since(t)}
    # appended when they END: children before their parent
    assert [r.name for r in _since(t)] == ["leaf", "mid", "sib", "outer"]
    assert recs["outer"].parent is None
    assert recs["outer"].root == recs["outer"].id == o.id
    assert recs["mid"].parent == o.id and recs["sib"].parent == o.id
    assert recs["leaf"].parent == m.id and recs["leaf"].root == o.id
    assert recs["leaf"].args == {"program": "decode"}
    # the outermost phase carries the host clock at its entry
    assert recs["outer"].args["tick"] == 7
    assert recs["outer"].args["pc_ns"] == pytest.approx(
        recs["outer"].t0 * 1e9, abs=2)
    assert "pc_ns" not in (recs["mid"].args or {})
    for r in recs.values():
        assert recs["outer"].t0 <= r.t0 <= r.t1 <= recs["outer"].t1
    assert (o.t0, o.t1) == (recs["outer"].t0, recs["outer"].t1)


def test_self_time_is_duration_less_direct_children():
    t = time.perf_counter()
    with tracing.phase("p") as p:
        with tracing.phase("c1") as c1:
            time.sleep(0.01)
            with tracing.phase("g") as g:
                time.sleep(0.005)
        with tracing.phase("c2") as c2:
            time.sleep(0.02)
    recs = _since(t)
    own = tracing.phases.self_times(recs)
    dur = {r.id: r.dur for r in recs}
    assert own[p.id] == pytest.approx(
        dur[p.id] - dur[c1.id] - dur[c2.id], abs=1e-9)
    # a grandchild is taken from its parent, not from the root (by the
    # records' own durations: a sleep under a loaded test run overshoots)
    assert own[c1.id] == pytest.approx(dur[c1.id] - dur[g.id], abs=1e-9)
    assert own[c1.id] >= 0.01
    assert own[c2.id] == pytest.approx(dur[c2.id])
    assert sum(own.values()) == pytest.approx(dur[p.id], abs=1e-9)
    assert own[p.id] < 2e-3


def test_ring_is_bounded_and_drops_the_oldest():
    ring = tracing.PhaseRing(maxlen=4)
    for i in range(10):
        ring._ring.append((f"n{i}", float(i), i + 0.5, None, i, i, None))
    assert [r.name for r in ring.records()] == ["n6", "n7", "n8", "n9"]
    assert [r.name for r in ring.records(name="n8")] == ["n8"]
    assert [r.name for r in ring.records(since=8.5)] == ["n8", "n9"]
    # the process's own ring is the documented flight recorder's size
    assert tracing.phases._ring.maxlen == 8192
    n0 = len(tracing.phases._ring)
    for _ in range(8192 + 5):
        with tracing.phase("fill"):
            pass
    assert len(tracing.phases._ring) == 8192 >= n0
    # a child whose parent has left the ring counts for itself
    assert ring.self_times([tracing.PhaseRecord(
        "c", 0.0, 1.0, 12345, 1, 12345, None)]) == {1: 1.0}


def test_disabled_records_nothing_but_still_times():
    t = time.perf_counter()
    tracing.phases.enabled = False
    with tracing.phase("off") as p:
        with tracing.phase("off.child"):
            time.sleep(0.002)
    assert p.id is None and p.t1 - p.t0 >= 0.002
    tracing.phases.enabled = True
    assert _since(t) == []
    with tracing.phase("on"):
        pass
    assert [r.name for r in _since(t)] == ["on"]


def test_worker_thread_keeps_its_own_parent_chain():
    t = time.perf_counter()
    ready, go = threading.Event(), threading.Event()

    def work():
        with tracing.phase("w.outer"):
            ready.set()
            go.wait(5)
            with tracing.phase("w.inner"):
                pass

    th = threading.Thread(target=work)
    with tracing.phase("m.outer") as mo:
        th.start()
        ready.wait(5)
        with tracing.phase("m.inner"):
            go.set()
            th.join()
    recs = {r.name: r for r in _since(t)}
    assert recs["m.inner"].parent == mo.id
    assert recs["w.outer"].parent is None           # not main's child
    assert recs["w.inner"].parent == recs["w.outer"].id
    assert recs["w.inner"].root == recs["w.outer"].id != mo.id
    assert "pc_ns" in recs["w.outer"].args


def test_exception_unwinds_the_stack_and_still_records():
    t = time.perf_counter()
    with pytest.raises(KeyError):
        with tracing.phase("a"):
            with tracing.phase("b"):
                raise KeyError("x")
    with tracing.phase("after"):
        pass
    recs = {r.name: r for r in _since(t)}
    assert recs["b"].parent == recs["a"].id
    assert recs["after"].parent is None


def test_phase_cost_without_a_session():
    """Reported, and held to the issue's 20 us: some 2.5 us a phase on
    this sandbox's CPU with the ring on, 1.5 us with it off."""
    def per_phase(n=20000):
        t = time.perf_counter()
        for i in range(n // 4):
            with tracing.phase("serve.beat", tick=i):
                with tracing.phase("a"):
                    pass
                with tracing.phase("a"):
                    pass
                with tracing.phase("a", program="x"):
                    pass
        return (time.perf_counter() - t) / n * 1e6

    on = min(per_phase() for _ in range(3))
    tracing.phases.enabled = False
    off = min(per_phase() for _ in range(3))
    print(f"phase(): {on:.2f} us with the ring on, {off:.2f} us off")
    assert on < 20 and off < 20


def test_timed_and_annotate_are_thin_callers():
    reg = MetricsRegistry()
    t = time.perf_counter()
    with telemetry.timed("ckpt.save", registry=reg):
        with pyprof.annotate("block"):
            pass
    recs = {r.name: r for r in _since(t)}
    assert recs["block"].parent == recs["ckpt.save"].id
    assert reg.histograms["ckpt.save"].count == 1
    assert reg.histograms["ckpt.save"].summary()["max"] == pytest.approx(
        recs["ckpt.save"].dur)


# ---------------------------------------------------- the serving beat
@pytest.fixture(scope="module")
def lm_and_params():
    m = TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                      num_heads=4, max_seq_len=64)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


@pytest.fixture(scope="module")
def engine(lm_and_params):
    m, params = lm_and_params
    return Engine(m, params, slots=2, max_len=64, prefill_len=24,
                  chunk_len=8,
                  policy=resolve_policy("O0", verbose=False), seed=5)


def _stream(seed=1):
    rng = np.random.default_rng(seed)
    return [Request(prompt=list(rng.integers(1, VOCAB, size=n)),
                    max_new_tokens=b)
            for n, b in [(5, 8), (13, 6), (9, 5)]]


@pytest.mark.parametrize("depth", [0, 1], ids=["sync", "pipelined"])
def test_each_beat_leaves_one_tree_from_the_catalogue(engine, depth):
    engine.reset(clear_prefixes=True)
    sched = Scheduler(engine, pipeline_depth=depth)
    for r in _stream():
        sched.submit(r)
    sched.step()                            # compiles: not looked at
    sched.step()
    seen, beats_checked = set(), 0
    while sched.pending:
        t = time.perf_counter()
        dw0 = engine.device_wait_s
        parts0 = engine.upload_s + engine.launch_s + engine.readback_s
        sched.step()
        recs = _since(t)
        beats = [r for r in recs if r.name == "serve.beat"]
        assert len(beats) == 1
        beat = beats[0]
        assert beat.parent is None and "pc_ns" in beat.args
        assert beat.args["tick"] == sched._tick - 1
        kids = [r for r in recs if r is not beat]
        assert {r.root for r in kids} == {beat.id}
        assert {r.name for r in kids} <= SERVE_CATALOGUE
        own = tracing.phases.self_times(recs)
        assert sum(own.values()) == pytest.approx(beat.dur, abs=1e-9)
        assert all(v >= -1e-9 for v in own.values())
        # the counters at the same boundaries: the three ends of every
        # program are all of the beat's device wait
        parts = engine.upload_s + engine.launch_s + engine.readback_s
        assert parts - parts0 == pytest.approx(
            engine.device_wait_s - dw0, abs=1e-6)
        by = {}
        for r in kids:
            by[r.name] = by.get(r.name, 0.0) + r.dur
        for name, attr in (("engine.upload", "upload_s"),
                           ("engine.launch", "launch_s"),
                           ("engine.readback", "readback_s")):
            assert by.get(name, 0.0) <= beat.dur
        seen |= {r.name for r in kids}
        beats_checked += 1
    assert beats_checked >= 5
    assert {"serve.expire", "serve.admit", "serve.chunk", "serve.decode",
            "serve.emit", "engine.grow", "engine.upload", "engine.launch",
            "engine.readback"} <= seen
    launches = [r for r in tracing.phases.records(name="engine.launch")]
    assert {r.args["program"] for r in launches} >= {"decode", "chunk"}


def test_counters_sum_to_device_wait_over_a_whole_serve(engine):
    engine.reset(clear_prefixes=True)
    dw0 = engine.device_wait_s
    p0 = (engine.upload_s, engine.launch_s, engine.readback_s)
    reg = MetricsRegistry()
    sched = Scheduler(engine, registry=reg)
    sched.run(_stream(3))
    up, la, rb = (engine.upload_s - p0[0], engine.launch_s - p0[1],
                  engine.readback_s - p0[2])
    assert min(up, la, rb) > 0
    assert up + la + rb == pytest.approx(engine.device_wait_s - dw0,
                                         abs=1e-6)
    h = reg.snapshot()["histograms"]
    beats = h["serving.heartbeat.host_s"]["count"]
    assert h["serving.heartbeat.launch_s"]["count"] == beats
    assert h["serving.heartbeat.readback_s"]["count"] == beats
    assert h["serving.heartbeat.launch_s"]["mean"] * beats == \
        pytest.approx(la, abs=1e-6)
    assert h["serving.heartbeat.readback_s"]["mean"] * beats == \
        pytest.approx(rb, abs=1e-6)


def test_watchdog_breach_names_the_largest_phases(engine, monkeypatch):
    engine.reset(clear_prefixes=True)
    stalls, lines = [], []
    sched = Scheduler(engine, fault_policy=FaultPolicy(
        watchdog_budget_s=0.05, on_stall=stalls.append))
    for r in _stream():
        sched.submit(r)
    for _ in range(3):
        sched.step()
    slow = sched._admit
    monkeypatch.setattr(sched, "_admit",
                        lambda: (time.sleep(0.12), slow())[1])

    class Catch(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    log, catch = logging.getLogger("apex_tpu.serving"), Catch()
    log.addHandler(catch)
    try:
        sched.step()
    finally:
        log.removeHandler(catch)
    assert len(stalls) == 1
    line = [m for m in lines if "stalled" in m][-1]
    assert "largest phases: serve.admit 1" in line     # 12x ms first
    assert line.count(" ms") == 3


# ------------------------------------------------------------ the recipe
def test_recipe_turn_has_batch_draw_dispatch_and_on_step():
    sys.path.insert(0, ROOT)
    from examples.lm import main_amp as lm

    frames = []

    def hook(it, metrics):
        f = sys._getframe(1)
        frames.append((f.f_code.co_name,
                       {"state", "batch", "compiled"} <= set(f.f_locals)))

    t = time.perf_counter()
    lm.main(["--size", "tiny", "--vocab-size", "128", "--seq-len", "32",
             "-b", "4", "--iters", "3", "--opt-level", "O0",
             "--data", os.path.join(ROOT, "tests", "data",
                                    "tiny_lm_tokens.npy")], on_step=hook)
    # the hook is still called from main()'s own frame
    assert frames == [("main", True)] * 3
    recs = _since(t)
    turns = [r for r in recs if r.name == "train.turn"]
    assert [r.args["it"] for r in turns] == [0, 1, 2]
    assert all("pc_ns" in r.args for r in turns)
    for turn in turns:
        kids = [r for r in recs if r.parent == turn.id]
        names = [r.name for r in kids]
        assert names[:3] == ["train.batch_draw", "train.dispatch",
                             "train.on_step"]
        draw = kids[0]
        assert [r.name for r in recs if r.parent == draw.id] == [
            "train.rng_readback", "train.gather", "train.h2d"]
    assert "train.log" in {r.name for r in recs
                           if r.parent == turns[0].id}


# ------------------------------------------------ the profiler's clock
def test_phases_land_on_the_host_plane_with_pc_ns(tmp_path):
    d = str(tmp_path / "tr")
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(64)).block_until_ready()
    with pyprof.trace(d):
        for i in range(3):
            with tracing.phase("serve.beat", tick=i):
                with tracing.phase("engine.launch", program="decode"):
                    y = f(jnp.ones(64))
                with tracing.phase("engine.readback") as p:
                    np.asarray(y)
                    p.note(tokens=64)
    # read the .xplane.pb itself, as on the chip where no chrome dump
    # is written
    run = sorted((tmp_path / "tr" / "plugins" / "profile").iterdir())[-1]
    xplane = [str(p) for p in run.iterdir()
              if p.name.endswith(".xplane.pb")]
    assert len(xplane) == 1
    evs = [e for lane, _, e in pyprof._load_events(xplane[0])
           if e["name"].startswith("apex.")]
    beats = [e for e in evs if e["name"] == "apex.serve.beat"]
    assert [e["args"]["tick"] for e in beats] == [0, 1, 2]
    recs = tracing.phases.records(name="serve.beat")[-3:]
    offs = [e["ts"] * 1e3 - e["args"]["pc_ns"] for e in beats]
    for e, r in zip(beats, recs):
        assert e["args"]["pc_ns"] == r.args["pc_ns"]
        assert e["dur"] * 1e-6 == pytest.approx(r.dur, abs=2e-4)
    # one offset maps perf_counter onto the trace's clock
    assert max(offs) - min(offs) < 1e6                 # ns
    launch = [e for e in evs if e["name"] == "apex.engine.launch"]
    assert len(launch) == 3
    assert launch[0]["args"]["program"] == "decode"
    assert "pc_ns" not in launch[0]["args"]
    rb = [e for e in evs if e["name"] == "apex.engine.readback"]
    assert rb[0]["args"]["tokens"] == 64
    for b, l_ in zip(beats, launch):
        assert b["ts"] <= l_["ts"] and \
            l_["ts"] + l_["dur"] <= b["ts"] + b["dur"] + 1e-3


_HAND = """planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 6000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 9000000000 duration_ps: 1000000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.12 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p.1), kind=kLoop, calls=%fc" } }
  event_metadata { key: 2 value { id: 2 name: "paged_decode_attention.7 = bf16[24,20,1,64]{3,2,1,0} custom-call(bf16[24,20,1,64]{3,2,1,0} %q)" } }
  event_metadata { key: 3 value { id: 3 name: "jit__paged_decode_impl(42)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 stats { metadata_id: 1 int64_value: 5000000 } }
    events { metadata_id: 2 offset_ps: 2100000000 duration_ps: 800000000 }
    events { metadata_id: 3 offset_ps: 4900000000 duration_ps: 1200000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "apex.serve.beat" } }
  event_metadata { key: 2 value { id: 2 name: "apex.engine.launch" } }
  event_metadata { key: 3 value { id: 3 name: "apex.engine.readback" } }
  stat_metadata { key: 1 value { id: 1 name: "pc_ns" } }
}
"""


def test_operator_readers_take_an_xplane_recording(tmp_path, capsys):
    """``pyprof.analyze`` / ``device_busy`` / ``summarize --trace`` on
    recordings with no chrome dump beside them: one second of cell B
    from the chip (PR 24's, trimmed), and a hand-written plane whose
    idle gaps have known phases."""
    rec = os.path.join(ROOT, "benchmarks", "checks", "data",
                       "cellB_1s.txtpb.gz")
    busy = pyprof.device_busy(rec)
    assert busy["n_lanes"] == 1 and busy["n_events"] == 27808
    assert busy["busy_ms"] == pytest.approx(957.39, abs=0.5)
    rows = pyprof.analyze(rec)
    by_cat = {}
    for r in rows:
        by_cat[r["category"]] = by_cat.get(r["category"], 0) + r["total_ms"]
    assert max(by_cat, key=by_cat.get) == "copy"
    assert by_cat["custom-call"] == pytest.approx(222.68, abs=0.1)
    assert summarize.phase_idle(rec)["phases"][0]["phase"] == \
        "(outside any apex.* span)"         # PR 24's run had no phases

    hand = str(tmp_path / "hand.txtpb.gz")
    with gzip.open(hand, "wt") as f:
        f.write(_HAND)
    pi = summarize.phase_idle(hand)
    # device ops cover [0,2] [3,5] [6,7] [9,10] ms: the gap 2-3 holds
    # launch's 0.8 ms and 0.2 of the beat's own, 5-6 lies in readback,
    # 7-9 in the beat alone
    assert pi["window_s"] == pytest.approx(10e-3)
    assert {r["phase"]: round(r["idle_s"] * 1e3, 6)
            for r in pi["phases"]} == {
        "apex.serve.beat": 2.2, "apex.engine.launch": 0.8,
        "apex.engine.readback": 1.0}
    assert pi["idle_s"] == pytest.approx(4e-3)
    # the host plane's line starts 1000 ns into the trace's clock
    assert pi["clock"]["trace_minus_perf_counter_ns"] == 1000 - 5000000
    from apex_tpu.telemetry.__main__ import main
    assert main(["summarize", "--trace", hand]) == 0
    out = capsys.readouterr().out
    assert "device idle by host phase" in out
    assert "apex.engine.readback" in out and "custom-call" in out
