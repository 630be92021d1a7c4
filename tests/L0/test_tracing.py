"""Request-level distributed tracing: the observability tentpole's
acceptance pins.

- **Off is free, bitwise**: ``tracer=None`` (the default) allocates not
  a single :class:`~apex_tpu.telemetry.Span` (pinned by a poisoned
  ``Span.__init__``), and a traced run's greedy tokens are bitwise
  identical to the untraced run on the SAME engine with ZERO new
  compiled programs — observation never perturbs the observed.
- **Lifecycle coverage**: every served request's trace carries the
  full span ladder (``submit`` → ``queue_wait`` → ``admit`` →
  ``prefill_chunk``+ → ``heartbeat``+ → terminal ``finish``), with the
  annotations the docs table promises (slot, pages, prompt/output
  token counts) and causally ordered timestamps.
- **Chrome export structure**: a 2-replica router run exports
  Perfetto-loadable trace-event JSON — one named process per replica,
  one named track per thread, ``args.trace_id`` on every span event,
  timestamps sorted within each lane — and every span of a routed
  request lands under its placement's pid.
- **Chaos composes** (the satellite pin): under a seeded
  :class:`~apex_tpu.serving.FaultPlan`, every trace ends in EXACTLY
  one terminal span, ``quarantine`` spans carry the typed
  :func:`~apex_tpu.serving.fault_kind`, un-faulted requests stay
  bitwise vs the fault-free untraced run, and tracing+chaos together
  still add zero compiled programs.
- **Router probe short-circuit** (the hash-skip satellite): with
  retention off there is nothing to probe — ``Router.submit`` must
  never touch ``PrefixCache.block_keys`` (the ``affinity_enabled``
  gate); with retention ON, a sub-block prompt (which can never match
  an entry) skips the hash walk and the N probes too.
- **JSONL export + CLI**: ``export_jsonl`` records join the
  ``serving.request`` completion stream on ``trace_id`` through
  ``python -m apex_tpu.telemetry trace``.

Hermetic on CPU with the tiny LM; rides the ``serving`` + ``chaos``
markers like the rest of the fault tier.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, FaultPlan, FaultPolicy, FaultSpec,
                              Request, RequestStatus, Router, Scheduler,
                              fault_kind)
from apex_tpu.serving.prefix_cache import PrefixCache
from apex_tpu.telemetry import JsonlSink, MetricsRegistry, Tracer
from apex_tpu.telemetry import tracing
from apex_tpu.telemetry.summarize import load_records

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

VOCAB = 101
CHUNK = 8

#: the docs table's three terminal names — exactly one per trace
TERMINALS = {"finish", "expired", "failed"}


@pytest.fixture(scope="module")
def lm_and_params():
    m = TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                      num_heads=4, max_seq_len=64)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _mk_engine(lm_and_params, *, pool=4, slots=2, seed=5, **kw):
    m, params = lm_and_params
    return Engine(m, params, slots=slots, max_len=64, prefill_len=24,
                  chunk_len=CHUNK, prefix_pool=pool,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  **kw)


@pytest.fixture(scope="module")
def engine(lm_and_params):
    """One shared paged engine: traced and untraced runs compare
    bitwise within the same compiled executables."""
    return _mk_engine(lm_and_params)


@pytest.fixture(scope="module")
def engines(lm_and_params):
    return [_mk_engine(lm_and_params), _mk_engine(lm_and_params)]


def _fast_policy(**kw):
    kw.setdefault("backoff_base_s", 0.0)
    kw.setdefault("audit_every_n", 1)
    return FaultPolicy(**kw)


def _stream(seed=1):
    rng = np.random.default_rng(seed)
    return [Request(prompt=list(rng.integers(1, VOCAB, size=n)),
                    max_new_tokens=b)
            for n, b in [(5, 8), (13, 6), (9, 5), (17, 4)]]


def _tokens(reqs):
    return [list(r.output_tokens) for r in reqs]


# --------------------------------------------------------- tracer unit
def test_tracer_spans_seal_and_late_attribution():
    clk = iter(float(i) for i in range(100))
    tr = Tracer(clock=lambda: next(clk))
    tr.begin("r1")
    tr.event("r1", "submit", prompt_tokens=5)
    tr.event("r1", "admit", t0=10.0, dur=0.5, slot=1)
    assert [t.trace_id for t in tr.live_traces()] == ["r1"]
    tr.end_trace("r1", "finish", reason="eos")
    t = tr.find("r1")
    assert t.terminal == "finish"
    assert [s.name for s in t.spans] == ["submit", "admit", "finish"]
    assert t.by_name("admit")[0].args == {"slot": 1}
    assert t.by_name("admit")[0].t0 == 10.0
    assert tr.live_traces() == [] and len(tr.traces()) == 1
    # a second terminal is a no-op: first terminal wins
    tr.end_trace("r1", "failed", reason="late")
    assert tr.find("r1").terminal == "finish"
    assert len(tr.find("r1").by_name("failed")) == 0
    # a LATE span (worker thread finishing after the seal) still lands
    tr.event("r1", "swap_out_store", pages=2)
    assert len(tr.find("r1").by_name("swap_out_store")) == 1


def test_tracer_bounded_rings():
    tr = Tracer(max_traces=2)
    for i in range(5):
        tr.event(f"live{i}", "submit")
    assert len(tr.live_traces()) == 2          # oldest evicted
    for i in range(5):
        tr.end_trace(f"done{i}", "finish")
    assert len(tr.traces()) == 2
    assert tr.find("done0") is None            # aged out of the ring
    assert tr.find("done4").terminal == "finish"


def test_tracer_bind_event_current_and_replica_views():
    tr = Tracer()
    tr.event_current("swap_in")                # unbound: silent no-op
    assert tr._all_spans() == []
    assert tr.current() is None
    with tr.bind("req", pid=3):
        assert tr.current() == "req"
        tr.event_current("swap_out", pages=1)
        with tr.bind("inner", pid=4):          # re-entrant stack
            tr.event_current("swap_out_store")
        tr.event_current("swap_in")
    assert tr.current() is None
    assert [s.pid for s in tr.find("req").spans] == [3, 3]
    assert tr.find("inner").spans[0].pid == 4
    # the replica view bakes its pid into events AND terminals
    v = tr.for_replica(7)
    v.event("req2", "admit")
    v.end_trace("req2", "finish")
    assert [s.pid for s in tr.find("req2").spans] == [7, 7]


# ------------------------------------------------------ off is free
def test_tracer_none_is_bitwise_invisible(engine, monkeypatch):
    """The zero-cost contract, both halves: an untraced run constructs
    ZERO Span objects (Span.__init__ is poisoned for its duration),
    and a traced run of the same stream on the same engine produces
    bitwise-identical greedy tokens with zero new compiled programs —
    attaching observability cannot perturb the serve."""
    engine.reset(clear_prefixes=True)

    def _boom(*a, **kw):
        raise AssertionError(
            "Span allocated with tracer=None — the off switch leaks")

    monkeypatch.setattr(tracing.Span, "__init__", _boom)
    plain = _stream()
    Scheduler(engine, retain_prefixes=True,
              fault_policy=_fast_policy()).run(plain)
    monkeypatch.undo()
    programs0 = engine.compiled_programs

    engine.reset(clear_prefixes=True)
    tr = Tracer()
    traced = _stream()
    Scheduler(engine, retain_prefixes=True, fault_policy=_fast_policy(),
              tracer=tr).run(traced)
    assert _tokens(traced) == _tokens(plain), \
        "attaching a tracer changed greedy tokens"
    assert engine.compiled_programs == programs0, \
        "tracing traced new programs"
    assert len(tr.traces()) == len(traced)


def test_phases_are_bitwise_invisible_on_off_and_under_a_tracer(engine):
    """The same contract for the beat's phases (``tracing.phase``, always
    on): greedy streams bitwise equal with the flight recorder on, with
    it off, and with a ``Tracer`` attached beside it; no new compiled
    program either way; off records nothing."""
    runs = {}
    programs0 = None
    try:
        for name, on, tr in (("on", True, None), ("off", False, None),
                             ("tracer", True, Tracer())):
            engine.reset(clear_prefixes=True)
            tracing.phases.enabled = on
            t_run = time.perf_counter()
            reqs = _stream()
            Scheduler(engine, retain_prefixes=True,
                      fault_policy=_fast_policy(), tracer=tr).run(reqs)
            runs[name] = _tokens(reqs)
            beats = tracing.phases.records(name="serve.beat",
                                           since=t_run)
            assert (len(beats) > 0) == on
            if programs0 is None:
                programs0 = engine.compiled_programs
            assert engine.compiled_programs == programs0
    finally:
        tracing.phases.enabled = True
    assert runs["on"] == runs["off"] == runs["tracer"]
    assert all(len(t) > 0 for t in runs["on"])


# ------------------------------------------------------ lifecycle
def test_lifecycle_spans_cover_every_request(engine):
    engine.reset(clear_prefixes=True)
    tr = Tracer()
    reqs = _stream()
    Scheduler(engine, retain_prefixes=True, fault_policy=_fast_policy(),
              tracer=tr).run(reqs)
    for r in reqs:
        t = tr.find(r.uid)
        assert t is not None and t.terminal == "finish"
        (submit,) = t.by_name("submit")
        assert submit.args["prompt_tokens"] == len(r.prompt)
        (qw,) = t.by_name("queue_wait")
        assert qw.dur >= 0.0
        (admit,) = t.by_name("admit")
        assert admit.args["slot"] in (0, 1)
        assert admit.args["pages"] > 0         # paged engine reserves
        chunks = t.by_name("prefill_chunk")
        assert len(chunks) == r.chunks and chunks[-1].args["final"]
        assert chunks[0].args["lo"] == 0
        beats = t.by_name("heartbeat")
        assert beats and all(b.dur >= 0.0 for b in beats)
        assert {"tick", "host_s", "device_wait_s"} <= set(
            beats[0].args)
        (fin,) = t.by_name("finish")
        assert fin.args["output_tokens"] == len(r.output_tokens)
        # causal order: submitted before admitted before finished
        assert submit.t0 <= admit.t0 <= fin.t0
        # every span on the bare scheduler carries replica 0
        assert {s.pid for s in t.spans} == {0}


# --------------------------------------------- router + chrome export
def test_router_tracing_and_chrome_export_structure(engines, tmp_path):
    for e in engines:
        e.reset(clear_prefixes=True)
    tr = Tracer()
    router = Router(engines, retain_prefixes=True, tracer=tr)
    reqs = _stream(seed=42) + _stream(seed=43)
    router.run(reqs)
    placements = dict(router.placements)
    router.close()
    used = set()
    for r in reqs:
        home = placements[r.uid]
        used.add(home)
        t = tr.find(r.uid)
        (route,) = t.by_name("route")
        assert route.args["replica"] == home
        assert route.args["policy"] == "affinity"
        assert route.dur >= 0.0 and "spills" in route.args
        # EVERY span of the request (route included) sits under its
        # placement's Chrome process — the for_replica(pid) contract
        assert {s.pid for s in t.spans} == {home}, \
            f"request {r.uid} spans leaked across replica pids"

    path = tmp_path / "trace.json"
    n = tr.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    spans = [e for e in events if e["ph"] == "X"]
    assert len(spans) == n > 0
    # one named process per replica pid that emitted anything
    procs = {e["pid"]: e["args"]["name"] for e in meta
             if e["name"] == "process_name"}
    assert procs == {i: f"replica{i}" for i in used}
    # every thread lane is named, spans reference only named lanes
    lanes = {(e["pid"], e["tid"]) for e in meta
             if e["name"] == "thread_name"}
    assert {(e["pid"], e["tid"]) for e in spans} <= lanes
    for e in spans:
        assert e["cat"] == "serving"
        assert "trace_id" in e["args"]
        assert e["ts"] >= 0 and e["dur"] >= 0
    # timestamps sorted within each (pid, tid) lane — what keeps the
    # Perfetto tracks readable
    for lane in {(e["pid"], e["tid"]) for e in spans}:
        ts = [e["ts"] for e in spans
              if (e["pid"], e["tid"]) == lane]
        assert ts == sorted(ts)


# ------------------------------------------------- chaos composition
def test_chaos_and_tracing_compose(engine):
    """The composition pin: tracing a chaotic serve keeps every
    guarantee of both features — exactly ONE terminal span per trace,
    quarantine spans typed by fault_kind, un-faulted requests bitwise
    vs the fault-free UNTRACED run, zero compiled programs added by
    the combination."""
    engine.reset(clear_prefixes=True)
    sched0 = Scheduler(engine, fault_policy=_fast_policy())
    clean_reqs = _stream()
    sched0.run(clean_reqs)
    clean = _tokens(clean_reqs)
    programs0 = engine.compiled_programs

    engine.reset(clear_prefixes=True)
    plan = FaultPlan([
        FaultSpec(kind="stall", tick=1, stall_s=0.03),
        FaultSpec(kind="exception", tick=2, site="chunk"),
        FaultSpec(kind="nonfinite", tick=3, slot=0),
        FaultSpec(kind="exception", tick=6, site="decode", slot=1),
    ])
    stalls = []
    tr = Tracer()
    reqs = _stream()
    Scheduler(engine,
              fault_policy=_fast_policy(max_retries=1,
                                        watchdog_budget_s=0.02,
                                        on_stall=stalls.append),
              fault_plan=plan, tracer=tr).run(reqs)
    assert plan.stats()["injected_nonfinite"] == 1
    assert plan.stats()["injected_exceptions"] == 2
    assert plan.stats()["injected_stalls"] == 1 and len(stalls) >= 1
    faulted = [r for r in reqs if r.retries > 0]
    assert faulted, "the plan must actually fault requests"
    for i, r in enumerate(reqs):
        t = tr.find(r.uid)
        assert t is not None
        # EXACTLY one terminal span, agreeing with the sealed name and
        # the request's typed terminal status
        terms = [s for s in t.spans if s.name in TERMINALS]
        assert len(terms) == 1, \
            f"request {r.uid}: {len(terms)} terminal spans"
        assert t.terminal == terms[0].name
        assert r.status.terminal
        expected = {RequestStatus.FINISHED: "finish",
                    RequestStatus.EXPIRED: "expired",
                    RequestStatus.FAILED: "failed"}[r.status]
        assert t.terminal == expected
        # quarantines are typed: one span per retry, kind from the
        # same classifier the docs table names
        qs = t.by_name("quarantine")
        assert len(qs) == r.retries
        for q in qs:
            assert q.args["kind"] in ("nonfinite", "exception",
                                      "swap", "injected")
            assert q.args["kind"] == fault_kind(q.args["error"])
        # un-faulted and retried-to-completion requests both bitwise
        # reproduce the fault-free untraced tokens
        if r.status is RequestStatus.FINISHED:
            assert list(r.output_tokens) == clean[i], \
                f"request {i} diverged under chaos+tracing"
    kinds = {q.args["kind"] for r in faulted
             for q in tr.find(r.uid).by_name("quarantine")}
    assert "nonfinite" in kinds and "injected" in kinds
    assert engine.compiled_programs == programs0, \
        "chaos+tracing traced new programs"


def test_swap_tracing_and_corruption_compose(lm_and_params):
    """The hierarchical-KV half of the composition pin: the swap-out
    span pair lands in the trace bound at dispatch (admission-side
    ``swap_out`` + store-side ``swap_out_store``), and a chaos
    ``swap_corruption`` racing the restore shows up as a ``swap_in``
    span with ``outcome=verify_failed`` / ``crc_ok=False`` while the
    request still finishes bitwise-cold with exactly one terminal span
    and zero retries (a verified miss is degradation, not a fault)."""
    from apex_tpu.serving import HostTier

    eng = _mk_engine(lm_and_params, host_tier=1 << 24, sync_swap=True)
    cold = _mk_engine(lm_and_params, pool=0)
    rng = np.random.default_rng(17)
    pre = list(rng.integers(1, VOCAB, size=16))
    p2 = pre + list(rng.integers(1, VOCAB, size=3))
    (oracle,) = Scheduler(cold).run(
        [Request(prompt=list(p2), max_new_tokens=5)])

    tr = Tracer()
    sched = Scheduler(eng, retain_prefixes=True,
                      fault_policy=_fast_policy(), tracer=tr)
    sched.run([Request(prompt=pre + [7, 8, 9], max_new_tokens=5)])
    # evict under an explicit binding: both swap-out halves attribute
    # to it (the engine never sees a request — context is the binding)
    with tr.bind("evict-ctx"):
        assert eng.prefix_cache.evict_lru()
    ev = tr.find("evict-ctx")
    (so,) = ev.by_name("swap_out")
    assert so.args["pages"] > 0 and so.args["bytes"] > 0
    (st,) = ev.by_name("swap_out_store")
    assert st.args["stored"] and st.args["inline"]   # sync_swap engine
    assert st.args["bytes"] > 0

    sched.fault_plan = FaultPlan(
        [FaultSpec(kind="swap_corruption", tick=sched._tick)])
    r2 = Request(prompt=list(p2), max_new_tokens=5)
    sched.run([r2])
    assert list(r2.output_tokens) == list(oracle.output_tokens)
    assert r2.retries == 0
    t = tr.find(r2.uid)
    (si,) = t.by_name("swap_in")
    assert si.args["outcome"] == "verify_failed"
    assert si.args["crc_ok"] is False
    assert not t.by_name("quarantine")
    assert [s.name for s in t.spans if s.name in TERMINALS] == ["finish"]
    assert isinstance(eng.host_tier, HostTier) and eng.host_tier.size == 0
    sched.close()
    eng.close()


def test_replica_death_tracing_composes(engines):
    """The router half of the composition pin: a replica killed
    mid-stream drains its requests onto the survivor — every trace
    still ends in exactly ONE terminal span, and that terminal carries
    the SURVIVOR's pid (the trace follows the request across the
    fleet, it doesn't die with the replica)."""
    for e in engines:
        e.reset(clear_prefixes=True)
    tr = Tracer()
    plan = FaultPlan([FaultSpec(kind="replica_death", tick=3,
                                replica=0)])
    router = Router(engines, retain_prefixes=True,
                    route_policy="least_loaded", fault_plan=plan,
                    tracer=tr)
    reqs = _stream(seed=9)
    router.run(reqs)
    assert plan.stats()["injected_replica_deaths"] == 1
    assert router.alive == [False, True]
    for r in reqs:
        assert r.status == "finished"
        t = tr.find(r.uid)
        terms = [s for s in t.spans if s.name in TERMINALS]
        assert len(terms) == 1 and t.terminal == "finish"
        assert terms[0].pid == router.placements[r.uid] != 0
        assert t.by_name("route")                 # routed at least once
    router.close()


# ------------------------------------------- router probe short-circuit
def test_router_submit_never_probes_without_retention(engines,
                                                      monkeypatch):
    """The hash-skip satellite, pinned by counting: with
    retain_prefixes=False (the default) affinity degrades to
    least-loaded and Router.submit must never call
    PrefixCache.block_keys — there are no entries to match, so hashing
    every prompt would be pure routing-path overhead."""
    for e in engines:
        e.reset(clear_prefixes=True)
    calls = []
    real = PrefixCache.block_keys
    monkeypatch.setattr(
        PrefixCache, "block_keys",
        lambda self, tokens, n: (calls.append(len(tokens)),
                                 real(self, tokens, n))[1])
    router = Router(engines)                   # retention off
    assert not router.affinity_enabled
    for r in _stream():
        router.submit(r)
    assert calls == [], \
        "Router.submit hashed prompts with retention off"
    router.close()


def test_router_submit_skips_probe_for_sub_block_prompts(engines,
                                                         monkeypatch):
    """With retention ON, a prompt shorter than one prefix block can
    never match a cache entry: submit must skip the hash walk AND the
    per-replica probes, while a full-block prompt still probes."""
    for e in engines:
        e.reset(clear_prefixes=True)
    block = engines[0].prefix_cache.block_len
    calls = []
    real = PrefixCache.block_keys
    monkeypatch.setattr(
        PrefixCache, "block_keys",
        lambda self, tokens, n: (calls.append(len(tokens)),
                                 real(self, tokens, n))[1])
    router = Router(engines, retain_prefixes=True)
    assert router.affinity_enabled
    router.submit(Request(prompt=list(range(1, block)),
                          max_new_tokens=2))
    assert calls == [], "a sub-block prompt was hashed on submit"
    router.submit(Request(prompt=list(range(1, block + 2)),
                          max_new_tokens=2))
    assert len(calls) == 1, \
        "a full-block prompt must hash exactly once (shared probe key)"
    router.close()


# --------------------------------------------------- jsonl export + CLI
def test_jsonl_export_joins_completion_records_via_cli(engine, tmp_path,
                                                       capsys):
    engine.reset(clear_prefixes=True)
    path = tmp_path / "run.jsonl"
    reg = MetricsRegistry(sinks=[JsonlSink(str(path))])
    tr = Tracer()
    reqs = _stream()
    Scheduler(engine, registry=reg, retain_prefixes=True,
              fault_policy=_fast_policy(), tracer=tr).run(reqs)
    reg.close()
    n = tr.export_jsonl(str(path))             # appends to the same file
    records = load_records(str(path))
    spans = [r for r in records if r.get("tag") == tracing.TRACE_TAG]
    assert len(spans) == n > 0
    for r in spans:
        assert {"trace_id", "span", "ts_s", "dur_s", "replica",
                "thread"} <= set(r)
    # completion records carry the join key and the placement
    comps = [r for r in records if r.get("tag") == "serving.request"]
    assert len(comps) == len(reqs)
    assert all(r["trace_id"] == r["uid"] and r["replica"] == 0
               for r in comps)

    from apex_tpu.telemetry.__main__ import main
    assert main(["trace", str(path), "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["traces"] == len(reqs)
    assert summary["spans"]["finish"]["count"] == len(reqs)
    assert summary["requests"]["matched"] == len(reqs)
    assert summary["requests"]["unmatched_traces"] == 0
    assert summary["requests"]["statuses"] == {"finished": len(reqs)}
    assert "prefill_chunk" in summary["critical_path"]
    # the human rendering names the stages and the join
    assert main(["trace", str(path)]) == 0
    out = capsys.readouterr().out
    for token in ("prefill_chunk", "finish", "p95", "matched"):
        assert token in out
