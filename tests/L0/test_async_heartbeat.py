"""Async pipelined heartbeat — dispatch-ahead decode with deferred
token readback (``Scheduler(pipeline_depth >= 1)``), hermetic.

The acceptance bar from the issue, as tests:

- **bitwise parity**: the greedy output stream at ``pipeline_depth >=
  1`` is identical to the ``pipeline_depth=0`` sync oracle over a mixed
  stream — chunk-boundary prompts, EOS discovered mid-pipeline,
  QueueFull backpressure, speculative decoding on and off, prefix hits,
  and a seeded chaos plan. Every comparison runs both modes through the
  SAME engine (reset between passes), so parity never crosses
  separately-jitted executables;
- **zero new compiled programs**: pipelining reuses the sync path's
  executables verbatim — trace counters pinned unchanged across a
  pipelined run;
- **zero leaked pages at drain**: the pool auditor reconciles to zero
  pages in use after every pipelined stream, including the chaos one;
- **rollback after speculated finality**: a slot whose EOS lands while
  younger speculated steps are in flight discards those steps' tokens
  (``serving.heartbeat.discarded``), and the slot's next occupant still
  produces the sync path's exact tokens — host rollback is length
  arithmetic, device state needs no undo;
- **watchdog semantics under pipelining** (satellite): the budget
  applies to the HOST portion of a beat (wall minus device-wait), so a
  beat dominated by healthy device execution never trips, while an
  injected host stall still does; the PR 8 warm-start exemption keeps
  working when tracing happens on a dispatch-ahead beat;
- the ``serving.heartbeat.*`` host-think / device-wait / duty-cycle
  telemetry lands on every beat, sync and pipelined;
- :class:`~apex_tpu.serving.DraftWorker` unit behavior: precomputed ==
  inline (purity), inline fallback, exception surfacing, idempotent
  submit, bounded unclaimed results, idempotent stop.

Everything runs on CPU with a tiny model (the kernels take their
interpret/reference paths); wall-clock wins are the bench's claim, not
this file's — here the contract is exactness and accounting.
"""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (DraftWorker, Engine, FaultPlan, FaultPolicy,
                              FaultSpec, QueueFull, Request, RequestStatus,
                              Scheduler, SpecConfig)

pytestmark = pytest.mark.serving

VOCAB = 101
CHUNK = 8


def _tiny_lm(max_seq_len=64, **kw):
    return TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                         num_heads=4, max_seq_len=max_seq_len, **kw)


@pytest.fixture(scope="module")
def lm_and_params():
    m = _tiny_lm()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _mk_engine(lm_and_params, *, slots=3, pool=0, seed=5, **kw):
    m, params = lm_and_params
    return Engine(m, params, slots=slots, max_len=64, prefill_len=24,
                  chunk_len=CHUNK, prefix_pool=pool,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  **kw)


@pytest.fixture(scope="module")
def engine(lm_and_params):
    """One shared paged engine: the sync oracle pass and every
    pipelined pass run the SAME compiled programs (reset between runs),
    so bitwise comparisons never cross executables."""
    return _mk_engine(lm_and_params)


def _mixed_stream():
    """Prompt lengths below / at / straddling chunk boundaries
    (chunk_len=8), budgets long and short — the parity sweep's
    workload."""
    rng = np.random.default_rng(42)
    return [Request(prompt=list(rng.integers(1, VOCAB, size=n)),
                    max_new_tokens=b)
            for n, b in [(5, 12), (8, 4), (13, 6), (21, 4), (3, 9),
                         (16, 5), (7, 1), (11, 7)]]


#: the synchronous beat the dispatch-ahead default is pinned against
ORACLE = {"pipeline_depth": 0}


def _serve(engine, stream, **sched_kw):
    """Run ``stream`` to completion; returns the per-request token
    lists in SUBMISSION order (completion order differs across
    pipeline depths — that reordering is scheduling, not output)."""
    sched = Scheduler(engine, **sched_kw)
    sched.run(stream)
    return [list(r.output_tokens) for r in stream], sched


# ------------------------------------------------------------ validation
def test_pipeline_depth_validation_and_worker_lifecycle(engine):
    engine.reset()
    with pytest.raises(ValueError, match="pipeline_depth"):
        Scheduler(engine, pipeline_depth=-1)
    # depth 0 never spins the worker thread — the sync oracle path
    # carries zero threading machinery
    assert Scheduler(engine, pipeline_depth=0)._worker is None
    # the default is the dispatch-ahead beat, one step in flight
    sched = Scheduler(engine)
    assert sched.pipeline_depth == 1 and sched._worker is not None
    sched._worker.stop()            # idempotent; finalizer runs it again


# ------------------------------------------------- the headline parity
def test_depth_parity_zero_new_programs_zero_leaks(engine):
    """THE acceptance pin: a mixed chunk-boundary stream served at
    depths 1 and 3 is bitwise the depth-0 stream, through the same
    executables (zero new compiled programs), with zero pages leaked
    at drain and an empty pipeline left behind."""
    engine.reset()
    oracle, sync_sched = _serve(engine, _mixed_stream(), **ORACLE)
    programs0 = engine.compiled_programs
    for depth in (1, 3):
        engine.reset()
        got, sched = _serve(engine, _mixed_stream(),
                            pipeline_depth=depth)
        assert got == oracle, f"depth {depth} diverged from sync oracle"
        assert engine.compiled_programs == programs0, \
            f"depth {depth} traced new programs"
        assert not sched._pipeline, "run() left steps in flight"
        assert sched.auditor.audit(engine)["pages_in_use"] == 0
    engine.reset()


def _backlog_stream(n=10, seed=7):
    """More requests than slots, prompts of one to three chunks: the
    backlog cells' traffic at the tests' geometry."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=list(rng.integers(1, VOCAB, size=int(ln))),
                    max_new_tokens=int(b))
            for ln, b in zip(rng.integers(3, 25, size=n),
                             rng.integers(4, 14, size=n))]


def test_default_beat_is_the_sync_oracle_on_a_chunked_backlog(engine):
    """What every server runs: ``Scheduler(engine)`` with nothing said
    is the dispatch-ahead beat, and over a chunked backlog - with an
    ``eos_id`` that ends requests mid-pipeline, their slots re-occupied
    by the queue - its greedy streams are bitwise the synchronous
    beat's, through the same two programs."""
    engine.reset()
    probe, _ = _serve(engine, _backlog_stream(), **ORACLE)
    # an id that some request first emits mid-generation: declared EOS,
    # it is discovered at a reconcile with a successor step in flight
    eos_id = next(t for out in probe for i, t in enumerate(out)
                  if i >= 2 and t not in out[:i])
    engine.reset()
    oracle, _ = _serve(engine, _backlog_stream(), eos_id=eos_id, **ORACLE)
    programs0 = engine.compiled_programs
    engine.reset()
    reg = telemetry.MetricsRegistry()
    reqs = _backlog_stream()
    try:
        got, sched = _serve(engine, reqs, eos_id=eos_id, registry=reg)
    finally:
        engine.set_registry(None)
    assert sched.pipeline_depth == 1
    assert got == oracle, "the default beat diverged from the sync oracle"
    assert engine.compiled_programs == programs0
    ended = [i for i, r in enumerate(reqs) if r.finish_reason == "eos"
             and len(r.output_tokens) >= 3]
    assert ended and ended[0] < len(reqs) - engine.slots, \
        "no EOS mid-pipeline with the queue still holding requests"
    assert reg.snapshot()["counters"].get(
        "serving.heartbeat.discarded", 0) >= 1
    assert not sched._pipeline
    assert sched.auditor.audit(engine)["pages_in_use"] == 0
    engine.reset()


def test_one_decode_launch_a_beat_and_no_op_beside_it(engine):
    """The chained step costs the host what a synchronous one does: one
    ``engine.upload`` and one ``engine.launch`` of the decode program a
    beat (the select of device tokens against host tokens runs INSIDE
    the program), and nothing the synchronous beat had not compiled is
    compiled when the dispatch-ahead beat first runs."""
    from apex_tpu.telemetry import tracing
    from benchmarks.lib.common import CompileCounter

    engine.reset()
    _serve(engine, _backlog_stream(4), **ORACLE)        # warm, depth 0
    engine.reset()
    compiles = CompileCounter()
    t0 = time.perf_counter()
    _serve(engine, _backlog_stream())
    assert compiles.n == 0, "the dispatch-ahead beat compiled something"
    recs = tracing.phases.records(since=t0)
    beats = {r.id for r in recs if r.name == "serve.beat"}
    launches = collections.Counter(
        r.root for r in recs if r.name == "engine.launch"
        and r.args["program"] == "decode")
    assert launches and set(launches) <= beats
    assert max(launches.values()) == 1
    n_programs = sum(1 for r in recs if r.name == "engine.launch")
    assert sum(1 for r in recs if r.name == "engine.upload") == n_programs
    # the dispatch's phase says how many steps were in flight behind it
    noted = [r.args["inflight"] for r in recs if r.name == "serve.decode"
             and "inflight" in (r.args or {})]
    assert noted and set(noted) <= {0, 1} and 1 in noted
    engine.reset()


def test_final_chunk_is_retired_at_the_beat_after_its_dispatch(
        lm_and_params):
    """Three slots prefilling under ``chunk_budget=1`` while a fourth
    decodes: the round-robin gives each a chunk every third beat, but a
    FINAL chunk's first token is read at the top of the very next beat,
    whichever slot that beat's visit reaches - its request decodes from
    then on, as in the synchronous beat."""
    eng = _mk_engine(lm_and_params, slots=4, seed=3)
    sched = Scheduler(eng, chunk_budget=1)
    c = Request(prompt=[1, 2], max_new_tokens=40)
    sched.submit(c)
    sched.step()
    sched.step()
    assert c.status == "running"
    a = Request(prompt=[7, 8, 9], max_new_tokens=6)            # 1 chunk
    b = Request(prompt=list(range(1, 24)), max_new_tokens=3)   # 3 chunks
    d = Request(prompt=list(range(2, 25)), max_new_tokens=3)   # 3 chunks
    for r in (a, b, d):
        sched.submit(r)
    sched.step()            # admits all three; the visit dispatches a's
    assert a._prefill_pos == 3 and a.output_tokens == []
    assert sched._pending_prefill[sched._running.index(a)] is not None
    sched.step()            # the visit is b's; a's token is read first
    assert len(a.output_tokens) == 1 and a.status == "running"
    assert a.ttft_s is not None and b._prefill_pos == CHUNK
    sched.step()            # a decodes beside c while d ingests
    assert len(a.output_tokens) == 2 and d._prefill_pos == CHUNK
    sched.run([])
    assert all(r.status is RequestStatus.FINISHED for r in (a, b, c, d))
    assert sched.auditor.audit(eng)["pages_in_use"] == 0


@pytest.mark.parametrize("depth", [0, 1])
def test_engagement_counter_over_a_backlog(engine, depth):
    """``serving.heartbeat.dispatched_ahead`` over
    ``serving.decode.steps`` is the share of decode steps that went to
    the device while an earlier one was un-read: nearly all of them
    with the queue kept full, none in the synchronous beat."""
    engine.reset()
    reg = telemetry.MetricsRegistry()
    engine.set_registry(reg)
    sched = Scheduler(engine, registry=reg, pipeline_depth=depth,
                      max_queue=4)
    stream = iter(_backlog_stream(64, seed=13))
    try:
        for _ in range(50):
            while len(sched._queue) < sched.max_queue:
                sched.submit(next(stream))
            sched.step()
        while sched.pending:
            sched.step()
    finally:
        engine.set_registry(None)
    counters = reg.snapshot()["counters"]
    ahead = counters.get("serving.heartbeat.dispatched_ahead", 0)
    assert counters["serving.decode.steps"] >= 50
    if depth == 0:
        assert ahead == 0
    else:
        assert ahead / counters["serving.decode.steps"] > 0.9
    # ``python -m apex_tpu.telemetry summarize`` prints the share
    from apex_tpu.telemetry.summarize import (render_summary,
                                              summarize_records)
    text = render_summary(summarize_records([reg.snapshot()]))
    share = ahead / counters["serving.decode.steps"]
    assert ("(serving.heartbeat.dispatched_ahead / serving.decode.steps) "
            f"{share:.4f}") in text
    engine.reset()


def test_eos_mid_pipeline_discards_and_slot_reuse(lm_and_params):
    """Rollback after speculated finality: EOS is the one terminal the
    dispatcher cannot predict, so a slot's EOS discovered at reconcile
    invalidates its in-flight speculated successors
    (``serving.heartbeat.discarded``) — and because host rollback is
    pure length arithmetic and the rejected K/V is overwritten
    write-then-attend, the slot's NEXT occupant emits the sync path's
    exact tokens. One slot, so the follow-up request reuses the EXACT
    slot that rolled back."""
    eng = _mk_engine(lm_and_params, slots=1, seed=11)
    # find an EOS id the greedy stream first emits MID-generation
    # (index >= 2): declaring an id the stream opens with would finish
    # the request at prefill, before anything is ever in flight
    probe = Request(prompt=[13, 5, 88], max_new_tokens=12)
    _serve(eng, [probe])
    toks = probe.output_tokens
    eos_id = next(t for i, t in enumerate(toks)
                  if i >= 2 and t not in toks[:i])
    mk = lambda: [Request(prompt=[13, 5, 88], max_new_tokens=20),
                  Request(prompt=[9, 4, 2, 8], max_new_tokens=6)]

    eng.reset()
    oracle, _ = _serve(eng, mk(), eos_id=eos_id, **ORACLE)

    eng.reset()
    reg = telemetry.MetricsRegistry()
    reqs = mk()
    sched = Scheduler(eng, eos_id=eos_id, pipeline_depth=3,
                      registry=reg)
    sched.run(reqs)
    got = [list(r.output_tokens) for r in reqs]
    assert got == oracle
    assert reqs[0].finish_reason == "eos"
    # the speculated successors of the EOS beat were really in flight
    # and really discarded — the rollback actually happened
    assert reg.snapshot()["counters"].get(
        "serving.heartbeat.discarded", 0) >= 1, \
        "EOS mid-pipeline discarded nothing — the pin exercised no " \
        "rollback"
    assert sched.auditor.audit(eng)["pages_in_use"] == 0

    # the LAST-request strand regression (found by end-to-end drive):
    # a stream whose final request EOSes with speculated successors in
    # flight must still drain — `pending` counts the pipeline, so
    # run()'s `while pending` loop reconciles (and discards) the
    # stragglers instead of exiting with steps stranded in flight
    eng.reset()
    reg = telemetry.MetricsRegistry()
    sched = Scheduler(eng, eos_id=eos_id, pipeline_depth=3,
                      registry=reg)
    (solo,) = sched.run([Request(prompt=[13, 5, 88],
                                 max_new_tokens=20)])
    assert solo.finish_reason == "eos"
    assert not sched._pipeline, \
        "run() exited with dispatched steps stranded in flight"
    assert reg.snapshot()["counters"].get(
        "serving.heartbeat.discarded", 0) >= 1


def test_queue_full_backpressure_parity(engine):
    """QueueFull under pipelining: submit still raises at capacity, and
    a stream pushed through run()'s backpressure absorption emits the
    sync path's exact tokens."""
    engine.reset()
    oracle, _ = _serve(engine, _mixed_stream(), max_queue=2, **ORACLE)
    engine.reset()
    sched = Scheduler(engine, max_queue=2, pipeline_depth=2)
    sched.submit(Request(prompt=[1], max_new_tokens=2))
    sched.submit(Request(prompt=[2], max_new_tokens=2))
    with pytest.raises(QueueFull):
        sched.submit(Request(prompt=[3], max_new_tokens=2))
    while sched.pending:
        sched.step()
    engine.reset()
    got, _ = _serve(engine, _mixed_stream(), max_queue=2,
                    pipeline_depth=2)
    assert got == oracle
    engine.reset()


# ------------------------------------------------- speculative + prefix
@pytest.fixture(scope="module")
def spec_engine(lm_and_params):
    return _mk_engine(lm_and_params, spec=SpecConfig(draft_len=4))


def _repetitive_stream():
    """Prompts whose trailing n-grams recur, so the prompt-lookup
    drafter actually drafts (and the verify program actually runs)."""
    base = [11, 12, 13, 14, 11, 12, 13, 14, 11, 12]
    return [Request(prompt=list(base), max_new_tokens=12),
            Request(prompt=[5, 6, 5, 6, 5, 6, 5], max_new_tokens=10),
            Request(prompt=list(range(1, 14)), max_new_tokens=6)]


def test_speculative_parity_with_threaded_drafter(spec_engine):
    """Speculative on: the pipelined beat settles the pipeline before
    verify, drafts on the worker thread, and still emits the sync
    speculative stream bit-for-bit — with speculation genuinely
    engaged (accepted tokens > 0) and no new programs."""
    eng = spec_engine
    eng.reset()
    oracle, _ = _serve(eng, _repetitive_stream(), speculative=True,
                       **ORACLE)
    programs0 = eng.compiled_programs
    eng.reset()
    reqs = _repetitive_stream()
    got, sched = _serve(eng, reqs, speculative=True, pipeline_depth=2)
    assert [list(t) for t in got] == oracle
    assert eng.compiled_programs == programs0
    assert sum(r.spec_accepted for r in reqs) > 0, \
        "speculation never engaged — the parity proved nothing"
    assert sched.auditor.audit(eng)["pages_in_use"] == 0
    eng.reset()


def test_prefix_hit_stream_parity_with_hash_offload(lm_and_params):
    """Prefix retention under pipelining: block hashing runs on the
    worker thread from submit time, and the hit/miss/registration
    stream (and every emitted token) matches the sync path exactly —
    precomputed and inline keys are interchangeable bit-for-bit."""
    eng = _mk_engine(lm_and_params, pool=16)
    shared = list(range(1, 17))
    mk = lambda: [Request(prompt=shared + [30 + i], max_new_tokens=6)
                  for i in range(4)]
    oracle, s0 = _serve(eng, mk(), retain_prefixes=True, **ORACLE)
    hits0 = eng.prefix_cache.hits          # cumulative across resets
    eng.reset(clear_prefixes=True)
    got, s1 = _serve(eng, mk(), retain_prefixes=True, pipeline_depth=2)
    assert got == oracle
    assert eng.prefix_cache.hits - hits0 == hits0, \
        "the pipelined pass matched a different hit stream"
    assert hits0 > 0, "no hits — the parity proved nothing"


# ------------------------------------------------------------- chaos
def test_chaos_stream_unfaulted_bitwise_and_zero_leaks(engine):
    """A seeded fault plan (host stall, transient chunk + decode
    exceptions, a non-finite decode slot) against the PIPELINED beat:
    un-faulted requests bitwise-match the fault-free sync run, faulted
    ones reach typed terminals, zero new programs, zero leaked
    pages."""
    engine.reset()
    clean_reqs = _mixed_stream()
    Scheduler(engine, fault_policy=FaultPolicy(backoff_base_s=0.0,
                                               audit_every_n=1),
              **ORACLE).run(clean_reqs)
    clean = [list(r.output_tokens) for r in clean_reqs]
    traces0 = (engine.chunk_traces, engine.decode_traces)

    engine.reset()
    plan = FaultPlan([
        FaultSpec(kind="stall", tick=1, stall_s=0.02),
        FaultSpec(kind="exception", tick=2, site="chunk"),
        FaultSpec(kind="nonfinite", tick=4, slot=0),
        FaultSpec(kind="exception", tick=6, site="decode", slot=1),
    ])
    reg = telemetry.MetricsRegistry()
    engine.set_registry(reg)
    sched = Scheduler(
        engine, registry=reg, fault_plan=plan, pipeline_depth=2,
        fault_policy=FaultPolicy(backoff_base_s=0.0, max_retries=1,
                                 audit_every_n=1))
    reqs = _mixed_stream()
    try:
        sched.run(reqs)
    finally:
        engine.set_registry(None)
    faulted = [r for r in reqs if r.retries > 0
               or r.status is RequestStatus.FAILED]
    assert faulted, "the plan must actually fault requests"
    for r in reqs:
        assert r.status.terminal
    for i, r in enumerate(reqs):
        if r.status is RequestStatus.FINISHED:
            # greedy retries are full cold restarts through the same
            # programs: finished requests reproduce the clean tokens
            # whether or not they absorbed a fault
            assert list(r.output_tokens) == clean[i], \
                f"request {i} diverged under pipelined chaos"
    assert (engine.chunk_traces, engine.decode_traces) == traces0
    assert sched.auditor.audit(engine)["pages_in_use"] == 0
    assert reg.snapshot()["counters"]["serving.faults.transient"] >= 1
    engine.reset()


def test_requeued_request_never_consumes_stale_inflight_tokens(
        lm_and_params):
    """The quarantine-requeue lineage pin (found by review): a
    quarantined request keeps its uid through requeue, so if it
    re-admits into the SAME slot while pre-quarantine steps are still
    in flight, a uid check at reconcile alone would emit their
    garbage-lineage tokens into the retried stream. ``_free_slot``
    drops the slot's in-flight entries eagerly instead — the retried
    request must reproduce the fault-free stream bitwise. One slot +
    empty queue + zero backoff forces same-slot re-admission on the
    very next beat (the exact collision window); the one-chunk prompt
    flips to running the same beat it admits."""
    eng = _mk_engine(lm_and_params, slots=1, seed=23)
    clean = Request(prompt=[4, 9, 1], max_new_tokens=8)
    Scheduler(eng, **ORACLE).run([clean])

    eng.reset()
    reg = telemetry.MetricsRegistry()
    eng.set_registry(reg)
    # non-finite injected at dispatch tick 3: with depth 2 the verdict
    # lands at reconcile two beats later, while two younger speculated
    # steps of the same lineage sit in flight
    plan = FaultPlan([FaultSpec(kind="nonfinite", tick=3, slot=0)])
    sched = Scheduler(
        eng, registry=reg, fault_plan=plan, pipeline_depth=2,
        fault_policy=FaultPolicy(backoff_base_s=0.0, max_retries=2))
    r = Request(prompt=[4, 9, 1], max_new_tokens=8)
    try:
        sched.run([r])
    finally:
        eng.set_registry(None)
    assert plan.stats()["injected_nonfinite"] == 1
    assert r.retries >= 1, "the fault never landed — nothing retried"
    assert r.status is RequestStatus.FINISHED
    assert list(r.output_tokens) == list(clean.output_tokens), \
        "retried stream diverged — a stale in-flight token leaked " \
        "into the re-admitted request"
    # the invalidated lineage really was in flight and was discarded
    assert reg.snapshot()["counters"].get(
        "serving.heartbeat.discarded", 0) >= 1
    assert sched.auditor.audit(eng)["pages_in_use"] == 0


def test_deferred_reconcile_failure_is_contained(lm_and_params):
    """Containment at the DEFERRED force (found by review): on async
    backends a dispatched step's runtime error surfaces at the first
    read inside ``decode_reconcile`` — beats later, in
    ``_reconcile_oldest`` — not at the wrapped dispatch site. The
    scheduler must quarantine the step's batch exactly like a sync
    decode-site fault (requeue → clean bitwise retry), never let the
    exception crash ``run()``. Simulated by failing the engine's
    reconcile once (the CPU backend's synchronous donated calls can't
    produce it for real)."""
    eng = _mk_engine(lm_and_params, slots=1, seed=31)
    clean = Request(prompt=[6, 2, 7], max_new_tokens=6)
    Scheduler(eng, **ORACLE).run([clean])

    eng.reset()
    orig = eng.decode_reconcile
    fails = {"left": 1}

    def flaky(pending, valid=None):
        out = orig(pending, valid=valid)
        if fails["left"]:
            fails["left"] -= 1
            raise RuntimeError("deferred device failure")
        return out

    reg = telemetry.MetricsRegistry()
    eng.decode_reconcile = flaky
    try:
        sched = Scheduler(
            eng, registry=reg, pipeline_depth=2,
            fault_policy=FaultPolicy(backoff_base_s=0.0, max_retries=2))
        r = Request(prompt=[6, 2, 7], max_new_tokens=6)
        sched.run([r])
    finally:
        del eng.decode_reconcile
    assert fails["left"] == 0, "the failure never fired"
    assert r.retries >= 1
    assert r.status is RequestStatus.FINISHED
    assert list(r.output_tokens) == list(clean.output_tokens), \
        "retry after a deferred reconcile failure diverged"
    assert reg.snapshot()["counters"]["serving.faults.transient"] >= 1
    assert not sched._pipeline
    assert sched.auditor.audit(eng)["pages_in_use"] == 0


# ------------------------------------------------- heartbeat telemetry
def test_heartbeat_host_device_split_emitted_every_beat(engine):
    """serving.heartbeat.host_s / device_wait_s land as histograms with
    one observation per beat (sync AND pipelined), and the duty-cycle
    gauge stays a fraction."""
    engine.reset()
    for depth in (0, 2):
        reg = telemetry.MetricsRegistry()
        sched = Scheduler(engine, registry=reg, pipeline_depth=depth)
        sched.submit(Request(prompt=[3, 1, 4], max_new_tokens=5))
        beats = 0
        while sched.pending:
            sched.step()
            beats += 1
        snap = reg.snapshot()
        h = snap["histograms"]
        assert h["serving.heartbeat.host_s"]["count"] == beats
        assert h["serving.heartbeat.device_wait_s"]["count"] == beats
        assert h["serving.heartbeat.host_s"]["mean"] >= 0.0
        assert 0.0 <= snap["gauges"]["serving.heartbeat.duty_cycle"] \
            <= 1.0
        engine.reset()


# ------------------------------------------------- watchdog semantics
def test_watchdog_budgets_host_portion_not_device_wait(engine):
    """Satellite pin: under pipelining the watchdog budgets HOST time.
    A beat whose wall is dominated by device-wait (simulated: the
    reconcile charges a sleep to ``device_wait_s``) never breaches a
    budget smaller than that wall — while an injected host stall of the
    same size still does."""
    engine.reset()
    # warm every program so tracing exemptions don't participate here
    Scheduler(engine).run([Request(prompt=[5, 6], max_new_tokens=3)])

    engine.reset()
    orig = engine.decode_reconcile

    def device_heavy(pending, valid=None):
        out = orig(pending, valid=valid)
        time.sleep(0.05)
        engine.device_wait_s += 0.05    # a slow DEVICE, not a slow host
        return out

    stalls = []
    engine.decode_reconcile = device_heavy
    try:
        sched = Scheduler(
            engine, pipeline_depth=1,
            fault_policy=FaultPolicy(watchdog_budget_s=0.03,
                                     on_stall=stalls.append))
        sched.run([Request(prompt=[5, 6], max_new_tokens=6)])
    finally:
        del engine.decode_reconcile     # restore the bound method
    assert not stalls, \
        "device-wait tripped the watchdog — the budget must cover " \
        "host think-time only"

    # the same budget against a HOST stall of the same magnitude trips
    engine.reset()
    plan = FaultPlan([FaultSpec(kind="stall", tick=1, stall_s=0.05)])
    sched = Scheduler(
        engine, pipeline_depth=1, fault_plan=plan,
        fault_policy=FaultPolicy(watchdog_budget_s=0.03,
                                 on_stall=stalls.append))
    sched.run([Request(prompt=[5, 6], max_new_tokens=6)])
    assert len(stalls) >= 1 and stalls[0] > 0.03
    engine.reset()


def test_watchdog_warm_start_exemption_on_dispatch_ahead_beat(
        lm_and_params):
    """The PR 8 warm-start regression, re-pinned under pipelining: a
    COLD engine's tracing beats are exempt from an impossible budget
    (counted as ``serving.watchdog.warmup_s``) even though tracing now
    happens at DISPATCH time, and warm beats breach — warmups + stalls
    partition the run exactly. A warmed engine stops claiming
    warm-up."""
    eng = _mk_engine(lm_and_params, seed=9)
    assert eng.compiled_programs == 0
    stalls = []
    reg = telemetry.MetricsRegistry()
    sched = Scheduler(
        eng, registry=reg, pipeline_depth=2,
        fault_policy=FaultPolicy(backoff_base_s=0.0,
                                 watchdog_budget_s=1e-9,
                                 on_stall=stalls.append))
    steps = 0
    sched.submit(Request(prompt=[5, 6, 7], max_new_tokens=4))
    while sched.pending:
        sched.step()
        steps += 1
    snap = reg.snapshot()
    warmups = snap["histograms"]["serving.watchdog.warmup_s"]["count"]
    stalls_n = snap["counters"].get("serving.watchdog.stall", 0)
    assert warmups >= 1, "the dispatch-ahead tracing beat was not " \
        "accounted as warm-up"
    assert warmups + stalls_n == steps
    assert len(stalls) == stalls_n
    sched.submit(Request(prompt=[5, 6, 7], max_new_tokens=2))
    more = 0
    while sched.pending:
        sched.step()
        more += 1
    snap = reg.snapshot()
    assert snap["histograms"]["serving.watchdog.warmup_s"]["count"] \
        == warmups, "a warm engine must not keep claiming warm-up"
    assert snap["counters"]["serving.watchdog.stall"] == stalls_n + more


# --------------------------------------------------- engine async halves
def test_decode_dispatch_reconcile_is_decode_step(engine):
    """The split is the sync step: dispatch + reconcile back-to-back
    returns decode_step's exact tokens (same program, same operands),
    a PendingDecode reads back exactly once, and every forced read
    charges device_wait_s."""
    engine.reset()
    tok = engine.prefill_chunked(0, [5, 9, 2])
    active = [True] + [False] * (engine.slots - 1)
    last = np.zeros(engine.slots, np.int64)
    last[0] = tok
    temps = np.zeros(engine.slots, np.float32)
    a = engine.decode_step(list(last), active, temps)
    pending = engine.decode_dispatch(
        np.asarray([int(a[0])] + [0] * (engine.slots - 1)), active,
        temps)
    dw0 = engine.device_wait_s
    toks, finite, dt = engine.decode_reconcile(pending)
    assert toks.shape == (engine.slots,) and finite.shape \
        == (engine.slots,)
    assert dt >= 0 and engine.device_wait_s > dw0
    with pytest.raises(RuntimeError, match="already reconciled"):
        engine.decode_reconcile(pending)
    engine.sync()                       # the explicit barrier is cheap
    engine.reset()


# -------------------------------------------------------- DraftWorker
def test_draft_worker_precomputed_equals_inline_and_fallback():
    w = DraftWorker()
    try:
        cfg = SpecConfig(draft_len=3)
        toks = [1, 2, 3, 1, 2, 3, 1]
        from apex_tpu.serving import draft_tokens
        inline = draft_tokens(toks, cfg)
        w.submit("k", lambda: draft_tokens(toks, cfg))
        assert w.take("k", lambda: draft_tokens(toks, cfg)) == inline
        # never submitted: take runs the closure inline
        assert w.take("nope", lambda: draft_tokens(toks, cfg)) == inline
        # results are consumed on take: a second take recomputes inline
        w.submit("k2", lambda: 42)
        assert w.take("k2", lambda: 0) == 42
        assert w.take("k2", lambda: 7) == 7
    finally:
        w.stop()


def test_draft_worker_surfaces_exceptions_and_is_idempotent():
    w = DraftWorker()
    try:
        w.submit("boom", lambda: 1 / 0)
        with pytest.raises(ZeroDivisionError):
            w.take("boom", lambda: None)
        # idempotent submit: a completed key is not re-run
        calls = []
        w.submit("once", lambda: calls.append(1) or len(calls))
        assert w.take("once", lambda: -1) == 1
        w.submit("once2", lambda: calls.append(1) or len(calls))
        w.submit("once2", lambda: calls.append(1) or len(calls))
        assert w.take("once2", lambda: -1) == 2
        assert len(calls) == 2
    finally:
        w.stop()
    # stop is idempotent, and a stopped worker degrades to inline
    w.stop()
    w.submit("late", lambda: 1)
    assert w.take("late", lambda: 9) == 9


def test_draft_worker_bounds_unclaimed_results():
    w = DraftWorker()
    try:
        n = w._MAX_UNCLAIMED + 40
        for i in range(n):
            w.submit(("job", i), lambda i=i: i)
        # drain: wait for the queue to empty via a sentinel take
        assert w.take(("job", n - 1), lambda: -1) == n - 1
        with w._lock:
            assert len(w._results) <= w._MAX_UNCLAIMED
        # an aged-out key recomputes inline — no wrong answers, no leak
        assert w.take(("job", 0), lambda: "inline") in (0, "inline")
    finally:
        w.stop()
