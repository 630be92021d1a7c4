"""Fault-isolated serving: chaos injection, quarantine, auditing.

The acceptance bar from the fault-isolation issue, as tests:

- **the chaos pin**: under a seeded :class:`FaultPlan` mixing
  non-finite logits, transient step exceptions and a watchdog stall,
  every UN-faulted greedy request's token stream is bitwise identical
  to a fault-free run on the same engine (healthy slots in a batch
  with a quarantined slot keep their exact tokens), every faulted
  request reaches a typed terminal status, and the
  :class:`PoolAuditor` reports zero leaked/double-freed pages at
  drain;
- containment adds ZERO compiled programs: the chaos run's trace
  counters match the fault-free run's (the guard is fused into the
  existing programs; injection rides a zero-in-production operand);
- the non-finite guard is per-slot (decode) / per-call (chunk)
  and fires on REAL NaN logits (a NaN-poisoned
  engine fails every request typed-``FAILED`` without crashing);
- the fault policy requeues with capped exponential backoff up to
  ``max_retries`` then lands the typed ``FAILED`` terminal status,
  reclaiming every page;
- the auditor detects manufactured corruption (leaked refcounts,
  double-frees, corrupted debug-copy page tables) and passes on
  healthy pools;
- the watchdog flags heartbeats over budget (``serving.watchdog.*``)
  and invokes the policy callback;
- ``QueueFull`` carries a decode-throughput-derived ``retry_after_s``;
- the slow soak: several hundred randomized heartbeats of faults
  interleaved with pool exhaustion and prefix eviction — zero leaks,
  zero clean-request token mismatches.

Everything hermetic on CPU with a tiny model (the kernels take their
reference paths); the ``chaos`` marker selects this tier.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serving, telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, FaultPlan, FaultPolicy, FaultSpec,
                              InjectedFault, PoolAuditor,
                              PoolInvariantError, QueueFull, Request,
                              RequestStatus, Scheduler)

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

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


def _mk_engine(lm_and_params, *, pool=0, slots=2, seed=5, **kw):
    m, params = lm_and_params
    return Engine(m, params, slots=slots, max_len=64, prefill_len=24,
                  chunk_len=CHUNK, prefix_pool=pool,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  **kw)


@pytest.fixture(scope="module")
def engine(lm_and_params):
    """One shared paged engine — the pin tests run clean and chaos
    passes on the SAME compiled programs (reset between runs), so
    bitwise comparisons never cross executables."""
    return _mk_engine(lm_and_params)


def _fast_policy(**kw):
    kw.setdefault("backoff_base_s", 0.0)
    kw.setdefault("audit_every_n", 1)
    return FaultPolicy(**kw)


def _stream():
    rng = np.random.default_rng(1)
    return [Request(prompt=list(rng.integers(1, VOCAB, size=n)),
                    max_new_tokens=b)
            for n, b in [(5, 8), (13, 6), (9, 5), (17, 4)]]


# ------------------------------------------------------------ FaultPlan
def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(kind="meteor", tick=0)
    with pytest.raises(ValueError, match="victim slot"):
        FaultSpec(kind="nonfinite", tick=0)
    with pytest.raises(ValueError, match="site"):
        FaultSpec(kind="exception", tick=0, site="prefix")
    with pytest.raises(ValueError, match="stall_s"):
        FaultSpec(kind="stall", tick=0)


def test_fault_plan_is_deterministic_and_seeded():
    a = FaultPlan.random(3, 50, slots=4, nonfinite_rate=0.2,
                         exception_rate=0.2, stall_rate=0.1)
    b = FaultPlan.random(3, 50, slots=4, nonfinite_rate=0.2,
                         exception_rate=0.2, stall_rate=0.1)
    assert a.specs == b.specs and len(a.specs) > 0
    c = FaultPlan.random(4, 50, slots=4, nonfinite_rate=0.2,
                         exception_rate=0.2, stall_rate=0.1)
    assert a.specs != c.specs


def test_fault_plan_injection_surface():
    plan = FaultPlan([
        FaultSpec(kind="nonfinite", tick=2, slot=1,
                  value=float("inf")),
        FaultSpec(kind="exception", tick=3, site="decode", slot=0),
        FaultSpec(kind="stall", tick=4, stall_s=0.01),
    ])
    assert plan.decode_bias(0, 3) is None
    bias = plan.decode_bias(2, 3)
    assert bias.shape == (3,) and np.isinf(bias[1])
    assert bias[0] == 0.0 and bias[2] == 0.0
    # victims outside the engine's slot range are ignored, not crashed
    assert plan.decode_bias(2, 1) is None
    plan.maybe_raise("chunk", 3)             # wrong site: no-op
    with pytest.raises(InjectedFault) as ei:
        plan.maybe_raise("decode", 3)
    assert ei.value.slot == 0 and ei.value.transient
    t0 = time.perf_counter()
    assert plan.maybe_stall(4) > 0
    assert time.perf_counter() - t0 >= 0.01
    assert plan.maybe_stall(5) == 0.0
    assert plan.stats()["injected_exceptions"] == 1


def test_corrupt_page_table_refuses_live_views(engine):
    plan = FaultPlan()
    live = engine._page_table
    with pytest.raises(ValueError, match="DEBUG COPIES"):
        plan.corrupt_page_table(live[:, :], engine._n_pages)


# ---------------------------------------------------------- PoolAuditor
def test_auditor_passes_on_healthy_pool_and_samples(engine, lm_and_params):
    engine.reset()
    sched = Scheduler(engine, fault_policy=_fast_policy())
    sched.run(_stream())
    report = sched.auditor.audit(engine)
    assert report["pages_in_use"] == 0       # drained: everything back
    aud = PoolAuditor(every_n=2)
    assert aud.maybe_audit(engine) is None   # event 1: sampled out
    assert aud.maybe_audit(engine) is not None
    assert aud.audits == 1
    off = PoolAuditor(every_n=0)             # disabled
    assert off.maybe_audit(engine) is None


def test_auditor_detects_leak_and_double_free(engine):
    engine.reset()
    auditor = PoolAuditor()
    page = engine.pool.alloc()
    try:
        # refcount 1 but NO table/prefix entry references it: a leak
        with pytest.raises(PoolInvariantError, match="LEAKED"):
            auditor.audit(engine)
    finally:
        engine.pool.release([page])
    auditor.audit(engine)                    # healthy again
    # a slot's table references a page whose refcount was dropped
    # behind the allocator's back: dangling/double-free
    engine.prefill_chunk(0, [1, 2, 3], 0)
    held = int(engine._page_table[0, 0])
    engine.pool.refcount[held] -= 1
    engine.pool._free.append(held)
    try:
        with pytest.raises(PoolInvariantError, match="dangling|DOUBLE"):
            auditor.audit(engine)
    finally:
        engine.pool._free.remove(held)
        engine.pool.refcount[held] += 1
    engine.release_slot(0)
    auditor.audit(engine)


def test_auditor_detects_corrupted_debug_copy(engine):
    engine.reset()
    engine.prefill_chunk(0, [4, 5, 6], 0)
    table, n_pages = engine.page_table_snapshot()
    FaultPlan().corrupt_page_table(table, n_pages, slot=0, value=-7)
    with pytest.raises(PoolInvariantError, match="outside the"):
        PoolAuditor().audit(engine, page_table=table, n_pages=n_pages)
    # the live tables were untouched: the real audit still passes
    PoolAuditor().audit(engine)
    engine.release_slot(0)


# ------------------------------------------------------ non-finite guard
def test_decode_nonfinite_guard_is_per_slot(lm_and_params):
    """A NaN bias into slot 1's logits flags ONLY slot 1, and slot 0's
    token is bitwise identical to the bias-free step (the +0.0 rows are
    value-identical — healthy batchmates never see the fault). Two
    engines built identically (same params/seed/geometry) run the same
    step, one clean and one injected — the comparison crosses two
    traces of the same program."""
    e1 = _mk_engine(lm_and_params)
    e2 = _mk_engine(lm_and_params)
    for e in (e1, e2):
        e.prefill_chunked(0, [3, 1, 4, 1, 5])
        e.prefill_chunked(1, [9, 2, 6, 5])
    clean = e1.decode_step([7, 8], [True, True], [0.0, 0.0])
    assert e1.last_decode_finite.tolist() == [True, True]
    assert e1.nonfinite_events == 0
    bad = e2.decode_step([7, 8], [True, True], [0.0, 0.0],
                         fault_bias=[0.0, float("nan")])
    assert e2.last_decode_finite.tolist() == [True, False]
    assert int(bad[0]) == int(clean[0])
    assert e2.nonfinite_events == 1
    with pytest.raises(ValueError, match="fault_bias"):
        e2.decode_step([7, 8], [True, True], [0.0, 0.0],
                       fault_bias=[0.0, 0.0, 0.0])


def test_nan_params_engine_fails_typed_and_survives(lm_and_params):
    """REAL non-finite logits (a NaN-poisoned weight) exercise the
    in-program guard end-to-end: every request lands in the typed
    FAILED terminal state, nothing crashes, the pool drains clean."""
    m, params = lm_and_params
    poisoned = jax.tree_util.tree_map(
        lambda x: (x.at[(0,) * x.ndim].set(float("nan"))
                   if jnp.issubdtype(x.dtype, jnp.floating) else x),
        params)
    reg = telemetry.MetricsRegistry()
    eng = Engine(m, poisoned, slots=2, max_len=64, prefill_len=24,
                 chunk_len=CHUNK, registry=reg,
                 policy=resolve_policy("O0", verbose=False))
    sched = Scheduler(eng, registry=reg,
                      fault_policy=_fast_policy(max_retries=1))
    reqs = _stream()
    done = sched.run(reqs)
    assert len(done) == len(reqs)
    assert all(r.status is RequestStatus.FAILED for r in reqs)
    assert all(r.status.terminal for r in reqs)
    assert all(r.finish_reason == "fault" for r in reqs)
    assert all(r.retries == 2 for r in reqs)     # max_retries + final
    assert all("non-finite" in r.error for r in reqs)
    snap = reg.snapshot()
    assert snap["counters"]["serving.requests.failed"] == len(reqs)
    assert snap["counters"]["serving.faults.nonfinite"] > 0
    assert snap["counters"]["serving.faults.requeued"] == len(reqs)
    assert sched.auditor.audit(eng)["pages_in_use"] == 0


# ------------------------------------------------------- the chaos pin
def test_chaos_pin_unfaulted_requests_bitwise_and_zero_leaks(engine):
    """THE acceptance pin: a seeded plan mixing non-finite logits,
    transient chunk/decode exceptions and a heartbeat stall — every
    un-faulted request bitwise-matches the fault-free run (same engine,
    same compiled programs), every faulted request reaches a typed
    terminal status, zero new programs trace, zero pages leak."""
    engine.reset()
    sched0 = Scheduler(engine, fault_policy=_fast_policy())
    clean_reqs = _stream()
    sched0.run(clean_reqs)
    clean = [list(r.output_tokens) for r in clean_reqs]
    traces0 = (engine.chunk_traces, engine.decode_traces)

    engine.reset()
    stalls = []
    plan = FaultPlan([
        FaultSpec(kind="stall", tick=1, stall_s=0.03),
        FaultSpec(kind="exception", tick=2, site="chunk"),
        FaultSpec(kind="nonfinite", tick=3, slot=0),
        FaultSpec(kind="exception", tick=6, site="decode", slot=1),
    ])
    policy = _fast_policy(max_retries=1, watchdog_budget_s=0.02,
                          on_stall=stalls.append)
    reg = telemetry.MetricsRegistry()
    engine.set_registry(reg)    # the engine owns the nonfinite counter
    sched = Scheduler(engine, registry=reg, fault_policy=policy,
                      fault_plan=plan)
    reqs = _stream()
    try:
        done = sched.run(reqs)
    finally:
        engine.set_registry(None)
    assert len(done) == len(reqs)
    # every injected fault actually landed on a live request
    assert plan.stats()["injected_nonfinite"] == 1
    assert plan.stats()["injected_exceptions"] == 2
    faulted = [r for r in reqs if r.retries > 0
               or r.status is RequestStatus.FAILED]
    assert len(faulted) >= 2, "the plan must actually fault requests"
    for r in reqs:
        assert r.status.terminal
        assert r.status in (RequestStatus.FINISHED, RequestStatus.FAILED)
    # the headline: un-faulted requests are bitwise identical
    for i, r in enumerate(reqs):
        if r.retries == 0 and r.status is RequestStatus.FINISHED:
            assert list(r.output_tokens) == clean[i], \
                f"clean request {i} diverged under chaos"
    # greedy retried-to-completion requests reproduce the clean tokens
    # too (a retry is a full cold restart through the same programs)
    for i, r in enumerate(reqs):
        if r.retries and r.status is RequestStatus.FINISHED:
            assert list(r.output_tokens) == clean[i]
    # containment added ZERO compiled programs
    assert (engine.chunk_traces, engine.decode_traces) == traces0
    # watchdog saw the injected stall; auditor sees zero leaks at drain
    assert plan.stats()["injected_stalls"] == 1
    assert len(stalls) >= 1
    snap = reg.snapshot()
    assert snap["counters"]["serving.watchdog.stall"] >= 1
    assert snap["histograms"]["serving.watchdog.stall_s"]["count"] >= 1
    assert snap["counters"]["serving.faults.transient"] == 2
    assert snap["counters"]["serving.faults.nonfinite"] >= 1
    assert sched.auditor.audit(engine)["pages_in_use"] == 0
    engine.reset()


# ------------------------------------------------- policy + scheduler
def test_failed_terminal_after_max_retries_reclaims_pages(engine):
    engine.reset()
    # every chunk call fails: the victim can never prefill
    plan = FaultPlan([FaultSpec(kind="exception", tick=t, site="chunk")
                      for t in range(64)])
    sched = Scheduler(engine, fault_policy=_fast_policy(max_retries=2),
                      fault_plan=plan)
    (r,) = sched.run([Request(prompt=[1, 2, 3], max_new_tokens=4)])
    assert r.status is RequestStatus.FAILED
    assert r.finish_reason == "fault" and r.retries == 3
    assert "InjectedFault" in r.error
    assert sched.auditor.audit(engine)["pages_in_use"] == 0
    # the engine is not poisoned: a clean follow-up run serves fine
    sched2 = Scheduler(engine, fault_policy=_fast_policy())
    (ok,) = sched2.run([Request(prompt=[1, 2, 3], max_new_tokens=4)])
    assert ok.status is RequestStatus.FINISHED
    engine.reset()


def test_backoff_schedule_and_eligibility(engine):
    pol = FaultPolicy(backoff_base_s=0.1, backoff_cap_s=0.3)
    assert pol.backoff_s(1) == pytest.approx(0.1)
    assert pol.backoff_s(2) == pytest.approx(0.2)
    assert pol.backoff_s(3) == pytest.approx(0.3)   # capped
    assert pol.backoff_s(9) == pytest.approx(0.3)
    assert FaultPolicy(backoff_base_s=0.0).backoff_s(5) == 0.0
    # a backing-off request is not admitted before its horizon, and it
    # never blocks an eligible request behind it
    engine.reset()
    sched = Scheduler(engine, fault_policy=_fast_policy())
    blocked = Request(prompt=[1, 2], max_new_tokens=2)
    eligible = Request(prompt=[3, 4], max_new_tokens=2)
    sched.submit(blocked)
    sched.submit(eligible)
    blocked._not_before = time.perf_counter() + 60.0
    sched.step()
    assert blocked.status is RequestStatus.QUEUED
    assert eligible.status.terminal or \
        eligible.status in (RequestStatus.PREFILLING,
                            RequestStatus.RUNNING)
    blocked._not_before = None      # horizon cleared: admits normally
    while sched.pending:
        sched.step()
    assert blocked.status is RequestStatus.FINISHED
    engine.reset()


def test_queue_full_carries_retry_after_hint(engine):
    engine.reset()
    sched = Scheduler(engine, max_queue=1,
                      fault_policy=_fast_policy())
    # before any decode step there is nothing honest to say
    sched.submit(Request(prompt=[1], max_new_tokens=2))
    with pytest.raises(QueueFull) as e0:
        sched.submit(Request(prompt=[2], max_new_tokens=2))
    assert e0.value.retry_after_s is None
    while sched.pending:
        sched.step()
    # after measured decode steps the hint is throughput-derived
    sched.submit(Request(prompt=[1], max_new_tokens=64))
    sched.step()
    sched.submit(Request(prompt=[2], max_new_tokens=2))
    with pytest.raises(QueueFull) as e1:
        sched.submit(Request(prompt=[3], max_new_tokens=2))
    assert e1.value.retry_after_s is not None
    assert e1.value.retry_after_s > 0
    assert "retry_after_s" in str(e1.value)
    while sched.pending:
        sched.step()
    engine.reset()


def test_status_enum_is_consistent_across_records_and_telemetry(engine):
    """The satellite pin: ONE status vocabulary. Request.status is the
    typed enum, the serving.request record carries its value, and the
    terminal counters (completed/timeout/failed) map onto it."""
    engine.reset()
    reg = telemetry.MetricsRegistry()
    sched = Scheduler(engine, registry=reg,
                      fault_policy=_fast_policy(),
                      default_timeout_s=0.0)
    expired = sched.submit(Request(prompt=[1, 2], max_new_tokens=4))
    time.sleep(0.01)
    sched.step()
    assert expired.status is RequestStatus.EXPIRED
    assert expired.status.terminal and expired.status == "expired"
    sched2 = Scheduler(engine, registry=reg,
                       fault_policy=_fast_policy())
    (fin,) = sched2.run([Request(prompt=[1, 2], max_new_tokens=2)])
    assert fin.status is RequestStatus.FINISHED
    for st in (RequestStatus.QUEUED, RequestStatus.PREFILLING,
               RequestStatus.RUNNING):
        assert not st.terminal
    recs = {rec["uid"]: rec for rec in reg.records
            if rec.get("tag") == "serving.request"}
    assert recs[expired.uid]["status"] == "expired"
    assert recs[fin.uid]["status"] == "finished"
    assert recs[fin.uid]["retries"] == 0
    snap = reg.snapshot()
    assert snap["counters"]["serving.requests.timeout"] == 1
    assert snap["counters"]["serving.requests.completed"] == 1
    engine.reset()


def test_watchdog_flags_slow_heartbeats_only_over_budget(engine):
    engine.reset()
    # warm the programs so trace time doesn't trip the tiny budget
    Scheduler(engine, fault_policy=_fast_policy()).run(
        [Request(prompt=[5, 6], max_new_tokens=2)])
    engine.reset()
    stalls = []
    reg = telemetry.MetricsRegistry()
    plan = FaultPlan([FaultSpec(kind="stall", tick=1, stall_s=0.2)])
    sched = Scheduler(
        engine, registry=reg, fault_plan=plan,
        fault_policy=_fast_policy(watchdog_budget_s=0.15,
                                  on_stall=stalls.append))
    sched.run([Request(prompt=[5, 6], max_new_tokens=8)])
    assert len(stalls) == 1 and stalls[0] > 0.15
    snap = reg.snapshot()
    assert snap["counters"]["serving.watchdog.stall"] == 1
    assert snap["histograms"]["serving.watchdog.stall_s"]["count"] == 1
    engine.reset()


def test_watchdog_warm_start_exempts_tracing_ticks(lm_and_params):
    """The warm-start regression (PR 7 NOTE): the first heartbeat on a
    COLD engine traces compiled programs, so a tiny
    ``watchdog_budget_s`` used to false-trip on tick 0 before the
    engine had done anything wrong. Tracing ticks are now exempt and
    separately accounted as ``serving.watchdog.warmup_s``: with a
    budget every tick must breach, stalls + warm-ups partition the run
    exactly, and the ticks that traced never counted as stalls."""
    eng = _mk_engine(lm_and_params, seed=9)     # cold: nothing traced
    assert eng.compiled_programs == 0
    stalls = []
    reg = telemetry.MetricsRegistry()
    sched = Scheduler(
        eng, registry=reg,
        fault_policy=_fast_policy(watchdog_budget_s=1e-9,
                                  on_stall=stalls.append))
    steps = 0
    sched.submit(Request(prompt=[5, 6, 7], max_new_tokens=4))
    while sched.pending:
        sched.step()
        steps += 1
    snap = reg.snapshot()
    warmups = snap["histograms"]["serving.watchdog.warmup_s"]["count"]
    stalls_n = snap["counters"].get("serving.watchdog.stall", 0)
    # tick 0 traced the chunk AND decode programs (the final chunk
    # flips the slot to decoding within the same heartbeat)
    assert warmups >= 1, "tracing ticks were not accounted as warm-up"
    # every tick either warmed or breached the (impossible) budget —
    # and the tracing ticks are exactly the ones that did NOT stall
    assert warmups + stalls_n == steps
    assert len(stalls) == stalls_n
    # a warmed engine stops producing warm-up ticks: one more request,
    # same scheduler — every subsequent tick breaches instead
    sched.submit(Request(prompt=[5, 6, 7], max_new_tokens=2))
    more = 0
    while sched.pending:
        sched.step()
        more += 1
    snap = reg.snapshot()
    assert snap["histograms"]["serving.watchdog.warmup_s"]["count"] \
        == warmups, "a warm engine must not keep claiming warm-up"
    assert snap["counters"]["serving.watchdog.stall"] == stalls_n + more


# ------------------------------------------------------------- the soak
@pytest.mark.slow
def test_chaos_soak_pool_exhaustion_prefix_eviction_zero_leaks(
        lm_and_params):
    """Several hundred randomized heartbeats of a seeded FaultPlan over
    a deliberately small pool with prefix retention on — admissions
    block on exhaustion, prefix entries evict under pressure, faults
    quarantine/requeue/fail throughout — and at every audit point and
    at drain: zero leaked pages, zero double-frees; clean requests'
    tokens bitwise-match the fault-free pass."""
    # a pool sized for ~2.5 in-flight worst cases: exhaustion is the
    # common case, so admission blocking + LRU prefix eviction are
    # exercised constantly
    def mk():
        return _mk_engine(lm_and_params, slots=3, pool=2,
                          num_pages=2 * (64 // CHUNK) + 5)

    rng = np.random.default_rng(11)
    shared = list(rng.integers(1, VOCAB, size=CHUNK * 2))

    def stream():
        out = []
        r2 = np.random.default_rng(12)
        for i in range(24):
            if i % 3:
                prompt = shared + list(r2.integers(1, VOCAB, size=int(
                    r2.integers(1, 8))))
            else:
                prompt = list(r2.integers(1, VOCAB, size=int(
                    r2.integers(1, 20))))
            out.append(Request(prompt=prompt,
                               max_new_tokens=int(r2.integers(1, 10))))
        return out

    def serve(engine, plan):
        policy = _fast_policy(max_retries=2)
        sched = Scheduler(engine, max_queue=64, retain_prefixes=True,
                          fault_policy=policy, fault_plan=plan)
        reqs = stream()
        feed = iter(reqs)
        fed = 0
        for tick in range(600):
            if tick % 2 == 0:
                r = next(feed, None)
                if r is not None:
                    sched.submit(r)
                    fed += 1
            sched.step()
            if fed == len(reqs) and not sched.pending:
                break
        assert not sched.pending, "soak failed to drain in 600 ticks"
        return reqs, sched

    clean_engine = mk()
    clean_reqs, _ = serve(clean_engine, None)
    assert all(r.status is RequestStatus.FINISHED for r in clean_reqs)

    chaos_engine = mk()
    plan = FaultPlan.random(7, 600, slots=3, nonfinite_rate=0.04,
                            exception_rate=0.04, stall_rate=0.01,
                            stall_s=0.001)
    chaos_reqs, sched = serve(chaos_engine, plan)
    injected = plan.stats()
    assert injected["injected_nonfinite"] \
        + injected["injected_exceptions"] > 0, \
        "the soak must actually inject faults"
    mismatches = 0
    for i, r in enumerate(chaos_reqs):
        assert r.status.terminal
        if r.retries == 0 and r.status is RequestStatus.FINISHED:
            if list(r.output_tokens) \
                    != list(clean_reqs[i].output_tokens):
                mismatches += 1
    assert mismatches == 0, \
        f"{mismatches} clean requests diverged under chaos"
    report = sched.auditor.audit(chaos_engine)     # raises on any leak
    # at drain only prefix-entry pages may remain resident
    held = sum(len(p) for p in
               chaos_engine.prefix_cache.page_holds())
    assert report["pages_in_use"] == held
    chaos_engine.reset(clear_prefixes=True)
    assert sched.auditor.audit(chaos_engine)["pages_in_use"] == 0


@pytest.mark.slow
def test_sharded_engine_chaos_quarantine_frees_pages_on_every_shard(
        lm_and_params):
    """The tensor-parallel satellite's containment case: on an
    Engine(mesh=<2 shards>) the same seeded chaos plan — non-finite
    logits and transient chunk/decode exceptions — quarantines only its
    victims, un-faulted requests stay bitwise identical to the sharded
    fault-free run, and every quarantine's page release drains the ONE
    host-side pool whose pages back all shards at heads/tp width: a
    page freed is freed on every shard by construction, and the auditor
    (which reconciles refcounts against the replicated page tables)
    proves zero leaks at drain."""
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs 2 virtual devices")
    mesh = Mesh(np.array(devs[:2]), ("tp",))
    # this module's shared VOCAB (101) is deliberately odd; the sharded
    # head needs vocab % tp == 0, so the case carries its own model
    m = TransformerLM(vocab_size=100, hidden=32, num_layers=2,
                      num_heads=4, max_seq_len=64)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    eng = Engine(m, params, slots=3, max_len=64, prefill_len=24,
                 chunk_len=CHUNK,
                 policy=resolve_policy("O0", verbose=False), seed=5,
                 mesh=mesh)
    assert eng.tp == 2

    def _stream100():
        rng = np.random.default_rng(1)
        return [Request(prompt=list(rng.integers(1, 100, size=n)),
                        max_new_tokens=b)
                for n, b in [(5, 8), (13, 6), (9, 5), (17, 4)]]

    eng.reset()
    clean_reqs = _stream100()
    Scheduler(eng, fault_policy=_fast_policy()).run(clean_reqs)
    clean = [list(r.output_tokens) for r in clean_reqs]
    traces0 = (eng.chunk_traces, eng.decode_traces)

    eng.reset()
    plan = FaultPlan([
        FaultSpec(kind="exception", tick=2, site="chunk"),
        FaultSpec(kind="nonfinite", tick=3, slot=0),
        FaultSpec(kind="exception", tick=6, site="decode", slot=1),
    ])
    reg = telemetry.MetricsRegistry()
    eng.set_registry(reg)
    sched = Scheduler(eng, registry=reg,
                      fault_policy=_fast_policy(max_retries=1),
                      fault_plan=plan)
    reqs = _stream100()
    try:
        done = sched.run(reqs)
    finally:
        eng.set_registry(None)
    assert len(done) == len(reqs)
    assert plan.stats()["injected_nonfinite"] == 1
    assert plan.stats()["injected_exceptions"] == 2
    faulted = [r for r in reqs if r.retries > 0
               or r.status is RequestStatus.FAILED]
    assert faulted, "the plan must actually fault requests"
    for i, r in enumerate(reqs):
        assert r.status.terminal
        if r.status is RequestStatus.FINISHED:
            assert list(r.output_tokens) == clean[i], \
                f"request {i} diverged under chaos on the sharded engine"
    # containment added ZERO compiled programs on the sharded engine
    assert (eng.chunk_traces, eng.decode_traces) == traces0
    snap = reg.snapshot()
    assert snap["counters"]["serving.faults.nonfinite"] >= 1
    # the tp gauges rode the same registry
    assert snap["gauges"]["serving.tp.shards"] == 2.0
    # zero leaked pages at drain — the heads-sharded pool's host
    # allocator is shard-agnostic, so this IS the every-shard claim
    assert sched.auditor.audit(eng)["pages_in_use"] == 0
    assert eng.pool.reserved_total == 0
