"""SLO-aware preemptive scheduling — priority classes, preempt-to-host
migration, deadline-aware admission (ISSUE 19), hermetic.

The acceptance bar, as tests:

- a preempted-then-resumed greedy request is **bitwise identical** to
  its uninterrupted run, across committed lengths below / at /
  straddling the chunk boundary, on the plain paged engine (resident
  COW retention) AND the host-tier engine (arena swap), at pipeline
  depth 0 and >= 1;
- N preempt/resume cycles on one request leak nothing: the
  :class:`~apex_tpu.serving.PoolAuditor` reconciles after every event,
  the host arena drains to zero records, and the stream stays bitwise;
- the full arrival-driven path: a high-priority arrival preempts
  exactly one strictly-lower victim (ties toward the newest submit),
  equal priority never preempts, and a decode whose committed stream
  outgrew the prefill re-ingest window is never a victim (it could not
  be resumed exactly);
- chaos (the satellite-1 bugfix): ``swap_corruption`` composed with
  preemption churn degrades the resume to a VERIFIED MISS — cold
  re-prefill of the committed stream, never a wrong token, never a
  leaked arena record; and a request rolled back WHILE preempted (the
  drain/quarantine path) clears its resume-ingest stream together with
  its outputs, so it re-enters as a fresh prompt instead of replaying
  a committed stream against a cleared output list (the silent
  wrong-token hazard);
- queue aging bounds starvation under a sustained high-priority flood;
- deadline-aware admission rejects unmeetable deadlines with a typed
  :class:`~apex_tpu.serving.DeadlineUnmeetable` (a ``QueueFull``
  subclass) carrying an honest EMA-derived ``retry_after_s``;
  accepted-then-blown deadlines are recorded honestly
  (``deadline_missed`` + per-class counters);
- tenant quotas cap concurrent slots per tenant (never below one) and
  the weighted-fair ledger admits the least-served tenant first;
- ``slo=None`` keeps the FIFO baseline verbatim: serving through it
  after heavy SLO/preemption churn compiles ZERO new programs and
  emits the identical token stream;
- ``SLOConfig`` pickles (it rides the fleet's wire frames);
  ``TenantLedger`` refuses loudly (process-local shared state).

Everything runs on CPU with a tiny model at policy O0 (exact fp32).
"""

import pickle
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (DeadlineUnmeetable, Engine, FaultPlan,
                              FaultSpec, PoolAuditor, QueueFull,
                              Request, RequestStatus, Scheduler,
                              SLOConfig, TenantLedger)

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

VOCAB = 101
CHUNK = 8
SLO = SLOConfig(classes={"batch": 0, "interactive": 10})


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
def engine_pair(lm_and_params):
    """One host-tier engine + one plain paged engine, identical
    geometry (jit caches warm across the module)."""
    return (_mk_engine(lm_and_params, host_tier=1 << 24),
            _mk_engine(lm_and_params))


def _oracle(engine, prompt, n_new):
    """``prompt`` served alone, uninterrupted, retention off — the
    bitwise reference stream."""
    engine.reset(clear_prefixes=True)
    (r,) = Scheduler(engine).run([Request(prompt=list(prompt),
                                          max_new_tokens=n_new)])
    assert r.status == "finished"
    return list(r.output_tokens)


def _step_until(sched, pred, limit=3000):
    for _ in range(limit):
        if pred():
            return
        sched.step()
    raise AssertionError("scheduler never reached the expected state")


# ------------------------------------------------------- the pure policy
def test_slo_config_arithmetic_and_pickle():
    cfg = SLOConfig(classes={"batch": 0, "interactive": 10},
                    aging_s=0.5, tenant_weights={"a": 2.0},
                    tenant_max_share=0.5)
    assert pickle.loads(pickle.dumps(cfg)) == cfg     # rides the wire
    r = Request(prompt=[1], max_new_tokens=1, slo_class="interactive",
                priority=3)
    assert cfg.base_priority(r) == 13          # class base + own field
    assert cfg.base_priority(Request(prompt=[1], max_new_tokens=1)) == 0
    with pytest.raises(ValueError, match="unknown slo_class"):
        cfg.base_priority(Request(prompt=[1], max_new_tokens=1,
                                  slo_class="platinum"))
    # aging: +1 per full aging_s since the ORIGINAL submit
    b = Request(prompt=[1], max_new_tokens=1, slo_class="batch")
    b._t_submit = 100.0
    assert cfg.effective_priority(b, 100.4) == 0
    assert cfg.effective_priority(b, 101.7) == 3
    assert cfg.top_priority == 10


def test_tenant_ledger_wfq_and_pickle_refusal():
    led = TenantLedger({"heavy": 2.0, "zero": 0.0})
    assert led.weight("heavy") == 2.0
    assert led.weight("unknown") == 1.0
    assert led.weight("zero") == 1.0           # guard: never divide by 0
    led.charge("heavy", 100)
    led.charge("light", 50)
    assert led.virtual_served("heavy") == 50.0   # 100 / weight 2
    assert led.virtual_served("light") == 50.0   # same virtual service
    assert led.tokens_served("heavy") == 100
    snap = led.snapshot()
    assert snap["heavy"] == {"tokens": 100, "virtual": 50.0,
                             "weight": 2.0}
    with pytest.raises(TypeError, match="process-local"):
        pickle.dumps(led)


def test_scheduler_slo_validation(engine_pair):
    _, ep = engine_pair
    with pytest.raises(ValueError, match="retain_prefixes"):
        Scheduler(ep, retain_prefixes=False, slo=SLO)
    # priority-only scheduling works without preemption machinery
    Scheduler(ep, slo=SLOConfig(preempt=False))
    sched = Scheduler(ep, retain_prefixes=True, slo=SLO)
    with pytest.raises(ValueError, match="unknown slo_class"):
        sched.submit(Request(prompt=[1, 2], max_new_tokens=1,
                             slo_class="platinum"))


# ------------------------------------------------ bitwise preempt/resume
@pytest.mark.parametrize("depth", [0, 1], ids=["sync", "pipelined"])
@pytest.mark.parametrize("tiered", [False, True],
                         ids=["paged", "host-tier"])
@pytest.mark.parametrize("n,k", [(5, 2), (11, 6), (11, 3)],
                         ids=["below-chunk", "at-chunk", "straddling"])
def test_preempt_resume_bitwise(engine_pair, tiered, depth, n, k):
    """The tentpole pin: preempt at a controlled committed length
    (below / at / straddling the chunk boundary), resume, and the
    greedy stream is IDENTICAL to the uninterrupted run — plain paged
    and host-tier, sync and dispatch-ahead."""
    engine = engine_pair[0] if tiered else engine_pair[1]
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(1, VOCAB, size=n)]
    oracle = _oracle(engine, prompt, 12)

    committed = n + k - 1      # the last sampled token's K/V is pending
    if n == 5:
        assert committed < CHUNK
    elif k == 6:
        assert committed % CHUNK == 0
    else:
        assert committed > CHUNK and committed % CHUNK != 0

    engine.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    engine.set_registry(reg)
    aud = PoolAuditor(every_n=1)
    try:
        sched = Scheduler(engine, retain_prefixes=True, slo=SLO,
                          pipeline_depth=depth, registry=reg,
                          auditor=aud)
        r = Request(prompt=list(prompt), max_new_tokens=12,
                    slo_class="batch")
        sched.submit(r)
        _step_until(sched, lambda: len(r.output_tokens) == k
                    and r.status == "running")
        sched._preempt(sched._running.index(r))
        assert r.status is RequestStatus.PREEMPTED
        assert r.preemptions == 1
        assert len(r.output_tokens) == k       # committed work survives
        _step_until(sched, lambda: r.status.terminal)
        assert r.status == "finished"
        assert list(r.output_tokens) == oracle, \
            "preempt/resume drifted from the uninterrupted stream"
        counters = reg.snapshot()["counters"]
        assert counters.get("serving.preempt.preemptions") == 1
        assert counters.get("serving.preempt.resumes") == 1
        aud.audit(engine)
        if tiered:
            assert engine.host_tier.size == 0, "leaked arena record"
    finally:
        engine.set_registry(None)


def test_preempt_resume_churn_leak_free(engine_pair):
    """Satellite: N preempt/resume cycles on ONE request — audited
    after every event, zero leaked pages or arena records, and the
    stream still bitwise."""
    engine, _ = engine_pair
    rng = np.random.default_rng(9)
    prompt = [int(t) for t in rng.integers(1, VOCAB, size=6)]
    oracle = _oracle(engine, prompt, 12)

    engine.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    engine.set_registry(reg)
    aud = PoolAuditor(every_n=1)
    try:
        sched = Scheduler(engine, retain_prefixes=True, slo=SLO,
                          registry=reg, auditor=aud)
        r = Request(prompt=list(prompt), max_new_tokens=12,
                    slo_class="batch")
        sched.submit(r)
        for cycle, k in enumerate((2, 4, 6, 8), start=1):
            _step_until(sched, lambda: len(r.output_tokens) >= k
                        and r.status == "running")
            sched._preempt(sched._running.index(r))
            assert r.preemptions == cycle
        _step_until(sched, lambda: r.status.terminal)
        assert r.status == "finished"
        assert list(r.output_tokens) == oracle
        counters = reg.snapshot()["counters"]
        assert counters.get("serving.preempt.preemptions") == 4
        assert counters.get("serving.preempt.resumes") == 4
        aud.audit(engine)
        assert engine.host_tier.size == 0, \
            "a re-preempted request left a stale arena record behind"
    finally:
        engine.set_registry(None)


def test_arrival_driven_preemption_victim_order(engine_pair):
    """The full admission path: an interactive arrival finds both
    slots held by batch work and preempts EXACTLY ONE victim — the
    newest-submitted equal-priority one (least sunk wait) — and all
    three streams finish bitwise."""
    engine, _ = engine_pair
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(1, VOCAB, size=sz)]
               for sz in (11, 13, 9)]
    oracles = [_oracle(engine, p, 10) for p in prompts]

    engine.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    engine.set_registry(reg)
    try:
        sched = Scheduler(engine, retain_prefixes=True, slo=SLO,
                          registry=reg, auditor=PoolAuditor(every_n=1))
        b0 = Request(prompt=list(prompts[0]), max_new_tokens=10,
                     slo_class="batch")
        b1 = Request(prompt=list(prompts[1]), max_new_tokens=10,
                     slo_class="batch")
        hi = Request(prompt=list(prompts[2]), max_new_tokens=10,
                     slo_class="interactive")
        sched.submit(b0)
        sched.submit(b1)
        _step_until(sched, lambda: b0.status == "running"
                    and b1.status == "running"
                    and len(b1.output_tokens) >= 2)
        sched.submit(hi)
        sched.step()
        assert b1.preemptions == 1 and b0.preemptions == 0, \
            "the newest-submitted equal-priority victim must go"
        assert hi.status in ("prefilling", "running")
        _step_until(sched, lambda: all(r.status.terminal
                                       for r in (b0, b1, hi)))
        for r, want in zip((b0, b1, hi), oracles):
            assert list(r.output_tokens) == want
        assert reg.snapshot()["counters"].get(
            "serving.preempt.preemptions") == 1
        PoolAuditor().audit(engine)
    finally:
        engine.set_registry(None)


def test_deep_decode_is_not_preemptible(engine_pair):
    """The resumability window: once a victim's committed stream
    (prompt + outputs) outgrows prefill_len it cannot be re-ingested
    exactly, so preemption SKIPS it (and ``preemptible_pages`` stops
    counting it) — the arrival waits for a natural slot instead of
    corrupting a resume."""
    engine, _ = engine_pair
    rng = np.random.default_rng(11)
    deep = [[int(t) for t in rng.integers(1, VOCAB, size=20)]
            for _ in range(2)]

    engine.reset(clear_prefixes=True)
    sched = Scheduler(engine, retain_prefixes=True, slo=SLO)
    bs = [Request(prompt=list(p), max_new_tokens=10, slo_class="batch")
          for p in deep]
    for r in bs:
        sched.submit(r)
    # past the window: 20 prompt + 5 outputs = 25 > prefill_len=24
    _step_until(sched, lambda: all(r.status == "running"
                                   and len(r.output_tokens) >= 5
                                   for r in bs))
    assert sched.load_snapshot()["preemptible_pages"] == 0
    hi = Request(prompt=[1, 2, 3], max_new_tokens=4,
                 slo_class="interactive")
    sched.submit(hi)
    sched.step()
    assert all(r.preemptions == 0 for r in bs), \
        "a decode past the re-ingest window must never be preempted"
    assert hi.status == "queued"
    _step_until(sched, lambda: all(r.status.terminal
                                   for r in bs + [hi]))
    assert all(r.status == "finished" for r in bs + [hi])
    PoolAuditor().audit(engine)


def test_load_snapshot_slo_fields(engine_pair):
    """The v2 snapshot columns: None/None without an SLO config;
    with one, ``preemptible_pages`` counts below-top running pages
    inside the resumability window and ``oldest_deadline_s`` is the
    tightest RELATIVE remaining deadline."""
    engine, _ = engine_pair
    engine.reset(clear_prefixes=True)
    fifo = Scheduler(engine, retain_prefixes=True)
    snap = fifo.load_snapshot()
    assert snap["oldest_deadline_s"] is None
    assert snap["preemptible_pages"] is None

    engine.reset(clear_prefixes=True)
    sched = Scheduler(engine, retain_prefixes=True,
                      slo=SLOConfig(classes={"batch": 0,
                                             "interactive": 10},
                                    deadline_admission=False))
    snap = sched.load_snapshot()
    assert snap["oldest_deadline_s"] is None    # nothing live
    assert snap["preemptible_pages"] == 0       # paged, SLO on, idle
    r = Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=8,
                slo_class="batch", deadline_s=30.0)
    sched.submit(r)
    _step_until(sched, lambda: r.status == "running")
    snap = sched.load_snapshot()
    assert snap["preemptible_pages"] >= 1       # its pages reclaimable
    assert 0 < snap["oldest_deadline_s"] <= 30.0
    _step_until(sched, lambda: r.status.terminal)


# --------------------------------------------------- deadline admission
def test_deadline_admission_rejects_with_honest_hint(engine_pair):
    engine, _ = engine_pair
    engine.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    sched = Scheduler(engine, retain_prefixes=True, slo=SLO,
                      registry=reg)
    # no EMA yet: the door cannot estimate, so it must admit
    ok = Request(prompt=[1, 2, 3], max_new_tokens=2, slo_class="batch",
                 deadline_s=1e-6)
    sched.submit(ok)
    _step_until(sched, lambda: ok.status.terminal)
    assert sched._step_s_ema is not None
    # saturate the queue so the estimate has positions ahead
    backlog = [Request(prompt=[int(t) for t in range(1, 9)],
                       max_new_tokens=8, slo_class="batch")
               for _ in range(4)]
    for r in backlog:
        sched.submit(r)
    ema, depth = sched._step_s_ema, len(sched._queue)
    tight = Request(prompt=[1, 2, 3, 4], max_new_tokens=8,
                    slo_class="interactive", deadline_s=1e-9)
    with pytest.raises(DeadlineUnmeetable) as ei:
        sched.submit(tight)
    assert isinstance(ei.value, QueueFull)      # rides the same channel
    # retry_after_s is rounded to microseconds before it rides the
    # exception (it is user-facing wire payload)
    assert ei.value.retry_after_s == pytest.approx(
        ema * max(1, depth), abs=5e-7)
    assert ei.value.retry_after_s > 0
    assert reg.snapshot()["counters"].get(
        "serving.slo.deadline_rejected") == 1
    # a meetable deadline admits
    sched.submit(Request(prompt=[1, 2], max_new_tokens=2,
                         slo_class="interactive", deadline_s=60.0))
    _step_until(sched, lambda: all(r.status.terminal for r in backlog))


def test_deadline_missed_verdict_is_honest(engine_pair):
    engine, _ = engine_pair
    engine.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    sched = Scheduler(engine, retain_prefixes=True, registry=reg,
                      slo=SLOConfig(classes={"batch": 0},
                                    deadline_admission=False))
    r = Request(prompt=[1, 2, 3], max_new_tokens=3, slo_class="batch",
                deadline_s=1e-9)
    sched.submit(r)
    _step_until(sched, lambda: r.status.terminal)
    assert r.status == "finished" and r.deadline_missed is True
    counters = reg.snapshot()["counters"]
    assert counters.get("serving.slo.deadline_missed") == 1
    assert counters.get("serving.slo.class.batch.deadline_missed") == 1
    assert counters.get("serving.slo.class.batch.completed") == 1


# ------------------------------------------------------ tenant fairness
def test_tenant_quota_caps_concurrency(engine_pair):
    engine, _ = engine_pair
    engine.reset(clear_prefixes=True)
    slo = SLOConfig(classes={"batch": 0}, tenant_max_share=0.5,
                    deadline_admission=False)
    sched = Scheduler(engine, retain_prefixes=True, slo=slo)
    a1 = Request(prompt=[1, 2, 3], max_new_tokens=8, slo_class="batch",
                 tenant="a")
    a2 = Request(prompt=[4, 5, 6], max_new_tokens=8, slo_class="batch",
                 tenant="a")
    b = Request(prompt=[7, 8, 9], max_new_tokens=8, slo_class="batch",
                tenant="b")
    for r in (a1, a2, b):                       # a2 submitted BEFORE b
        sched.submit(r)
    _step_until(sched, lambda: sum(q is not None
                                   for q in sched._running) == 2)
    held = {q.tenant for q in sched._running if q is not None}
    assert held == {"a", "b"}, \
        "the 0.5-share quota (1 of 2 slots) must hold tenant a to one"
    _step_until(sched, lambda: all(r.status.terminal
                                   for r in (a1, a2, b)))
    assert all(r.status == "finished" for r in (a1, a2, b))


def test_weighted_fair_admission_order(engine_pair):
    """Among equal-priority candidates the LEAST-served tenant admits
    first: pre-charging tenant a pushes its request behind tenant b's
    even though a's was submitted earlier."""
    engine, _ = engine_pair
    engine.reset(clear_prefixes=True)
    ledger = TenantLedger({"a": 2.0})
    ledger.charge("a", 1000)                   # virtual 500 owed-less
    slo = SLOConfig(classes={"batch": 0}, deadline_admission=False)
    sched = Scheduler(engine, retain_prefixes=True, slo=slo,
                      tenant_ledger=ledger)
    blockers = [Request(prompt=[1, 2, 3], max_new_tokens=4,
                        slo_class="batch"),
                Request(prompt=[4, 5, 6], max_new_tokens=12,
                        slo_class="batch")]
    for r in blockers:
        sched.submit(r)
    _step_until(sched, lambda: all(r.status == "running"
                                   for r in blockers))
    ra = Request(prompt=[7, 8], max_new_tokens=2, slo_class="batch",
                 tenant="a")
    rb = Request(prompt=[9, 10], max_new_tokens=2, slo_class="batch",
                 tenant="b")
    sched.submit(ra)                           # a first in FIFO order
    sched.submit(rb)
    _step_until(sched, lambda: ra.status != "queued"
                or rb.status != "queued")
    assert rb.status != "queued" and ra.status == "queued", \
        "WFQ must admit the owed-more tenant first, not FIFO"
    _step_until(sched, lambda: all(r.status.terminal
                                   for r in blockers + [ra, rb]))
    # finish-time charging reached the shared ledger, weighted
    assert ledger.tokens_served("b") == len(rb.output_tokens)
    assert ledger.virtual_served("b") == float(len(rb.output_tokens))
    assert ledger.tokens_served("a") == 1000 + len(ra.output_tokens)


# -------------------------------------------------------- aging (starvation)
def test_aging_bounds_starvation_under_flood(engine_pair):
    """A batch request under a sustained interactive flood: strict
    priority alone would starve it indefinitely (fresh priority-10
    arrivals always outrank priority 0); the aging boost (+1 per
    aging_s queued) lifts it past the flood and it finishes WHILE the
    flood is still arriving."""
    engine, _ = engine_pair
    engine.reset(clear_prefixes=True)
    slo = SLOConfig(classes={"batch": 0, "interactive": 10},
                    aging_s=0.02, deadline_admission=False)
    sched = Scheduler(engine, retain_prefixes=True, slo=slo,
                      max_queue=8)
    rng = np.random.default_rng(21)
    batch = Request(prompt=[int(t) for t in rng.integers(1, VOCAB,
                                                         size=6)],
                    max_new_tokens=4, slo_class="batch")
    sched.submit(batch)
    flood_done = 0
    live = []
    deadline = time.perf_counter() + 30.0
    while not batch.status.terminal:
        assert time.perf_counter() < deadline, \
            "batch request starved: aging never lifted it past the flood"
        while len(sched._queue) < 4:
            r = Request(prompt=[int(t) for t in rng.integers(
                1, VOCAB, size=4)], max_new_tokens=2,
                slo_class="interactive")
            sched.submit(r)
            live.append(r)
        sched.step()
        flood_done = sum(r.status.terminal for r in live)
    assert batch.status == "finished"
    assert flood_done >= 5, \
        "the flood never actually contended — the pin proves nothing"
    _step_until(sched, lambda: all(r.status.terminal for r in live),
                limit=20000)
    PoolAuditor().audit(engine)


# ----------------------------------------------------------------- chaos
def test_swap_corruption_during_preemption_chaos(engine_pair):
    """Satellite 1, half one: arena bytes corrupted while a request
    sits PREEMPTED make its resume a VERIFIED MISS — the committed
    stream re-prefills cold (never a wrong token), the corrupt record
    is dropped (never leaked), and the pool audits clean.

    The prompt fits one chunk so prefill registers NO resident prefix
    of its own — the preempt-export's arena record is the only thing
    that can back the resume, which is exactly what the corruption
    must hit (a longer prompt resumes warm off its resident prompt
    entry and the arena copy is released unused)."""
    engine, _ = engine_pair
    rng = np.random.default_rng(17)
    prompt = [int(t) for t in rng.integers(1, VOCAB, size=7)]
    oracle = _oracle(engine, prompt, 12)

    engine.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    engine.set_registry(reg)
    try:
        sched = Scheduler(engine, retain_prefixes=True, slo=SLO,
                          registry=reg, auditor=PoolAuditor(every_n=1))
        r = Request(prompt=list(prompt), max_new_tokens=12,
                    slo_class="batch")
        sched.submit(r)
        _step_until(sched, lambda: len(r.output_tokens) == 4
                    and r.status == "running")
        sched._preempt(sched._running.index(r))
        assert engine.host_tier.size == 1       # the export landed
        # let the async swap-out land before rotting the bytes — an
        # armed in-flight corruption resolves the same way, but the
        # resident path is the one the reference chaos test pins
        t0 = time.perf_counter()
        while engine.host_tier.pending_keys():
            time.sleep(0.001)
            assert time.perf_counter() - t0 < 10.0
        sched.fault_plan = FaultPlan(
            [FaultSpec(kind="swap_corruption", tick=sched._tick)])
        _step_until(sched, lambda: r.status.terminal)
        assert r.status == "finished"
        assert list(r.output_tokens) == oracle, \
            "a corrupt resume must re-prefill, never emit wrong tokens"
        counters = reg.snapshot()["counters"]
        assert counters.get("serving.preempt.resumes") == 1
        assert counters.get("serving.preempt.resume_reprefills") == 1
        assert counters.get("serving.swap.verify_failed") == 1
        assert sched.fault_plan.injected_swap_corruptions == 1
        assert engine.host_tier.size == 0, "leaked corrupt record"
        assert not engine.prefix_cache.swapped_keys()
        PoolAuditor().audit(engine)
    finally:
        engine.set_registry(None)


def test_rollback_while_preempted_clears_ingest_stream(engine_pair):
    """Satellite 1, half two (the bugfix pin): a request rolled back
    WHILE preempted (drain/quarantine) clears outputs AND the
    resume-ingest stream together — replaying the committed stream
    against a cleared output list would emit every token shifted. The
    re-serve is bitwise from the prompt, and the orphaned arena record
    is released, not leaked."""
    engine, _ = engine_pair
    rng = np.random.default_rng(23)
    prompt = [int(t) for t in rng.integers(1, VOCAB, size=9)]
    oracle = _oracle(engine, prompt, 10)

    engine.reset(clear_prefixes=True)
    sched = Scheduler(engine, retain_prefixes=True, slo=SLO)
    r = Request(prompt=list(prompt), max_new_tokens=10,
                slo_class="batch")
    sched.submit(r)
    _step_until(sched, lambda: len(r.output_tokens) == 3
                and r.status == "running")
    sched._preempt(sched._running.index(r))
    assert r._ingest_tokens == prompt + oracle[:3]
    assert engine.host_tier.size == 1

    (drained,) = sched.drain_requests()
    assert drained is r
    assert r.status is RequestStatus.QUEUED
    assert r.output_tokens == [] and r._ingest_tokens is None, \
        "the rollback must clear the resume stream WITH the outputs"
    assert engine.host_tier.size == 0, \
        "the drain must release the preempted request's arena record"
    # re-serve through the same scheduler: a fresh prompt, bitwise
    sched.submit(r)
    _step_until(sched, lambda: r.status.terminal)
    assert r.status == "finished"
    assert list(r.output_tokens) == oracle, \
        "the rolled-back resume replayed a stale committed stream"
    PoolAuditor().audit(engine)


# -------------------------------------------------- the FIFO baseline pin
def test_fifo_baseline_verbatim_zero_new_programs(lm_and_params):
    """``slo=None`` is the pre-SLO scheduler verbatim: after heavy
    SLO + preemption churn has exercised every new code path, a FIFO
    serve compiles ZERO new programs and emits the identical stream
    it did before the SLO machinery ever ran."""
    engine = _mk_engine(lm_and_params)
    rng = np.random.default_rng(29)
    prompts = [[int(t) for t in rng.integers(1, VOCAB, size=sz)]
               for sz in (11, 13, 9)]

    def _fifo_serve():
        engine.reset(clear_prefixes=True)
        reqs = [Request(prompt=list(p), max_new_tokens=8)
                for p in prompts]
        Scheduler(engine, retain_prefixes=True).run(reqs)
        return [list(r.output_tokens) for r in reqs]

    before = _fifo_serve()

    # SLO churn: arrival-driven preemption end to end
    engine.reset(clear_prefixes=True)
    sched = Scheduler(engine, retain_prefixes=True, slo=SLO)
    bs = [Request(prompt=list(p), max_new_tokens=8, slo_class="batch")
          for p in prompts[:2]]
    for r in bs:
        sched.submit(r)
    _step_until(sched, lambda: all(r.status == "running" for r in bs)
                and len(bs[1].output_tokens) >= 2)
    hi = Request(prompt=list(prompts[2]), max_new_tokens=8,
                 slo_class="interactive")
    sched.submit(hi)
    _step_until(sched, lambda: all(r.status.terminal
                                   for r in bs + [hi]))
    assert bs[1].preemptions == 1

    n_programs = engine.compiled_programs
    after = _fifo_serve()
    assert engine.compiled_programs == n_programs, \
        "the slo=None path must stay trace-identical (no new programs)"
    assert after == before, \
        "the FIFO baseline stream drifted after SLO churn"
