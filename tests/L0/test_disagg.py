"""Disaggregated prefill/decode serving — the role split, hermetic.

The acceptance bar from the disaggregation issue, as tests:

- a SPLIT fleet (1 prefill-role + N decode-role replicas behind one
  ``Router(roles=[...])``) serves a greedy mixed-length stream —
  including multi-turn sessions whose later prompts extend earlier
  ones — **bitwise identical** to a ``"both"`` fleet over the same
  engines: the handoff travels as an ordinary CRC'd swapped prefix
  through the shared host arena and the decode side resumes chunk
  prefill at the exact committed offset, so the first sampled token
  comes from byte-exact K/V through the same compiled programs;
- the ``handoff_corruption`` chaos kind degrades per the
  hierarchical-KV contract: the decode side re-prefills COLD (counted
  ``serving.disagg.reprefills`` + ``serving.swap.verify_failed``),
  tokens stay bitwise, ZERO retries are charged and every request
  still reaches the typed ``COMPLETED`` terminal — never a wrong
  token, never a fault charged to the request;
- zero leaked pages AND zero leaked arena bytes at drain on both
  sides: per-engine pool audits reconcile, the fleet-level union of
  every cache's swapped keys equals the shared arena's key set, and a
  clearing reset leaves the arena at zero bytes;
- role validation raises loudly: an all-prefill fleet, an all-decode
  fleet, a mixed fleet without ONE shared ``HostTier(shared=True)``,
  a roles/engines length mismatch, and a direct ``submit`` to a
  ``role="decode"`` scheduler are all configuration errors;
- program-count pins per role: a prefill-role engine compiles exactly
  {chunk prefill, swap-out} and a decode-role engine exactly
  {chunk prefill, decode, swap-in} — the existing swap pair split
  across the roles, zero new executables;
- dispatch-ahead chunk prefill (the satellite): ``pipeline_depth=0``
  stays the bitwise oracle for the dispatch-then-reconcile split, on
  a bare scheduler and on the split fleet;
- quarantine requeues on a mixed fleet flow back through the router
  (``on_requeue``): the retry re-probes LIVE replicas at re-route
  time instead of being pinned to the replica that faulted.

Everything runs on CPU with a tiny model at policy O0 (exact fp32).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, FaultPlan, FaultSpec, HostTier,
                              PoolAuditor, Request, RequestStatus,
                              Router, Scheduler)

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

VOCAB = 64
CHUNK = 8


@pytest.fixture(scope="module")
def lm_and_params():
    m = TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                      num_heads=4, max_seq_len=64)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _mk_engine(lm_and_params, *, tier=None, slots=2, pool=4, seed=5,
               **kw):
    m, params = lm_and_params
    return Engine(m, params, slots=slots, max_len=64, prefill_len=24,
                  chunk_len=CHUNK, prefix_pool=pool,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  host_tier=tier, **kw)


@pytest.fixture(scope="module")
def fleet(lm_and_params):
    """Three identically-built paged engines co-owning ONE shared host
    arena: every test resets them (clear_prefixes=True — on a shared
    arena each engine discards only its own records), so bitwise
    comparisons across role layouts stay within the same compiled
    executables per engine."""
    tier = HostTier(1 << 24, shared=True)
    engines = [_mk_engine(lm_and_params, tier=tier) for _ in range(3)]
    return tier, engines


def _reset(fleet):
    tier, engines = fleet
    for e in engines:
        e.reset(clear_prefixes=True)
        e.set_registry(None)
    assert tier.bytes_used == 0, \
        "shared arena holds bytes after every co-owner reset"


def _stream(seed=42):
    """Mixed-length prompts below / at / straddling the chunk boundary
    (short prompts exercise the key-less handoff: no full chunk means
    nothing to hand over, the decode side cold-prefills)."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=list(rng.integers(1, VOCAB, size=n)),
                    max_new_tokens=b)
            for n, b in [(5, 10), (8, 4), (13, 6), (21, 4), (3, 9),
                         (16, 5), (7, 1), (24, 6), (17, 5), (11, 7)]]


def _session_waves(turns=2, sessions=3):
    """Multi-turn sessions: turn t+1's prompt EXTENDS turn t's, served
    wave after wave — the affinity + handoff-interaction workload (a
    later turn may match a locally registered session prefix INSTEAD
    of its own handoff record; the unused record must be released, not
    leaked)."""
    rng = np.random.default_rng(7)
    base = rng.integers(1, VOCAB, size=CHUNK).tolist()
    prompts = []
    for s in range(sessions):
        srng = np.random.default_rng(100 + s)
        p = base + srng.integers(1, VOCAB, size=CHUNK).tolist()
        turns_s = [list(p)]
        for _ in range(turns - 1):
            p = p + srng.integers(1, VOCAB, size=4).tolist()
            turns_s.append(list(p))
        prompts.append(turns_s)
    return [[Request(prompt=prompts[s][t], max_new_tokens=4)
             for s in range(sessions)] for t in range(turns)]


def _tokens(reqs):
    return [list(r.output_tokens) for r in reqs]


def _audit_fleet(fleet):
    """The zero-leak pin, both tiers: every engine's pool reconciles,
    and the fleet-level cross-arena walk closes — the union of every
    cache's swapped keys IS the shared arena's key set (no dangling
    swapped entry anywhere, no orphaned arena record)."""
    tier, engines = fleet
    aud = PoolAuditor()
    swapped = set()
    for e in engines:
        aud.audit(e)                # raises PoolInvariantError on leaks
        swapped |= set(e.prefix_cache.swapped_keys())
    assert swapped == set(tier.keys()), (
        f"fleet swapped keys {sorted(swapped)} != arena keys "
        f"{sorted(tier.keys())}")


def _serve(fleet, roles, requests, *, registry=None, replica_plans=None,
           **kw):
    tier, engines = fleet
    router = Router(engines, registry=registry, roles=roles,
                    retain_prefixes=True, max_queue=16,
                    replica_plans=replica_plans, **kw)
    if isinstance(requests[0], list):            # session waves
        for wave in requests:
            router.run(wave)
        served = [r for wave in requests for r in wave]
    else:
        router.run(requests)
        served = requests
    return served


# ------------------------------------------------------------- validation
def test_roles_validation_raises_loudly(lm_and_params):
    tier = HostTier(1 << 20, shared=True)
    engines = [_mk_engine(lm_and_params, tier=tier) for _ in range(2)]
    with pytest.raises(ValueError, match="no decode-capable"):
        Router(engines, roles=["prefill", "prefill"],
               retain_prefixes=True)
    with pytest.raises(ValueError, match="no prefill-capable"):
        Router(engines, roles=["decode", "decode"],
               retain_prefixes=True)
    with pytest.raises(ValueError, match="roles has 1 entries"):
        Router(engines, roles=["both"], retain_prefixes=True)
    with pytest.raises(ValueError, match="fleet policy"):
        Router(engines, roles=["prefill", "decode"],
               retain_prefixes=True, role="decode")
    # the arena must be ONE instance, marked shared
    unshared = HostTier(1 << 20)
    pair = [_mk_engine(lm_and_params, tier=unshared) for _ in range(2)]
    with pytest.raises(ValueError, match="shared=True"):
        Router(pair, roles=["prefill", "decode"], retain_prefixes=True)
    split_tiers = [_mk_engine(lm_and_params,
                              tier=HostTier(1 << 20, shared=True))
                   for _ in range(2)]
    with pytest.raises(ValueError, match="same"):
        Router(split_tiers, roles=["prefill", "decode"],
               retain_prefixes=True)
    # roles ride on the prefix/handoff machinery: both seams required
    with pytest.raises(ValueError, match="retain_prefixes"):
        Scheduler(engines[0], role="prefill")
    with pytest.raises(ValueError, match="host_tier"):
        Scheduler(_mk_engine(lm_and_params), role="decode",
                  retain_prefixes=True)
    with pytest.raises(ValueError, match="role must be"):
        Scheduler(engines[0], role="draft", retain_prefixes=True)


def test_decode_role_rejects_direct_submit(lm_and_params):
    """A decode-role replica serves router hand-overs only — a raw
    prompt submitted straight at it is a configuration error, not a
    silent cold prefill on the wrong tier."""
    tier = HostTier(1 << 20, shared=True)
    sched = Scheduler(_mk_engine(lm_and_params, tier=tier),
                      role="decode", retain_prefixes=True)
    with pytest.raises(ValueError, match="hand-overs only"):
        sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
    sched.close()


# ------------------------------------------------------ bitwise + leak-free
def test_split_fleet_bitwise_identical_to_both_fleet(fleet):
    """The tentpole pin: 1 prefill + 2 decode serves the identical
    greedy mixed-length + session stream BITWISE as an all-"both"
    fleet over the SAME engines, with zero re-prefills charged on the
    happy path beyond the key-less short prompts, zero retries, and
    both tiers draining leak-free."""
    _reset(fleet)
    baseline = _serve(fleet, ["both"] * 3, _stream())
    base_waves = _serve(fleet, ["both"] * 3, _session_waves())
    base = _tokens(baseline) + _tokens(base_waves)
    _audit_fleet(fleet)

    _reset(fleet)
    reg = telemetry.MetricsRegistry()
    split = _serve(fleet, ["prefill", "decode", "decode"], _stream(),
                   registry=reg)
    split_waves = _serve(fleet, ["prefill", "decode", "decode"],
                         _session_waves(), registry=reg)
    got = _tokens(split) + _tokens(split_waves)
    assert got == base, "split fleet diverged from the 'both' fleet"
    served = split + split_waves
    assert all(r.status is RequestStatus.FINISHED for r in served)
    assert all(r.retries == 0 for r in served), \
        "a handoff charged a retry"
    counters = dict(reg.counters)
    assert counters.get("serving.disagg.handoffs", 0) == len(served), \
        "every ingested prompt must hand over exactly once"
    assert counters.get("serving.disagg.reprefills", 0) == 0, \
        "happy-path handoffs must not re-prefill"
    assert counters.get("serving.disagg.handoff_bytes", 0) > 0
    _audit_fleet(fleet)
    _reset(fleet)


def test_decode_isolation_gauge_and_heartbeat_split(fleet):
    """Decode-role replicas must not spend their beats on prompt
    ingestion: the decode_isolation gauge (fraction of decode-role
    beats that ran NO chunk prefill) stays high on the split fleet —
    only verified-miss re-prefills and the resumed final chunk may
    dent it — while a 'both' fleet pays prefill beats everywhere."""
    _reset(fleet)
    reg = telemetry.MetricsRegistry()
    _serve(fleet, ["prefill", "decode", "decode"], _stream(),
           registry=reg)
    iso = dict(reg.gauges).get("serving.disagg.decode_isolation")
    assert iso is not None, "split fleet emitted no isolation gauge"
    assert 0.0 < iso <= 1.0
    # only the resumed final chunk may touch a decode beat here (no
    # chaos in this test): well over half the decode beats are pure
    assert iso > 0.5, f"decode replicas spent {1 - iso:.0%} of beats " \
        "prefilling — the role split is not isolating ingestion"
    reg2 = telemetry.MetricsRegistry()
    _serve(fleet, ["both"] * 3, _stream(), registry=reg2)
    assert "serving.disagg.decode_isolation" not in dict(reg2.gauges), \
        "a 'both' fleet has no decode-role beats to measure"
    _reset(fleet)


# ------------------------------------------------------------------ chaos
def test_handoff_corruption_reprefills_never_wrong_token(fleet):
    """Seeded ``handoff_corruption`` chaos: the record's CRC fails at
    the importer's swap-in, the request re-prefills COLD on the decode
    side (typed COMPLETED terminal, zero retries charged), tokens stay
    bitwise vs the clean run, and both tiers drain leak-free."""
    _reset(fleet)
    clean = _tokens(_serve(fleet, ["prefill", "decode", "decode"],
                           _stream()))
    _reset(fleet)
    reg = telemetry.MetricsRegistry()
    plan = FaultPlan([FaultSpec(kind="handoff_corruption", tick=3),
                      FaultSpec(kind="handoff_corruption", tick=5)])
    chaos = _serve(fleet, ["prefill", "decode", "decode"], _stream(),
                   registry=reg, replica_plans=[plan, None, None])
    assert _tokens(chaos) == clean, \
        "handoff corruption changed a token — the CRC verify leaked " \
        "rotten bytes into decode"
    assert all(r.status is RequestStatus.FINISHED for r in chaos)
    assert all(r.retries == 0 for r in chaos), \
        "arena rot is not the request's fault — no retry may be charged"
    counters = dict(reg.counters)
    assert counters.get("serving.disagg.reprefills", 0) >= 1, \
        "corruption injected but nothing re-prefilled"
    assert counters.get("serving.swap.verify_failed", 0) >= 1
    assert plan.injected_handoff_corruptions >= 1
    assert plan.stats()["injected_handoff_corruptions"] \
        == plan.injected_handoff_corruptions
    _audit_fleet(fleet)
    _reset(fleet)


def test_faultplan_handoff_corruption_replay_compatible():
    """``handoff_corruption_rate=0.0`` must not perturb the RNG draw
    sequence (seed-N replays from before the kind existed stay
    identical), and a positive rate emits the kind."""
    kw = dict(slots=4, nonfinite_rate=0.3, exception_rate=0.2)
    assert FaultPlan.random(3, 40, **kw).specs \
        == FaultPlan.random(3, 40, handoff_corruption_rate=0.0,
                            **kw).specs
    plan = FaultPlan.random(3, 60, slots=4, handoff_corruption_rate=0.5)
    assert any(s.kind == "handoff_corruption" for s in plan.specs)
    # no uid-keyed records in the arena: armed but nothing to corrupt
    empty = FaultPlan([FaultSpec(kind="handoff_corruption", tick=0)])
    assert not empty.maybe_corrupt_handoff(0, HostTier(1 << 10))


# ----------------------------------------------- dispatch-ahead prefill
def test_dispatch_ahead_prefill_depth0_is_bitwise_oracle(fleet):
    """The satellite's oracle: chunk prefill split into dispatch +
    reconcile halves (``pipeline_depth>=1``) emits bitwise the tokens
    of the synchronous ``depth=0`` beat — on a bare scheduler and on
    the split fleet."""
    _reset(fleet)
    tier, engines = fleet

    def run_sched(depth):
        sched = Scheduler(engines[0], retain_prefixes=True,
                          pipeline_depth=depth, max_queue=16)
        reqs = _stream()
        for r in reqs:
            sched.submit(r)
        steps = 0
        while sched.pending and steps < 5000:
            sched.step()
            steps += 1
        sched.close()
        return _tokens(reqs)

    sync = run_sched(0)
    assert run_sched(1) == sync
    _reset(fleet)
    split = _serve(fleet, ["prefill", "decode", "decode"], _stream(),
                   pipeline_depth=1)
    assert all(r.status is RequestStatus.FINISHED for r in split)
    assert _tokens(split) == sync
    _audit_fleet(fleet)
    _reset(fleet)


# ------------------------------------------------------ requeue re-probe
def test_quarantine_requeue_reroutes_through_router(fleet):
    """Satellite: on a mixed fleet a quarantined request goes back to
    the ROUTER (which re-probes live replicas and the arena at
    re-route time), not the faulted replica's private queue — and
    still completes bitwise with exactly the one charged retry."""
    _reset(fleet)
    clean = _tokens(_serve(fleet, ["prefill", "decode", "decode"],
                           _stream()))
    _reset(fleet)
    reg = telemetry.MetricsRegistry()
    plan = FaultPlan([FaultSpec(kind="exception", tick=2,
                                site="decode")])
    chaos = _serve(fleet, ["prefill", "decode", "decode"], _stream(),
                   registry=reg, replica_plans=[None, plan, None])
    assert _tokens(chaos) == clean
    assert all(r.status is RequestStatus.FINISHED for r in chaos)
    assert sum(r.retries for r in chaos) >= 1, "fault never fired"
    assert dict(reg.counters).get("serving.router.requeued", 0) >= 1, \
        "quarantine requeue bypassed the router"
    _audit_fleet(fleet)
    _reset(fleet)


# ------------------------------------------------------- program pins
def test_program_counts_pin_exact_per_role(lm_and_params):
    """Zero new executables: the role split re-uses the existing swap
    pair, one direction per side. Fresh engines so the census is
    exact: prefill-role = {chunk prefill, swap-out}; decode-role =
    {chunk prefill, decode, swap-in}."""
    tier = HostTier(1 << 24, shared=True)
    pe = _mk_engine(lm_and_params, tier=tier)
    de = _mk_engine(lm_and_params, tier=tier)
    router = Router([pe, de], roles=["prefill", "decode"],
                    retain_prefixes=True, max_queue=16)
    router.run(_stream())
    assert (pe.chunk_traces, pe.swap_out_traces) == (1, 1)
    assert (pe.decode_traces, pe.swap_in_traces,
            pe.verify_traces) == (0, 0, 0), \
        "a prefill-role engine traced a decode-side program"
    assert (de.chunk_traces, de.decode_traces,
            de.swap_in_traces) == (1, 1, 1)
    assert (de.swap_out_traces, de.verify_traces) == (0, 0), \
        "a decode-role engine traced an ingest-side program"
    assert pe.compiled_programs == 2 and de.compiled_programs == 3
    router.close()
