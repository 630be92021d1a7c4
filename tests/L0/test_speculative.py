"""Speculative decoding: n-gram draft-and-verify, bitwise-greedy parity.

The acceptance bar from the speculative-decoding issue, as tests:

- **the parity pin**: greedy speculative output is bitwise identical to
  plain decode across a request stream with prompt lengths below / at /
  straddling chunk boundaries, and matches one
  teacher-forcing full recompute (every emitted token is the verify
  program's own greedy target — the structural argument — and the
  verify/decode programs agree token-for-token — the pinned one);
- **acceptance mechanics at the engine level**: a draft equal to the
  plain-decode continuation is fully accepted (tokens == the next K+1
  plain tokens); a draft wrong at position i accepts exactly i and the
  stream CONTINUES correctly through plain decode afterwards — the
  rollback pin: rejected-tail K/V written by the verify step never
  becomes visible;
- **compiled-programs pin**: the verify program is exactly ONE new
  executable — 3 in all — across a stream that varies
  drafts, offsets, draft lengths and slots (drafting never retraces);
- **chaos composition**: a seeded FaultPlan (verify-site exceptions +
  non-finite injection into a verifying slot) over a speculative run —
  un-faulted requests bitwise vs the fault-free speculative run, zero
  leaked pages at drain, zero new traces;
- drafter units: most-recent-occurrence prompt lookup, n-gram size
  degradation, draft truncation, empty-draft fallbacks, SpecConfig
  validation;
- registry wiring: a scheduler-only registry auto-propagates to a
  registry-less engine (so engine-side counters like
  ``serving.faults.nonfinite`` are never silently dropped), and a loud
  warning fires when both are set and differ.

Everything hermetic on CPU with the tiny test model at policy O0 (the
kernels take their interpret/reference paths — same math, pinned
bitwise against the Pallas paths by the kernel test tiers).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, FaultPlan, FaultPolicy, FaultSpec,
                              Request, RequestStatus, Scheduler,
                              SpecConfig, draft_tokens)

pytestmark = pytest.mark.serving

VOCAB = 101
CHUNK = 8
K = 3


def _tiny_lm(max_seq_len=128, **kw):
    return TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                         num_heads=4, max_seq_len=max_seq_len, **kw)


@pytest.fixture(scope="module")
def lm_and_params():
    m = _tiny_lm()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _mk_engine(lm_and_params, *, slots=3, seed=5, spec=True, **kw):
    m, params = lm_and_params
    return Engine(m, params, slots=slots, max_len=128, prefill_len=24,
                  chunk_len=CHUNK,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  spec=SpecConfig(draft_len=K, ngram=2) if spec else None,
                  **kw)


@pytest.fixture(scope="module")
def engine(lm_and_params):
    """One spec-enabled engine shared module-wide: parity
    comparisons run plain and speculative passes through the SAME
    compiled programs, and the trace pin at the end of the module
    covers every test in between."""
    return _mk_engine(lm_and_params)


def _boundary_reqs():
    """Prompt lengths below (5), at (8), straddling one (13) and two
    (21) chunk boundaries at chunk_len=8 — the issue's sweep — with
    budgets that exercise full verify windows AND the endgame
    plain-decode tail."""
    rng = np.random.default_rng(42)
    return [Request(prompt=list(rng.integers(1, VOCAB, size=n)),
                    max_new_tokens=b)
            for n, b in [(5, 16), (8, 12), (13, 10), (21, 8)]]


# ------------------------------------------------------------------ drafter
def test_spec_config_validation():
    with pytest.raises(ValueError, match="draft_len"):
        SpecConfig(draft_len=0)
    with pytest.raises(ValueError, match="ngram"):
        SpecConfig(ngram=0)
    with pytest.raises(ValueError, match="min_ngram"):
        SpecConfig(ngram=2, min_ngram=3)
    cfg = SpecConfig(draft_len=4, ngram=3)
    assert (cfg.draft_len, cfg.ngram, cfg.min_ngram) == (4, 3, 1)


def test_draft_tokens_prompt_lookup():
    cfg = SpecConfig(draft_len=3, ngram=2)
    # suffix [1, 2] occurs at index 0; drafts the 3 followers
    assert draft_tokens([1, 2, 3, 9, 1, 2], cfg) == [3, 9, 1]
    # most RECENT occurrence wins (index 4, not 0)
    assert draft_tokens([1, 2, 7, 9, 1, 2, 8, 5, 1, 2], cfg) == [8, 5, 1]
    # followers may overlap into the suffix itself (how repetition
    # drafting works): [5, 6] at index 0 is followed by [7, 5, 6]
    assert draft_tokens([5, 6, 7, 5, 6], cfg) == [7, 5, 6]
    # truncated draft when fewer followers exist than draft_len wants
    assert draft_tokens([7, 7], cfg) == [7]
    # no 2-gram match -> degrade to 1-gram (min_ngram=1 default)
    assert draft_tokens([4, 9, 2, 4, 7, 3, 4], cfg) == [7, 3, 4]
    # nothing repeats at all -> empty draft (plain-decode fallback)
    assert draft_tokens([1, 2, 3, 4, 5], cfg) == []
    # min_ngram=2 refuses the 1-gram fallback
    assert draft_tokens([4, 9, 2, 4, 7, 3, 4],
                        SpecConfig(draft_len=3, ngram=2,
                                   min_ngram=2)) == []
    # max_draft caps below draft_len
    assert draft_tokens([1, 2, 3, 9, 1, 2], cfg, max_draft=1) == [3]
    # too short to match anything: never raises
    assert draft_tokens([7], cfg) == []
    assert draft_tokens([], cfg) == []


def test_draft_repetition_drafts_the_loop():
    # a repeating tail drafts its own continuation — the generated-text
    # case where speculation wins big (tiny greedy models loop). The
    # full-follower-window preference matters exactly here: the newest
    # match ends right next to the sequence end and would truncate
    # every draft to the period length, so the drafter backs up to the
    # most recent occurrence that can fill draft_len.
    cfg = SpecConfig(draft_len=4, ngram=2)
    assert draft_tokens([7, 8, 7, 8, 7, 8], cfg) == [7, 8, 7, 8]
    assert draft_tokens([9] * 8, cfg) == [9, 9, 9, 9]
    # too short for a full window: truncated draft, not an empty one
    assert draft_tokens([9, 9, 9, 9], cfg) == [9]


# ------------------------------------------------ engine-level verify pins
def _plain_greedy(engine, prompt, n):
    """n greedy tokens via prefill + plain decode on slot 0 — the
    reference stream (same compiled programs as the spec path)."""
    engine.reset()
    tok = engine.prefill_chunked(0, prompt)
    out = [tok]
    last = np.zeros(engine.slots, np.int32)
    active = np.zeros(engine.slots, bool)
    active[0] = True
    temps = np.zeros(engine.slots, np.float32)
    for _ in range(n - 1):
        last[0] = out[-1]
        out.append(int(engine.decode_step(last, active, temps)[0]))
    return out


def test_verify_accepts_correct_draft_and_rejects_wrong(engine):
    """A draft equal to the plain continuation accepts fully and the
    returned tokens are the next K+1 plain tokens; a draft wrong at
    position i accepts exactly i tokens; plain decode AFTER the
    rejection reproduces the reference stream — the rejected tail's
    K/V (written into the cache by the verify program) never became
    visible."""
    eng = engine
    prompt = [3, 17, 91, 42, 8]
    ref = _plain_greedy(eng, prompt, 10)
    offset = len(prompt)

    eng.reset()
    t0 = eng.prefill_chunked(0, prompt)
    assert t0 == ref[0]
    toks, m = eng.verify_step(0, t0, ref[1:1 + K], offset)
    assert m == K, "the true continuation must be fully accepted"
    assert toks.tolist() == ref[1:1 + K + 1]

    # wrong draft at position 2 -> exactly 1 accepted
    eng.reset()
    t0 = eng.prefill_chunked(0, prompt)
    wrong = [ref[1], (ref[2] + 1) % VOCAB, ref[3]]
    toks, m = eng.verify_step(0, t0, wrong, offset)
    assert m == 1
    assert toks.tolist()[:2] == ref[1:3]

    # rollback pin: plain decode continues the reference stream
    out = [ref[0], int(toks[0]), int(toks[1])]
    last = np.zeros(eng.slots, np.int32)
    active = np.zeros(eng.slots, bool)
    active[0] = True
    temps = np.zeros(eng.slots, np.float32)
    while len(out) < len(ref):
        last[0] = out[-1]
        out.append(int(eng.decode_step(last, active, temps)[0]))
    assert out == ref, "stale rejected-tail K/V leaked into decode"

    # short (padded) draft: one executable, acceptance capped at the
    # real draft length
    eng.reset()
    t0 = eng.prefill_chunked(0, prompt)
    toks, m = eng.verify_step(0, t0, ref[1:2], offset)
    assert m == 1 and toks.tolist()[:2] == ref[1:3]


def test_verify_step_validation(engine, lm_and_params):
    eng = engine
    eng.reset()
    eng.prefill_chunked(0, [1, 2, 3])
    with pytest.raises(ValueError, match="draft length"):
        eng.verify_step(0, 1, [], 3)
    with pytest.raises(ValueError, match="draft length"):
        eng.verify_step(0, 1, [1] * (K + 1), 3)
    with pytest.raises(ValueError, match="slot"):
        eng.verify_step(eng.slots, 1, [1], 3)
    with pytest.raises(ValueError, match="verify window"):
        eng.verify_step(0, 1, [1], eng.max_len - K)   # window spills
    with pytest.raises(ValueError, match="disagrees"):
        eng.verify_step(0, 1, [1], 7)                 # committed len is 3
    no_spec = _mk_engine(lm_and_params, spec=False)
    with pytest.raises(RuntimeError, match="SpecConfig"):
        no_spec.verify_step(0, 1, [1], 3)
    with pytest.raises(ValueError, match="speculative=True requires"):
        Scheduler(no_spec, speculative=True)


def test_engine_spec_validation(lm_and_params):
    m, params = lm_and_params
    with pytest.raises(TypeError, match="SpecConfig"):
        Engine(m, params, slots=1, max_len=32, prefill_len=16, spec=3)
    with pytest.raises(ValueError, match="cannot fit max_len"):
        Engine(m, params, slots=1, max_len=4, prefill_len=4,
               spec=SpecConfig(draft_len=4))


# --------------------------------------------------------- the parity pin
def test_speculative_bitwise_parity_and_recompute(engine, lm_and_params):
    """THE acceptance pin: a greedy stream with prompt lengths below /
    at / straddling chunk boundaries served speculative vs plain on the
    same engine — bitwise-identical token streams, real acceptances,
    and agreement with one teacher-forcing full recompute."""
    m, params = lm_and_params
    eng = engine
    eng.reset()
    plain = _boundary_reqs()
    Scheduler(eng, speculative=False).run(plain)
    base = [list(r.output_tokens) for r in plain]
    assert all(r.spec_drafted == 0 for r in plain), \
        "speculative=False must keep today's path untouched"

    eng.reset()
    reg = telemetry.MetricsRegistry()
    sp = _boundary_reqs()
    Scheduler(eng, registry=reg, speculative=True).run(sp)
    got = [list(r.output_tokens) for r in sp]
    assert got == base, "speculative greedy output diverged from plain"
    snap = reg.snapshot()
    drafted = snap["counters"].get("serving.spec.drafted", 0)
    accepted = snap["counters"].get("serving.spec.accepted", 0)
    assert drafted > 0, "the drafter never fired — the test is vacuous"
    assert accepted > 0, "nothing accepted — speculation never engaged"
    assert accepted == sum(r.spec_accepted for r in sp)
    assert snap["histograms"]["serving.spec.acceptance_rate"]["count"] \
        > 0
    assert "serving.spec.tokens_per_step" in snap["gauges"]

    # teacher-forcing: one full forward re-derives every greedy step
    for r in sp:
        seq = jnp.asarray([list(r.prompt) + r.output_tokens], jnp.int32)
        full = m.apply({"params": params}, seq, train=False)
        want = np.asarray(jnp.argmax(full[0], axis=-1))
        for i, tok in enumerate(r.output_tokens):
            assert tok == int(want[len(r.prompt) - 1 + i]), \
                f"prompt len {len(r.prompt)}: divergence at token {i}"


def test_speculative_with_eos_matches_plain(engine):
    """EOS inside an accepted run truncates exactly where plain decode
    stops (emitted tokens past the EOS are discarded)."""
    eng = engine
    eng.reset()
    prompt = [3, 17, 91, 42, 8]
    ref = _plain_greedy(eng, prompt, 8)
    eos = ref[4]                 # finishes mid-stream in both modes
    mk = lambda: [Request(prompt=list(prompt), max_new_tokens=16)]
    eng.reset()
    plain = mk()
    Scheduler(eng, eos_id=eos, speculative=False).run(plain)
    eng.reset()
    sp = mk()
    Scheduler(eng, eos_id=eos, speculative=True).run(sp)
    assert sp[0].output_tokens == plain[0].output_tokens
    assert sp[0].finish_reason == plain[0].finish_reason == "eos"


# --------------------------------------------------------- batched verify
def test_verify_batch_matches_sequential_per_slot(engine):
    """The batched-verify satellite's parity pin: B verify-eligible
    slots through ONE [slots, K+1] call emit bitwise the same tokens
    and acceptance counts as B sequential single-slot verify_step calls
    — the wrapper routes through the SAME executable, so this is the
    per-row-independence guarantee (a slot's verify never reads or
    writes a batchmate's rows)."""
    eng = engine
    prompts = {0: [3, 17, 91, 42, 8], 1: [7, 7, 9, 7, 7, 9, 2],
               2: [11, 4, 11, 4, 11]}
    drafts = {0: [5, 9, 1], 1: [7, 9], 2: [11]}   # varied draft lengths

    def prep():
        eng.reset()
        return {s: eng.prefill_chunked(s, p) for s, p in prompts.items()}

    first = prep()
    toks_b, acc_b = eng.verify_batch(
        {s: (first[s], drafts[s]) for s in prompts})
    assert toks_b.shape == (eng.slots, K + 1)
    assert acc_b.shape == (eng.slots,)
    assert eng.last_verify_finite_slots.all()
    first = prep()
    for s in prompts:
        toks_s, m_s = eng.verify_step(s, first[s], drafts[s],
                                      len(prompts[s]))
        assert int(acc_b[s]) == m_s, f"slot {s}: acceptance diverged"
        assert toks_b[s].tolist() == toks_s.tolist(), \
            f"slot {s}: batched verify diverged from per-slot verify"
    eng.reset()


def test_verify_batch_leaves_nonverifying_slots_untouched(engine):
    """Fixed-shape safety: a decoding slot NOT in the verify batch must
    keep its exact cache bytes — its subsequent plain-decode stream is
    bitwise the reference stream even though a batched verify ran on a
    batchmate in between (paged: the passenger's table-row operand is
    zeroed so writes land on the sentinel; this is the guarantee that
    lets the scheduler verify some slots while others decode)."""
    eng = engine
    prompt = [3, 17, 91, 42, 8]
    ref = _plain_greedy(eng, prompt, 8)

    eng.reset()
    t0 = eng.prefill_chunked(0, prompt)             # the bystander
    t1 = eng.prefill_chunked(1, [7, 7, 9, 7, 7, 9, 2])  # the verifier
    eng.verify_batch({1: (t1, [7, 7, 9])})
    out = [t0]
    last = np.zeros(eng.slots, np.int32)
    active = np.zeros(eng.slots, bool)
    active[0] = True
    temps = np.zeros(eng.slots, np.float32)
    while len(out) < len(ref):
        last[0] = out[-1]
        out.append(int(eng.decode_step(last, active, temps)[0]))
    assert out == ref, "a batched verify on slot 1 corrupted slot 0's " \
        "cache"
    eng.reset()


def test_verify_batch_validation(engine):
    eng = engine
    eng.reset()
    eng.prefill_chunked(0, [1, 2, 3])
    with pytest.raises(ValueError, match="at least one"):
        eng.verify_batch({})
    with pytest.raises(ValueError, match="draft length"):
        eng.verify_batch({0: (1, [])})
    with pytest.raises(ValueError, match="draft length"):
        eng.verify_batch({0: (1, [1] * (K + 1))})
    with pytest.raises(ValueError, match="slot"):
        eng.verify_batch({eng.slots: (1, [1])})
    eng.reset()


def test_verify_batch_window_and_offset_raise(lm_and_params):
    """Loud-failure contract (a window masked in-program would
    return n_accepted=0 — indistinguishable from a real zero-accept,
    so the caller would emit a token whose K/V never landed): a
    verifying slot whose committed length leaves no room for the
    padded [K+1] window raises BEFORE anything mutates, and so does a
    caller offset that disagrees with the committed length."""
    m, params = lm_and_params
    eng = Engine(m, params, slots=2, max_len=8, prefill_len=8,
                 chunk_len=8,
                 policy=resolve_policy("O0", verbose=False),
                 spec=SpecConfig(draft_len=K, ngram=2))
    t = eng.prefill_chunked(0, [1, 2, 3, 4, 5])   # committed length 5
    with pytest.raises(ValueError, match="verify window"):
        eng.verify_batch({0: (t, [1, 2])})        # [5, 9) spills 8
    t1 = eng.prefill_chunked(1, [1, 2, 3])        # committed length 3
    with pytest.raises(ValueError, match="disagrees"):
        eng.verify_batch({1: (t1, [1])}, offsets={1: 4})  # fits, drifts
    assert eng.verify_traces == 0, \
        "validation must fire before the program ever traces"
    # tokens_generated counted nothing for the refused calls
    assert eng.tokens_generated == 2              # the prefill tokens


# ------------------------------------------------- compiled-programs pin
def test_exactly_one_new_executable(engine):
    """The compiled-programs pin: across everything this
    module ran on the shared engine — streams varying drafts, offsets,
    slots, draft lengths — the verify
    program traced EXACTLY once (drafting never retraces), moving the
    pin 2 -> 3."""
    eng = engine
    eng.reset()
    sched = Scheduler(eng, speculative=True)
    sched.run(_boundary_reqs())
    assert eng.verify_traces == 1, "the verify program retraced"
    assert (eng.chunk_traces, eng.decode_traces) == (1, 1)
    assert eng.compiled_programs == 3


# ------------------------------------------------------ chaos composition
@pytest.mark.chaos
def test_chaos_composition_speculative(engine):
    """Satellite pin: a seeded FaultPlan — a verify-site exception plus
    non-finite logits routed into a verifying slot — over a speculative
    run. Un-faulted requests are bitwise identical to the fault-free
    SPECULATIVE run, faulted requests reach typed terminals, zero new
    programs traced, zero pages leaked at drain."""
    eng = engine
    eng.reset()
    policy = FaultPolicy(backoff_base_s=0.0, audit_every_n=1)
    clean_reqs = _boundary_reqs()
    Scheduler(eng, speculative=True, fault_policy=policy).run(clean_reqs)
    clean = [list(r.output_tokens) for r in clean_reqs]
    traces0 = (eng.chunk_traces, eng.decode_traces, eng.verify_traces)

    eng.reset()
    # tick 1 is DETERMINISTIC: the chaos schedule is identical to the
    # clean one until the first injection, and in the clean schedule
    # slot 0 takes a verify step at tick 1 — so the non-finite spec is
    # routed through the VERIFY program's guard (take_nonfinite), not
    # the decode batch. The verify-site exceptions are sprayed over a
    # tick range because quarantines reshuffle slots afterwards — at
    # least one must land on a live verify call (asserted below).
    plan = FaultPlan(
        [FaultSpec(kind="nonfinite", tick=1, slot=0)]
        + [FaultSpec(kind="exception", tick=t, site="verify")
           for t in range(3, 7)])
    reg = telemetry.MetricsRegistry()
    eng.set_registry(reg)
    sched = Scheduler(eng, registry=reg, speculative=True,
                      fault_policy=policy, fault_plan=plan)
    reqs = _boundary_reqs()
    try:
        done = sched.run(reqs)
    finally:
        eng.set_registry(None)
    assert len(done) == len(reqs)
    assert plan.stats()["injected_exceptions"] >= 1, \
        "no verify-site exception ever fired — the site is dead"
    assert plan.stats()["injected_nonfinite"] == 1
    faulted = [r for r in reqs if r.retries > 0
               or r.status is RequestStatus.FAILED]
    assert faulted, "the plan must actually fault requests"
    for r in reqs:
        assert r.status.terminal
    for i, r in enumerate(reqs):
        if r.status is RequestStatus.FINISHED:
            assert list(r.output_tokens) == clean[i], \
                f"request {i} diverged under chaos"
    # containment + injection added ZERO compiled programs
    assert (eng.chunk_traces, eng.decode_traces,
            eng.verify_traces) == traces0
    assert reg.snapshot()["counters"]["serving.faults.nonfinite"] >= 1
    assert sched.auditor.audit(eng)["pages_in_use"] == 0
    eng.reset()


# -------------------------------------------------------- registry wiring
def test_scheduler_registry_propagates_to_engine(lm_and_params):
    """Satellite pin (PR 7 NOTE): a scheduler-only registry silently
    missed every engine-emitted metric (serving.faults.nonfinite above
    all). The scheduler now hands its registry to a registry-less
    engine at construction."""
    eng = _mk_engine(lm_and_params, spec=False)
    assert eng._registry is None
    reg = telemetry.MetricsRegistry()
    sched = Scheduler(eng, registry=reg)
    assert eng._registry is reg
    sched.run([Request(prompt=[1, 2, 3], max_new_tokens=2)])
    snap = reg.snapshot()
    # engine-side metrics now land in the scheduler's registry
    assert snap["counters"]["serving.prefill.chunks"] >= 1
    assert snap["counters"]["serving.tokens_generated"] >= 2


def test_scheduler_registry_conflict_logs_loudly(lm_and_params):
    # the package logger keeps propagate=False (log_util), so capture
    # with a handler on the serving logger rather than caplog
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger("apex_tpu.serving")
    logger.addHandler(handler)
    try:
        eng = _mk_engine(lm_and_params, spec=False)
        eng.set_registry(telemetry.MetricsRegistry())
        other = telemetry.MetricsRegistry()
        Scheduler(eng, registry=other)
    finally:
        logger.removeHandler(handler)
        eng.set_registry(None)
    assert any(r.levelno >= logging.WARNING
               and "DIFFERENT telemetry registries" in r.getMessage()
               for r in records), \
        "conflicting registries must warn loudly"
    assert eng._registry is not other, \
        "a deliberate split must not be overwritten"
