"""``serving.Engine`` + ``Scheduler`` serving the ``zaya`` architecture
through the paged pool and the per-slot state beside it
(``kv_cache.SlotState``), against the float32 reference's one forward
pass - small size on the CPU (hidden 64, 4 query and 2 K/V heads of 16, 4
experts of width 32, 3 layers, vocabulary 256; Pallas in interpret mode;
chunk and page 128).

The engine hands back tokens, not logits, so here a served token is held
to the reference's logits: it must lie within LOGIT_TOL = 1e-4 of the
reference's best at its position (float32 policy O0: summation order
alone, the logits' scale being 0.2). The logits themselves are compared in
test_zaya_model.py, mode by mode.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serving
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models import build_lm
from apex_tpu.telemetry import MetricsRegistry
from benchmarks.checks.tiny_zaya import TINY_ZAYA_CFG
from benchmarks.lib import reference_zaya as rz

pytestmark = pytest.mark.serving

CFG = TINY_ZAYA_CFG
SLOTS, MAX_LEN, CHUNK = 3, 512, 128
LOGIT_TOL = 1e-4


@pytest.fixture(scope="module")
def weights():
    return rz.seeded_weights(CFG, 3, jnp.float32)


def _engine(weights, **kw):
    kw.setdefault("policy", resolve_policy("O0", verbose=False))
    return serving.Engine(build_lm(CFG, dtype=jnp.float32),
                          rz.program_tree(weights), slots=SLOTS,
                          max_len=MAX_LEN, chunk_len=CHUNK, page_len=CHUNK,
                          **kw)


@pytest.fixture(scope="module")
def engine(weights):
    return _engine(weights, registry=MetricsRegistry())


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _serve(eng, slot, prompt, n_new, others=()):
    """Greedy tokens of ``prompt`` in ``slot``: chunked prefill, then
    ``n_new - 1`` decode steps; ``others`` = [(slot, last token)] ride the
    same decode batch."""
    out = [eng.prefill_chunked(slot, prompt)]
    last = np.zeros(SLOTS, np.int32)
    act = np.zeros(SLOTS, bool)
    for s, t in others:
        last[s], act[s] = t, True
    act[slot] = True
    for _ in range(n_new - 1):
        last[slot] = out[-1]
        toks = eng.decode_step(last, act, np.zeros(SLOTS, np.float32))
        out.append(int(toks[slot]))
        for s, _ in others:
            last[s] = int(toks[s])
    return out


def _gaps(weights, prompt, out):
    """How far each served token's reference logit lies below the
    reference's best at its position."""
    served, _, _ = rz.served_token_gaps(weights, CFG, prompt, out)
    return served


@pytest.mark.parametrize("n", [127, 128, 129, 255, 257])
def test_prefill_then_decode_serves_the_references_tokens(engine, weights, n):
    """Prompts one below, at and one past a chunk (= page) boundary, and
    around the second: the state crosses the chunk boundary inside the
    chunk program and the page boundary inside decode."""
    prompt = _prompt(n, n)
    out = _serve(engine, 1, prompt, 5)
    engine.release_slot(1)
    assert _gaps(weights, prompt, out).max() < LOGIT_TOL


def test_neighbouring_slots_never_see_each_others_state(engine, weights):
    pa, pb = _prompt(11, 140), _prompt(12, 90)
    alone = _serve(engine, 1, pa, 6)
    engine.release_slot(1)
    # B in slot 0 and then in slot 2, decoding beside A
    tb = engine.prefill_chunked(0, pb)
    tb2 = engine.prefill_chunked(2, pb)
    beside = _serve(engine, 1, pa, 6, others=[(0, tb), (2, tb2)])
    for s in range(SLOTS):
        engine.release_slot(s)
    assert beside == alone
    assert _gaps(weights, pa, beside).max() < LOGIT_TOL


def test_a_prefilling_slot_keeps_its_state_through_others_decode(engine,
                                                                 weights):
    """A request mid-prefill rides the decode batch inactive: the decode
    program must not move its state."""
    pa, pb = _prompt(21, 200), _prompt(22, 60)
    tb = engine.prefill_chunked(0, pb)
    first = engine.prefill_chunk(1, pa[:CHUNK], 0)          # A: chunk 1
    assert first is not None
    last = np.zeros(SLOTS, np.int32)
    last[0] = tb
    act = np.array([True, False, False])
    engine.decode_step(last, act, np.zeros(SLOTS, np.float32))  # B decodes
    ta = engine.prefill_chunk(1, pa[CHUNK:], CHUNK)         # A: chunk 2
    for s in range(SLOTS):
        engine.release_slot(s)
    assert _gaps(weights, pa, [int(ta)]).max() < LOGIT_TOL


def test_a_reused_slot_starts_from_zeros(engine, weights):
    px, py = _prompt(31, 150), _prompt(32, 70)
    _serve(engine, 2, px, 4)              # leaves X's state in slot 2
    engine.release_slot(2)
    reused = _serve(engine, 2, py, 4)
    engine.release_slot(2)
    assert _gaps(weights, py, reused).max() < LOGIT_TOL


def test_scheduler_serves_more_requests_than_slots(weights):
    reg = MetricsRegistry()
    eng = _engine(weights, registry=reg)
    sched = serving.Scheduler(eng, registry=reg, max_queue=8)
    reqs = [serving.Request(prompt=_prompt(40 + i, n), max_new_tokens=4,
                            temperature=0.0)
            for i, n in enumerate((30, 129, 64, 200, 128))]
    for r in reqs:
        sched.submit(r)
    for _ in range(200):
        if all(r.status.terminal for r in reqs):
            break
        sched.step()
    assert [r.status.value for r in reqs] == ["finished"] * 5
    for r in reqs:
        assert _gaps(weights, list(r.prompt),
                     list(r.output_tokens)).max() < LOGIT_TOL
    # counters: every prompt and decoded token routed once a layer
    counts = eng.moe_tokens_per_expert()
    assert counts.shape == (3, 4)
    assert (counts.sum(1) == counts[0].sum()).all()
    # one routing a layer for each prompt token and each decode step's
    # input token; decode batches run every slot's row, so at least that
    assert counts[0].sum() >= sum(len(r.prompt) + 3 for r in reqs)
    assert reg.counters["serving.moe.tokens_routed"] == counts[0].sum()
    assert sum(reg.counters[f"serving.moe.tokens_per_expert.e{e}"]
               for e in range(4)) == counts.sum()
    # a second read adds nothing
    eng.moe_tokens_per_expert()
    assert reg.counters["serving.moe.tokens_routed"] == counts[0].sum()
    assert reg.gauges["serving.moe.experts_held"] == 4
    assert reg.gauges["serving.kv.bytes_per_token"] == 3 * 2 * 16 * 4 * 2
    assert reg.gauges["serving.state.bytes_per_slot"] == 3 * 208 * 4
    assert reg.gauges["serving.kv.state_bytes"] == SLOTS * 3 * 208 * 4
    # same compiled programs as any paged engine: one chunk, one decode
    assert (eng.chunk_traces, eng.decode_traces) == (1, 1)
    assert eng.pool_stats()["pages_in_use"] == 0


def test_default_beat_is_the_sync_oracle_with_slot_state(engine, weights):
    """The dispatch-ahead default against ``pipeline_depth=0`` for a
    model with per-slot state, over a chunked backlog in which an
    ``eos_id`` ends requests with a speculated successor step in flight:
    that step wrote K/V and convolution state for a slot since freed,
    and the next occupant's chunk at offset 0 must reset the state
    before anything reads it - so the streams are bitwise equal and the
    re-occupied slot's tokens are still the reference's."""
    def stream():
        return [serving.Request(prompt=_prompt(60 + i, n), max_new_tokens=6,
                                temperature=0.0)
                for i, n in enumerate((40, 129, 70, 200, 33, 131))]

    def serve(**kw):
        for s in range(SLOTS):
            engine.release_slot(s)
        reqs = stream()
        sched = serving.Scheduler(engine, max_queue=8, **kw)
        sched.run(reqs)
        assert [r.status.value for r in reqs] == ["finished"] * len(reqs)
        return reqs, sched

    probe, _ = serve(pipeline_depth=0)
    # an id one of the FIRST occupants first emits mid-generation, so the
    # slot it frees is taken by a request still queued
    eos_id = next(t for r in probe[:SLOTS]
                  for i, t in enumerate(r.output_tokens)
                  if i >= 2 and t not in r.output_tokens[:i])
    oracle, _ = serve(pipeline_depth=0, eos_id=eos_id)
    discarded0 = engine._registry.counters.get(
        "serving.heartbeat.discarded", 0)
    got, sched = serve(eos_id=eos_id)
    assert sched.pipeline_depth == 1
    assert [r.output_tokens for r in got] == \
        [r.output_tokens for r in oracle]
    assert any(r.finish_reason == "eos" and len(r.output_tokens) >= 3
               for r in got[:SLOTS])
    assert engine._registry.counters["serving.heartbeat.discarded"] \
        > discarded0, "no speculated step was in flight at an EOS"
    for r in got[SLOTS:]:
        assert _gaps(weights, list(r.prompt),
                     list(r.output_tokens)).max() < LOGIT_TOL
    assert (engine.chunk_traces, engine.decode_traces) == (1, 1)
    assert engine.pool_stats()["pages_in_use"] == 0


def test_the_pool_and_the_state_take_their_geometry_from_the_model(engine):
    c = engine.cache
    assert c.k.shape == (3, engine.num_pages, 2, 16, CHUNK)   # 2 K/V heads
    assert c.state.rows.shape == (3, SLOTS, 208)
    assert c.state.expert_tokens.shape == (3, 4)
    mem = engine.program_memory()
    assert mem["decode"]["state_bytes"] == c.state.nbytes()


REFUSED_BY_ENGINE = {
    "prefix_cache retention": dict(prefix_pool=2),
    "host_tier swap": dict(host_tier=1 << 20),
    "speculative verify": dict(spec=serving.SpecConfig(draft_len=3)),
    "LoRA": dict(lora=serving.LoRAConfig(rank=2)),
    "int8 KV tier": dict(kv_quant=serving.KVQuantConfig()),
    "int8 weight tier": dict(weight_quant=serving.WeightQuantConfig()),
    "tensor parallelism (mesh=)": dict(mesh="any"),
}


@pytest.mark.parametrize("what", sorted(REFUSED_BY_ENGINE))
def test_the_engine_refuses_by_name_what_slot_state_breaks(weights, what):
    with pytest.raises(NotImplementedError) as e:
        _engine(weights, **REFUSED_BY_ENGINE[what])
    assert what in str(e.value) and "'zaya'" in str(e.value)


REFUSED_BY_SCHEDULER = {
    "prefix_cache retention": dict(retain_prefixes=True),
    "slo preemption with resume": dict(slo=serving.SLOConfig(
        classes={"batch": 0})),
    "speculative verify": dict(speculative=True),
    "disaggregated role": dict(role="decode"),
}


@pytest.mark.parametrize("what", sorted(REFUSED_BY_SCHEDULER))
def test_the_scheduler_refuses_by_name_what_slot_state_breaks(engine, what):
    with pytest.raises(NotImplementedError) as e:
        serving.Scheduler(engine, **REFUSED_BY_SCHEDULER[what])
    assert what in str(e.value) and "'zaya'" in str(e.value)
