"""``serving.Engine`` + ``Scheduler`` serving the models that keep state
per slot beside their pages (``kv_cache.SlotState``, built from the
model's ``kv_cache.CacheSpec``), each against its float32 reference's one
forward pass, every case for both - small sizes on the CPU, Pallas in
interpret mode, chunk and page 128:

- ``zaya`` (hidden 64, 4 query and 2 K/V heads of 16, 4 experts of width
  32, 3 layers, vocabulary 256): one block of convolution rows on every
  layer, pages on every layer;
- ``qwen3_next`` (hidden 64, two periods of a linear and a full layer, 4
  value heads of 128 x 128, 16 experts at 4 a token): a float32 recurrent
  block and the convolution's tail on the linear layers, pages on the
  full ones only;
- ``ling_v3`` (hidden 64, one period of five Kimi delta layers of 2 heads
  of 128 x 128 and one latent layer, 16 experts in 4 groups at 4 a token
  on four of the six layers): the same two blocks on the linear layers, a
  decay a key channel; ONE pool of latent rows (32 + 16 wide, no V pool)
  on the latent layer, written and read absorbed.

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
from benchmarks.checks.tiny_ling3 import TINY_LING_CFG
from benchmarks.checks.tiny_qwen3next import TINY_Q3N_CFG
from benchmarks.checks.tiny_zaya import TINY_ZAYA_CFG
from benchmarks.lib import reference_ling3 as rl
from benchmarks.lib import reference_qwen3next as rq
from benchmarks.lib import reference_zaya as rz

pytestmark = pytest.mark.serving

SLOTS, MAX_LEN, CHUNK = 3, 512, 128
LOGIT_TOL = 1e-4


class Kind:
    """One stateful model: its configuration, its reference, and what
    the engine must hold for it."""

    def __init__(self, name, cfg, ref, *, layers, experts, per_token,
                 pool, blocks, kv_bytes_per_token):
        self.name, self.cfg, self.ref = name, cfg, ref
        self.layers, self.experts, self.per_token = layers, experts, \
            per_token
        self.pool, self.blocks = pool, blocks
        self.kv_bytes_per_token = kv_bytes_per_token
        self.state_bytes_per_slot = sum(
            int(np.prod(shape[2:])) * shape[0] * 4
            for shape in blocks.values())       # float32 under O0

    def engine(self, weights, **kw):
        kw.setdefault("policy", resolve_policy("O0", verbose=False))
        return serving.Engine(build_lm(self.cfg, dtype=jnp.float32),
                              self.ref.program_tree(weights), slots=SLOTS,
                              max_len=MAX_LEN, chunk_len=CHUNK,
                              page_len=CHUNK, **kw)


KINDS = {
    "zaya": Kind("zaya", TINY_ZAYA_CFG, rz, layers=3, experts=4,
                 per_token=1, pool=(3, 2, 16), kv_bytes_per_token=768,
                 blocks={"rows": (3, SLOTS, 208)}),
    "qwen3_next": Kind("qwen3_next", TINY_Q3N_CFG, rq, layers=4,
                       experts=16, per_token=4, pool=(2, 2, 32),
                       kv_bytes_per_token=1024,
                       blocks={"recurrent": (2, SLOTS, 4, 128, 128),
                               "conv": (2, SLOTS, 3, 1024)}),
    # layers: the four that have experts; the pool one latent row a token
    "ling_v3": Kind("ling_v3", TINY_LING_CFG, rl, layers=4, experts=16,
                    per_token=4, pool=(1, 1, 48), kv_bytes_per_token=192,
                    blocks={"recurrent": (5, SLOTS, 2, 128, 128),
                            "conv": (5, SLOTS, 3, 768)}),
}


@pytest.fixture(scope="module", params=sorted(KINDS))
def kind(request):
    return KINDS[request.param]


@pytest.fixture(scope="module")
def weights(kind):
    return kind.ref.seeded_weights(kind.cfg, 3, jnp.float32)


@pytest.fixture(scope="module")
def engine(kind, weights):
    return kind.engine(weights, registry=MetricsRegistry())


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


def _gaps(kind, weights, prompt, out):
    """How far each served token's reference logit lies below the
    reference's best at its position."""
    served, _, _ = kind.ref.served_token_gaps(weights, kind.cfg, prompt,
                                              out)
    return served


@pytest.mark.parametrize("n", [127, 128, 129, 255, 257])
def test_prefill_then_decode_serves_the_references_tokens(kind, engine,
                                                          weights, n):
    """Prompts one below, at and one past a chunk (= page) boundary, and
    around the second: the state crosses the chunk boundary inside the
    chunk program and the page boundary inside decode."""
    prompt = _prompt(n, n)
    out = _serve(engine, 1, prompt, 5)
    engine.release_slot(1)
    assert _gaps(kind, weights, prompt, out).max() < LOGIT_TOL


def test_neighbouring_slots_never_see_each_others_state(kind, engine,
                                                        weights):
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
    assert _gaps(kind, weights, pa, beside).max() < LOGIT_TOL


def test_a_prefilling_slot_keeps_its_state_through_others_decode(
        kind, engine, weights):
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
    assert _gaps(kind, weights, pa, [int(ta)]).max() < LOGIT_TOL


def test_a_reused_slot_starts_from_zeros(kind, engine, weights):
    px, py = _prompt(31, 150), _prompt(32, 70)
    _serve(engine, 2, px, 4)              # leaves X's state in slot 2
    engine.release_slot(2)
    reused = _serve(engine, 2, py, 4)
    engine.release_slot(2)
    assert _gaps(kind, weights, py, reused).max() < LOGIT_TOL


def test_scheduler_serves_more_requests_than_slots(kind, weights):
    reg = MetricsRegistry()
    eng = kind.engine(weights, registry=reg)
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
        assert _gaps(kind, weights, list(r.prompt),
                     list(r.output_tokens)).max() < LOGIT_TOL
    # counters: every prompt and decoded token routed to its experts
    # (one, or four) once a layer
    counts = eng.moe_tokens_per_expert()
    assert counts.shape == (kind.layers, kind.experts)
    assert (counts.sum(1) == counts[0].sum()).all()
    assert counts[0].sum() % kind.per_token == 0
    # one routing a layer for each prompt token and each decode step's
    # input token; decode batches run every slot's row, so at least that
    assert counts[0].sum() >= kind.per_token * sum(
        len(r.prompt) + 3 for r in reqs)
    assert reg.counters["serving.moe.tokens_routed"] == counts[0].sum()
    # every expert is held here, so the held count is the whole
    assert reg.counters["serving.moe.tokens_routed_held"] \
        == counts[0].sum()
    assert sum(reg.counters[f"serving.moe.tokens_per_expert.e{e}"]
               for e in range(kind.experts)) == counts.sum()
    # a second read adds nothing
    eng.moe_tokens_per_expert()
    assert reg.counters["serving.moe.tokens_routed"] == counts[0].sum()
    assert reg.gauges["serving.moe.experts_held"] == kind.experts
    assert reg.gauges["serving.moe.experts_per_token"] == kind.per_token
    assert reg.gauges["serving.kv.page_layers"] == kind.pool[0]
    assert reg.gauges["serving.kv.bytes_per_token"] \
        == kind.kv_bytes_per_token
    assert reg.gauges["serving.state.bytes_per_slot"] \
        == kind.state_bytes_per_slot
    assert reg.gauges["serving.state.bytes"] \
        == SLOTS * kind.state_bytes_per_slot
    # same compiled programs as any paged engine: one chunk, one decode
    assert (eng.chunk_traces, eng.decode_traces) == (1, 1)
    assert eng.pool_stats()["pages_in_use"] == 0


def test_default_beat_is_the_sync_oracle_with_slot_state(kind, engine,
                                                         weights):
    """The dispatch-ahead default against ``pipeline_depth=0`` for a
    model with per-slot state, over a chunked backlog in which an
    ``eos_id`` ends requests with a speculated successor step in flight:
    that step wrote K/V and per-slot state for a slot since freed,
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
        assert _gaps(kind, weights, list(r.prompt),
                     list(r.output_tokens)).max() < LOGIT_TOL
    assert (engine.chunk_traces, engine.decode_traces) == (1, 1)
    assert engine.pool_stats()["pages_in_use"] == 0


def test_the_pool_and_the_state_take_their_geometry_from_the_model(kind,
                                                                   engine):
    """The cache spec per kind of layer: pages only on the layers that
    attend them, each state block on the layers that keep it, in its own
    shape and dtype."""
    c = engine.cache
    page_layers, kv_heads, head_dim = kind.pool
    assert c.k.shape == (page_layers, engine.num_pages, kv_heads, head_dim,
                         CHUNK)
    assert {n: b.shape for n, b in c.state.blocks.items()} == kind.blocks
    assert c.state.expert_tokens.shape == (kind.layers, kind.experts)
    assert c.state.bytes_per_slot() == kind.state_bytes_per_slot
    spec = engine.cache_spec
    assert spec.page_layers == page_layers
    assert [b.name for b in spec.state] == list(kind.blocks)
    mem = engine.program_memory()
    assert mem["decode"]["state_bytes"] == c.state.nbytes()


def test_a_stateless_model_states_its_pool_through_the_same_spec():
    from apex_tpu.models.transformer_lm import TransformerLM
    from apex_tpu.serving.kv_cache import CacheSpec

    spec = CacheSpec.of(TransformerLM(vocab_size=96, hidden=32, num_layers=2,
                                      num_heads=4))
    assert (spec.page_layers, spec.kv_heads, spec.head_dim) == (2, 4, 8)
    assert spec.state == () and spec.num_experts == 0


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
def test_the_engine_refuses_by_name_what_slot_state_breaks(kind, weights,
                                                           what):
    with pytest.raises(NotImplementedError) as e:
        kind.engine(weights, **REFUSED_BY_ENGINE[what])
    assert what in str(e.value) and repr(kind.name) in str(e.value)


REFUSED_BY_SCHEDULER = {
    "prefix_cache retention": dict(retain_prefixes=True),
    "slo preemption with resume": dict(slo=serving.SLOConfig(
        classes={"batch": 0})),
    "speculative verify": dict(speculative=True),
    "disaggregated role": dict(role="decode"),
}


@pytest.mark.parametrize("what", sorted(REFUSED_BY_SCHEDULER))
def test_the_scheduler_refuses_by_name_what_slot_state_breaks(kind, engine,
                                                              what):
    with pytest.raises(NotImplementedError) as e:
        serving.Scheduler(engine, **REFUSED_BY_SCHEDULER[what])
    assert what in str(e.value) and repr(kind.name) in str(e.value)
