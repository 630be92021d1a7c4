"""Multi-tenant LoRA serving: the adapter-arena acceptance pins.

The perf claim (one base engine serving heterogeneous fine-tunes in
one batch) is only honest with these bars, per ISSUE 20:

- **adapter=None bitwise**: a LoRA-enabled engine with no adapter
  bound serves the EXACT base-engine stream on the same executables —
  the zero arena row's epilogue term is ``+0.0`` everywhere, and the
  program-count pins do not move;
- **one invocation**: a mixed-adapter batch decodes in ONE compiled
  invocation — the compiled-program count is independent of how many
  adapters are registered, resident or bound (adapter id is data, not
  a trace key);
- **per-slot isolation**: slot A's adapter provably never perturbs
  slot B's tokens — a mixed-adapter batch is bitwise identical to
  per-adapter sequential runs at the same geometry;
- **graceful degradation + loud failure**: a full arena holds the
  request queued (FIFO preserved); an unknown or checksum-corrupt
  adapter fails the request LOUDLY, never a silent base-model
  fallback, never wrong tokens;
- **churn is leak-free**: hot-load/evict under faulted traffic drains
  with zero leaked pages (PoolAuditor) and a clean arena refcount
  audit;
- **routing**: ``Request.adapter`` crosses the wire (v3) and both
  routing fronts rank a resident-adapter hit right after the prefix
  match;
- **composition**: kv_quant + weight_quant + speculative verify ride
  along; tp=1 mesh is bitwise (the tp=2 parity run carries the
  ``slow`` marker like every multi-device test).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, FaultPlan, FaultSpec, KVQuantConfig,
                              LoRAConfig, LoRAManager, PoolAuditor,
                              Request, RequestStatus, Router, Scheduler,
                              SpecConfig, WeightQuantConfig,
                              request_from_wire, request_to_wire)
from apex_tpu.serving.lora import SITES, lora_spec_tree
from apex_tpu.serving.routing_policy import rank_replicas

pytestmark = pytest.mark.serving

VOCAB, H, LAYERS, HEADS = 64, 32, 2, 4
CHUNK = 8
RANK = 4


@pytest.fixture(scope="module")
def lm_and_params():
    m = TransformerLM(vocab_size=VOCAB, hidden=H, num_layers=LAYERS,
                      num_heads=HEADS, max_seq_len=64)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _mk_adapter(seed, scale=0.5, rank=RANK):
    rng = np.random.default_rng(seed)
    dims = {"qkv": (H, 3 * H), "proj": (H, H),
            "mlp_in": (H, 4 * H), "mlp_out": (4 * H, H)}
    return {s: (rng.normal(size=(LAYERS, di, rank))
                .astype(np.float32) * scale,
                rng.normal(size=(LAYERS, rank, do))
                .astype(np.float32) * scale)
            for s, (di, do) in dims.items()}


_CFG = LoRAConfig(rank=RANK, arena_slots=2, host_bytes=1 << 22)

#: name -> deterministic generator seed, shared by every engine build
#: so any two engines hold bitwise-identical adapters
_ADAPTERS = {"a1": 1, "a2": 2, "a3": 3}


def _mk_engine(lm_and_params, *, lora=_CFG, slots=3, mesh=None,
               register=("a1", "a2"), **kw):
    m, params = lm_and_params
    eng = Engine(m, params, slots=slots, max_len=64, prefill_len=24,
                 chunk_len=CHUNK, prefix_pool=0, seed=5,
                 page_len=CHUNK, num_pages=64, lora=lora, mesh=mesh,
                 **kw)
    if lora is not None:
        for name in register:
            eng.lora_register(name, _mk_adapter(_ADAPTERS[name]),
                              alpha=0.7)
    return eng


def _prompts(n=6, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=8 + i).tolist()
            for i in range(n)]


def _run_jobs(eng, jobs, *, sched_kw=None, budget=5):
    """Serve ``[(prompt, adapter), ...]`` and return each job's token
    stream in submission order (plus the requests themselves)."""
    sched = Scheduler(eng, **(sched_kw or {}))
    reqs = [Request(prompt=list(p), max_new_tokens=budget, adapter=ad)
            for p, ad in jobs]
    sched.run(reqs)
    return [list(r.output_tokens) for r in reqs], reqs


# ------------------------------------------------------------- config/units
def test_lora_config_validation():
    with pytest.raises(ValueError, match="rank"):
        LoRAConfig(rank=0)
    with pytest.raises(ValueError, match="arena_slots"):
        LoRAConfig(arena_slots=0)
    with pytest.raises(ValueError, match="host_bytes"):
        LoRAConfig(host_bytes=0)


def test_spec_tree_rides_the_pr9_axes():
    """A column-split, B row-split, restated for the stacked arena:
    column-parallel sites split B's OUTPUT axis, row-parallel sites
    split A's INPUT axis, everything else is replicated — the existing
    post-proj/post-mlp psums restore the row-parallel partial sums, so
    the tier adds zero collectives."""
    tree = lora_spec_tree("tp")
    assert tree["qkv_b"] == P(None, None, None, "tp")
    assert tree["mlp_in_b"] == P(None, None, None, "tp")
    assert tree["proj_a"] == P(None, None, "tp", None)
    assert tree["mlp_out_a"] == P(None, None, "tp", None)
    for k in ("qkv_a", "mlp_in_a", "proj_b", "mlp_out_b", "alpha"):
        assert tree[k] == P(), k


def _bare_manager(host_bytes=1 << 22, arena_slots=2):
    return LoRAManager(
        LoRAConfig(rank=RANK, arena_slots=arena_slots,
                   host_bytes=host_bytes),
        hidden=H, num_heads=HEADS, num_layers=LAYERS)


def test_manager_register_validation():
    mgr = _bare_manager()
    sites = _mk_adapter(1)
    bad = dict(sites)
    del bad["proj"]
    with pytest.raises(ValueError, match="missing site"):
        mgr.register("x", bad)
    bad = dict(sites)
    a, b = bad["qkv"]
    bad["qkv"] = (a[:, :, :-1], b)          # wrong rank
    with pytest.raises(ValueError, match="shapes"):
        mgr.register("x", bad)
    # an adapter alone larger than the store is loud, not an LRU spin
    one = sum(a.nbytes + b.nbytes for a, b in sites.values())
    small = _bare_manager(host_bytes=one - 1)
    with pytest.raises(ValueError, match="exceeds the host store"):
        small.register("x", sites)


def test_manager_lru_refcount_and_residency():
    sites = _mk_adapter(1)
    one = sum(a.nbytes + b.nbytes for a, b in sites.values())
    mgr = _bare_manager(host_bytes=2 * one)
    mgr.register("a1", _mk_adapter(1))
    mgr.register("a2", _mk_adapter(2))
    row = mgr.acquire("a1")                 # a1 pinned (refcount 1)
    assert row and mgr.resident_names() == ["a1"]
    # byte pressure evicts the LRU UNPINNED record (a2), never a1
    mgr.register("a3", _mk_adapter(3))
    assert not mgr.contains("a2") and mgr.contains("a1")
    assert mgr.evictions == 1
    # a pinned record refuses re-register (live math must not change)
    with pytest.raises(ValueError, match="pinned"):
        mgr.register("a1", _mk_adapter(9))
    # with every byte pinned, registration fails loudly
    mgr.acquire("a3")
    with pytest.raises(ValueError, match="pinned"):
        mgr.register("a4", _mk_adapter(4))
    # release keeps residency: the next acquire is a HIT, not a load
    mgr.release(row)
    loads = mgr.loads
    assert mgr.acquire("a1") == row
    assert mgr.loads == loads and mgr.hits == 1
    mgr.release(row)
    with pytest.raises(ValueError, match="below zero"):
        mgr.release(row)
        mgr.release(row)
    mgr.audit()


def test_manager_crc_corrupt_is_a_loud_reload():
    mgr = _bare_manager()
    mgr.register("a1", _mk_adapter(1))
    mgr.corrupt_entry("a1")
    with pytest.raises(KeyError, match="checksum"):
        mgr.acquire("a1")
    # the record is DROPPED — a retry cannot silently serve the
    # corrupt bytes — and a re-register reloads cleanly
    assert not mgr.contains("a1")
    assert mgr.corruptions_detected == 1
    mgr.register("a1", _mk_adapter(1))
    assert mgr.acquire("a1") == 1
    mgr.audit({1: 1})


# ------------------------------------------------- bitwise + program pins
def test_adapter_none_bitwise_with_program_pins(lm_and_params):
    base = _mk_engine(lm_and_params, lora=None)
    lled = _mk_engine(lm_and_params)        # LoRA on, nothing bound
    jobs = [(p, None) for p in _prompts(4)]
    b_toks, _ = _run_jobs(base, jobs)
    l_toks, _ = _run_jobs(lled, jobs)
    assert l_toks == b_toks, \
        "a LoRA engine with no adapter bound must be BITWISE the base"
    assert lled.compiled_programs == base.compiled_programs, \
        "the LoRA tier moved the program-count pin"


def test_heterogeneous_batch_one_invocation_per_slot_isolated(
        lm_and_params):
    """The tentpole pin: a mixed-adapter batch (base + a1 + a2 across
    the slots) decodes through the SAME compiled programs as the
    adapter-less engine — and each request's stream is bitwise what a
    per-adapter sequential run produces at identical geometry."""
    prompts = _prompts(6)
    jobs = [(prompts[0], None), (prompts[1], "a1"), (prompts[2], "a2"),
            (prompts[3], "a1"), (prompts[4], None), (prompts[5], "a2")]
    eng = _mk_engine(lm_and_params)
    mixed, reqs = _run_jobs(eng, jobs)
    assert all(r.status is RequestStatus.FINISHED for r in reqs)
    base = _mk_engine(lm_and_params, lora=None)
    _run_jobs(base, [(p, None) for p, _ in jobs])
    assert eng.compiled_programs == base.compiled_programs, \
        "adapter count leaked into the trace key set"
    # the adapters actually do something: a1 jobs differ from base
    b_toks, _ = _run_jobs(_mk_engine(lm_and_params, lora=None),
                          [(prompts[1], None)])
    assert mixed[1] != b_toks[0], "bound adapter had no effect"
    # per-adapter sequential runs, identical geometry: bitwise
    for group in (None, "a1", "a2"):
        gjobs = [(p, ad) for p, ad in jobs if ad == group]
        gtoks, _ = _run_jobs(_mk_engine(lm_and_params), gjobs)
        want = [mixed[k] for k, (_, ad) in enumerate(jobs)
                if ad == group]
        assert gtoks == want, f"adapter group {group!r} not isolated"
    eng.lora_audit()                        # zero bindings at drain
    assert PoolAuditor().audit(eng)["pages_in_use"] == 0


def test_arena_full_holds_fifo_and_degrades_gracefully(lm_and_params):
    """Three adapters through a one-row arena: binds beyond capacity
    return False (never an exception), the scheduler holds the queue
    FIFO, and everything finishes as rows free up."""
    cfg = LoRAConfig(rank=RANK, arena_slots=1, host_bytes=1 << 22)
    eng = _mk_engine(lm_and_params, lora=cfg,
                     register=("a1", "a2", "a3"))
    prompts = _prompts(4)
    jobs = [(prompts[0], "a1"), (prompts[1], "a2"),
            (prompts[2], "a3"), (prompts[3], "a1")]
    toks, reqs = _run_jobs(eng, jobs)
    assert all(r.status is RequestStatus.FINISHED for r in reqs)
    assert eng.lora.evictions >= 2          # real churn happened
    eng.lora_audit()
    assert PoolAuditor().audit(eng)["pages_in_use"] == 0


def test_unknown_adapter_fails_loudly(lm_and_params):
    eng = _mk_engine(lm_and_params)
    toks, reqs = _run_jobs(eng, [(_prompts(1)[0], "nope")])
    assert reqs[0].status is RequestStatus.FAILED
    assert "nope" in reqs[0].error and toks[0] == [], \
        "an unknown adapter must never decode (no base-model fallback)"


def test_adapter_on_loraless_engine_rejected_at_submit(lm_and_params):
    eng = _mk_engine(lm_and_params, lora=None)
    with pytest.raises(ValueError, match="without lora"):
        Scheduler(eng).submit(Request(prompt=[1, 2, 3],
                                      max_new_tokens=2, adapter="a1"))


def test_corrupt_record_fails_request_then_reloads(lm_and_params):
    """The swap_corruption contract for adapter records: a corrupt
    host record fails the NEXT cold bind loudly (request FAILED, the
    record dropped) — never wrong tokens — and a re-register serves
    the stream bitwise clean."""
    prompt = _prompts(1)[0]
    oracle, _ = _run_jobs(_mk_engine(lm_and_params), [(prompt, "a1")])
    eng = _mk_engine(lm_and_params)
    eng.lora.corrupt_entry("a1")
    toks, reqs = _run_jobs(eng, [(prompt, "a1")])
    assert reqs[0].status is RequestStatus.FAILED
    assert "checksum" in reqs[0].error and toks[0] == []
    assert eng.lora.corruptions_detected == 1
    # loud reload: re-register, serve again, bitwise the clean run
    eng.lora_register("a1", _mk_adapter(_ADAPTERS["a1"]), alpha=0.7)
    toks, reqs = _run_jobs(eng, [(prompt, "a1")])
    assert reqs[0].status is RequestStatus.FINISHED
    assert toks[0] == oracle[0]
    eng.lora_audit()


def test_adapter_churn_chaos_drains_leak_free(lm_and_params):
    """Seeded fault stream over adapter churn (3 adapters, 2 arena
    rows, transient chunk/decode exceptions + a non-finite injection):
    every request reaches a terminal state, retried requests re-serve
    bitwise (greedy is deterministic), and the drain leaves zero
    leaked pages AND a clean arena refcount audit."""
    prompts = _prompts(6, seed=11)
    jobs = [(prompts[0], "a1"), (prompts[1], "a2"), (prompts[2], None),
            (prompts[3], "a3"), (prompts[4], "a1"), (prompts[5], "a3")]
    oracle, _ = _run_jobs(
        _mk_engine(lm_and_params, register=("a1", "a2", "a3")), jobs)
    plan = FaultPlan([
        FaultSpec(kind="exception", tick=2, site="chunk"),
        FaultSpec(kind="nonfinite", tick=3, slot=1),
        FaultSpec(kind="exception", tick=5, site="decode", slot=0),
    ])
    eng = _mk_engine(lm_and_params, register=("a1", "a2", "a3"))
    toks, reqs = _run_jobs(eng, jobs,
                           sched_kw={"fault_plan": plan})
    assert all(r.status.terminal for r in reqs)
    for k, r in enumerate(reqs):
        if r.status is RequestStatus.FINISHED:
            assert toks[k] == oracle[k], \
                f"request {k} (adapter={jobs[k][1]!r}) drifted " \
                "under faulted churn"
    assert PoolAuditor().audit(eng)["pages_in_use"] == 0, \
        "the churn leaked pages"
    stats = eng.lora_audit()                # raises on refcount drift
    assert stats["bytes_used"] == sum(
        a.nbytes + b.nbytes for nm in ("a1", "a2", "a3")
        for a, b in _mk_adapter(_ADAPTERS[nm]).values()), \
        "the churn leaked arena bytes"


# --------------------------------------------------------------- routing
def test_request_wire_carries_adapter():
    r = Request(prompt=[1, 2], max_new_tokens=2, adapter="tenant-7")
    back = request_from_wire(request_to_wire(r))
    assert back.adapter == "tenant-7"
    assert request_from_wire(
        request_to_wire(Request(prompt=[1], max_new_tokens=1))
    ).adapter is None


def test_rank_replicas_adapter_affinity():
    """A resident-adapter hit ranks right after the prefix match:
    it beats free slots, and a longer prefix match still beats it.
    ``adapter_hits=None`` preserves the pre-LoRA ordering exactly."""
    snaps = {i: {"slots_free": s, "queue_depth": 0, "pages_free": None,
                 "host_bytes_free": None}
             for i, s in ((0, 4), (1, 1))}
    lens = {0: 0, 1: 0}
    assert rank_replicas([0, 1], lens, snaps) == [0, 1]
    assert rank_replicas([0, 1], lens, snaps,
                         adapter_hits={0: 0, 1: 1}) == [1, 0]
    # prefix affinity still dominates
    assert rank_replicas([0, 1], {0: 2, 1: 0}, snaps,
                         adapter_hits={0: 0, 1: 1}) == [0, 1]


def test_router_routes_to_the_resident_adapter(lm_and_params):
    """Adapter affinity on the in-process front: with equal load and
    no prefix signal, a request lands on the replica whose arena
    already holds its adapter (replica 1 here — index order would
    pick 0)."""
    engines = [_mk_engine(lm_and_params, slots=2) for _ in range(2)]
    # warm replica 1's arena: bind+release leaves a1 RESIDENT there
    assert engines[1].lora_bind(0, "a1")
    engines[1].lora_unbind(0)
    assert engines[1].resident_adapters() == ["a1"]
    router = Router(engines)
    r = Request(prompt=_prompts(1)[0], max_new_tokens=3, adapter="a1")
    router.submit(r)
    assert router.placements[r.uid] == 1
    while router.pending:
        router.step()
    assert r.status is RequestStatus.FINISHED
    # base-model requests rank exactly as before (index tie-break)
    r2 = Request(prompt=_prompts(1)[0], max_new_tokens=3)
    router.submit(r2)
    assert router.placements[r2.uid] == 0
    while router.pending:
        router.step()
    router.close()


def test_snapshot_reports_resident_adapters(lm_and_params):
    eng = _mk_engine(lm_and_params)
    sched = Scheduler(eng)
    assert sched.load_snapshot()["resident_adapters"] == []
    assert eng.lora_bind(0, "a2")
    assert sched.load_snapshot()["resident_adapters"] == ["a2"]
    eng.lora_unbind(0)
    base = _mk_engine(lm_and_params, lora=None)
    assert Scheduler(base).load_snapshot()["resident_adapters"] is None


# ----------------------------------------------------------- composition
def test_composes_with_quant_and_speculative(lm_and_params):
    """kv_quant + weight_quant + speculative verify, LoRA on: the
    no-adapter stream matches the same-config LoRA-less engine
    bitwise (the int8 tiers quantize identically — the zero row adds
    +0.0 AFTER the dequant epilogue), and bound adapters still
    isolate per slot."""
    kw = dict(kv_quant=KVQuantConfig(), weight_quant=WeightQuantConfig(),
              spec=SpecConfig(draft_len=3, ngram=2))
    prompts = _prompts(4, seed=3)
    jobs = [(prompts[0], None), (prompts[1], "a1"),
            (prompts[2], "a2"), (prompts[3], "a1")]
    base = _mk_engine(lm_and_params, lora=None, **kw)
    b_toks, _ = _run_jobs(base, [(p, None) for p, _ in jobs],
                          sched_kw={"speculative": True}, budget=8)
    eng = _mk_engine(lm_and_params, **kw)
    toks, reqs = _run_jobs(eng, jobs, sched_kw={"speculative": True},
                           budget=8)
    assert all(r.status is RequestStatus.FINISHED for r in reqs)
    assert toks[0] == b_toks[0], \
        "adapter=None drifted under kv_quant+weight_quant+spec"
    assert toks[1] != b_toks[1], "adapter inert under the quant tiers"
    assert eng.compiled_programs == base.compiled_programs
    solo, _ = _run_jobs(_mk_engine(lm_and_params, **kw),
                        [(prompts[1], "a1")],
                        sched_kw={"speculative": True}, budget=8)
    assert solo[0] == toks[1], "mixed vs sequential drifted under spec"


def _mesh(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} virtual devices")
    return Mesh(np.array(devs[:n]), ("tp",))


def test_tp1_mesh_bitwise(lm_and_params):
    """A 1-device mesh LoRA engine is the same serving engine: the
    no-adapter stream AND a bound-adapter stream are bitwise the
    mesh=None LoRA engine's."""
    prompts = _prompts(3, seed=7)
    jobs = [(prompts[0], None), (prompts[1], "a1"), (prompts[2], "a2")]
    plain, _ = _run_jobs(_mk_engine(lm_and_params), jobs)
    meshed, reqs = _run_jobs(_mk_engine(lm_and_params, mesh=_mesh(1)),
                             jobs)
    assert all(r.status is RequestStatus.FINISHED for r in reqs)
    assert meshed == plain


@pytest.mark.slow
def test_tp2_mesh_token_exact(lm_and_params):
    """The sharded arena (A column-split, B row-split, qkv B
    head-group-permuted) over 2 shards: token-exact vs the single-chip
    LoRA engine on a mixed-adapter stream — the existing post-proj /
    post-mlp psums restore the row-parallel partial sums."""
    prompts = _prompts(4, seed=9)
    jobs = [(prompts[0], None), (prompts[1], "a1"),
            (prompts[2], "a2"), (prompts[3], "a1")]
    plain, _ = _run_jobs(_mk_engine(lm_and_params), jobs)
    sharded, reqs = _run_jobs(_mk_engine(lm_and_params, mesh=_mesh(2)),
                              jobs)
    assert all(r.status is RequestStatus.FINISHED for r in reqs)
    assert sharded == plain
