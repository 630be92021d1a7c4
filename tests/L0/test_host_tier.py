"""Hierarchical KV — the host-DRAM prefix tier, hermetic.

The acceptance bar from the host-tier issue (+ the async/mesh
migration issue), as tests:

- a hit-after-swap greedy stream is **bitwise identical** to a
  never-swapped one, across prefix lengths below / at / straddling the
  block boundary (the swap round-trips exact bytes through the same
  compiled programs — storage moved, nothing recomputed);
- swap-out is ASYNC by default (dispatch on the admission path, the
  force/CRC/store on a ``SwapWorker`` thread) and bitwise identical
  to the ``sync_swap=True`` escape hatch — including a hit that lands
  while the bytes are still in flight (the *swapping* state: the hit
  JOINS the copy, never reads partial bytes) and a chaos
  ``swap_corruption`` racing the in-flight swap (verified miss,
  never a wrong token); a kill with a non-empty swap queue drains
  leak-free and no worker threads leak across construct/serve/close;
- the mesh restriction is LIFTED: a tp=1 mesh host-tier engine is
  bitwise vs ``mesh=None``, tp=2 (slow) is token-exact with
  per-shard arena records (one CRC per shard), and compiled HLO of
  BOTH swap programs carries ZERO collectives (swap is pure data
  movement — each shard moves its own heads slice);
- the tier adds AT MOST one compiled program PER DIRECTION (the
  fixed-shape ``swap_out`` page-block gather and ``swap_in`` scatter
  — one dispatch each, shape-padded to max_pages so no entry size
  can trace a second copy; the chunk/decode/prefill/verify set is
  untouched);
- zero leaked pages at drain across swap churn: the
  :class:`~apex_tpu.serving.PoolAuditor`'s device walk reconciles, and
  its new cross-tier walk reconciles host-arena entries against the
  prefix cache's swapped state (and is SENSITIVE: fabricated dangling /
  orphaned / drifted states raise);
- the host arena is capacity-bounded with its own LRU: an insert that
  does not fit evicts least-recently-put entries (whose index entries
  are dropped — never left dangling), and an entry bigger than the
  whole arena is declined (destroy fallback, the pre-tier behaviour);
- composition pins: ``kv_quant`` int8 pages swap out and restore
  byte-exact (half the transfer bytes for free), and the
  :class:`~apex_tpu.serving.Router`'s affinity probe still sees
  swapped prefixes (a swapped entry is warm state, not a cold miss);
- chaos: the ``swap_corruption`` fault kind (seeded,
  replay-compatible — rate 0 skips the draw) corrupts arena bytes and
  the next swap-in degrades to a VERIFIED MISS (re-prefill, counted as
  ``serving.swap.verify_failed``, hit/miss accounting reversed) —
  never a wrong token.

Everything runs on CPU with a tiny model at policy O0 (exact fp32).
"""

import threading
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, FaultPlan, FaultSpec, HostTier,
                              PoolAuditor, PoolInvariantError,
                              PrefixCache, Request, Router, Scheduler)

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

VOCAB = 101
CHUNK = 8          # chunk_len == page_len: every chunk is one page
# tiny-model page bytes: layers(2) * heads(4) * page_len(8) * head_dim(8)
# * fp32(4) * K-and-V(2) — the arena-capacity arithmetic below
PAGE_BYTES = 2 * 4 * 8 * 8 * 4 * 2


def _tiny_lm(max_seq_len=64, **kw):
    return TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                         num_heads=4, max_seq_len=max_seq_len, **kw)


@pytest.fixture(scope="module")
def lm_and_params():
    m = _tiny_lm()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _mk_engine(lm_and_params, *, pool=2, slots=3, seed=5, **kw):
    m, params = lm_and_params
    return Engine(m, params, slots=slots, max_len=64, prefill_len=24,
                  chunk_len=CHUNK, prefix_pool=pool,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  **kw)


@pytest.fixture(scope="module")
def engine_pair(lm_and_params):
    """One hierarchical engine (host tier on) + one plain engine —
    identical geometry, so a hit-after-swap stream and a never-swapped
    stream compare bitwise (jit caches warm across the module)."""
    return (_mk_engine(lm_and_params, host_tier=1 << 24),
            _mk_engine(lm_and_params))


# -------------------------------------------------------- arena (pure host)
def _fake_pages(rng, m=2, dtype=np.float32):
    shape = (2, m, 4, 8, 8)         # [layers, m, heads, page_len, d]
    return (rng.normal(size=shape).astype(dtype),
            rng.normal(size=shape).astype(dtype))


def test_host_tier_put_take_contains_and_lru_capacity():
    rng = np.random.default_rng(0)
    k, v = _fake_pages(rng)
    nbytes = k.nbytes + v.nbytes
    evicted = []
    tier = HostTier(2 * nbytes + 1, on_evict=evicted.append)
    assert tier.put(-1, k, v) and tier.put(-2, *_fake_pages(rng))
    assert tier.size == 2 and tier.bytes_used == 2 * nbytes
    assert tier.contains(-1) and not tier.contains(-9)
    assert tier.nbytes_of(-1) == nbytes and tier.nbytes_of(-9) == 0
    # a third insert exceeds the bound: the least-recently-put entry
    # (-1) is evicted and its owner notified
    assert tier.put(-3, *_fake_pages(rng))
    assert evicted == [-1] and not tier.contains(-1)
    assert tier.bytes_used == 2 * nbytes <= tier.capacity_bytes
    assert tier.evictions == 1
    # an entry alone bigger than the arena is DECLINED, nothing evicted
    big = HostTier(nbytes - 1)
    assert not big.put(-7, k, v)
    assert big.declined == 1 and big.size == 0
    # take pops and verifies
    rec = tier.take(-2)
    assert rec is not None and rec.valid and not tier.contains(-2)
    assert tier.take(-2) is None
    with pytest.raises(ValueError, match="capacity_bytes"):
        HostTier(0)
    tier.clear()
    assert tier.size == 0 and tier.bytes_used == 0


def test_host_tier_checksum_detects_corruption():
    rng = np.random.default_rng(1)
    tier = HostTier(1 << 20)
    tier.put(-1, *_fake_pages(rng))
    tier.put(-2, *_fake_pages(rng))
    tier.corrupt_entry(-1)
    bad, good = tier.take(-1), tier.take(-2)
    assert bad is not None and not bad.valid
    assert good is not None and good.valid
    assert tier.corruptions_detected == 1
    with pytest.raises(KeyError):
        tier.corrupt_entry(-99)


def test_prefix_cache_swap_state_and_pressure_valve():
    """Cache↔tier interplay without an engine: eviction under a wired
    tier is a swap (entry stays matchable/probeable), swapped entries
    are never pressure-valve victims (they hold no device pages — the
    pool loop must not spin on them), and a drop reverses cleanly."""
    released, store = [], {}
    pc = PrefixCache(block_len=4, on_evict=released.extend)
    pc.set_swap_hooks(swap_out=lambda key, pages: store.setdefault(
        key, tuple(pages)) is not None, contains=lambda key: key in store)
    prompt = list(range(10, 22))                     # 3 blocks of 4
    assert pc.register(prompt, pages=(3, 7, 9)) == "registered"
    (key,) = [e.row for e in pc._entries.values()]
    assert pc.evict_lru()                            # swap, not destroy
    assert released == [(3, 7, 9)][0:1] or released == [3, 7, 9]
    assert pc.swapped_keys() == [key] and pc.swap_outs == 1
    # still matchable (swapped=True) and probeable, read-only
    m = pc.match(prompt + [1])
    assert m is not None and m.swapped and m.pages is None \
        and m.length == 12
    assert pc.probe(prompt + [1]) == 12
    # no resident victims left: the valve reports nothing evictable
    # instead of spinning on the page-less swapped entry
    assert not pc.evict_lru()
    # the backing disappearing (tier capacity eviction) makes the next
    # match a miss, not a crash
    store.clear()
    assert pc.match(prompt + [1]) is None
    assert pc.drop(key) and not pc.drop(key)
    assert pc.swapped_keys() == [] and pc.size == 0


# ------------------------------------------------- hit-after-swap, bitwise
def _boundary_cases():
    """(prompt_a, prompt_b, expected_reuse) with shared-prefix lengths
    below / at / straddling the block boundary (block == page == 8) —
    the same sweep the paged-pool tests run, now across a swap."""
    rng = np.random.default_rng(42)
    out = []
    for pre_len, want in [(5, 0), (8, 8), (13, 8), (16, 16)]:
        pre = list(rng.integers(1, VOCAB, size=pre_len))
        out.append((pre + list(rng.integers(1, VOCAB, size=3)),
                    pre + list(rng.integers(1, VOCAB, size=3)), want))
    return out


def test_hit_after_swap_bitwise_vs_never_swapped(engine_pair):
    """THE acceptance pin: register a prefix, force it through a full
    device→host→device round trip, and the hit-after-swap stream must
    be bitwise identical to the never-swapped stream on the plain
    engine — same reuse accounting included."""
    et, ec = engine_pair
    for prompt_a, prompt_b, want_reuse in _boundary_cases():
        et.reset(clear_prefixes=True)
        ec.reset(clear_prefixes=True)
        st = Scheduler(et, retain_prefixes=True)
        sc = Scheduler(ec, retain_prefixes=True)
        (ra_t,) = st.run([Request(prompt=list(prompt_a),
                                  max_new_tokens=5)])
        (ra_c,) = sc.run([Request(prompt=list(prompt_a),
                                  max_new_tokens=5)])
        # every prompt here spans >= 1 block, so prompt_a always
        # registered an entry — eviction must SWAP it, not destroy it
        assert et.prefix_cache.evict_lru()
        assert et.prefix_cache.swapped_keys()
        assert et.host_tier.size == 1
        # the affinity probe still sees the swapped prefix (0 when
        # prompt_b's first block genuinely differs — the 5-token case)
        assert et.prefix_cache.probe(prompt_b) == want_reuse
        (rb_t,) = st.run([Request(prompt=list(prompt_b),
                                  max_new_tokens=5)])
        (rb_c,) = sc.run([Request(prompt=list(prompt_b),
                                  max_new_tokens=5)])
        assert ra_t.output_tokens == ra_c.output_tokens
        assert rb_t.output_tokens == rb_c.output_tokens, \
            f"hit-after-swap diverged (prefix {want_reuse})"
        assert rb_t.reused_tokens == rb_c.reused_tokens == want_reuse
        if want_reuse:
            # restored and re-resident: entry back on fresh pages,
            # arena drained of the migrated record
            assert not et.prefix_cache.swapped_keys()
            assert et.host_tier.size == 0


def test_at_most_one_new_program_per_direction_and_zero_leaks(
        engine_pair):
    """Program-count pin + leak pin, over all the swap churn the
    module has driven so far: the hierarchical engine compiled exactly
    chunk + decode + swap_out + swap_in (TWO more than the plain
    engine's two — one per swap direction, each shape-padded so every
    entry size shares it), and both pools audit clean — then drain to
    zero pages."""
    et, ec = engine_pair
    assert et.chunk_traces == 1 and et.decode_traces == 1
    assert et.swap_in_traces == 1          # every page shares ONE program
    assert et.swap_out_traces == 1         # ... in each direction
    assert et.verify_traces == 0
    assert et.compiled_programs == 4
    assert ec.compiled_programs == 2
    assert ec.swap_in_traces == ec.swap_out_traces == 0
    for eng in engine_pair:
        PoolAuditor().audit(eng)
        eng.reset(clear_prefixes=True)
        assert eng.pool.pages_in_use == 0
        PoolAuditor().audit(eng)
    assert et.host_tier.size == 0 and et.host_tier.bytes_used == 0


def test_engine_host_tier_validation(lm_and_params):
    with pytest.raises(ValueError, match="prefix_pool"):
        _mk_engine(lm_and_params, host_tier=1 << 20, pool=0)
    # a pre-built arena is accepted as-is (capacity honoured)
    eng = _mk_engine(lm_and_params, host_tier=HostTier(1 << 20))
    assert isinstance(eng.host_tier, HostTier)
    assert eng.host_tier.capacity_bytes == 1 << 20


# -------------------------------------------------- capacity + composition
def test_capacity_bounded_arena_evicts_and_drops_entries(lm_and_params):
    """Engine-level capacity bound: an arena sized for ONE two-page
    prefix holds the latest swap-out; swapping a second entry out
    evicts the first's bytes AND drops its index entry (no dangling
    swapped state), with the auditor's cross-tier walk green
    throughout."""
    eng = _mk_engine(lm_and_params, pool=3,
                     host_tier=2 * PAGE_BYTES + 1)
    sched = Scheduler(eng, retain_prefixes=True)
    rng = np.random.default_rng(7)
    pres = [list(rng.integers(1, VOCAB, size=16)) for _ in range(2)]
    for pre in pres:
        sched.run([Request(prompt=pre + [1, 2], max_new_tokens=3)])
    auditor = PoolAuditor()
    assert eng.prefix_cache.evict_lru()        # swap entry 0 out
    auditor.audit(eng)
    assert eng.prefix_cache.evict_lru()        # swap entry 1: evicts 0
    auditor.audit(eng)
    tier = eng.host_tier
    assert tier.size == 1 and tier.evictions == 1
    assert tier.bytes_used <= tier.capacity_bytes
    # entry 0 is GONE from the index (dropped with its bytes): its
    # prefix probes 0, entry 1's still probes through the tier
    assert eng.prefix_cache.probe(pres[0] + [9]) == 0
    assert eng.prefix_cache.probe(pres[1] + [9]) == 16
    assert len(eng.prefix_cache.swapped_keys()) == 1


def test_int8_pages_swap_and_restore_byte_exact(lm_and_params):
    """kv_quant composition: int8 pages ride the tier at half the
    transfer bytes, and the restored device bytes are EXACTLY the
    evicted ones (the whole bitwise argument, at the byte level)."""
    from apex_tpu.serving import KVQuantConfig

    eng = _mk_engine(lm_and_params, host_tier=1 << 24,
                     kv_quant=KVQuantConfig())
    sched = Scheduler(eng, retain_prefixes=True)
    rng = np.random.default_rng(11)
    pre = list(rng.integers(1, VOCAB, size=16))
    sched.run([Request(prompt=pre + [7, 8], max_new_tokens=3)])
    (key,) = list(eng.prefix_cache._entries)
    pages0 = list(eng.prefix_cache._entries[key].pages)
    before_k = np.asarray(eng.cache.k[:, pages0]).copy()
    before_v = np.asarray(eng.cache.v[:, pages0]).copy()
    assert before_k.dtype == np.int8       # half the swap bytes, free
    assert eng.prefix_cache.evict_lru()
    assert eng.host_tier.bytes_used == 2 * PAGE_BYTES // 4   # int8 vs fp32
    (r,) = sched.run([Request(prompt=pre + [9, 10],
                              max_new_tokens=3)])
    assert r.reused_tokens == 16
    pages1 = list(eng.prefix_cache._entries[key].pages)
    np.testing.assert_array_equal(before_k,
                                  np.asarray(eng.cache.k[:, pages1]))
    np.testing.assert_array_equal(before_v,
                                  np.asarray(eng.cache.v[:, pages1]))
    PoolAuditor().audit(eng)


def test_router_affinity_probe_sees_swapped_prefixes(engine_pair):
    """Router composition: a replica whose prefix was swapped to host
    still wins the affinity probe — swap-out moves bytes, not
    routing signal."""
    et, ec = engine_pair
    for eng in engine_pair:
        eng.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    router = Router([et, ec], registry=reg, retain_prefixes=True)
    try:
        rng = np.random.default_rng(13)
        pre = list(rng.integers(1, VOCAB, size=16))
        (r1,) = router.run([Request(prompt=pre + [1, 2],
                                    max_new_tokens=3)])
        # find the replica that served turn 1 and swap its prefix out
        (home,) = [i for i, e in enumerate((et, ec))
                   if e.prefix_cache is not None and e.prefix_cache.size]
        owner = (et, ec)[home]
        if owner.host_tier is not None:
            assert owner.prefix_cache.evict_lru()
            assert owner.prefix_cache.swapped_keys()
        hits0 = reg.snapshot()["counters"].get(
            "serving.router.affinity_hits", 0)
        (r2,) = router.run([Request(prompt=pre + [3, 4],
                                    max_new_tokens=3)])
        hits1 = reg.snapshot()["counters"].get(
            "serving.router.affinity_hits", 0)
        assert hits1 == hits0 + 1          # the probe saw the prefix
        assert r2.reused_tokens == 16
    finally:
        router.close()


# ----------------------------------------------------------------- chaos
def test_swap_corruption_degrades_to_verified_miss(engine_pair):
    """The chaos pin: corrupt arena bytes make the next swap-in fail
    its checksum and the request re-prefills COLD — bitwise identical
    to a cold run, `serving.swap.verify_failed` counted, hit/miss
    accounting reversed, request FINISHED (never failed, never a wrong
    token)."""
    et, ec = engine_pair
    for eng in engine_pair:
        eng.reset(clear_prefixes=True)
    rng = np.random.default_rng(17)
    pre = list(rng.integers(1, VOCAB, size=16))
    p2 = pre + list(rng.integers(1, VOCAB, size=3))
    # cold oracle on the plain engine (no retention: fully cold)
    (oracle,) = Scheduler(ec).run([Request(prompt=list(p2),
                                           max_new_tokens=5)])
    reg = telemetry.MetricsRegistry()
    et.set_registry(reg)
    try:
        sched = Scheduler(et, registry=reg, retain_prefixes=True)
        sched.run([Request(prompt=pre + [7, 8, 9], max_new_tokens=5)])
        assert et.prefix_cache.evict_lru()
        base = dict(et.prefix_cache.stats())
        sched.fault_plan = FaultPlan(
            [FaultSpec(kind="swap_corruption", tick=sched._tick)])
        (r,) = sched.run([Request(prompt=list(p2), max_new_tokens=5)])
        assert r.output_tokens == oracle.output_tokens
        assert r.status == "finished" and r.reused_tokens == 0
        assert sched.fault_plan.injected_swap_corruptions == 1
        assert sched.fault_plan.stats()["injected_swap_corruptions"] == 1
        counters = reg.snapshot()["counters"]
        assert counters.get("serving.swap.verify_failed") == 1
        delta = et.prefix_cache.stats_since(base)
        assert delta["hits"] == 0 and delta["misses"] == 1   # reversed
        # the corrupt entry is gone everywhere; the pool stays clean
        assert not et.prefix_cache.swapped_keys()
        assert et.host_tier.size == 0
        PoolAuditor().audit(et)
    finally:
        et.set_registry(None)


def test_faultplan_swap_corruption_replay_compatible():
    """Rate 0 skips the draw entirely (the PR 12 replica-death
    pattern), so every pre-host-tier seed replays bit-for-bit; a
    positive rate draws the new kind."""
    kw = dict(slots=4, nonfinite_rate=0.3, exception_rate=0.2,
              stall_rate=0.1)
    assert FaultPlan.random(3, 40, **kw).specs \
        == FaultPlan.random(3, 40, swap_corruption_rate=0.0, **kw).specs
    plan = FaultPlan.random(3, 60, slots=4, swap_corruption_rate=0.5)
    assert any(s.kind == "swap_corruption" for s in plan.specs)
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(kind="swap_rot", tick=0)
    # an empty arena makes the injection a consumed no-op
    empty = FaultPlan([FaultSpec(kind="swap_corruption", tick=0)])
    assert not empty.maybe_corrupt_swap(0, HostTier(1 << 10))
    assert empty.injected_swap_corruptions == 0


# --------------------------------------------------------------- auditor
def test_auditor_cross_tier_walk_is_sensitive(engine_pair):
    """The extended conservation audit detects every cross-tier rot it
    claims to: dangling swapped entries, orphaned arena bytes, drifted
    byte accounting, and an over-capacity arena."""
    et, _ = engine_pair
    et.reset(clear_prefixes=True)
    sched = Scheduler(et, retain_prefixes=True)
    rng = np.random.default_rng(23)
    pre = list(rng.integers(1, VOCAB, size=16))
    sched.run([Request(prompt=pre + [1, 2], max_new_tokens=3)])
    assert et.prefix_cache.evict_lru()
    auditor = PoolAuditor()
    auditor.audit(et)                      # consistent: green
    tier = et.host_tier
    (key,) = tier.keys()
    # (1) dangling: swapped entry with no arena backing
    rec = tier._entries.pop(key)
    tier._bytes_used -= rec.nbytes
    with pytest.raises(PoolInvariantError, match="no host-tier backing"):
        auditor.audit(et)
    tier._entries[key] = rec
    tier._bytes_used += rec.nbytes
    auditor.audit(et)
    # (2) orphan: arena bytes backing no swapped entry
    tier._entries[-777] = rec
    tier._bytes_used += rec.nbytes
    with pytest.raises(PoolInvariantError, match="host-side leak"):
        auditor.audit(et)
    del tier._entries[-777]
    tier._bytes_used -= rec.nbytes
    # (3) byte-accounting drift
    tier._bytes_used += 1
    with pytest.raises(PoolInvariantError, match="drifted"):
        auditor.audit(et)
    tier._bytes_used -= 1
    # (4) over-capacity arena
    saved = tier.capacity_bytes
    tier.capacity_bytes = 1
    with pytest.raises(PoolInvariantError, match="over capacity"):
        auditor.audit(et)
    tier.capacity_bytes = saved
    auditor.audit(et)
    et.reset(clear_prefixes=True)


# ----------------------------------------------- async swap-out (tentpole)
def _gate_worker(eng):
    """Block ``eng``'s SwapWorker behind an Event so the NEXT
    eviction's bytes deterministically sit in flight (the *swapping*
    state) until the gate opens."""
    gate = threading.Event()
    eng._swap_worker.submit(("gate", id(gate)), gate.wait)
    return gate


def test_async_default_vs_sync_escape_hatch_bitwise(lm_and_params):
    """THE async acceptance pin: the default (worker-threaded)
    swap-out and the ``sync_swap=True`` escape hatch serve identical
    greedy streams token-for-token — including a hit forced to land
    while its entry's swap-out bytes are STILL IN FLIGHT, which must
    JOIN the copy (counted as ``serving.swap.swap_join_waits``),
    never read partial bytes. Zero leaks, clean cross-tier audits."""
    from apex_tpu import telemetry

    ea = _mk_engine(lm_and_params, host_tier=1 << 24)
    es = _mk_engine(lm_and_params, host_tier=1 << 24, sync_swap=True)
    assert ea._swap_worker is not None and es._swap_worker is None
    reg = telemetry.MetricsRegistry()
    ea.set_registry(reg)
    try:
        rng = np.random.default_rng(31)
        pre = list(rng.integers(1, VOCAB, size=16))
        p1, p2 = pre + [1, 2], pre + [3, 4]
        outs = {}
        for name, eng in (("async", ea), ("sync", es)):
            sched = Scheduler(eng, retain_prefixes=True)
            (r1,) = sched.run([Request(prompt=list(p1),
                                       max_new_tokens=5)])
            gate = _gate_worker(eng) if eng._swap_worker is not None \
                else None
            assert eng.prefix_cache.evict_lru()
            if gate is not None:
                # the swap is dispatched but NOT complete: the entry
                # is in the swapping state — reserved in the arena,
                # still matchable and probeable
                assert eng.host_tier.pending_keys()
                assert eng.host_tier.stats()["swapping"] == 1
                assert eng.prefix_cache.probe(p2) == 16
                threading.Timer(0.1, gate.set).start()
            (r2,) = sched.run([Request(prompt=list(p2),
                                       max_new_tokens=5)])
            outs[name] = (list(r1.output_tokens),
                          list(r2.output_tokens), r2.reused_tokens)
            PoolAuditor().audit(eng)
            assert eng.host_tier.size == 0      # restored + drained
        assert outs["async"] == outs["sync"], \
            "async swap-out diverged from the sync escape hatch"
        assert outs["async"][2] == 16
        counters = reg.snapshot()["counters"]
        assert counters.get("serving.swap.swap_join_waits", 0) >= 1, \
            "the in-flight hit never joined the worker copy"
        assert counters.get("serving.swap.verify_failed", 0) == 0
    finally:
        ea.set_registry(None)
        ea.close()
        es.close()


def test_swap_corruption_racing_inflight_swap(lm_and_params):
    """Chaos × async: a ``swap_corruption`` landing while the victim's
    swap-out bytes are still in flight arms the corruption (it rots
    the bytes the moment the worker stores them), and the racing hit
    degrades to a VERIFIED MISS — bitwise identical to a cold run,
    never a wrong token, pool and arena reconciled."""
    from apex_tpu import telemetry

    eng = _mk_engine(lm_and_params, host_tier=1 << 24)
    cold = _mk_engine(lm_and_params)
    try:
        rng = np.random.default_rng(37)
        pre = list(rng.integers(1, VOCAB, size=16))
        p2 = pre + [5, 6, 7]
        (oracle,) = Scheduler(cold).run([Request(prompt=list(p2),
                                                 max_new_tokens=5)])
        reg = telemetry.MetricsRegistry()
        eng.set_registry(reg)
        sched = Scheduler(eng, registry=reg, retain_prefixes=True)
        sched.run([Request(prompt=pre + [1, 2], max_new_tokens=5)])
        gate = _gate_worker(eng)
        assert eng.prefix_cache.evict_lru()
        assert eng.host_tier.pending_keys()
        # the injection races the in-flight swap: consumed NOW, lands
        # at completion time
        plan = FaultPlan([FaultSpec(kind="swap_corruption", tick=0)])
        assert plan.maybe_corrupt_swap(0, eng.host_tier)
        threading.Timer(0.05, gate.set).start()
        (r,) = sched.run([Request(prompt=list(p2), max_new_tokens=5)])
        assert r.output_tokens == oracle.output_tokens
        assert r.status == "finished" and r.reused_tokens == 0
        counters = reg.snapshot()["counters"]
        assert counters.get("serving.swap.verify_failed") == 1
        assert not eng.prefix_cache.swapped_keys()
        assert eng.host_tier.size == 0
        PoolAuditor().audit(eng)
    finally:
        eng.set_registry(None)
        eng.close()


def test_close_with_nonempty_swap_queue_drains_leak_free(lm_and_params):
    """The kill contract: an engine closed while its swap queue is
    non-empty DRAINS — every queued swap-out completes its arena put
    (the bytes were snapshotted at dispatch), so the cross-tier audit
    walks clean with nothing dangling; the engine stays usable after
    close (swap-outs degrade to inline/sync)."""
    eng = _mk_engine(lm_and_params, pool=3, host_tier=1 << 24)
    sched = Scheduler(eng, retain_prefixes=True)
    rng = np.random.default_rng(41)
    pres = [list(rng.integers(1, VOCAB, size=16)) for _ in range(2)]
    for pre in pres:
        sched.run([Request(prompt=pre + [1, 2], max_new_tokens=3)])
    # host_bytes_free load gauge: full arena headroom before any swap
    snap = sched.load_snapshot()
    assert snap["host_bytes_free"] == eng.host_tier.capacity_bytes
    gate = _gate_worker(eng)
    assert eng.prefix_cache.evict_lru()
    assert eng.prefix_cache.evict_lru()
    assert len(eng.host_tier.pending_keys()) == 2   # both in flight
    assert len(eng._swap_worker.pending_keys()) >= 2
    assert sched.load_snapshot()["host_bytes_free"] \
        < eng.host_tier.capacity_bytes      # reservations count NOW
    threading.Timer(0.05, gate.set).start()
    eng.close()                              # drains, then stops
    assert not eng.host_tier.pending_keys()
    assert eng.host_tier.size == 2
    assert len(eng.prefix_cache.swapped_keys()) == 2
    PoolAuditor().audit(eng)
    # post-close swap-outs run inline (sync degradation, never dropped)
    sched.run([Request(prompt=pres[0] + [9], max_new_tokens=3)])
    PoolAuditor().audit(eng)


def test_no_swap_worker_thread_leaks(lm_and_params):
    """No worker-thread leaks across construct/serve/close; close is
    idempotent; sync_swap engines never start a thread; a plain
    scheduler's load snapshot reads host_bytes_free=None."""
    def workers():
        return sum(t.name == "serving-swap-worker"
                   for t in threading.enumerate())

    base = workers()
    eng = _mk_engine(lm_and_params, host_tier=1 << 22)
    assert workers() == base + 1
    sched = Scheduler(eng, retain_prefixes=True)
    sched.run([Request(prompt=list(range(1, 18)), max_new_tokens=3)])
    eng.close()
    eng.close()                              # idempotent
    assert workers() == base
    es = _mk_engine(lm_and_params, host_tier=1 << 22, sync_swap=True)
    assert workers() == base and es._swap_worker is None
    plain = _mk_engine(lm_and_params)
    assert Scheduler(plain).load_snapshot()["host_bytes_free"] is None
    es.close()


# ------------------------------------------------------- mesh composition
def _mesh(n: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} virtual devices")
    return Mesh(np.array(devs[:n]), ("tp",))


VOCAB_TP = 96       # divisible by the tp sizes under test (1, 2)


@pytest.fixture(scope="module")
def tp_lm_and_params():
    """A tp-divisible tiny model (vocab 96) for the tp>1 mesh tests —
    the module default's 101-token vocab cannot split over 2 shards."""
    m = TransformerLM(vocab_size=VOCAB_TP, hidden=32, num_layers=2,
                      num_heads=4, max_seq_len=64)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _serve_swap_stream(eng, seed=42, vocab=VOCAB):
    """One register → evict(=swap) → hit-after-swap stream; returns
    every request's tokens + the hit's reuse accounting."""
    rng = np.random.default_rng(seed)
    pre = list(rng.integers(1, vocab, size=16))
    p1, p2 = pre + [1, 2, 3], pre + [4, 5, 6]
    sched = Scheduler(eng, retain_prefixes=True)
    (r1,) = sched.run([Request(prompt=list(p1), max_new_tokens=5)])
    assert eng.prefix_cache.evict_lru()
    (r2,) = sched.run([Request(prompt=list(p2), max_new_tokens=5)])
    PoolAuditor().audit(eng)
    return (list(r1.output_tokens), list(r2.output_tokens),
            r2.reused_tokens)


def test_mesh_tp1_host_tier_bitwise_vs_unsharded(lm_and_params):
    """The mesh-lift pin, fast half: a tp=1 mesh host-tier engine
    (shard_map-wrapped swap programs over one device) serves the
    register → swap → hit-after-swap stream BITWISE identical to the
    unsharded ``mesh=None`` host-tier engine, one compiled program per
    swap direction on both."""
    em = _mk_engine(lm_and_params, mesh=_mesh(1), host_tier=1 << 24)
    e0 = _mk_engine(lm_and_params, host_tier=1 << 24)
    try:
        om, o0 = _serve_swap_stream(em), _serve_swap_stream(e0)
        assert om == o0, "tp=1 mesh host tier diverged from mesh=None"
        assert om[2] == 16
        for eng in (em, e0):
            assert eng.swap_out_traces == 1
            assert eng.swap_in_traces == 1
    finally:
        em.close()
        e0.close()


@pytest.mark.slow
def test_mesh_tp2_host_tier_token_exact_with_per_shard_records(
        tp_lm_and_params):
    """The mesh-lift pin, tp=2 half (CPU device emulation): the same
    swap stream is token-exact vs mesh=None, and the arena records are
    PER-SHARD — ``shards == tp`` with one CRC per shard, each
    independently verifying exactly its shard's heads slice of the
    stored bytes."""
    em = _mk_engine(tp_lm_and_params, mesh=_mesh(2), host_tier=1 << 24)
    e0 = _mk_engine(tp_lm_and_params, host_tier=1 << 24)
    try:
        assert _serve_swap_stream(em, vocab=VOCAB_TP) \
            == _serve_swap_stream(e0, vocab=VOCAB_TP)
        # force a fresh swap-out and inspect the resident record
        rng = np.random.default_rng(7)
        pre = list(rng.integers(1, VOCAB_TP, size=16))
        Scheduler(em, retain_prefixes=True).run(
            [Request(prompt=pre + [9], max_new_tokens=3)])
        assert em.prefix_cache.evict_lru()
        em._swap_worker.drain()
        (key,) = em.host_tier.keys()
        rec = em.host_tier._entries[key]
        assert rec.shards == 2 and len(rec.crc) == 2
        # each CRC covers exactly its shard's heads slice (K then V)
        heads = rec.k.shape[2]
        for t in range(2):
            sl = slice(t * heads // 2, (t + 1) * heads // 2)
            want = zlib.crc32(
                np.ascontiguousarray(rec.v[:, :, sl]).tobytes(),
                zlib.crc32(
                    np.ascontiguousarray(rec.k[:, :, sl]).tobytes()))
            assert rec.crc[t] == want, f"shard {t} CRC drifted"
        # and per-shard verification is SENSITIVE: rot one shard's
        # bytes and the take must flag the record invalid
        em.host_tier.corrupt_entry(key)
        bad = em.host_tier.take(key)
        assert bad is not None and not bad.valid
        em.prefix_cache.drop(key)
        PoolAuditor().audit(em)
    finally:
        em.close()
        e0.close()


@pytest.mark.slow
def test_swap_programs_compile_zero_collectives(tp_lm_and_params):
    """The collective pin: compiled HLO of BOTH sharded swap programs
    (tp=2) contains ZERO collectives — swap is pure data movement,
    each shard gathers/scatters its own heads/tp slice of the pool.
    A dedicated engine (``.lower()`` re-traces, which must not touch
    the shared fixtures' trace pins)."""
    import re as _re

    eng = _mk_engine(tp_lm_and_params, mesh=_mesh(2),
                     host_tier=1 << 24)
    try:
        ids = jnp.zeros(eng.max_pages, jnp.int32)
        c = eng.cache
        blk = jnp.zeros((c.layers, eng.max_pages, c.heads, c.page_len,
                         c.head_dim), c.dtype)

        def ncoll(txt):
            return len(_re.findall(
                r"= \S+ (all-reduce|all-gather|collective-permute|"
                r"all-to-all)\(", txt))

        out_hlo = eng._jit_swap_out.lower(
            eng.cache, ids).compile().as_text()
        in_hlo = eng._jit_swap_in.lower(
            eng.cache, blk, blk, ids).compile().as_text()
        assert ncoll(out_hlo) == 0, "swap-out grew a collective"
        assert ncoll(in_hlo) == 0, "swap-in grew a collective"
    finally:
        eng.close()


def test_router_probe_hits_swapping_entry_on_mesh_replica(
        lm_and_params):
    """Router × host-tier × mesh (the composition the mesh=None
    restriction made untestable): an affinity probe landing on a
    *swapping*-state entry — swap-out bytes still in flight — of a
    MESH-SHARDED replica routes the request home, the hit joins the
    copy, and the stream is bitwise identical to a never-swapped hit
    on an identically-built bare scheduler."""
    from apex_tpu import telemetry

    em = _mk_engine(lm_and_params, mesh=_mesh(1), host_tier=1 << 24)
    ep = _mk_engine(lm_and_params)
    eo = _mk_engine(lm_and_params, mesh=_mesh(1), host_tier=1 << 24)
    reg = telemetry.MetricsRegistry()
    router = Router([em, ep], registry=reg, retain_prefixes=True)
    try:
        rng = np.random.default_rng(53)
        pre = list(rng.integers(1, VOCAB, size=16))
        p1, p2 = pre + [1, 2], pre + [3, 4]
        # the never-swapped oracle: same stream, plain hit
        so = Scheduler(eo, retain_prefixes=True)
        (o1,) = so.run([Request(prompt=list(p1), max_new_tokens=5)])
        (o2,) = so.run([Request(prompt=list(p2), max_new_tokens=5)])
        # turn 1 routes to replica 0 (cold caches: least-loaded tie →
        # lowest index) and registers its prefix there
        (r1,) = router.run([Request(prompt=list(p1), max_new_tokens=5)])
        assert router.placements[r1.uid] == 0
        assert em.prefix_cache.size == 1
        # squeeze the home replica: the entry enters the swapping
        # state (swap dispatched, bytes gated in flight)
        gate = _gate_worker(em)
        assert em.prefix_cache.evict_lru()
        assert em.host_tier.pending_keys()
        hits0 = reg.snapshot()["counters"].get(
            "serving.router.affinity_hits", 0)
        threading.Timer(0.1, gate.set).start()
        (r2,) = router.run([Request(prompt=list(p2), max_new_tokens=5)])
        hits1 = reg.snapshot()["counters"].get(
            "serving.router.affinity_hits", 0)
        assert hits1 == hits0 + 1, "probe missed the swapping entry"
        assert router.placements[r2.uid] == 0, "request routed away " \
            "from its swapping prefix"
        assert r2.reused_tokens == 16
        assert r1.output_tokens == o1.output_tokens
        assert r2.output_tokens == o2.output_tokens, \
            "hit-through-swapping-state diverged"
        # the tie-break input is dashboard-visible per replica
        assert "serving.router.replica0.host_bytes_free" \
            in reg.snapshot()["gauges"]
        PoolAuditor().audit(em)
    finally:
        router.close()
        eo.close()
