"""Content-addressed KV prefix reuse (PR 5), hermetic.

The acceptance bar from the issue, as tests:

- a prefix-cache HIT is bitwise token-exact against BOTH the cold
  chunked path and one teacher-forcing full recompute, for shared
  prefixes below / at / straddling a block boundary (and for a prompt
  that is entirely cached, where the final block must still prefill —
  sharing pages produces no logits to sample from);
- a request stream exercising hit, miss and eviction is served by
  exactly TWO compiled programs (chunk prefill + decode), pinned by
  trace counters;
- LRU eviction with refcount pinning: a pinned entry is never evicted,
  and evicting the donor of a live hit is harmless (its pages carry
  their own refcounts);
- telemetry carries ``serving.prefix.*`` and the per-request completion
  record carries ``reused_tokens``.

Everything runs on CPU with a tiny model at policy O0 (exact fp32), the
same shared-program discipline as test_serving.py: the hit path and the
cold path literally execute the same XLA programs, so exactness is
bitwise, not approximately. The page-level half of the story —
copy-on-write refcounts, pool exhaustion — lives in
tests/L0/test_paged_kv.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, PrefixCache, PrefixMatch, Request,
                              Scheduler)

pytestmark = pytest.mark.serving

VOCAB = 101
CHUNK = 8


def _tiny_lm(max_seq_len=128, **kw):
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
    return Engine(m, params, slots=slots, max_len=128, prefill_len=24,
                  chunk_len=CHUNK, prefix_pool=pool,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  **kw)


@pytest.fixture(scope="module")
def pool2_pair(lm_and_params):
    """One retain-capable engine + one cold-reference engine (identical
    geometry, pool=2), shared across the e2e tests — each test starts
    from reset(clear_prefixes=True), and jit caching means the compile
    cost is paid once for the whole module."""
    return _mk_engine(lm_and_params), _mk_engine(lm_and_params)


@pytest.fixture(scope="module")
def tight_engine(lm_and_params):
    """Shared engine whose pool holds ONE max_len request and nothing
    more (16 pages + the sentinel): a long request's admission evicts
    the retained prefixes (eviction-pressure tests)."""
    return _mk_engine(lm_and_params, pool=1, num_pages=17)


# --------------------------------------------------- host-side PrefixCache
def _pc():
    return PrefixCache(block_len=4)


def _pages(prompt, first=1):
    """Page ids for ``prompt``'s block-aligned prefix at page_len 4."""
    return list(range(first, first + len(prompt) // 4))


def test_match_is_block_aligned_and_capped_below_the_prompt():
    pc = _pc()
    # 10 tokens -> 2 full blocks retained, on two pages
    assert pc.register(list(range(1, 11)), pages=[5, 6]) == "registered"
    # identical 8-token prefix, longer prompt: match the 2 blocks
    m = pc.match(list(range(1, 9)) + [77, 78, 79])
    assert (m.length, m.pages) == (8, (5, 6))
    # the whole prompt cached (exact 8 tokens): cap at aligned(7) = 4 —
    # the final block must prefill to produce the first token's logits
    m = pc.match(list(range(1, 9)))
    assert (m.length, m.pages) == (4, (5,))
    # shares only one block
    assert pc.match([1, 2, 3, 4] + [9, 9, 9, 9, 9]).length == 4
    # diverges inside the first block: miss
    assert pc.match([1, 2, 3, 99, 5, 6, 7, 8, 9]) is None
    # shorter than one block + 1: nothing block-aligned to reuse
    assert pc.match([1, 2, 3, 4]) is None
    assert pc.hits == 3 and pc.misses == 2
    assert pc.tokens_reused == 8 + 4 + 4


def test_probe_reads_like_match_but_mutates_nothing():
    """The router's affinity probe: identical verified-longest-prefix
    answer as match(), with ZERO bookkeeping — no hit/miss counters, no
    LRU refresh, no refcounts. A probe that counted would poison every
    non-chosen replica's hit_rate N-1 times per routed request."""
    pc = _pc()
    pc.register(list(range(1, 11)), pages=[5, 6])
    prompt = list(range(1, 9)) + [77, 78, 79]
    m = pc.match(prompt)
    assert pc.probe(prompt) == m.length == 8
    stats0 = pc.stats()
    clock0 = pc._entries[m.row].last_used
    # hits, misses and LRU order are all untouched by any probe outcome
    assert pc.probe(prompt) == 8
    assert pc.probe([5, 5, 5, 5, 5]) == 0          # a miss probes as 0
    assert pc.probe(prompt, keys=pc.block_keys(prompt, 2)) == 8
    assert pc.stats() == stats0
    assert pc._entries[m.row].last_used == clock0
    # and the verified-tokens guarantee holds: a would-be hash hit over
    # different tokens probes as 0, never a wrong length
    assert pc.probe([1, 2, 3, 99] + list(range(5, 12))) == 0


def test_stats_since_reads_window_deltas_across_warm_resets():
    """The counters are run-scoped on purpose (they survive clear() and
    warm engine resets), so per-window accounting — the router's
    per-replica affinity rates, the bench's measured windows — must be
    a delta: stats_since(baseline) isolates the window, where reading
    hit_rate directly would blend every prior window in."""
    pc = _pc()
    pc.register([1] * 8, pages=[1, 2])
    assert pc.match([1] * 9) is not None            # warmup hit
    assert pc.match([7] * 9) is None                # warmup miss
    base = pc.stats()
    # a warm reset drops entries but NOT counters — the PR 11 quirk
    pc.clear()
    assert pc.hits == 1 and pc.misses == 1
    pc.register([2] * 8, pages=[3, 4])
    assert pc.match([2] * 12) is not None
    assert pc.match([2] * 12) is not None
    assert pc.match([9] * 9) is None
    delta = pc.stats_since(base)
    assert delta["hits"] == 2 and delta["misses"] == 1
    assert delta["hit_rate"] == pytest.approx(2 / 3)
    assert delta["registrations"] == 1
    assert delta["tokens_reused"] == 16
    # the cumulative view is (deliberately) different from the window's
    assert pc.hit_rate == pytest.approx(3 / 5)
    # occupancy is state, not a counter: reported as-of-now
    assert delta["entries"] == pc.size == 1
    # an empty window reads all-zero, hit_rate 0.0 (not NaN/raise)
    empty = pc.stats_since(pc.stats())
    assert empty["hits"] == empty["misses"] == 0
    assert empty["hit_rate"] == 0.0


def test_register_dedupes_and_rejects_too_short():
    pc = _pc()
    assert pc.register([1, 2, 3], pages=[1]) == "too_short"
    assert pc.register(list(range(1, 10)), pages=[1, 2]) == "registered"
    # same aligned prefix again (different tail): no second entry
    assert pc.register(list(range(1, 9)) + [55], pages=[3, 4]) \
        == "duplicate"
    assert pc.size == 1 and pc.registrations == 1
    assert pc.page_holds() == [(1, 2)]


def test_lru_eviction_prefers_least_recently_used():
    freed = []
    pc = PrefixCache(block_len=4, on_evict=freed.append)
    a, b, c = ([1] * 8), ([2] * 8), ([3] * 8)
    assert pc.register(a, pages=[1, 2]) == "registered"
    assert pc.register(b, pages=[3, 4]) == "registered"
    assert pc.match(a + [7]) is not None       # refresh A
    assert pc.evict_lru()                      # pool pressure -> LRU: B
    assert pc.register(c, pages=[5, 6]) == "registered"
    assert pc.evictions == 1 and freed == [(3, 4)]
    assert pc.match(b + [7]) is None           # B gone
    assert pc.match(a + [7]) is not None       # A survived (recently used)
    assert pc.match(c + [7]) is not None


def test_refcount_pins_against_eviction_and_degrades_when_all_pinned():
    pc = _pc()
    a, b, c = ([1] * 8), ([2] * 8), ([3] * 8)
    pc.register(a, pages=[1, 2])
    pc.register(b, pages=[3, 4])
    ma = pc.match(a + [7])
    mb = pc.match(b + [7])
    pc.acquire(ma)                  # A pinned
    assert pc.evict_lru()           # evicts B (refcount 0)
    assert pc.match(a + [7]) is not None, "pinned entry was evicted"
    pc.register(c, pages=[5, 6])
    mc = pc.match(c + [7])
    pc.acquire(mc)                  # now A and C both pinned
    assert not pc.evict_lru()       # nothing evictable: the valve says so
    assert pc.evictions == 1 and pc.size == 2
    pc.release(ma)
    assert pc.evict_lru()           # A evictable again
    assert pc.match(a + [7]) is None
    pc.release(mb)                  # releasing an evicted match: no-op


def test_eviction_rebinds_shared_shorter_prefix_keys():
    """A shorter shared prefix addressed by an evicted entry is still
    resident inside a surviving longer entry — eviction must rebind the
    key, not orphan the depth."""
    pc = _pc()
    base = [5, 5, 5, 5]
    pc.register(base + [1, 1, 1, 1], pages=[1, 2])  # owns H_1 (base)
    pc.register(base + [2, 2, 2, 2], pages=[1, 3])  # same H_1 kept by first
    assert pc.evict_lru()                           # evicts the LRU (first)
    m = pc.match(base + [7, 7, 7, 7, 7])
    assert m is not None and (m.length, m.pages) == (4, (1,)), \
        "depth-1 key orphaned by eviction despite a surviving cover"


def test_hash_collision_cannot_fake_a_hit(monkeypatch):
    import apex_tpu.serving.prefix_cache as mod

    monkeypatch.setattr(mod, "_roll", lambda h, block: 42)  # all collide
    pc = _pc()
    pc.register([1] * 8, pages=[1, 2])
    assert pc.match([2] * 9) is None    # same key, different tokens
    m = pc.match([1] * 9)
    assert m is not None                # real content still matches


def test_prefix_cache_validates():
    with pytest.raises(ValueError, match="block_len"):
        PrefixCache(block_len=0)
    pc = _pc()
    for pages in ([], [1, 2, 3]):       # 8 tokens on 0 or on 3 pages
        with pytest.raises(ValueError, match="cannot evenly hold"):
            pc.register([1] * 8, pages=pages)
    with pytest.raises(TypeError):
        pc.register([1] * 8)            # an entry is its pages


def test_scheduler_retain_prefixes_validation(lm_and_params, pool2_pair):
    eng_no_pool = _mk_engine(lm_and_params, pool=0)   # never traced: cheap
    with pytest.raises(ValueError, match="prefix_pool"):
        Scheduler(eng_no_pool, retain_prefixes=True)
    with pytest.raises(ValueError, match="prefix_pool"):
        _mk_engine(lm_and_params, pool=-1)


# --------------------------------------------------- end-to-end exactness
def _cases():
    """(shared_prefix_len, expected_reuse_on_hit) for prefixes below /
    at / straddling one block boundary and spanning two blocks, at
    CHUNK=8. Tails are 3 tokens, so e.g. pre=13 registers aligned(16)=16
    donor tokens of which only the first block matches the next prompt."""
    rng = np.random.default_rng(42)
    out = []
    for pre_len, want in [(5, 0), (8, 8), (13, 8), (16, 16)]:
        pre = list(rng.integers(1, VOCAB, size=pre_len))
        tail_a = list(rng.integers(1, VOCAB, size=3))
        tail_b = list(rng.integers(1, VOCAB, size=3))
        out.append((pre + tail_a, pre + tail_b, want))
    return out


def test_prefix_hit_bitwise_exact_vs_cold_and_recompute(lm_and_params,
                                                        pool2_pair):
    """The tentpole acceptance bar: after request A registers its
    prefix, request B (same shared prefix, different tail) is served
    from the cache — and its greedy tokens are bitwise identical to a
    retention-off engine's AND to one teacher-forcing full recompute."""
    m, params = lm_and_params
    eng_hot, eng_cold = pool2_pair
    eng_hot.reset(clear_prefixes=True)
    eng_cold.reset()
    sched_hot = Scheduler(eng_hot, retain_prefixes=True)
    sched_cold = Scheduler(eng_cold, retain_prefixes=False)
    for prompt_a, prompt_b, want_reuse in _cases():
        (ra,) = sched_hot.run([Request(prompt=prompt_a, max_new_tokens=6)])
        (rb,) = sched_hot.run([Request(prompt=prompt_b, max_new_tokens=6)])
        assert rb.reused_tokens == want_reuse, \
            f"prefix len {len(prompt_a) - 3}: reused {rb.reused_tokens}"
        assert ra.reused_tokens == 0
        (cb,) = sched_cold.run([Request(prompt=prompt_b,
                                        max_new_tokens=6)])
        assert rb.output_tokens == cb.output_tokens, \
            f"hit path diverged from cold (prefix len {len(prompt_a) - 3})"
        # skipped chunks are real: the hit ran fewer prefill steps
        assert rb.chunks == eng_hot.chunks_for(len(prompt_b)) \
            - want_reuse // CHUNK
        # teacher-forcing recompute: one full forward re-derives every
        # greedy step (identical-program discipline of test_serving.py)
        seq = jnp.asarray([list(prompt_b) + rb.output_tokens], jnp.int32)
        full = m.apply({"params": params}, seq, train=False)
        want = np.asarray(jnp.argmax(full[0], axis=-1))
        for i, tok in enumerate(rb.output_tokens):
            assert tok == int(want[len(prompt_b) - 1 + i]), \
                f"recompute divergence at token {i}"


def test_fully_cached_prompt_still_prefills_its_final_block(pool2_pair):
    """A prompt whose every token is cached must still run >= 1 chunk:
    sharing pages moves no K/V and samples nothing — the first output
    token's logits only exist if the last block goes through chunk
    prefill. The cap (aligned(n-1)) enforces exactly that."""
    eng, eng_cold = pool2_pair
    eng.reset(clear_prefixes=True)
    eng_cold.reset()
    sched = Scheduler(eng, retain_prefixes=True)
    prompt = list(np.random.default_rng(3).integers(1, VOCAB, size=16))
    sched.run([Request(prompt=prompt, max_new_tokens=4)])
    (r2,) = sched.run([Request(prompt=list(prompt), max_new_tokens=4)])
    assert r2.reused_tokens == 8            # aligned(15), not 16
    assert r2.chunks == 1
    (cold,) = Scheduler(eng_cold, retain_prefixes=False).run(
        [Request(prompt=list(prompt), max_new_tokens=4)])
    assert r2.output_tokens == cold.output_tokens


def test_exactly_two_compiled_programs_over_hit_miss_evict(tight_engine):
    """The compiled-program pin: a stream driving hits, misses,
    registrations and LRU evictions (a long request's admission needs
    the whole pool) traces exactly one chunk-prefill + one decode
    program — a hit is host bookkeeping."""
    eng = tight_engine
    eng.reset(clear_prefixes=True)
    pc = eng.prefix_cache
    hits0, miss0, evic0 = pc.hits, pc.misses, pc.evictions
    sched = Scheduler(eng, retain_prefixes=True)
    rng = np.random.default_rng(1)
    pre1 = list(rng.integers(1, VOCAB, size=8))
    long = list(rng.integers(1, VOCAB, size=17))
    stream = [
        (pre1 + [7, 8], 3),       # miss, registers pre1
        (pre1 + [9], 3),          # hit (shares pre1's page)
        (long, 111),              # miss; reserving 16 pages evicts pre1
        (pre1 + [5, 6], 3),       # miss (evicted), registers pre1 again
        (pre1 + [1, 2, 3], 3),    # hit at 8
    ]
    for p, budget in stream:
        sched.run([Request(prompt=p, max_new_tokens=budget)])
    assert (pc.hits - hits0, pc.misses - miss0) == (2, 3)
    assert pc.evictions - evic0 >= 1
    assert (eng.chunk_traces, eng.decode_traces) == (1, 1)
    assert eng.compiled_programs == 2


def test_evicting_a_live_hits_donor_entry_is_harmless(tight_engine,
                                                      pool2_pair):
    """A hit needs no pin: its shared pages carry their own refcounts,
    so evicting the donor entry while the hit still decodes changes
    nothing it reads — same tokens as a retention-off engine, and the
    pool drains clean."""
    eng = tight_engine
    eng.reset(clear_prefixes=True)
    sched = Scheduler(eng, retain_prefixes=True)
    rng = np.random.default_rng(9)
    pre = list(rng.integers(1, VOCAB, size=8))
    sched.run([Request(prompt=pre + [1], max_new_tokens=2)])
    b = Request(prompt=pre + [2], max_new_tokens=12)
    sched.submit(b)
    while b.status != "running":
        sched.step()
    assert b.reused_tokens == 8
    evic0 = eng.prefix_cache.evictions
    while eng.prefix_cache.evict_lru():     # every entry, b's donor among them
        pass
    assert eng.prefix_cache.evictions > evic0
    assert eng.prefix_cache.probe(pre + [3]) == 0
    while sched.pending:
        sched.step()
    cold = pool2_pair[1]
    cold.reset()
    (want,) = Scheduler(cold).run([Request(prompt=pre + [2],
                                           max_new_tokens=12)])
    assert b.output_tokens == want.output_tokens
    eng.reset(clear_prefixes=True)
    assert sched.auditor.audit(eng)["pages_in_use"] == 0


def test_prefix_telemetry_and_request_records(pool2_pair):
    reg = telemetry.MetricsRegistry()
    eng, _ = pool2_pair
    eng.reset(clear_prefixes=True)
    eng.set_registry(reg)
    sched = Scheduler(eng, retain_prefixes=True, registry=reg)
    rng = np.random.default_rng(11)
    pre = list(rng.integers(1, VOCAB, size=16))
    reqs = [Request(prompt=pre + [1], max_new_tokens=3),
            Request(prompt=pre + [2, 3], max_new_tokens=3)]
    try:
        sched.run([reqs[0]])
        sched.run([reqs[1]])
    finally:
        eng.set_registry(None)
    snap = reg.snapshot()
    c = snap["counters"]
    assert c["serving.prefix.hits"] == 1
    assert c["serving.prefix.misses"] == 1
    assert c["serving.prefix.tokens_reused"] == 16
    assert c["serving.prefix.chunks_skipped"] == 2
    assert c["serving.prefix.registrations"] == 1   # second is duplicate
    # the gauge tracks the cache's cumulative rate (shared engine: the
    # pcache's counters span the module, the registry's are this test's)
    assert snap["gauges"]["serving.prefix.hit_rate"] \
        == pytest.approx(eng.prefix_cache.hit_rate)
    assert "serving.prefix.copy_s" not in snap["histograms"]   # no copies
    recs = {rec["uid"]: rec for rec in reg.records
            if rec.get("tag") == "serving.request"}
    assert recs[reqs[0].uid]["reused_tokens"] == 0
    assert recs[reqs[1].uid]["reused_tokens"] == 16
    assert recs[reqs[1].uid]["chunks_per_prompt"] == 1


def test_reset_keeps_warm_prefixes_unless_cleared(pool2_pair):
    eng, _ = pool2_pair
    eng.reset(clear_prefixes=True)
    sched = Scheduler(eng, retain_prefixes=True)
    pre = list(np.random.default_rng(13).integers(1, VOCAB, size=8))
    sched.run([Request(prompt=pre + [1], max_new_tokens=2)])
    eng.reset()
    assert eng.lengths()[:eng.slots].tolist() == [0, 0, 0]
    (r,) = Scheduler(eng, retain_prefixes=True).run(
        [Request(prompt=pre + [2], max_new_tokens=2)])
    assert r.reused_tokens == 8, "reset() must not drop warm prefixes"
    eng.reset(clear_prefixes=True)
    assert eng.prefix_cache.size == 0
    (r2,) = Scheduler(eng, retain_prefixes=True).run(
        [Request(prompt=pre + [3], max_new_tokens=2)])
    assert r2.reused_tokens == 0
