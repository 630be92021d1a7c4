"""apex_tpu.serving — KV-cache engine + continuous batching, hermetic.

The acceptance bar from the subsystem's issues (PR 3 + PR 4's chunked
prefill), as tests:

- greedy KV-cache decode is token-exact against the full-recompute
  forward's argmax for >= 64 generated tokens (teacher-forcing form:
  ONE full forward over [prompt + generated] re-derives every step's
  argmax, so both paths are compared through identical programs — the
  shared-program discipline of test_amp_train_step.py, avoiding 64
  separately-fused eager forwards);
- chunked prefill is token-exact (bitwise argmax) against full
  recompute, for prompt lengths shorter than / equal to / straddling a
  chunk boundary;
- a variable-length request stream is served by exactly 2 compiled
  programs (chunk prefill + decode step), pinned by trace counters;
- the KV layout and the prefill strategy are constants: ``paged`` and
  ``chunked`` are no keywords, and the constructors' keyword counts
  are pinned;
- chunk-prefill steps interleave with the decode heartbeat: an
  in-flight decode gains a token on EVERY tick of a long admit (the
  head-of-line-blocking fix);
- telemetry records tokens/sec, the TTFT decomposition (queue wait +
  prefill chunks), chunks-per-prompt, and slot occupancy.

Everything runs on CPU with a tiny model; the engine's Pallas decode
and chunk-prefill kernels take their interpret/reference paths here
(the Mosaic lowering is the tests/tpu tier's job).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serving, telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import Engine, QueueFull, Request, Scheduler

pytestmark = pytest.mark.serving

VOCAB = 101


def _tiny_lm(max_seq_len=128, **kw):
    return TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                         num_heads=4, max_seq_len=max_seq_len, **kw)


@pytest.fixture(scope="module")
def lm_and_params():
    m = _tiny_lm()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


@pytest.fixture(scope="module")
def fp32_engine(lm_and_params):
    """Exact-fp32 engine (policy O0) shared by the parity/trace tests."""
    m, params = lm_and_params
    return Engine(m, params, slots=3, max_len=128, prefill_len=16,
                  policy=resolve_policy("O0", verbose=False), seed=7)


# ------------------------------------------------------------------ sampling
def test_sample_tokens_greedy_vs_temperature():
    logits = jnp.asarray([[0.0, 5.0, 1.0], [9.0, 0.0, 0.0]])
    key = jax.random.PRNGKey(0)
    greedy = serving.sample_tokens(logits, jnp.zeros(2), key)
    np.testing.assert_array_equal(np.asarray(greedy), [1, 0])
    # temperature sampling is deterministic per key and stays in-vocab
    hot = serving.sample_tokens(logits, jnp.full(2, 2.0), key)
    hot2 = serving.sample_tokens(logits, jnp.full(2, 2.0), key)
    np.testing.assert_array_equal(np.asarray(hot), np.asarray(hot2))
    assert np.all((np.asarray(hot) >= 0) & (np.asarray(hot) < 3))


def test_sample_tokens_top_k_restricts_support():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, -1.0]])
    keys = jax.random.split(jax.random.PRNGKey(1), 32)
    got = {int(serving.sample_tokens(logits, jnp.full(1, 5.0), k,
                                     top_k=2)[0]) for k in keys}
    assert got <= {2, 3}            # only the top-2 ids are reachable


# ----------------------------------------------------------- decode parity
def test_greedy_decode_token_exact_vs_full_recompute(fp32_engine,
                                                     lm_and_params):
    """>= 64 greedy tokens from the KV-cache engine == the argmax chain
    of one full-recompute forward over the final sequence (causality
    makes teacher-forcing re-derivation exact for greedy decode).

    The default scheduler now admits through CHUNKED prefill, so this is
    also the PR 4 acceptance pin: the chunked path is token-exact for
    >= 64 generated tokens against full-recompute argmax (the
    chunk-boundary sweep lives in
    test_chunked_prefill_token_exact_vs_recompute)."""
    m, params = lm_and_params
    eng = fp32_engine
    sched = Scheduler(eng)
    prompt = [3, 17, 91, 42, 8]
    n_gen = 65
    (req,) = sched.run([Request(prompt=prompt, max_new_tokens=n_gen)])
    assert req.finish_reason == "max_new_tokens"
    assert len(req.output_tokens) == n_gen
    seq = jnp.asarray([list(prompt) + req.output_tokens], jnp.int32)
    full = m.apply({"params": params}, seq, train=False)   # [1, S, V]
    want = np.asarray(jnp.argmax(full[0], axis=-1))
    for i, tok in enumerate(req.output_tokens):
        # token i was sampled from the logits at position prompt+i-1
        assert tok == int(want[len(prompt) - 1 + i]), \
            f"divergence at generated token {i}"


# --------------------------------------------------------- chunked prefill
@pytest.fixture(scope="module")
def chunk_engine(lm_and_params):
    """An O0 engine with chunk_len=8 shared by the chunk tests."""
    m, params = lm_and_params
    return Engine(m, params, slots=3, max_len=128, prefill_len=24,
                  chunk_len=8, policy=resolve_policy("O0", verbose=False),
                  seed=5)


def _greedy_reqs():
    rng = np.random.default_rng(42)
    # shorter than (5), equal to (8), straddling one (13) and two (21)
    # chunk boundaries at chunk_len=8 (the >= 64-token stream lives in
    # test_greedy_decode_token_exact_vs_full_recompute — same chunked
    # admission path — keeping this sweep fast)
    return [Request(prompt=list(rng.integers(1, VOCAB, size=n)),
                    max_new_tokens=b)
            for n, b in [(5, 12), (8, 4), (13, 4), (21, 4)]]


def test_exactly_two_compiled_programs(chunk_engine):
    """Variable-length, variable-budget, variable-chunk-count request
    stream through the scheduler, then direct ``prefill_chunked`` calls
    → exactly one chunk-prefill trace and one decode-step trace
    (the fixed-shape contract: no
    per-token, per-request, per-offset or per-chunk-count recompiles).
    Runs first on the module's shared engine, so the pin covers every
    later test on it too."""
    eng = chunk_engine
    sched = Scheduler(eng)
    rng = np.random.default_rng(0)
    # prompt lengths span 1-3 chunks, including exact chunk multiples
    reqs = [Request(prompt=list(rng.integers(1, VOCAB, size=n)),
                    max_new_tokens=mnt, temperature=t)
            for n, mnt, t in [(1, 3, 0.0), (8, 9, 0.0), (17, 5, 0.7),
                              (24, 12, 0.0), (11, 2, 1.3), (5, 4, 0.0)]]
    done = sched.run(reqs)
    assert len(done) == 6
    assert [r.chunks for r in reqs] == [eng.chunks_for(len(r.prompt))
                                        for r in reqs]
    # callers without a scheduler land in the same chunk program
    eng.reset()
    eng.prefill_chunked(0, [5, 9, 2])
    eng.prefill_chunked(1, list(range(1, 20)))
    assert (eng.chunk_traces, eng.decode_traces) == (1, 1)
    assert eng.compiled_programs == 2


def test_chunked_prefill_token_exact_vs_recompute(chunk_engine,
                                                  lm_and_params):
    """The PR 4 acceptance bar: greedy decode after chunked prefill is
    bitwise-argmax identical to one teacher-forcing full recompute,
    across chunk-boundary prompt lengths."""
    m, params = lm_and_params
    eng_c = chunk_engine
    eng_c.reset()
    reqs_c = _greedy_reqs()
    Scheduler(eng_c).run(reqs_c)
    for rc in reqs_c:
        assert rc.chunks == eng_c.chunks_for(len(rc.prompt))
        # teacher-forcing: one full forward re-derives every greedy step
        seq = jnp.asarray([list(rc.prompt) + rc.output_tokens], jnp.int32)
        full = m.apply({"params": params}, seq, train=False)
        want = np.asarray(jnp.argmax(full[0], axis=-1))
        for i, tok in enumerate(rc.output_tokens):
            assert tok == int(want[len(rc.prompt) - 1 + i]), \
                f"prompt len {len(rc.prompt)}: divergence at token {i}"


def test_chunked_prefill_interleaves_with_decode(chunk_engine):
    """The head-of-line fix, observed at token granularity: while a
    3-chunk prompt ingests (one chunk per heartbeat), the in-flight
    decode gains a token on EVERY tick — the monolithic path would
    stall it for the whole prefill."""
    eng = chunk_engine
    eng.reset()
    sched = Scheduler(eng, pipeline_depth=0)    # the synchronous order
    a = Request(prompt=[3, 1, 4], max_new_tokens=50)
    sched.submit(a)
    sched.step()                      # admit + single final chunk + decode
    assert a.status == "running" and len(a.output_tokens) == 2
    b = Request(prompt=list(range(1, 25)), max_new_tokens=4)  # 3 chunks
    sched.submit(b)
    for tick in range(1, 4):
        n_before = len(a.output_tokens)
        sched.step()
        assert len(a.output_tokens) == n_before + 1, \
            f"decode stalled at tick {tick} during b's prefill"
        assert b.chunks == tick
    # b's final-chunk tick yields its first token AND a decode token —
    # the fresh slot joins the same heartbeat it finished prefilling in
    assert b.status == "running" and len(b.output_tokens) == 2
    assert b.ttft_s is not None and b.chunks == 3
    # the budget caps chunk work per heartbeat at one chunk
    assert eng.chunks_for(len(b.prompt)) == 3


def test_chunked_prefill_interleaves_with_decode_dispatch_ahead(
        chunk_engine):
    """The same head-of-line fix under the default dispatch-ahead beat:
    the in-flight decode gains a token on EVERY tick of b's ingestion;
    what differs from the synchronous order is only WHEN the host reads
    - a chunk is counted, and a final chunk's token emitted, at the top
    of the beat after the one that dispatched it."""
    eng = chunk_engine
    eng.reset()
    sched = Scheduler(eng)
    assert sched.pipeline_depth == 1
    a = Request(prompt=[3, 1, 4], max_new_tokens=50)
    sched.submit(a)
    sched.step()                      # admit + final chunk dispatched
    assert a.status == "prefilling" and a.output_tokens == []
    sched.step()                      # token read; first decode dispatched
    assert a.status == "running" and len(a.output_tokens) == 1
    sched.step()                      # second dispatched, first read
    assert len(a.output_tokens) == 2
    b = Request(prompt=list(range(1, 25)), max_new_tokens=4)  # 3 chunks
    sched.submit(b)
    for tick in range(1, 4):
        n_before = len(a.output_tokens)
        sched.step()
        assert len(a.output_tokens) == n_before + 1, \
            f"decode stalled at tick {tick} during b's prefill"
        # one chunk DISPATCHED a beat, the one before it read
        assert b._prefill_pos == 8 * tick and b.chunks == tick - 1
    assert b.status == "prefilling" and b.output_tokens == []
    n_before = len(a.output_tokens)
    sched.step()                      # b's first token, read at the top
    assert b.status == "running" and len(b.output_tokens) == 1
    assert b.ttft_s is not None and b.chunks == 3
    assert len(a.output_tokens) == n_before + 1
    sched.step()                      # b decodes beside a from here on
    assert len(b.output_tokens) == 2
    assert len(a.output_tokens) == n_before + 2
    sched.run([])
    eng.reset()


def test_chunked_ttft_decomposition_and_request_records(chunk_engine):
    """serving.queue_wait_s and serving.prefill_chunk_s land as separate
    histograms from serving.ttft_s, and every completion emits a
    serving.request record carrying chunks_per_prompt."""
    reg = telemetry.MetricsRegistry()
    eng = chunk_engine
    eng.reset()
    eng.set_registry(reg)
    sched = Scheduler(eng, registry=reg)
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=4),
            Request(prompt=list(range(1, 20)), max_new_tokens=3)]
    try:
        sched.run(reqs)
    finally:
        eng.set_registry(None)
    snap = reg.snapshot()
    h = snap["histograms"]
    assert h["serving.queue_wait_s"]["count"] == 2
    assert h["serving.prefill_chunk_s"]["count"] == 1 + 3   # 1 + 3 chunks
    assert h["serving.ttft_s"]["count"] == 2
    assert snap["counters"]["serving.prefill.chunks"] == 4
    for r in reqs:
        assert r.queue_wait_s is not None and r.prefill_s > 0
        assert r.ttft_s >= r.queue_wait_s
    # event-shaped records stay OUT of the histogram layer: no junk
    # per-request reservoirs for uid / duplicated latencies
    assert not any(k.startswith("serving.request.") for k in h)
    recs = [rec for rec in reg.records
            if rec.get("tag") == "serving.request"]
    assert len(recs) == 2
    by_uid = {rec["uid"]: rec for rec in recs}
    assert by_uid[reqs[0].uid]["chunks_per_prompt"] == 1
    assert by_uid[reqs[1].uid]["chunks_per_prompt"] == 3
    for rec in recs:
        assert rec["finish_reason"] == "max_new_tokens"
        assert rec["queue_wait_s"] is not None
        assert rec["ttft_s"] is not None


def test_prefill_chunk_validation(lm_and_params, chunk_engine):
    m, params = lm_and_params
    with pytest.raises(ValueError, match="chunk_len"):
        Engine(m, params, slots=1, max_len=32, prefill_len=8,
               chunk_len=16)
    eng = chunk_engine                     # chunk_len=8, prefill 24
    with pytest.raises(ValueError, match="chunk length"):
        eng.prefill_chunk(0, list(range(1, 10)), 0)
    with pytest.raises(ValueError, match="slot"):
        eng.prefill_chunk(5, [1], 0)
    with pytest.raises(ValueError, match="exceeds prefill_len"):
        eng.prefill_chunk(0, [1, 2, 3, 4], 21)
    with pytest.raises(ValueError, match="prompt length"):
        eng.prefill_chunked(0, list(range(25)))
    with pytest.raises(ValueError, match="chunk_budget"):
        Scheduler(eng, chunk_budget=0)
    # the final PADDED chunk window must fit max_len: a geometry whose
    # last chunk would spill past the cache (and be silently relocated
    # by the model's position clip, corrupting earlier prompt K/V) is
    # rejected at construction, not discovered as wrong tokens
    with pytest.raises(ValueError, match="final chunk window"):
        Engine(m, params, slots=1, max_len=20, prefill_len=20,
               chunk_len=8)
    # ... and direct prefill_chunk callers at arbitrary offsets hit the
    # same wall per call
    eng24 = Engine(m, params, slots=1, max_len=24, prefill_len=24,
                   chunk_len=8)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng24.prefill_chunk(0, [1, 2], 18)


def test_chunk_budget_caps_ingestion_only_while_decoding(chunk_engine):
    """The budget bounds the stall imposed ON in-flight decodes: with a
    decode active, at most chunk_budget chunks run per tick; with
    nothing decoding there is nothing to stall, so a cold queue bursts
    straight to full ingestion instead of idling between heartbeats."""
    eng = chunk_engine
    eng.reset()
    sched = Scheduler(eng, chunk_budget=2, pipeline_depth=0)
    c = Request(prompt=[1, 2], max_new_tokens=50)
    sched.submit(c)
    sched.step()                               # c: 1 chunk → decoding
    assert c.status == "running"
    a = Request(prompt=list(range(1, 17)), max_new_tokens=3)   # 2 chunks
    b = Request(prompt=list(range(2, 18)), max_new_tokens=3)   # 2 chunks
    sched.submit(a)
    sched.submit(b)
    sched.step()
    assert a.chunks == 1 and b.chunks == 1     # one chunk EACH this tick
    sched.step()
    assert a.chunks == 2 and b.chunks == 2
    assert a.status == "running" and b.status == "running"


def test_chunk_budget_caps_ingestion_while_decoding_dispatch_ahead(
        chunk_engine):
    """The budget under the default beat: at most ``chunk_budget``
    chunks are DISPATCHED a tick beside a decode in flight, each read
    at the top of the next."""
    eng = chunk_engine
    eng.reset()
    sched = Scheduler(eng, chunk_budget=2)
    c = Request(prompt=[1, 2], max_new_tokens=50)
    sched.submit(c)
    sched.step()
    sched.step()                               # c's token read → decoding
    assert c.status == "running"
    a = Request(prompt=list(range(1, 17)), max_new_tokens=3)   # 2 chunks
    b = Request(prompt=list(range(2, 18)), max_new_tokens=3)   # 2 chunks
    sched.submit(a)
    sched.submit(b)
    sched.step()
    assert a._prefill_pos == 8 and b._prefill_pos == 8   # one chunk EACH
    assert a.chunks == 0 and b.chunks == 0
    sched.step()
    assert a._prefill_pos == 16 and b._prefill_pos == 16
    assert a.chunks == 1 and b.chunks == 1
    sched.step()                               # both final chunks read
    assert a.chunks == 2 and b.chunks == 2
    assert a.status == "running" and b.status == "running"
    assert len(a.output_tokens) == 1 and len(b.output_tokens) == 1
    sched.run([])
    eng.reset()


def test_cold_queue_bursts_to_full_ingestion(chunk_engine):
    eng = chunk_engine
    eng.reset()
    sched = Scheduler(eng, pipeline_depth=0)   # chunk_budget=1
    a = Request(prompt=list(range(1, 24)), max_new_tokens=4)   # 3 chunks
    sched.submit(a)
    sched.step()
    # nothing was decoding, so one tick burst through ALL 3 chunks
    # (instead of idling two heartbeats) and ran the first decode; the
    # burst stops the moment a slot flips to decoding, so the budget
    # bound on in-flight stalls is never violated
    assert a.chunks == 3
    assert a.status == "running" and len(a.output_tokens) == 2


def test_cold_queue_bursts_to_full_ingestion_dispatch_ahead(chunk_engine):
    """The burst under the default beat: one tick puts ALL 3 chunks on
    the device; it stops at the first FINAL chunk dispatched (that slot
    decodes as soon as the chunk is read), so a second cold prompt
    waits for the budget like any chunk beside a decode."""
    eng = chunk_engine
    eng.reset()
    sched = Scheduler(eng)                     # chunk_budget=1
    a = Request(prompt=list(range(1, 24)), max_new_tokens=4)   # 3 chunks
    b = Request(prompt=list(range(2, 20)), max_new_tokens=2)   # 3 chunks
    sched.submit(a)
    sched.submit(b)
    sched.step()
    assert a._prefill_pos == 23 and a.chunks == 2
    assert a.status == "prefilling" and a.output_tokens == []
    assert b._prefill_pos == 16                # its turns between a's
    sched.step()                               # a's first token is read
    assert a.chunks == 3
    assert a.status == "running" and len(a.output_tokens) == 1
    assert b._prefill_pos == 18                # one chunk a beat now
    sched.step()
    assert len(a.output_tokens) == 2
    sched.run([])
    eng.reset()


# ----------------------------------------------------------------- engine
def test_engine_default_policy_is_pure_half(lm_and_params):
    """Default O3 policy: weights AND cache in bf16 — no fp32 masters."""
    m, params = lm_and_params
    eng = Engine(m, params, slots=2, max_len=32, prefill_len=8)
    assert eng.cache.dtype == jnp.bfloat16
    for leaf in jax.tree_util.tree_leaves(eng.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.bfloat16
    tok = eng.prefill_chunked(0, [5, 9, 2])
    assert 0 <= tok < VOCAB
    out = eng.decode_step([tok, 0], [True, False], [0.0, 0.0])
    assert out.shape == (2,) and 0 <= int(out[0]) < VOCAB
    assert eng.lengths().tolist() == [4, 0]


def test_engine_validation(lm_and_params):
    m, params = lm_and_params
    with pytest.raises(ValueError, match="max_seq_len"):
        Engine(m, params, slots=1, max_len=4096)
    with pytest.raises(ValueError, match="prefill_len"):
        Engine(m, params, slots=1, max_len=32, prefill_len=64)
    eng = Engine(m, params, slots=1, max_len=16, prefill_len=8)
    with pytest.raises(ValueError, match="prompt length"):
        eng.prefill_chunked(0, list(range(9)))
    with pytest.raises(ValueError, match="slot"):
        eng.prefill_chunked(3, [1, 2])


def test_engine_has_one_kv_layout(lm_and_params):
    """The paged pool is the engine's one layout: ``paged`` is no
    keyword (a TypeError, not a deprecation shim)."""
    m, params = lm_and_params
    for value in (True, False):
        with pytest.raises(TypeError, match="paged"):
            Engine(m, params, slots=1, max_len=16, **{"paged": value})


def test_scheduler_has_one_prefill_strategy(fp32_engine):
    """Chunked ingest is the scheduler's one strategy: ``chunked`` is no
    keyword."""
    for value in (True, False):
        with pytest.raises(TypeError, match="chunked"):
            Scheduler(fp32_engine, **{"chunked": value})


def test_constructor_keyword_counts_are_pinned():
    """18 and 16 keyword-only options: every independent option doubles
    the configurations tests and cells must cover, so the next one has
    to be argued for (and this count changed with it)."""
    import inspect

    def keywords(cls):
        return [p.name for p in
                inspect.signature(cls.__init__).parameters.values()
                if p.kind is p.KEYWORD_ONLY]

    eng, sched = keywords(Engine), keywords(Scheduler)
    assert len(eng) == 18, eng
    assert len(sched) == 16, sched
    assert not {"paged", "chunked"} & set(eng + sched)


# -------------------------------------------------------------- scheduler
def test_scheduler_backpressure_bounded_queue(fp32_engine):
    sched = Scheduler(fp32_engine, max_queue=2)
    sched.submit(Request(prompt=[1], max_new_tokens=2))
    sched.submit(Request(prompt=[2], max_new_tokens=2))
    with pytest.raises(QueueFull):
        sched.submit(Request(prompt=[3], max_new_tokens=2))
    # a step drains the queue into slots; capacity frees up
    sched.step()
    sched.submit(Request(prompt=[3], max_new_tokens=2))
    while sched.pending:
        sched.step()
    assert len(sched.completed) == 3


def test_scheduler_rejects_unservable_prompts(fp32_engine):
    sched = Scheduler(fp32_engine)
    with pytest.raises(ValueError, match="prefill"):
        sched.submit(Request(prompt=list(range(17))))   # > prefill_len 16
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit(Request(prompt=[1], max_new_tokens=0))


def test_scheduler_timeout(fp32_engine):
    sched = Scheduler(fp32_engine, default_timeout_s=0.0)
    r = sched.submit(Request(prompt=[1, 2], max_new_tokens=500))
    time.sleep(0.01)
    sched.step()
    assert r.status == "expired" and r.finish_reason == "timeout"
    assert sched.pending == 0


def test_scheduler_eos_and_max_len_eviction(lm_and_params):
    m, params = lm_and_params
    eng = Engine(m, params, slots=1, max_len=12, prefill_len=8,
                 policy=resolve_policy("O0", verbose=False))
    # find the greedy first token, then declare it EOS: request must
    # finish at prefill without ever occupying a slot
    probe = eng.prefill_chunked(0, [7, 7, 7])
    eng.reset()
    sched = Scheduler(eng, eos_id=probe)
    (r,) = sched.run([Request(prompt=[7, 7, 7], max_new_tokens=50)])
    assert r.finish_reason == "eos" and len(r.output_tokens) == 1
    # cache exhaustion: prompt 8 + budget 50 >> max_len 12
    eng.reset()
    sched = Scheduler(eng)
    (r2,) = sched.run([Request(prompt=list(range(1, 9)),
                               max_new_tokens=50)])
    assert r2.finish_reason == "max_len"
    # prompt(8) fills to 8; decode may write positions 8..11
    assert len(r2.output_tokens) <= 12 - 8 + 1


def test_serving_telemetry_records_the_issue_metrics(lm_and_params):
    """tokens/sec, time-to-first-token, per-step decode latency and
    slot occupancy all land in the MetricsRegistry."""
    m, params = lm_and_params
    reg = telemetry.MetricsRegistry()
    eng = Engine(m, params, slots=2, max_len=32, prefill_len=8,
                 policy=resolve_policy("O0", verbose=False), registry=reg)
    sched = Scheduler(eng, registry=reg)
    sched.run([Request(prompt=[1, 2, 3], max_new_tokens=4),
               Request(prompt=[9], max_new_tokens=6)])
    snap = reg.snapshot()
    assert snap["gauges"]["serving.tokens_per_s"] > 0
    assert snap["histograms"]["serving.ttft_s"]["count"] == 2
    assert snap["histograms"]["serving.decode.step_s"]["count"] >= 5
    assert 0.0 < snap["histograms"]["serving.slot_occupancy"]["mean"] <= 1.0
    assert snap["counters"]["serving.requests.completed"] == 2
    assert snap["counters"]["serving.tokens_generated"] >= 8
    # padding waste is the occupancy complement
    occ = snap["histograms"]["serving.slot_occupancy"]["mean"]
    waste = snap["histograms"]["serving.padding_waste"]["mean"]
    assert abs((occ + waste) - 1.0) < 1e-9


def test_full_prompt_finishes_at_prefill_without_cache_corruption(
        lm_and_params):
    """A prompt that already fills the cache (n == max_len) must finish
    at prefill: a decode step would clamp its write to max_len-1,
    destroying the last prompt position's K/V and emitting a corrupted
    token as real output."""
    m, params = lm_and_params
    eng = Engine(m, params, slots=1, max_len=8, prefill_len=8,
                 policy=resolve_policy("O0", verbose=False))
    sched = Scheduler(eng)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    (r,) = sched.run([Request(prompt=prompt, max_new_tokens=4)])
    assert r.finish_reason == "max_len"
    assert len(r.output_tokens) == 1          # prefill's token is valid
    full = m.apply({"params": params}, jnp.asarray([prompt], jnp.int32),
                   train=False)
    assert r.output_tokens[0] == int(jnp.argmax(full[0, -1]))


def test_prefill_and_decode_agree_on_tokens_generated_counter(
        lm_and_params):
    """The serving.tokens_generated counter must match the engine's own
    tokens_generated tally (the tokens/s numerator) — prefill's first
    token counts in both."""
    m, params = lm_and_params
    reg = telemetry.MetricsRegistry()
    eng = Engine(m, params, slots=2, max_len=32, prefill_len=8,
                 policy=resolve_policy("O0", verbose=False), registry=reg)
    Scheduler(eng, registry=reg).run(
        [Request(prompt=[1, 2], max_new_tokens=3),
         Request(prompt=[4], max_new_tokens=5)])
    assert reg.snapshot()["counters"]["serving.tokens_generated"] \
        == eng.tokens_generated == 8


def test_temperature_decode_stays_in_vocab_and_finishes(fp32_engine):
    sched = Scheduler(fp32_engine)
    (r,) = sched.run([Request(prompt=[5, 6], max_new_tokens=10,
                              temperature=1.5)])
    assert len(r.output_tokens) == 10
    assert all(0 <= t < VOCAB for t in r.output_tokens)
