"""Tensor-parallel serving: the Engine(mesh=...) acceptance pins.

The bars from the tensor-parallel issue, as tests:

- **the tp=1 bitwise pin** (tier-1): ``Engine(mesh=<1-device>)`` serves
  a greedy stream — prefix hit/miss/evict, warm reset, speculative
  verify — BITWISE token-identical to the verbatim ``mesh=None``
  single-chip baseline (the sharded programs over one device must be
  the same serving engine, not a numerically-adjacent cousin);
- **the tp>1 parity pin** (slow — CPU device emulation): the same
  stream over a 2-shard mesh is token-exact vs the baseline, with the
  pool provably heads-sharded and per-shard HBM halved;
- **the collective pin** (slow): compiled HLO of the sharded decode /
  chunk-prefill / verify programs schedules EXACTLY
  ``2 * num_layers`` all-reduces (the two canonical Megatron psums per
  block: post-attention projection, post-MLP down-projection) plus
  ONE all-gather (the sampled logits rows' vocab/tp slices rejoined)
  — attention contributes zero collectives because the pool shards
  along heads (:func:`serving.sharding.expected_collectives`);
- **rule-table units**: ``match_partition_rules`` assigns every
  TransformerLM leaf a spec (column/row/replicated per the Megatron
  split), ``shard_params`` hands each shard head-grouped qkv slices
  and 1/tp-scaled row biases;
- **mesh lifecycle**: heads/vocab/MLP-inner divisibility rejected at
  construction, contiguous+mesh rejected, 2-D meshes rejected; warm
  ``reset()`` keeps retained prefixes valid per shard (hits after the
  reset, tokens bitwise vs the cold pass);
- **compiled-programs + trace discipline**: a sharded engine keeps the
  paged pin (3 programs + 1 lazy verify), shard_map adds no hidden
  retraces.

The whole suite is hermetic on the 8-virtual-device CPU backend
(tests/conftest.py); the multi-device (tp=2) tests carry the ``slow``
marker to hold the tier-1 wall-time budget, exactly like the other
multi-device files.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, Request, Scheduler, SpecConfig,
                              sharding)

pytestmark = pytest.mark.serving

VOCAB = 96          # divisible by the tp sizes under test (1, 2, 4)
CHUNK = 8
K = 3


def _tiny_lm(**kw):
    return TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                         num_heads=4, max_seq_len=128, **kw)


@pytest.fixture(scope="module")
def lm_and_params():
    m = _tiny_lm()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _mesh(n: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} virtual devices")
    return Mesh(np.array(devs[:n]), ("tp",))


def _mk_engine(lm_and_params, *, mesh=None, slots=3, seed=5,
               prefix_pool=2, spec=True, **kw):
    m, params = lm_and_params
    return Engine(m, params, slots=slots, max_len=128, prefill_len=24,
                  chunk_len=CHUNK, prefix_pool=prefix_pool,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  spec=SpecConfig(draft_len=K, ngram=2) if spec else None,
                  mesh=mesh, **kw)


def _stream_reqs(seed=42):
    """Prompt lengths below/at/straddling chunk boundaries; a shared
    leading block so retention produces real hits on the second pass."""
    rng = np.random.default_rng(seed)
    shared = list(rng.integers(1, VOCAB, size=CHUNK))
    reqs = []
    for n, b in [(5, 16), (CHUNK, 12), (13, 10), (21, 8)]:
        tail = list(rng.integers(1, VOCAB, size=max(1, n - CHUNK)))
        prompt = (shared + tail)[:n] if n > CHUNK else \
            list(rng.integers(1, VOCAB, size=n))
        reqs.append(Request(prompt=prompt, max_new_tokens=b))
    return reqs


def _serve_stream(eng, registry=None):
    """The acceptance stream: two retained-prefix speculative passes
    (pass 1 registers — misses; pass 2 hits), an LRU eviction between
    them, and a warm reset — hit/miss/evict + speculative, exactly the
    greedy stream the tp=1 pin names. Returns every request's tokens in
    order."""
    out = []
    for window in range(2):
        reqs = _stream_reqs()
        Scheduler(eng, registry=registry, retain_prefixes=True,
                  speculative=True).run(reqs)
        out.append([list(r.output_tokens) for r in reqs])
        if window == 0 and eng.prefix_cache is not None:
            # exercise the evict path identically on every engine under
            # comparison, then re-register on the next pass
            eng.prefix_cache.evict_lru()
        eng.reset()     # warm: retained prefixes survive
    return out


# ------------------------------------------------------------- rule table
def test_match_partition_rules_covers_the_tree(lm_and_params):
    m, params = lm_and_params
    specs = sharding.match_partition_rules(
        sharding.partition_rules("tp"), params)
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): spec
            for path, spec in jax.tree_util.tree_flatten_with_path(
                specs)[0]}
    assert flat["block_0/attn/qkv/kernel"] == P(None, "tp")
    assert flat["block_0/attn/qkv/bias"] == P("tp")
    assert flat["block_0/attn/proj/kernel"] == P("tp", None)
    assert flat["block_0/attn/proj/bias"] == P()
    assert flat["block_1/mlp_in/kernel"] == P(None, "tp")
    assert flat["block_1/mlp_out/kernel"] == P("tp", None)
    # replicated tail: embeddings, positional table, every LayerNorm
    assert flat["wte/embedding"] == P()
    assert flat["wpe"] == P()
    assert flat["block_0/ln_attn/scale"] == P()
    assert flat["ln_f/bias"] == P()
    assert jax.tree_util.tree_structure(specs) \
        == jax.tree_util.tree_structure(params)


def test_match_partition_rules_requires_a_match():
    rules = ((r"attn/qkv/kernel$", P(None, "tp")),)   # no catch-all
    with pytest.raises(ValueError, match="no partition rule"):
        sharding.match_partition_rules(
            rules, {"mlp_out": {"kernel": np.zeros((4, 4))}})


def test_shard_params_shapes_and_values(lm_and_params):
    """tp=2 placement: column splits halve output features, row splits
    halve input features, qkv shards are head-grouped (each shard owns
    its heads' Q AND K AND V), row-parallel biases are value-scaled by
    1/tp so the in-program psum restores them exactly once."""
    m, params = lm_and_params
    mesh = _mesh(2)
    sharded = sharding.shard_params(params, mesh, num_heads=4)
    b0 = sharded["block_0"]
    qkv = b0["attn"]["qkv"]["kernel"]
    assert qkv.shape == (32, 96)        # global shape unchanged
    shards = {s.index[1].start or 0: np.asarray(s.data)
              for s in qkv.addressable_shards}
    assert all(x.shape == (32, 48) for x in shards.values())
    # head-grouped: shard 0's slice is the full kernel's (3, heads 0-1,
    # d) block, not its first 48 contiguous columns
    full = np.asarray(params["block_0"]["attn"]["qkv"]["kernel"])
    want0 = full.reshape(32, 3, 4, 8)[:, :, :2, :].reshape(32, 48)
    np.testing.assert_array_equal(shards[0], want0)
    want1 = full.reshape(32, 3, 4, 8)[:, :, 2:, :].reshape(32, 48)
    np.testing.assert_array_equal(shards[48], want1)
    proj = b0["attn"]["proj"]
    assert [s.data.shape for s in
            proj["kernel"].addressable_shards] == [(16, 32)] * 2
    # row-parallel bias: replicated, scaled 1/tp
    np.testing.assert_allclose(
        np.asarray(proj["bias"].addressable_shards[0].data),
        np.asarray(params["block_0"]["attn"]["proj"]["bias"]) / 2)
    mlp_in = b0["mlp_in"]["kernel"]
    assert [s.data.shape for s in mlp_in.addressable_shards] \
        == [(32, 64)] * 2
    # replicated leaves: every shard holds the full value, untouched
    wte = sharded["wte"]["embedding"]
    np.testing.assert_array_equal(
        np.asarray(wte.addressable_shards[0].data),
        np.asarray(params["wte"]["embedding"]))


def test_expected_collectives_inventory():
    assert sharding.expected_collectives(6) \
        == {"all_reduce": 12, "all_gather": 1}


# --------------------------------------------------------- mesh lifecycle
def test_engine_mesh_validation(lm_and_params):
    m, params = lm_and_params
    kw = dict(slots=2, max_len=64, prefill_len=16, chunk_len=8,
              policy=resolve_policy("O0", verbose=False))
    # heads not divisible by tp (4 heads over 8 shards)
    with pytest.raises(ValueError, match="not divisible"):
        Engine(m, params, mesh=_mesh(8), **kw)
    # 2-D meshes are a configuration error
    devs = jax.devices()
    mesh2d = Mesh(np.array(devs[:4]).reshape(2, 2), ("tp", "dp"))
    with pytest.raises(ValueError, match="1-D"):
        Engine(m, params, mesh=mesh2d, **kw)
    # vocab not divisible by tp
    m_odd = TransformerLM(vocab_size=97, hidden=32, num_layers=1,
                          num_heads=4, max_seq_len=64)
    p_odd = m_odd.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 4), jnp.int32),
                       train=False)["params"]
    with pytest.raises(ValueError, match="vocab_size"):
        Engine(m_odd, p_odd, mesh=_mesh(2), **kw)


def test_tp_geometry_validation_units():
    sharding.validate_tp_geometry(2, num_heads=4, hidden=32, mlp_ratio=4,
                                  vocab_size=96)
    with pytest.raises(ValueError, match="num_heads"):
        sharding.validate_tp_geometry(3, num_heads=4, hidden=32,
                                      mlp_ratio=4, vocab_size=96)
    with pytest.raises(ValueError, match="vocab"):
        sharding.validate_tp_geometry(4, num_heads=4, hidden=32,
                                      mlp_ratio=4, vocab_size=98)
    with pytest.raises(ValueError, match=">= 1"):
        sharding.validate_tp_geometry(0, num_heads=4, hidden=32,
                                      mlp_ratio=4, vocab_size=96)
    with pytest.raises(ValueError, match="1-D"):
        devs = jax.devices()
        sharding.tp_axis_of(Mesh(np.array(devs[:4]).reshape(2, 2),
                                 ("a", "b")))


# ------------------------------------------------------- the tp=1 pin
def test_tp1_mesh_bitwise_vs_unsharded(lm_and_params):
    """THE tier-1 acceptance pin: a 1-device mesh runs the SHARDED
    programs (shard_map, rule-table param placement, vocab-parallel
    head + gather) and must reproduce the verbatim mesh=None baseline
    BITWISE on a greedy stream exercising prefix hit/miss/evict, warm
    reset and speculative verify."""
    base_eng = _mk_engine(lm_and_params)
    base = _serve_stream(base_eng)
    eng = _mk_engine(lm_and_params, mesh=_mesh(1))
    assert eng.tp == 1 and eng.mesh is not None
    got = _serve_stream(eng)
    assert got == base, "tp=1 mesh diverged from the mesh=None baseline"
    # the sharded engine keeps the paged compiled-programs discipline
    assert eng.chunk_traces == 1
    assert eng.decode_traces == 1
    assert eng.verify_traces == 1
    assert eng.compiled_programs == 3


def test_sharded_warm_reset_keeps_prefixes_valid(lm_and_params):
    """Mesh lifecycle satellite: retained prefixes survive a sharded
    warm reset — the second pass HITS (zero-copy page shares into the
    sharded pool) and its tokens are bitwise the first pass's (the
    hit-vs-cold guarantee, per shard)."""
    eng = _mk_engine(lm_and_params, mesh=_mesh(1))
    reg = telemetry.MetricsRegistry()
    # serve, warm-reset, serve the same prompts: pass 2 must hit
    reqs1 = _stream_reqs()
    Scheduler(eng, retain_prefixes=True, speculative=True).run(reqs1)
    eng.reset()                         # warm: prefixes survive
    reqs2 = _stream_reqs()
    Scheduler(eng, registry=reg, retain_prefixes=True,
              speculative=True).run(reqs2)
    snap = reg.snapshot()
    assert snap["counters"].get("serving.prefix.hits", 0) > 0, \
        "warm reset dropped the retained prefixes"
    got1 = [list(r.output_tokens) for r in reqs1]
    got2 = [list(r.output_tokens) for r in reqs2]
    assert got1 == got2, "a prefix hit changed tokens on the sharded " \
        "engine — per-shard K/V reuse is not byte-identical"
    assert sum(r.reused_tokens for r in reqs2) > 0


def test_tp_gauges_emitted(lm_and_params):
    """The serving.tp.* telemetry family: shard count, per-program
    collective inventory (the HLO pin's numbers), per-shard pool
    gauges. Single-chip engines emit none of it."""
    reg = telemetry.MetricsRegistry()
    eng = _mk_engine(lm_and_params, mesh=_mesh(1), registry=reg)
    g = reg.snapshot()["gauges"]
    assert g["serving.tp.shards"] == 1.0
    assert g["serving.tp.psums_per_program"] == 4.0     # 2 blocks x 2
    assert g["serving.tp.all_gathers_per_program"] == 1.0
    assert g["serving.tp.hbm_bytes_per_shard"] \
        == eng.cache.nbytes() / eng.tp
    assert g["serving.tp.pool_pages_per_shard"] == float(eng.num_pages)
    reg2 = telemetry.MetricsRegistry()
    _mk_engine(lm_and_params, registry=reg2, spec=False)
    assert not any(k.startswith("serving.tp.")
                   for k in reg2.snapshot()["gauges"])


def test_model_requires_tp_fields(lm_and_params):
    """A model without the tp_axis/tp_size contract is rejected loudly
    at construction, not with a shape error inside the first trace."""

    class NoTP:
        hidden, num_heads, num_layers, max_seq_len = 32, 4, 2, 128
        vocab_size = VOCAB

        def clone(self, **kw):
            raise TypeError("unexpected fields")

    _, params = lm_and_params
    with pytest.raises(TypeError, match="tp_axis"):
        Engine(NoTP(), params, slots=2, max_len=64, prefill_len=16,
               mesh=_mesh(1))


# ------------------------------------------------ multi-device (slow tier)
@pytest.mark.slow
def test_tp2_token_exact_vs_unsharded(lm_and_params):
    """The tp>1 parity pin (CPU device emulation): the full acceptance
    stream — hit/miss/evict, warm reset, speculative — over a 2-shard
    mesh is token-exact vs the single-chip baseline, the pool is
    provably heads-sharded (each shard holds heads/tp of every page),
    and the trace discipline is unchanged."""
    base = _serve_stream(_mk_engine(lm_and_params))
    mesh = _mesh(2)
    eng = _mk_engine(lm_and_params, mesh=mesh)
    assert eng.tp == 2
    # heads-sharded pool: global shape keeps all 4 heads, each shard
    # holds 2 — per-shard HBM is half the pool
    assert eng.cache.k.shape[2] == 4
    shard_shapes = {s.data.shape for s in eng.cache.k.addressable_shards}
    assert shard_shapes == {(2, eng.num_pages, 2, eng.page_len, 8)}
    got = _serve_stream(eng)
    assert got == base, "tp=2 diverged from the single-chip baseline"
    assert (eng.chunk_traces, eng.decode_traces, eng.verify_traces) \
        == (1, 1, 1)


@pytest.mark.slow
def test_tp2_collective_counts_from_hlo(lm_and_params):
    """The scheduled-HLO certificate: each sharded program compiles
    EXACTLY expected_collectives(num_layers) — 2 psums per block
    (post-attention, post-MLP) + 1 all-gather at the sampled logits.
    Attention adds nothing (heads-sharded pool). A fresh engine is used
    because .lower() re-traces (the shared engines' trace pins must not
    see it)."""
    eng = _mk_engine(lm_and_params, mesh=_mesh(2), prefix_pool=0,
                     seed=0)
    want = sharding.expected_collectives(2)     # 2-layer tiny model

    def counts(txt):
        return {"all_reduce": len(re.findall(r"= \S+ all-reduce\(",
                                             txt)),
                "all_gather": len(re.findall(r"= \S+ all-gather\(",
                                             txt))}

    key = jax.random.PRNGKey(0)
    mp = eng.max_pages
    decode = eng._jit_decode.lower(
        eng.params, eng.cache, jnp.zeros(3, jnp.int32),
        jnp.zeros(3, jnp.int32),
        jnp.zeros((3, mp), jnp.int32), jnp.zeros(3, jnp.int32),
        jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.float32),
        key).compile().as_text()
    assert counts(decode) == want, "decode collectives drifted"
    chunk = eng._jit_chunk.lower(
        eng.params, eng.cache, jnp.zeros((1, CHUNK), jnp.int32),
        jnp.zeros((1, mp), jnp.int32), np.int32(0), np.int32(CHUNK),
        np.float32(0), np.float32(0), key).compile().as_text()
    assert counts(chunk) == want, "chunk-prefill collectives drifted"
    verify = eng._jit_verify.lower(
        eng.params, eng.cache, jnp.zeros((3, K + 1), jnp.int32),
        jnp.zeros((3, mp), jnp.int32), jnp.zeros(3, jnp.int32),
        jnp.zeros(3, jnp.int32),
        jnp.zeros(3, jnp.float32)).compile().as_text()
    assert counts(verify) == want, "verify collectives drifted"


@pytest.mark.slow
def test_tp2_verify_batch_matches_sequential(lm_and_params):
    """Batched-verify satellite, composed with the mesh: one
    [slots, K+1] call over two verifying slots emits bitwise the same
    tokens as two sequential single-slot verify_step calls through the
    same executable — on a 2-shard engine."""
    eng = _mk_engine(lm_and_params, mesh=_mesh(2), prefix_pool=0)
    prompts = {0: [3, 17, 91, 42, 8], 1: [7, 7, 9, 7, 7, 9, 2]}
    drafts = {0: [5, 9, 1], 1: [7, 9, 2]}

    def prep():
        eng.reset()
        return {s: eng.prefill_chunked(s, p)
                for s, p in prompts.items()}

    first = prep()
    toks_b, acc_b = eng.verify_batch(
        {s: (first[s], drafts[s]) for s in prompts})
    first = prep()
    seq = {s: eng.verify_step(s, first[s], drafts[s], len(prompts[s]))
           for s in prompts}
    for s in prompts:
        assert int(acc_b[s]) == seq[s][1]
        assert toks_b[s].tolist() == seq[s][0].tolist(), \
            f"slot {s}: batched verify diverged from per-slot verify"
