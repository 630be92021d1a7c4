"""Quantized serving weights — int8 per-output-channel GEMMs, hermetic.

The acceptance bar from the weight-quantization issue, as tests:

- **config validation + loud calibration failure**: non-int8 dtypes /
  unknown granularities / bad margins are rejected at config time, and
  an all-zero (or non-finite) output channel raises at ENGINE
  construction with the parameter path and channel named — degenerate
  scales must never surface later as NaN logits;
- **per-channel round-trip exactness**: weights already on the
  quantization grid recover their exact codes and values, arbitrary
  weights round-trip within ``scale / 2`` per element, and each output
  channel carries its OWN scale (the epilogue-fold exactness argument
  needs per-channel, not per-tensor);
- **token-match-rate >= threshold vs the bf16 oracle** across
  chunk-boundary prompt lengths (below/at/straddling), the PR 10
  tolerance contract one tier over;
- **zero new compiled programs**: the quantized engine compiles the
  same pinned program set — quantization is a params property;
- **composition is the point**: wq+kv_quant serves within tolerance
  with both tiers' storage shrunk, wq+speculative stays bitwise
  plain-vs-spec (accept-longest-prefix emits the program's own greedy
  targets — quantization moves both modes identically), a wq prefix
  hit matches its cold miss token-for-token, and a tp=1 mesh is
  bitwise vs the unsharded wq engine (tp=2 slow-marked, per the PR 5
  pattern) with the scale leaves sharded next to their kernels;
- **the bf16 default stays the bitwise baseline**: ``weight_quant=
  None`` carries no scale leaves, compiles the same programs, and two
  default engines serve token-identically — none of the quant code is
  on its trace path.

Everything runs on CPU with a tiny model at policy O0 (exact fp32
compute — the match-rate tolerance isolates QUANTIZATION error, not
bf16 rounding); the kernels take their interpret/reference paths.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, KVQuantConfig, Request, Scheduler,
                              SpecConfig, WeightQuantConfig)
from apex_tpu.serving.quant_common import QMAX, dequantize, quantize
from apex_tpu.serving.weight_quant import (param_bytes, param_count,
                                           quant_scale_absmax)

pytestmark = pytest.mark.serving

VOCAB = 96          # divisible by the tp sizes under test (1, 2)
CHUNK = 8
# the tolerance of the issue's token-match contract at tiny-model
# scale: a single early argmax flip diverges a request's whole greedy
# tail, so the bound is deliberately below the bench-scale claim
MATCH_THRESHOLD = 0.95


def _tiny_lm(**kw):
    return TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                         num_heads=4, max_seq_len=64, **kw)


@pytest.fixture(scope="module")
def lm_and_params():
    m = _tiny_lm()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _mk_engine(lm_and_params, *, weight_quant=None, pool=2, slots=3,
               seed=5, **kw):
    m, params = lm_and_params
    return Engine(m, params, slots=slots, max_len=64, prefill_len=24,
                  chunk_len=CHUNK, prefix_pool=pool,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  weight_quant=weight_quant, **kw)


@pytest.fixture(scope="module")
def engine_pair(lm_and_params):
    """bf16(O0) oracle + int8-weights engine, identical geometry — the
    match-rate pair (jit caches warm across the module)."""
    return (_mk_engine(lm_and_params),
            _mk_engine(lm_and_params, weight_quant=WeightQuantConfig()))


def _shared_prefix_stream(seed, n=8, new_tokens=8):
    """Prefix hit/miss/evict shape: every prompt opens with one shared
    16-token (2-page) prefix plus a short unique tail."""
    rng = np.random.default_rng(seed)
    pre = list(rng.integers(1, VOCAB, size=16))
    reqs = []
    for _ in range(n):
        tail = list(rng.integers(1, VOCAB,
                                 size=int(rng.integers(1, 7))))
        reqs.append(Request(prompt=pre + tail,
                            max_new_tokens=new_tokens))
    return reqs


def _serve(engine, seed, **sched_kw):
    engine.reset(clear_prefixes=True)
    sched = Scheduler(engine, retain_prefixes=True, **sched_kw)
    reqs = _shared_prefix_stream(seed)
    sched.run(reqs)
    return [list(r.output_tokens) for r in reqs]


def _match_rate(a_lists, b_lists):
    tot = hit = 0
    for a, b in zip(a_lists, b_lists):
        assert len(a) == len(b)
        tot += len(a)
        hit += sum(int(x == y) for x, y in zip(a, b))
    return hit / tot if tot else 1.0


# ---------------------------------------------- config + loud calibration
def test_config_validation():
    with pytest.raises(ValueError, match="int8"):
        WeightQuantConfig(dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="granularity"):
        WeightQuantConfig(granularity="tensor")
    with pytest.raises(ValueError, match="margin"):
        WeightQuantConfig(margin=0.0)
    with pytest.raises(ValueError, match="margin"):
        WeightQuantConfig(margin=float("nan"))


def test_engine_type_validation(lm_and_params):
    with pytest.raises(TypeError, match="WeightQuantConfig"):
        _mk_engine(lm_and_params, weight_quant="int8")


def test_degenerate_channel_raises_at_construction(lm_and_params):
    """The loud-calibration satellite: an all-zero (or non-finite)
    output channel raises at engine construction with the parameter
    path and channel index named — never deferred to NaN logits."""
    m, params = lm_and_params
    for poison in (0.0, float("nan")):
        bad = copy.deepcopy(jax.device_get(params))
        bad["block_1"]["mlp_in"]["kernel"][:, 7] = poison
        with pytest.raises(ValueError,
                           match=r"degenerate.*mlp_in/kernel output "
                                 r"channel 7"):
            Engine(m, bad, slots=2, max_len=64, prefill_len=24,
                   chunk_len=CHUNK,
                   policy=resolve_policy("O0", verbose=False),
                   weight_quant=WeightQuantConfig())
    # a zero vocab ROW is the embedding's degenerate channel (the tied
    # head's output channel) — same loud contract
    bad = copy.deepcopy(jax.device_get(params))
    bad["wte"]["embedding"][3, :] = 0.0
    with pytest.raises(ValueError,
                       match=r"degenerate.*wte/embedding output "
                             r"channel 3"):
        Engine(m, bad, slots=2, max_len=64, prefill_len=24,
               chunk_len=CHUNK,
               policy=resolve_policy("O0", verbose=False),
               weight_quant=WeightQuantConfig())


def test_unquantizable_tree_raises(lm_and_params):
    """A tree with no recognizable GEMM site must refuse loudly, not
    serve silently unquantized."""
    with pytest.raises(ValueError, match="no quantizable"):
        WeightQuantConfig().quantize_params(
            {"dense": {"kernel": np.ones((4, 4), np.float32)}})


# ------------------------------------------------- round-trip + structure
def test_per_channel_roundtrip_exactness():
    """Grid weights recover exactly; arbitrary weights round-trip
    within scale/2 per element; each output channel carries its own
    scale (per-channel, not per-tensor — channels with wildly
    different ranges must not share a grid)."""
    rng = np.random.default_rng(3)
    # per-channel ranges spanning 3 orders of magnitude
    chan_absmax = np.array([1e-2, 0.5, 2.0, 40.0], np.float32)
    w = rng.uniform(-1, 1, size=(16, 4)).astype(np.float32) * chan_absmax
    # force the absmax onto the grid edge so scales are known exactly
    w[0] = chan_absmax
    # margin=1.0 isolates the GRID's properties (the absmax lands on
    # code 127 exactly, so quantize∘dequantize is a fixed point); the
    # 1.2 production default only stretches the same grid
    cfg = WeightQuantConfig(margin=1.0)
    q = cfg.quantize_params({"mlp_in": {"kernel": w}})["mlp_in"]
    assert q["kernel"].dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(q["kernel_scale"]),
                               chan_absmax / QMAX, rtol=1e-6)
    back = np.asarray(dequantize(q["kernel"], q["kernel_scale"], axis=1))
    bound = chan_absmax / QMAX / 2
    assert (np.abs(back - w) <= bound[None, :] * (1 + 1e-6)).all()
    # grid weights: quantize∘dequantize is the identity (exact code
    # recovery — the engine's storage quantize reproduces the values
    # the GEMM loads)
    q2 = cfg.quantize_params({"mlp_in": {"kernel": back}})["mlp_in"]
    back2 = np.asarray(dequantize(q2["kernel"], q2["kernel_scale"],
                                  axis=1))
    np.testing.assert_allclose(back2, back, rtol=1e-6, atol=1e-9)


def test_quantize_params_structure_and_bytes(lm_and_params):
    """The quantized tree: int8 kernels + fp32 sibling scales at every
    GEMM site, the tied embedding per-vocab-row, everything else
    untouched — and the bf16->int8 weight-bytes reduction clears the
    45% acceptance bar at this geometry."""
    _, params = lm_and_params
    p16 = resolve_policy("O3", verbose=False).cast_params(params)
    q = WeightQuantConfig().quantize_params(p16)
    for site in ("attn/qkv", "attn/proj"):
        a, b = site.split("/")
        node = q["block_0"][a][b]
        assert node["kernel"].dtype == jnp.int8
        assert node["kernel_scale"].dtype == jnp.float32
        assert node["kernel_scale"].shape == (node["kernel"].shape[-1],)
        assert node["bias"].dtype == jnp.bfloat16     # untouched
    for site in ("mlp_in", "mlp_out"):
        node = q["block_1"][site]
        assert node["kernel"].dtype == jnp.int8
        assert node["kernel_scale"].shape == (node["kernel"].shape[-1],)
    assert q["wte"]["embedding"].dtype == jnp.int8
    assert q["wte"]["embedding_scale"].shape == (VOCAB,)   # per row
    assert q["wpe"].dtype == jnp.bfloat16                  # untouched
    assert q["block_0"]["ln_attn"]["scale"].dtype == jnp.bfloat16
    # this fixture's hidden=32 model is overhead-heavy (wpe/LN/bias are
    # a third of it), so the reduction reads low here — pin a floor,
    # and pin the issue's 45% acceptance bar at the bench smoke
    # geometry below
    reduction = 1.0 - param_bytes(q) / param_bytes(p16)
    assert reduction >= 0.40, f"weight-bytes reduction {reduction:.3f}"
    # scale overhead charges the bytes-per-param gauge, not the count
    assert param_count(q) == param_count(p16)
    assert quant_scale_absmax(q) > 0


def test_weight_bytes_reduction_clears_the_bar_at_bench_geometry():
    """The >= 45% acceptance bar, pinned at the geometry the bench
    smoke serves (create_lm('tiny'), vocab 512): bf16 -> int8+scales
    must clear it, and the production 'small' shape sits near the 50%
    construction limit."""
    from apex_tpu.models.transformer_lm import create_lm

    m = create_lm("tiny", vocab_size=512, max_seq_len=128)
    p = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
               train=False)["params"]
    p16 = resolve_policy("O3", verbose=False).cast_params(p)
    q = WeightQuantConfig().quantize_params(p16)
    reduction = 1.0 - param_bytes(q) / param_bytes(p16)
    assert reduction >= 0.45, f"weight-bytes reduction {reduction:.3f}"


# ------------------------------------------------------------- composition
def test_token_match_vs_bf16_oracle_over_hit_miss_evict(engine_pair):
    """THE tentpole pin: the int8-weights engine serves the prefix
    hit/miss/evict stream at greedy token-match-rate >= threshold vs
    the bf16 oracle."""
    oracle, wq = engine_pair
    out_o = _serve(oracle, seed=42)
    out_w = _serve(wq, seed=42)
    rate = _match_rate(out_o, out_w)
    assert rate >= MATCH_THRESHOLD, \
        f"weight-quant token-match-rate {rate:.3f} vs bf16 oracle"


def test_chunk_boundary_prompt_lengths_match(engine_pair):
    """Match-rate across chunk-boundary prompt lengths (below / at /
    straddling / multi-chunk) — both ingest paths quantize the same
    GEMMs, so no boundary may open a divergence cliff."""
    oracle, wq = engine_pair
    rng = np.random.default_rng(17)
    prompts = [list(rng.integers(1, VOCAB, size=n))
               for n in (5, CHUNK, CHUNK + 5, 2 * CHUNK, 21)]
    outs = {}
    for label, eng in (("oracle", oracle), ("wq", wq)):
        eng.reset(clear_prefixes=True)
        reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
        Scheduler(eng).run(reqs)
        outs[label] = [list(r.output_tokens) for r in reqs]
    rate = _match_rate(outs["oracle"], outs["wq"])
    assert rate >= MATCH_THRESHOLD, \
        f"chunk-boundary token-match-rate {rate:.3f}"


def test_zero_new_programs(engine_pair):
    """Quantization is a params property: the wq engine compiles the
    SAME pinned program set (chunk + decode) — zero new executables."""
    _, wq = engine_pair
    wq.prefill_chunked(0, [5, 9, 2])  # scheduler-less callers: same program
    assert (wq.chunk_traces, wq.decode_traces) == (1, 1)
    assert wq.compiled_programs == 2


def test_wq_composes_with_kv_quant(lm_and_params):
    """The two int8 tiers together: weight bytes AND cache bytes both
    shrink, served output stays within the match-rate contract vs the
    all-bf16 oracle, and still zero new programs."""
    oracle = _mk_engine(lm_and_params, seed=7)
    both = _mk_engine(lm_and_params, weight_quant=WeightQuantConfig(),
                      kv_quant=KVQuantConfig(), seed=7)
    assert jnp.dtype(both.cache.dtype) == jnp.int8
    assert both.params["block_0"]["attn"]["qkv"]["kernel"].dtype \
        == jnp.int8
    # O0 oracle stores fp32 cache; int8 quarters it at this policy
    assert both.cache.nbytes() * 2 <= oracle.cache.nbytes()
    rate = _match_rate(_serve(oracle, seed=33), _serve(both, seed=33))
    assert rate >= MATCH_THRESHOLD, \
        f"wq+kv_quant token-match-rate {rate:.3f}"
    assert both.compiled_programs == both.chunk_traces \
        + both.decode_traces


def test_speculative_is_bitwise_plain_vs_spec_on_wq_engine(
        lm_and_params):
    """Speculative composition: ON the weight-quantized engine,
    spec-vs-plain stays bitwise (the verify program's emitted tokens
    ARE its own greedy targets — weight quantization moves both modes
    identically) with real drafts accepted."""
    eng = _mk_engine(lm_and_params, weight_quant=WeightQuantConfig(),
                     spec=SpecConfig(draft_len=3, ngram=2))
    rng = np.random.default_rng(7)
    hist = list(rng.integers(1, VOCAB, size=10))

    def stream(r):
        reqs = []
        for _ in range(4):
            tail = list(r.integers(1, VOCAB, size=3))
            reqs.append(Request(prompt=(hist + tail + tail)[:24],
                                max_new_tokens=10))
        return reqs

    outs, accepted = {}, {}
    for mode, sp in (("plain", False), ("spec", True)):
        eng.reset(clear_prefixes=True)
        sched = Scheduler(eng, speculative=sp)
        reqs = stream(np.random.default_rng(3))
        sched.run(reqs)
        outs[mode] = [list(r.output_tokens) for r in reqs]
        accepted[mode] = sum(r.spec_accepted for r in reqs)
    assert outs["spec"] == outs["plain"]
    assert accepted["spec"] > 0, "drafter never fired — the exactness " \
        "pin proved nothing"
    assert eng.verify_traces == 1


def test_prefix_hit_matches_cold_miss_on_wq_engine(lm_and_params):
    """COW composition: a prefix hit on the wq engine shares pages as
    usual (weights are engine state, not cache state — the tier adds
    nothing to the hit path) and the hit's tokens match the cold miss
    token-for-token."""
    eng = _mk_engine(lm_and_params, weight_quant=WeightQuantConfig())
    eng.reset(clear_prefixes=True)
    sched = Scheduler(eng, retain_prefixes=True)
    rng = np.random.default_rng(9)
    pre = list(rng.integers(1, VOCAB, size=8))      # exactly one page
    tail = list(rng.integers(1, VOCAB, size=3))
    (miss,) = sched.run([Request(prompt=pre + tail, max_new_tokens=4)])
    assert miss.reused_tokens == 0
    (hit,) = sched.run([Request(prompt=pre + tail, max_new_tokens=4)])
    assert hit.reused_tokens == 8
    assert hit.output_tokens == miss.output_tokens


def test_tp1_mesh_is_bitwise_vs_unsharded_wq_engine(lm_and_params):
    """Tensor-parallel composition (tier-1 half): a 1-device mesh over
    the wq engine — scale leaves sharded next to their kernels under
    the rule table — serves the greedy stream BITWISE identical to the
    unsharded wq engine, the same pin the bf16 and kv-quant tiers
    carry."""
    from jax.sharding import Mesh

    e0 = _mk_engine(lm_and_params, weight_quant=WeightQuantConfig(),
                    seed=11)
    e1 = _mk_engine(lm_and_params, weight_quant=WeightQuantConfig(),
                    seed=11,
                    mesh=Mesh(np.array(jax.devices()[:1]), ("tp",)))
    assert _serve(e1, seed=21) == _serve(e0, seed=21)


@pytest.mark.slow
def test_tp2_mesh_is_token_exact_vs_unsharded_wq_engine(lm_and_params):
    """Tensor-parallel composition (slow half, per the PR 5 pattern):
    tp=2 CPU device emulation over the wq engine is token-exact vs the
    unsharded wq engine, with column-parallel scales SPLIT on the
    output axis (qkv head-group permuted with its kernel) and
    row-parallel scales replicated."""
    from jax.sharding import Mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    e0 = _mk_engine(lm_and_params, weight_quant=WeightQuantConfig(),
                    seed=11)
    e2 = _mk_engine(lm_and_params, weight_quant=WeightQuantConfig(),
                    seed=11,
                    mesh=Mesh(np.array(jax.devices()[:2]), ("tp",)))
    assert _serve(e2, seed=23) == _serve(e0, seed=23)
    b0 = e2.params["block_0"]
    qkv_scale = b0["attn"]["qkv"]["kernel_scale"]     # column-parallel
    assert {s.data.shape for s in qkv_scale.addressable_shards} \
        == {(48,)}                                    # 96 / tp
    # shard 0 holds the head-group-PERMUTED first half: its heads' Q,
    # K and V scales, exactly the kernel's split
    full = np.asarray(
        e0.params["block_0"]["attn"]["qkv"]["kernel_scale"])
    perm = full.reshape(3, 2, 2, 8).transpose(1, 0, 2, 3).reshape(-1)
    np.testing.assert_array_equal(
        np.asarray(qkv_scale.addressable_shards[0].data), perm[:48])
    proj_scale = b0["attn"]["proj"]["kernel_scale"]   # row-parallel
    assert all(s.data.shape == (32,)
               for s in proj_scale.addressable_shards)  # replicated


# ----------------------------------------------------- the bf16 default pin
def test_weight_quant_none_stays_the_bitwise_baseline(lm_and_params):
    """The contract the issue states: weight_quant=None is the DEFAULT
    and the bitwise baseline. Two default engines serve the stream
    token-identically, their params carry NO scale leaves and keep the
    original kernel dtype, and the program set is the pinned one."""
    a = _mk_engine(lm_and_params, seed=11)
    b = _mk_engine(lm_and_params, seed=11)
    assert a.weight_quant is None
    qkv = a.params["block_0"]["attn"]["qkv"]
    assert "kernel_scale" not in qkv
    assert "embedding_scale" not in a.params["wte"]
    assert qkv["kernel"].dtype == jnp.float32         # O0 cast, not int8
    assert _serve(a, seed=31) == _serve(b, seed=31)
    a.prefill_chunked(0, [5, 9, 2])
    assert (a.chunk_traces, a.decode_traces) == (1, 1)
    assert a.compiled_programs == 2


def test_wq_gauges_report_the_capacity_claim(lm_and_params):
    """serving.wq.* telemetry: bytes_per_param drops below half the
    bf16 figure's 2.0 at this geometry (the measurable weight-capacity
    claim, scale overhead included), quant_scale_absmax reports the
    grid's representable range, and neither gauge exists on the
    default engine (the family doubles as the tier's liveness
    signal)."""
    reg_b, reg_q = telemetry.MetricsRegistry(), telemetry.MetricsRegistry()
    _mk_engine(lm_and_params, registry=reg_b)
    eq = _mk_engine(lm_and_params, weight_quant=WeightQuantConfig(),
                    registry=reg_q)
    gb = reg_b.snapshot()["gauges"]
    gq = reg_q.snapshot()["gauges"]
    assert "serving.wq.bytes_per_param" not in gb
    assert "serving.wq.quant_scale_absmax" not in gb
    # O0 keeps fp32 (4 B) non-kernel leaves, so the quantized mean sits
    # above 1.0 but far below the fp32 tree's 4.0
    assert 1.0 <= gq["serving.wq.bytes_per_param"] < 2.0
    assert gq["serving.wq.quant_scale_absmax"] > 0
    # swap-in registry path (warmup pattern) re-emits the gauges
    reg2 = telemetry.MetricsRegistry()
    eq.set_registry(reg2)
    assert "serving.wq.bytes_per_param" in reg2.snapshot()["gauges"]
