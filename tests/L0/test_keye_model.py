"""The ``KeyeVL2`` architecture (models/keye.py) against its float32
reference (benchmarks/lib/reference_keye.py) at a small size on the CPU
(``benchmarks/checks/tiny_keye.py``: hidden 256, four layers of 16 query
heads over 2 K/V heads of 128, an indexer of 8 heads of 64 keeping 64
positions a token, 16 of 32 experts held, 8 a token; contexts of about
300 positions, so that the selection keeps a fifth of them; Pallas in
interpret mode). The DSA kernels alone are in test_dsa_kernels.py.

Tolerances, and why. In float32 the program and the reference do the same
sums in another order (the paged, blocked kernels against plain products
over the whole sequence): given the same selections and expert choices
the logits (scale 1) agree to F32_TOL = 1e-4 (readings about 5e-6). The
program's own selections are the reference's wherever the reference's
score of a position lies further than SEL_TOL = 1e-4 from its row's 64th
largest (the scores are of order 1 and agree to about 1e-6; a position
nearer than that is a tie that rounding decides).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, serving
from apex_tpu.kernels import dsa
from apex_tpu.models import KeyeLM, build_lm
from apex_tpu.serving.kv_cache import CacheSpec, PagedKVCache, SlotAddr
from apex_tpu.telemetry import MetricsRegistry
from benchmarks.checks.tiny_keye import TINY_KEYE_CFG
from benchmarks.lib import common
from benchmarks.lib import reference_keye as rk
from benchmarks.lib import work_keye

pytestmark = pytest.mark.serving

CFG = TINY_KEYE_CFG
F32_TOL = 1e-4
SEL_TOL = 1e-4
PL, MAX_PAGES = 128, 4
SPAN = PL * MAX_PAGES


def _tokens(seed, n):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 512, n))


# ------------------------------------------------------------ build_lm
def test_build_lm_builds_keye_and_refuses_what_it_does_not_build():
    m = build_lm(CFG, dtype=jnp.float32)
    assert type(m) is KeyeLM and m.model_kind == "KeyeVL2"
    assert (m.num_experts, m.experts_held) == (32, tuple(range(16)))
    assert (m.index_heads, m.index_dim, m.index_topk) == (8, 64, 64)
    for bad in (dict(shared_expert_intermediate_size=128),
                dict(mlp_only_layers=[1]), dict(use_sliding_window=True),
                dict(sliding_window=4096), dict(tie_word_embeddings=True),
                dict(norm_topk_prob=False),
                dict(sa_config=dict(CFG["sa_config"],
                                    indexer_num_kv_heads=2))):
        with pytest.raises(NotImplementedError, match="as published"):
            build_lm(dict(CFG, **bad))
    with pytest.raises(ValueError, match="KeyeVL2"):
        build_lm({"model_type": "mamba"})


def test_the_published_file_gives_the_published_sizes():
    """The configuration's file through ``build_lm``: its cache spec, a
    position's bytes and a layer's weights as counted by hand."""
    cfg = common.load_json(common.ROOT,
                           "benchmarks/configs/keye-vl-2.0-30b-a3b.json")
    m = build_lm(cfg)
    spec = CacheSpec.of(m)
    assert (spec.page_layers, spec.kv_heads, spec.head_dim) == (8, 4, 128)
    assert spec.row_pages and (spec.index_dim, spec.index_topk) == (64,
                                                                    2048)
    assert spec.state == () and (spec.counter_layers,
                                 spec.num_experts) == (8, 128)
    assert m.experts_held == tuple(range(16))
    # a position: K and V rows of 4 x 128 and a 64-wide index key, bf16
    assert 8 * (2 * 4 * 128 + 64) * 2 == 17408
    assert abs(work_keye.layer_params(cfg) / 1e6 - 21.4) < 0.05
    assert 16 * 3 * 2048 * 768 == 75_497_472


def test_the_parameter_tree_is_the_references():
    m = build_lm(CFG, dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: m.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    want = rk.layer_shapes(CFG)
    got = {f"{mod}/{leaf}": tuple(a.shape)
           for mod, leaves in shapes["layer_0"].items()
           for leaf, a in leaves.items()}
    assert got == want


# ------------------------------------------------------------ the cache
def test_the_index_pool_is_this_models_alone():
    """KeyeLM's spec makes contiguous-row K/V pools and an index pool
    beside them; every other model's cache keeps exactly its leaves."""
    from apex_tpu.models import LingLM, Qwen3NextLM, ZayaLM, create_lm
    from apex_tpu.serving.kv_cache import SlotState

    def cache_of(model):
        spec = CacheSpec.of(model)
        state = SlotState.create(spec, slots=2) \
            if spec.state or spec.num_experts else None
        return PagedKVCache.create(
            layers=spec.page_layers, num_pages=3, heads=spec.kv_heads,
            page_len=PL, head_dim=spec.head_dim, state=state,
            value_dim=spec.value_dim, row_pages=spec.row_pages,
            index_dim=spec.index_dim)

    keye = cache_of(build_lm(CFG))
    assert keye.k.shape == keye.v.shape == (4, 3, PL, 2 * 128)
    assert keye.index.shape == (4, 3, 64, PL)
    assert keye.row_pages and keye.page_len == PL
    assert keye.state.blocks == {} and keye.state.expert_tokens.shape \
        == (4, 32)
    assert keye.bytes_per_token() == 4 * (2 * 256 + 64) * 2
    for model, n_state in ((create_lm("tiny"), 0),
                           (ZayaLM(num_layers=2), 1),
                           (Qwen3NextLM(num_layers=4), 2),
                           (LingLM(num_layers=6), 2)):
        c = cache_of(model)
        assert c.index is None and not c.row_pages
        # K, V (+ the state's blocks and the expert counter)
        want = 2 + (n_state + 1 if c.state is not None else 0)
        assert len(jax.tree_util.tree_leaves(c)) == want, type(model)


# ------------------------------------------------------------ selection
def test_the_exact_top_k_keeps_ties_at_the_lower_position():
    """Rows of repeated values, a row of zeros and a row with no more
    live positions than k: the kernel, its twin, the reference's
    bisection and ``lax.top_k`` keep the same positions."""
    k, span = 16, 256
    rng = np.random.default_rng(0)
    s = rng.integers(-3, 4, size=(8, span)).astype(np.float32) / 2
    s[1] = 0.0
    s[2, ::2] = -0.0                       # -0.0 and 0.0 are one value
    s[3] = rng.normal(size=span)
    pos = np.asarray([255, 255, 255, 200, 15, 16, 100, 9], np.int32)
    s = np.where(np.arange(span)[None] <= pos[:, None], s, -np.inf)
    s, pos = jnp.asarray(s), jnp.asarray(pos)
    _, idx = jax.lax.top_k(s, k)
    want = np.zeros((8, span), bool)
    np.put_along_axis(want, np.asarray(idx), True, 1)
    want &= np.arange(span)[None] <= np.asarray(pos)[:, None]
    for thr, cut in (dsa.dsa_select(s, pos, k),
                     dsa.select_reference(s, pos, k)):
        got = np.asarray(dsa.selected_mask(s, thr, cut, pos))
        assert (got == want).all()
    assert (np.asarray(rk.top_mask(s, pos, k)) == want).all()
    assert (want.sum(1) == np.minimum(np.asarray(pos) + 1, k)).all()


# ------------------------------------------------------ serving by hand
def _serve_by_hand(m, v, toks, prompt_len):
    """Aligned chunks (the last one padded), then three decode steps
    beside an idle row, through the model's serving modes as the engine
    calls them: the logits of every chunk's last valid row and of every
    decode step, and each position's kept positions ``[L, S, SPAN]`` and
    experts ``[L, S, k]``."""
    spec = CacheSpec.of(m)
    L, W = spec.page_layers, spec.kv_heads * spec.head_dim
    pools = (jnp.zeros((L, MAX_PAGES + 1, PL, W)),
             jnp.zeros((L, MAX_PAGES + 1, PL, W)),
             jnp.zeros((L, MAX_PAGES + 1, spec.index_dim, PL)))
    pt = jnp.arange(1, MAX_PAGES + 1, dtype=jnp.int32)[None]

    def run(t, pools, pt, **kw):
        (lg, (k, v_, _, _, ix)), aux = m.apply(
            v, t, train=False, cache=(pools[0], pools[1], pt, pools[2]),
            state={}, addr=SlotAddr(), mutable=["intermediates"], **kw)
        inter = aux["intermediates"]
        return lg, (k, v_, ix), jnp.stack(inter["dsa_selection"]), \
            jnp.stack(inter["expert_choice"])

    chunk = jax.jit(lambda t, pools, off, n: run(
        t, pools, pt, positions=off[None], n_valid=n[None]))
    decode = jax.jit(lambda t, pools, pos: run(
        jnp.stack([t, t])[:, None], pools, jnp.concatenate([pt * 0, pt]),
        positions=jnp.stack([pos, pos]),
        valid=jnp.asarray([[False], [True]])))
    pad = jnp.concatenate([toks[:prompt_len],
                           jnp.zeros((-prompt_len % PL,), toks.dtype)])
    rows, sel, choice = [], [], []
    for off in range(0, prompt_len, PL):
        n = min(PL, prompt_len - off)
        lg, pools, s, c = chunk(pad[None, off:off + PL], pools,
                                jnp.int32(off), jnp.int32(n))
        rows.append((off + n - 1, np.asarray(lg[0, 0])))
        sel.append(np.asarray(s[:, 0, :n]))
        choice.append(np.asarray(c[:, 0, :n]))
    for pos in range(prompt_len, prompt_len + 3):
        lg, pools, s, c = decode(toks[pos], pools, jnp.int32(pos))
        rows.append((pos, np.asarray(lg[1, 0])))
        sel.append(np.asarray(s[:, 1]))
        choice.append(np.asarray(c[:, 1]))
    return rows, np.concatenate(sel, 1), np.concatenate(choice, 1)


def test_chunks_then_decode_given_the_programs_choices_are_the_reference():
    """300 positions through three aligned chunks, then three tokens
    through decode - K/V rows, index keys and pages handed on each time,
    64 of up to 303 positions kept a token - give the logits of the
    reference's one forward pass GIVEN the program's selections and
    expert choices; and the program's selections are the reference's own
    but at near-ties."""
    p = rk.seeded_weights(CFG, 4, jnp.float32)
    m = build_lm(CFG, dtype=jnp.float32)
    n = 300
    toks = _tokens(4, n + 3)
    rows, sel, choice = _serve_by_hand(m, {"params": rk.program_tree(p)},
                                       toks, n)
    S = n + 3
    assert sel.shape == (4, S, SPAN)
    assert (sel.sum(-1) == np.minimum(np.arange(S) + 1, 64)[None]).all()
    # the reference over the same 303 tokens padded to SPAN, given them
    given = np.zeros((4, SPAN, SPAN), bool)
    given[:, :S] = sel
    given[:, S:, 0] = True                 # padding keeps position 0
    chosen = np.zeros((4, SPAN, choice.shape[-1]), np.int32)
    chosen[:, :S] = choice
    pad = jnp.concatenate([toks, jnp.zeros((SPAN - S,), toks.dtype)])
    h, _, _, own, scores = rk.hidden_states(
        p, CFG, pad, choices=chosen, selections=given, block=PL,
        with_scores=True)
    ref = np.asarray(rk.logits_of(p, h))
    for pos, lg in rows:
        assert np.abs(lg - ref[pos]).max() < F32_TOL, pos
    own, scores = np.asarray(own)[:, :S], np.asarray(scores)[:, :S]
    differ = own != sel
    kth = -np.sort(-scores, -1)[..., 63:64]
    live = np.isfinite(scores)
    near = np.abs(np.where(live, scores, 0.0) - kth) < SEL_TOL
    assert (~differ | near).all()


def test_the_engine_serves_it_and_counts_the_selection():
    """Through ``Engine`` (two of the preset's layers, which is what a
    whole program compile costs here): a 300-token prompt and three decode
    steps; every served token is the reference's best (float32), the
    index pool rides the programs, and the registry counts the positions
    each program's queries had live and kept."""
    cfg = dict(CFG, num_hidden_layers=2)
    p = rk.seeded_weights(cfg, 3, jnp.float32)
    reg = MetricsRegistry()
    eng = serving.Engine(build_lm(cfg, dtype=jnp.float32),
                         rk.program_tree(p), slots=2, max_len=SPAN,
                         chunk_len=PL, page_len=PL, registry=reg,
                         policy=amp.resolve_policy("O0", verbose=False))
    assert eng.cache.index.shape == (2, 2 * MAX_PAGES + 1, 64, PL)
    prompt = [int(t) for t in _tokens(3, 300)]
    out = [eng.prefill_chunked(0, prompt)]
    for _ in range(3):
        out.append(int(eng.decode_step(np.asarray([out[-1], 0], np.int32),
                                       np.asarray([True, False]),
                                       np.zeros(2, np.float32))[0]))
    gaps, _, _ = rk.served_token_gaps(p, cfg, prompt, out, block=PL)
    assert float(np.max(gaps)) < F32_TOL
    live = sum(range(1, 301)) + 301 + 302 + 303
    kept = sum(min(t, 64) for t in range(1, 301)) + 3 * 64
    assert reg.counters["serving.dsa.positions_live"] == live
    assert reg.counters["serving.dsa.positions_selected"] == kept
    assert eng.moe_tokens_per_expert().sum() == 2 * 8 * (300 + 3)


def test_the_engine_counts_the_steps_its_dsa_kernels_walk():
    """An engine of 32-page tables (4,096 positions) serving a 200-token
    prompt and two decode steps: each kernel walks the program's live
    span, so the registry's ``serving.dsa.steps_walked`` is the steps
    that hold the deepest query and ``steps_tabled`` what the whole table
    would cost, a layer each."""
    from apex_tpu.telemetry.summarize import render_summary

    cfg = dict(CFG, num_hidden_layers=2)
    p = rk.seeded_weights(cfg, 3, jnp.float32)
    reg = MetricsRegistry()
    eng = serving.Engine(build_lm(cfg, dtype=jnp.float32),
                         rk.program_tree(p), slots=2, max_len=32 * PL,
                         chunk_len=PL, page_len=PL, registry=reg,
                         policy=amp.resolve_policy("O0", verbose=False))
    out = [eng.prefill_chunked(0, [int(t) for t in _tokens(5, 200)])]
    for _ in range(2):
        out.append(int(eng.decode_step(np.asarray([out[-1], 0], np.int32),
                                       np.asarray([True, False]),
                                       np.zeros(2, np.float32))[0]))
    # two chunks (offsets 0 and 128): the indexer and the attention each
    # walk one step of four pages, of eight; two decode steps of two
    # rows: the indexer walks one step of sixteen pages, of two
    walked, tabled = 2 * 2 * 1 + 2 * 2 * 1, 2 * 2 * 8 + 2 * 2 * 2
    assert reg.counters["serving.dsa.steps_walked"] == walked
    assert reg.counters["serving.dsa.steps_tabled"] == tabled
    text = render_summary({"metrics": {}, "steps": {},
                           "counters": dict(reg.counters)})
    assert "sparse-attention kernels' walk of the table" in text
    assert f"{walked / tabled:.4f}" in text


# --------------------------------------------------------------- refusals
REFUSED_BY_ENGINE = {
    "prefix_cache retention": dict(prefix_pool=2),
    "host_tier swap": dict(host_tier=1 << 20),
    "speculative verify": dict(spec=serving.SpecConfig(draft_len=3)),
    "LoRA": dict(lora=serving.LoRAConfig(rank=2)),
    "int8 KV tier": dict(kv_quant=serving.KVQuantConfig()),
    "int8 weight tier": dict(weight_quant=serving.WeightQuantConfig()),
    "tensor parallelism (mesh=)": dict(mesh="any"),
}


@pytest.fixture(scope="module")
def weights():
    return rk.program_tree(rk.seeded_weights(CFG, 1))


def _engine(weights, **kw):
    return serving.Engine(build_lm(CFG), weights, slots=2, max_len=SPAN,
                          chunk_len=PL, page_len=PL, **kw)


@pytest.mark.parametrize("what", sorted(REFUSED_BY_ENGINE))
def test_the_engine_refuses_by_name_what_the_index_pool_breaks(weights,
                                                               what):
    with pytest.raises(NotImplementedError) as e:
        _engine(weights, **REFUSED_BY_ENGINE[what])
    assert what in str(e.value) and "'KeyeVL2'" in str(e.value)
    assert "index page kind" in str(e.value)


REFUSED_BY_SCHEDULER = {
    "prefix_cache retention": dict(retain_prefixes=True),
    "slo preemption with resume": dict(slo=serving.SLOConfig(
        classes={"batch": 0})),
    "speculative verify": dict(speculative=True),
    "disaggregated role": dict(role="decode"),
}


@pytest.mark.parametrize("what", sorted(REFUSED_BY_SCHEDULER))
def test_the_scheduler_refuses_by_name_what_the_index_pool_breaks(weights,
                                                                  what):
    with pytest.raises(NotImplementedError) as e:
        serving.Scheduler(_engine(weights), **REFUSED_BY_SCHEDULER[what])
    assert what in str(e.value) and "'KeyeVL2'" in str(e.value)
    assert "index page kind" in str(e.value)


# ----------------------------------------------------------- the share
def test_the_held_shares_of_both_chips_add_up_to_the_uncut_layer():
    """The expert-parallel share test: two chips hold 16 of the 32 experts each
    (the second's ids reversed); each routes over all 32 with the top 8
    renormalised over all 8 and computes its own experts' part. The two
    parts are the reference's whole layer; there is no shared expert to
    count once."""
    rng = np.random.default_rng(6)
    whole_cfg = dict(CFG, num_experts=32, num_hidden_layers=1)
    lp = rk.seeded_weights(whole_cfg, 6, jnp.float32)["layers"][0]
    u = jnp.asarray(rng.normal(size=(40, 256)), jnp.float32)
    valid = jnp.ones((1, 40), bool)
    total = 0.0
    for held in (tuple(range(16)), tuple(range(31, 15, -1))):
        m = build_lm(CFG, dtype=jnp.float32, experts_held=held)
        groups = {"router": {"w": lp["router/w"]},
                  "experts": {"w_gate_up": lp["experts/w_gate_up"][
                      jnp.asarray(held)],
                      "w_down": lp["experts/w_down"][jnp.asarray(held)]}}
        y, _, _ = jax.jit(lambda u, m=m, g=groups: m._experts(
            u[None], g, jnp.float32, valid))(u)
        total = total + y[0]
    with jax.default_matmul_precision("highest"):
        own, weights, _ = rk.route(u, lp, whole_cfg)
        whole = rk.experts(u, own, weights, lp, whole_cfg)
    assert float(jnp.max(jnp.abs(whole))) > 0.1
    assert float(jnp.max(jnp.abs(total - whole))) < 5e-5


def test_summarize_reads_the_share_of_positions_kept():
    from apex_tpu.telemetry.summarize import render_summary

    text = render_summary({"metrics": {}, "steps": {}, "counters": {
        "serving.dsa.positions_live": 1000.0,
        "serving.dsa.positions_selected": 250.0}})
    assert "positions a sparse-attention query keeps" in text
    assert text.rstrip().endswith("0.2500")
