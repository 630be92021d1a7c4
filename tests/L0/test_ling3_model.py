"""The ``ling_v3`` architecture (models/ling.py) against its float32
reference (benchmarks/lib/reference_ling3.py) at a small size on the CPU
(hidden 64, one period of six layers: two dense MLPs, five Kimi delta
layers of 2 heads of 128 x 128 and one latent layer with a latent of 32
and a rotary key of 16, 16 experts in 4 groups of which 2 are kept, 4 a
token, a shared expert, vocabulary 256; Pallas in interpret mode). The
recurrence's kernels are in test_gated_delta.py, the latent kernels in
test_mla_attention.py, the engine in test_zaya_serving.py (all three
stateful models, case by case).

Tolerances, and why. In float32 the program and the reference do the same
sums in another order - the chunked delta rule against the token-by-token
scan, the ABSORBED latent attention against the expanded one: logits
(scale 1) agree to 1e-4, and the expert SETS agree wherever the
reference's margin between its 4th choice and the best not chosen exceeds
1e-4 (selection scores are sigmoids: scale 0.1); the served path reads
6e-6 off the reference. That is tight enough for what must not pass: the
recurrent state kept in bfloat16 moves the served path's logits by 0.016,
a skipped ``rms(c)`` by 0.044 (``test_what_must_not_pass_does_not``). In
bfloat16 the logits are compared GIVEN the program's expert sets at
BF16_GIVEN_SETS = 0.35 (sound readings 0.077 to 0.116 over seeds 1 to 3,
the float8 control 0.98 at the least).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import LingLM, build_lm
from apex_tpu.serving.kv_cache import CacheSpec, SlotAddr
from apex_tpu.transformer.moe import group_limited_sigmoid_topk
from benchmarks.checks.tiny_ling3 import TINY_LING_CFG
from benchmarks.lib import common
from benchmarks.lib import reference_ling3 as rl

pytestmark = pytest.mark.serving

CFG = TINY_LING_CFG
F32_TOL = 1e-4
BF16_GIVEN_SETS = 0.35


def _tokens(seed, n):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, n))


def _sets(choice):
    return np.sort(np.asarray(choice), -1)


# ------------------------------------------------------------ the builder
def test_build_lm_builds_the_kind_and_refuses_what_it_is_not():
    m = build_lm(CFG, dtype=jnp.float32)
    assert type(m) is LingLM and m.model_kind == "ling_v3"
    assert [m.is_latent(i) for i in range(6)] == [False] * 5 + [True]
    assert (m.first_dense, m.n_group, m.topk_group) == (2, 4, 2)
    assert (m.num_experts, m.experts_per_token, m.experts_held) \
        == (16, 4, None)
    cut = build_lm(dict(CFG, num_experts=4, published={"num_experts": 16}))
    assert (cut.num_experts, cut.experts_held) == (16, (0, 1, 2, 3))
    for bad in (dict(q_lora_rank=64), dict(score_function="softmax"),
                dict(norm_topk_prob=False), dict(use_kda_lora=True),
                dict(gated_attention_proj_granularity_type="element_wise"),
                dict(expert_swiglu_limit_list=[0, 0, 4, 0, 0, 0])):
        with pytest.raises(NotImplementedError, match="as published"):
            build_lm(dict(CFG, **bad))
    # a clamp on a layer that is not kept refuses nothing
    build_lm(dict(CFG, expert_swiglu_limit_list=[0] * 6 + [4]))
    with pytest.raises(ValueError, match="ling_v3"):
        build_lm({"model_type": "mamba"})


def test_the_published_file_gives_the_issues_sizes():
    """The configuration's file through ``build_lm``: the cache spec, a
    slot's state and a token's page row as ISSUE 36 reckons them, and the
    parameter count of every kind of layer."""
    cfg = common.load_json(common.ROOT,
                           "benchmarks/configs/ling-3.0-flash-vl.json")
    m = build_lm(cfg)
    assert (m.num_experts, len(m.experts_held), m.num_layers) == (512, 128, 6)
    spec = CacheSpec.of(m)
    assert (spec.page_layers, spec.kv_heads, spec.head_dim,
            spec.value_dim) == (1, 1, 576, 512)
    assert spec.head_dim * 2 == 1152                  # bytes a token
    rec, conv = spec.state
    assert (rec.name, rec.layers, rec.shape, rec.dtype) \
        == ("recurrent", 5, (32, 128, 128), jnp.float32)
    assert (conv.name, conv.layers, conv.shape) == ("conv", 5, (3, 12288))
    assert int(np.prod(rec.shape)) * 4 == 2_097_152
    assert int(np.prod(conv.shape)) * 2 == 73_728
    assert (spec.counter_layers, spec.num_experts) == (4, 512)
    count = lambda i: sum(int(np.prod(s)) for s in  # noqa: E731
                          rl.layer_shapes(cfg, i).values())
    mixer = lambda i, pre: sum(  # noqa: E731
        int(np.prod(s)) for n, s in rl.layer_shapes(cfg, i).items()
        if n.startswith(pre))
    assert round(mixer(0, "kda/") / 1e6, 2) == 52.65
    assert round(mixer(5, "mla/") / 1e6, 2) == 31.97
    assert round(count(0) / 1e6, 1) == 99.8 and count(0) == count(1)
    assert round(count(2) / 1e6, 1) == 814.8
    assert round(count(5) / 1e6, 1) == 794.2
    total = sum(count(i) for i in range(6)) + 2 * 157184 * 2560 + 2560
    assert round(total * 2 / 1e9, 2) == 8.49          # bfloat16 bytes


def test_the_parameter_tree_is_the_references():
    m = build_lm(CFG, dtype=jnp.float32)
    init = jax.eval_shape(lambda: m.init(
        jax.random.PRNGKey(0), _tokens(0, 8)[None], train=False))
    tree = rl.program_tree(rl.seeded_weights(CFG, 1, jnp.float32))
    assert (jax.tree_util.tree_structure(init["params"])
            == jax.tree_util.tree_structure(tree))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape, init["params"], tree))


# ------------------------------------------------------------- the router
def _route_case(seed, n=64):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(n, 16)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(16,)), jnp.float32)
    return logits, bias


def _reference_route(logits, bias):
    """``rl.route`` on given logits: an identity router."""
    lp = {"router/w": jnp.eye(16, dtype=jnp.float32), "router/bias": bias}
    return rl.route(logits, lp, dict(CFG, hidden_size=16))


@pytest.mark.parametrize("seed", [1, 2])
def test_group_limited_choosing_is_the_references(seed):
    logits, bias = _route_case(seed)
    w, c = group_limited_sigmoid_topk(logits, bias, k=4, n_group=4,
                                      topk_group=2, scale=2.5)
    own, weights, _ = _reference_route(logits, bias)
    assert (np.asarray(c) == np.asarray(own)).all()
    np.testing.assert_allclose(np.asarray(w), np.asarray(weights),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    # every chosen expert lies in one of two groups of four
    assert (np.array([len(set(r // 4)) for r in np.asarray(c)]) <= 2).all()


def test_the_bias_chooses_and_does_not_weigh():
    logits = jnp.zeros((1, 16), jnp.float32).at[0, :4].set(
        jnp.asarray([2.0, 1.0, 0.5, 0.2]))
    none = jnp.zeros((16,), jnp.float32)
    w0, c0 = group_limited_sigmoid_topk(logits, none, k=4, n_group=4,
                                        topk_group=2)
    # group 0 (the four large scores) and, all else equal, the LOWEST
    # other group; within it the lowest ids: ties go to the lower index
    assert sorted(np.asarray(c0[0])) == [0, 1, 2, 3]
    # a bias lifts group 3 over group 0's weaker members: it changes the
    # choice, and the weights stay the scores' own (no bias in them)
    bias = none.at[12].set(0.6).at[13].set(0.55)
    w1, c1 = group_limited_sigmoid_topk(logits, bias, k=4, n_group=4,
                                        topk_group=2)
    assert sorted(np.asarray(c1[0])) == [0, 1, 12, 13]
    s = jax.nn.sigmoid(jnp.asarray([2.0, 1.0, 0.0, 0.0]))
    got = dict(zip(np.asarray(c1[0]).tolist(), np.asarray(w1[0]).tolist()))
    np.testing.assert_allclose([got[0], got[1], got[12], got[13]],
                               np.asarray(s / s.sum()), rtol=1e-6)
    own, weights, _ = _reference_route(logits, bias)
    assert (np.asarray(own) == np.asarray(c1)).all()
    # all scores equal: groups 0 and 1, ids 0, 1, 2, 3
    _, ct = group_limited_sigmoid_topk(jnp.zeros((1, 16)), none, k=4,
                                       n_group=4, topk_group=2)
    assert np.asarray(ct[0]).tolist() == [0, 1, 2, 3]
    assert np.asarray(_reference_route(jnp.zeros((1, 16)), none)[0][0]
                      ).tolist() == [0, 1, 2, 3]


# ------------------------------------------- the model against the reference
def _forward(m, p, toks):
    logits, aux = jax.jit(lambda t: m.apply(
        {"params": rl.program_tree(p)}, t, train=False,
        mutable=["intermediates"]))(toks[None])
    return logits[0], np.stack([np.asarray(c[0]) for c in
                                aux["intermediates"]["expert_choice"]])


def _all_layers(choice):
    """The program's choices ``[4, S, k]`` as the reference's ``[L, S,
    k]`` (the dense layers' rows are not read)."""
    z = np.zeros_like(choice[0])
    return np.stack([z, z] + list(choice))


@pytest.mark.parametrize("seed", [1, 2])
def test_float32_forward_matches_the_reference(seed):
    p = rl.seeded_weights(CFG, seed, jnp.float32)
    toks = _tokens(seed, 72)
    logits, choice = _forward(build_lm(CFG, dtype=jnp.float32), p, toks)
    h, own, margins = rl.hidden_states(p, CFG, toks)
    own, margins = np.asarray(own)[2:], np.asarray(margins)[2:]
    decisive = margins > 1e-4
    assert (_sets(choice) == _sets(own))[decisive].all()
    if decisive.all():
        assert float(jnp.max(jnp.abs(logits - rl.logits_of(p, h)))) \
            < F32_TOL


@pytest.mark.parametrize("seed", [1, 3])
def test_bfloat16_logits_given_the_programs_expert_sets(seed):
    p = rl.seeded_weights(CFG, seed)                 # bfloat16, as served
    toks = _tokens(seed, 48)
    logits, choice = _forward(build_lm(CFG, dtype=jnp.bfloat16), p, toks)
    given = _all_layers(choice)
    h_given, _, _ = rl.hidden_states(p, CFG, toks, choices=given)
    ref = rl.logits_of(p, h_given)
    h8, _, _ = rl.hidden_states(p, CFG, toks, "fp8", choices=given)
    program = float(jnp.max(jnp.abs(logits - ref)))
    control = float(jnp.max(jnp.abs(rl.logits_of(p, h8) - ref)))
    assert program < BF16_GIVEN_SETS < control, (program, control)


def test_the_blocked_reference_is_the_whole_one():
    """Blocks of 64 positions, the delta rule's matrix, the convolution's
    tail and the keys handed on, against one block of 192."""
    p = rl.seeded_weights(CFG, 5, jnp.float32)
    toks = _tokens(5, 192)
    whole, own, _ = rl.hidden_states(p, CFG, toks)
    blocked, own_b, _ = rl.hidden_states(p, CFG, toks, block=64, cap=256)
    assert float(jnp.max(jnp.abs(whole - blocked))) < 5e-6
    assert (np.asarray(own) == np.asarray(own_b)).all()


def _serve_by_hand(m, v, toks, prompt_len, state_dtype=jnp.float32):
    """Aligned chunks (the last one padded), then three decode steps,
    through the model's serving modes as the engine calls them; the
    logits of every chunk's last valid row and of every decode step."""
    spec = CacheSpec.of(m)
    PL, slots, slot = 128, 2, 1
    pool = jnp.zeros((spec.page_layers, 4, 1, spec.head_dim, PL),
                     jnp.float32)
    none = jnp.zeros((spec.page_layers, 4, 0, spec.head_dim, PL),
                     jnp.float32)
    pt = jnp.asarray([[1, 2, 3]], jnp.int32)
    blocks = {b.name: jnp.full((b.layers, slots) + b.shape, 0.5,
                               state_dtype if b.name == "recurrent"
                               else jnp.float32) for b in spec.state}

    @jax.jit
    def chunk(t, pool, blocks, off, n):
        return m.apply(v, t, train=False, cache=(pool, none, pt),
                       positions=off[None], state=blocks, n_valid=n[None],
                       addr=SlotAddr(slot=jnp.int32(slot), fresh=off == 0))

    @jax.jit
    def decode(t, pool, blocks, pos):
        return m.apply(v, jnp.stack([t, t])[:, None], train=False,
                       cache=(pool, none, jnp.concatenate([pt * 0, pt])),
                       positions=jnp.stack([pos, pos]), state=blocks,
                       addr=SlotAddr(active=jnp.asarray([False, True])))

    pad = jnp.concatenate([toks[:prompt_len],
                           jnp.zeros((-prompt_len % PL,), toks.dtype)])
    rows = []
    for off in range(0, prompt_len, PL):
        n = min(PL, prompt_len - off)
        lg, (pool, _, blocks, _) = chunk(pad[None, off:off + PL], pool,
                                         blocks, jnp.int32(off),
                                         jnp.int32(n))
        rows.append((off + n - 1, np.asarray(lg[0, 0])))
    for pos in range(prompt_len, prompt_len + 3):
        lg, (pool, _, blocks, _) = decode(toks[pos], pool, blocks,
                                          jnp.int32(pos))
        rows.append((pos, np.asarray(lg[1, 0])))
    return rows, blocks


@pytest.mark.parametrize("prompt_len", [130, 300])
def test_chunks_then_decode_equal_the_references_one_forward(prompt_len):
    """The same tokens through two or three aligned chunks and then token
    by token through decode - the delta rule's matrix, the convolution's
    tail and the LATENT pages handed on each time, attention absorbed -
    give the logits of the reference's one expanded forward pass."""
    p = rl.seeded_weights(CFG, 4, jnp.float32)
    m = build_lm(CFG, dtype=jnp.float32)
    toks = _tokens(4, prompt_len + 3)
    h, _, margins = rl.hidden_states(p, CFG, toks)
    ref = np.asarray(rl.logits_of(p, h))
    assert float(margins.min()) > 2e-5      # else pick another seed
    rows, blocks = _serve_by_hand(m, {"params": rl.program_tree(p)}, toks,
                                  prompt_len)
    for pos, lg in rows:
        assert np.abs(lg - ref[pos]).max() < F32_TOL, pos
    # the idle neighbour's state never moved
    assert all(float(jnp.abs(b[:, 0] - 0.5).max()) == 0
               for b in blocks.values())


@pytest.mark.parametrize("fault", ["bfloat16_state", "no_latent_norm"])
def test_what_must_not_pass_does_not(fault):
    """The tolerance above against two wrong programs: the recurrent
    state rounded to bfloat16 between programs, and the latent cached
    without its RMSNorm (gains at 1, so only the normalisation is
    missing)."""
    p = rl.seeded_weights(CFG, 4, jnp.float32)
    m = build_lm(CFG, dtype=jnp.float32)
    toks = _tokens(4, 133)
    h, _, _ = rl.hidden_states(p, CFG, toks)
    ref = np.asarray(rl.logits_of(p, h))
    v = {"params": rl.program_tree(p)}
    if fault == "bfloat16_state":
        # (the kernels take a float32 state only and give way to the jnp
        # form on the rounded one)
        rows, _ = _serve_by_hand(m, v, toks, 130, state_dtype=jnp.bfloat16)
    else:
        import apex_tpu.models.ling as ling
        real = ling.rms
        # rms(c) left out: the latent goes to the page as projected
        ling.rms = lambda x, w, eps: (
            jnp.asarray(x, jnp.float32) * w if w.shape == (32,)
            else real(x, w, eps))
        try:
            rows, _ = _serve_by_hand(m, v, toks, 130)
        finally:
            ling.rms = real
    worst = max(np.abs(lg - ref[pos]).max() for pos, lg in rows)
    assert worst > 10 * F32_TOL, worst


def test_a_serving_model_refuses_training_and_a_cache_without_state():
    m = build_lm(CFG, dtype=jnp.float32)
    v = {"params": rl.program_tree(rl.seeded_weights(CFG, 1, jnp.float32))}
    with pytest.raises(NotImplementedError, match="serving model"):
        m.apply(v, _tokens(0, 8)[None], train=True)
    with pytest.raises(NotImplementedError, match="state blocks"):
        m.apply(v, _tokens(0, 8)[None], train=False,
                cache=(jnp.zeros(1), jnp.zeros(1), jnp.zeros(1)),
                positions=jnp.zeros(1))


# ------------------------------------------------------- the expert layer
def _layer_inputs(seed, n=40):
    p = rl.seeded_weights(CFG, seed, jnp.float32)
    lp = {k: jnp.asarray(x, jnp.float32)
          for k, x in p["layers"][2].items()}
    u = jnp.asarray(np.random.default_rng(seed).normal(size=(n, 64)),
                    jnp.float32)
    return lp, u


def _program_groups(lp, held=None):
    ids = jnp.arange(16) if held is None else jnp.asarray(held)
    return {"router": {"w": lp["router/w"], "bias": lp["router/bias"]},
            "experts": {"w_gate_up": lp["experts/w_gate_up"][ids],
                        "w_down": lp["experts/w_down"][ids]},
            "shared": {k: lp[f"shared/{k}"]
                       for k in ("w_gate_up", "w_down")}}


def _whole_layer(lp, u):
    own, weights, margin = rl.route(u, lp, CFG)
    shared = rl.swiglu(u, lp["shared/w_gate_up"], lp["shared/w_down"])
    return own, weights, margin, shared


def test_the_expert_block_is_the_dense_loop_over_its_experts():
    lp, u = _layer_inputs(7)
    m = build_lm(CFG, dtype=jnp.float32)
    y, choice, counts = jax.jit(lambda u: m._experts(
        u[None], _program_groups(lp), jnp.float32,
        jnp.ones((1, u.shape[0]), bool)))(u)
    own, weights, margin, shared = _whole_layer(lp, u)
    assert float(margin.min()) > 1e-5
    assert (_sets(choice[0]) == _sets(own)).all()
    want = rl.experts(u, own, weights, lp, CFG) + shared
    assert float(jnp.max(jnp.abs(y[0] - want))) < 2e-5
    assert int(counts.sum()) == 4 * u.shape[0]
    assert (np.bincount(np.asarray(own).ravel(), minlength=16)
            == np.asarray(counts)).all()


@pytest.mark.parametrize("block_rows", [768, 32])
def test_the_models_row_blocks_change_no_number(block_rows, monkeypatch):
    """160 (token, expert) rows in one block (the model's 768) or in as
    many blocks of 32 as the held rows need: the same sum."""
    import apex_tpu.models.ling as ling
    assert ling.EXPERT_BLOCK_ROWS == 768
    monkeypatch.setattr(ling, "EXPERT_BLOCK_ROWS", block_rows)
    lp, u = _layer_inputs(9)
    m = build_lm(CFG, dtype=jnp.float32)
    y, _, _ = jax.jit(lambda u: m._experts(
        u[None], _program_groups(lp), jnp.float32,
        jnp.ones((1, u.shape[0]), bool)))(u)
    own, weights, _, shared = _whole_layer(lp, u)
    want = rl.experts(u, own, weights, lp, CFG) + shared
    assert float(jnp.max(jnp.abs(y[0] - want))) < 2e-5


def test_the_parts_of_all_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test at the deployment's shape: 4 chips hold one
    of the router's four groups each (4 of the 16 experts; the last
    share's ids reversed); every chip routes over all 16 in 4 groups with
    weights normalised over all 4 chosen and scaled, computes its own
    experts' part, and the shared expert whole. The routed parts of all
    the shares, the shared expert counted once, are the uncut layer."""
    lp, u = _layer_inputs(8)
    valid = jnp.ones((1, u.shape[0]), bool)
    shares = [tuple(range(4 * i, 4 * i + 4)) for i in range(3)] \
        + [(15, 14, 13, 12)]
    total = 0.0
    for held in shares:
        m = build_lm(CFG, dtype=jnp.float32, experts_held=held)
        y, _, _ = jax.jit(lambda u, m=m, held=held: m._experts(
            u[None], _program_groups(lp, held), jnp.float32, valid))(u)
        total = total + y[0]
    own, weights, _, shared = _whole_layer(lp, u)
    whole = rl.experts(u, own, weights, lp, CFG) + shared
    assert float(jnp.max(jnp.abs(total - 3.0 * shared - whole))) < 5e-5
    # and one share alone is the reference's over the same experts
    m = build_lm(CFG, dtype=jnp.float32, experts_held=(4, 5, 6, 7))
    y, _, _ = jax.jit(lambda u: m._experts(
        u[None], _program_groups(lp, (4, 5, 6, 7)), jnp.float32, valid))(u)
    part = rl.experts(u, own, weights, lp, CFG, held=(4, 5, 6, 7)) + shared
    assert float(jnp.max(jnp.abs(y[0] - part))) < 2e-5
