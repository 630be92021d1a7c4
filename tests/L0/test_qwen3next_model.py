"""The ``qwen3_next`` architecture (models/qwen3_next.py) against its
float32 reference (benchmarks/lib/reference_qwen3next.py) at a small size
on the CPU (hidden 64, two periods of a linear and a full layer, 4 value
heads of 128 x 128, 4 query and 2 K/V heads of 32, 16 experts of width 32
at 4 a token and a shared expert, vocabulary 256; Pallas in interpret
mode). The recurrence's kernels are in test_gated_delta.py, the engine in
test_zaya_serving.py (both stateful models, case by case).

Tolerances, and why. In float32 the program and the reference do the same
sums in another order: logits (scale 1) agree to 5e-5, and the expert SETS
agree wherever the reference's margin between its 4th and 5th router
logit exceeds 1e-4. In bfloat16 (the precision the configuration states)
a near-tie at the k-th expert swaps one term of weight about 1/k, so the
logits are compared GIVEN the program's expert sets, at BF16_GIVEN_SETS =
0.4 (sound readings reach 0.17 over the seeds below - the reference with
only its matrix products' operands rounded to bfloat16 reads 0.08, the
program also keeps the residual stream in bfloat16; the float8 control
reads 1.26 at the least), and the sets must agree wherever the
reference's margin exceeds TIE_MARGIN = 0.25 (router logits have scale 2;
sets differed at margins up to 0.113).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import Qwen3NextLM, build_lm
from apex_tpu.serving.kv_cache import CacheSpec, SlotAddr
from benchmarks.checks.tiny_qwen3next import TINY_Q3N_CFG
from benchmarks.lib import reference_qwen3next as rq

pytestmark = pytest.mark.serving

CFG = TINY_Q3N_CFG
TIE_MARGIN = 0.25
BF16_GIVEN_SETS = 0.4


def _tokens(seed, n):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, n))


def _sets(choice):
    return np.sort(np.asarray(choice), -1)


# ------------------------------------------------------------ the builder
def test_build_lm_builds_the_kind_and_refuses_what_it_is_not():
    m = build_lm(CFG, dtype=jnp.float32)
    assert type(m) is Qwen3NextLM and m.model_kind == "qwen3_next"
    assert [m.is_full(i) for i in range(4)] == [False, True, False, True]
    assert (m.num_heads, m.num_kv_heads, m.head_dim) == (4, 2, 32)
    assert (m.num_experts, m.experts_per_token, m.experts_held) \
        == (16, 4, None)
    # a file that states the published count beside the chip's share:
    # the router keeps its width, the first ids are held
    cut = build_lm(dict(CFG, num_experts=4, published={"num_experts": 16}))
    assert (cut.num_experts, cut.experts_held) == (16, (0, 1, 2, 3))
    with pytest.raises(NotImplementedError, match="untied head"):
        build_lm(dict(CFG, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="qwen3_next"):
        build_lm({"model_type": "mamba"})


def test_the_published_sizes_give_the_issues_state_and_cache_sizes():
    spec = CacheSpec.of(Qwen3NextLM(num_layers=12))   # the cell's depth
    assert spec.page_layers == 3
    assert spec.page_layers * 2 * spec.kv_heads * spec.head_dim * 2 == 6144
    rec, conv = spec.state
    assert (rec.name, rec.layers, rec.shape, rec.dtype) \
        == ("recurrent", 9, (32, 128, 128), jnp.float32)
    assert (conv.name, conv.layers, conv.shape) == ("conv", 9, (3, 8192))
    assert int(np.prod(rec.shape)) * 4 == 2_097_152
    assert int(np.prod(conv.shape)) * 2 == 49_152
    assert (spec.counter_layers, spec.num_experts) == (12, 512)


def test_the_parameter_tree_is_the_references():
    m = build_lm(CFG, dtype=jnp.float32)
    init = jax.eval_shape(lambda: m.init(
        jax.random.PRNGKey(0), _tokens(0, 8)[None], train=False))
    tree = rq.program_tree(rq.seeded_weights(CFG, 1, jnp.float32))
    assert (jax.tree_util.tree_structure(init["params"])
            == jax.tree_util.tree_structure(tree))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape, init["params"], tree))
    assert tree["head"]["kernel"].shape == (64, 256)      # its own matrix


# ------------------------------------------- the model against the reference
def _forward(m, p, toks):
    logits, aux = jax.jit(lambda t: m.apply(
        {"params": rq.program_tree(p)}, t, train=False,
        mutable=["intermediates"]))(toks[None])
    return logits[0], np.stack([np.asarray(c[0]) for c in
                                aux["intermediates"]["expert_choice"]])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float32_forward_matches_the_reference(seed):
    p = rq.seeded_weights(CFG, seed, jnp.float32)
    toks = _tokens(seed, 40)
    logits, choice = _forward(build_lm(CFG, dtype=jnp.float32), p, toks)
    h, own, margins = rq.hidden_states(p, CFG, toks)
    decisive = np.asarray(margins) > 1e-4
    assert (_sets(choice) == _sets(own))[decisive].all()
    if decisive.all():
        assert float(jnp.max(jnp.abs(logits - rq.logits_of(p, h)))) < 5e-5


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_logits_given_the_programs_expert_sets(seed):
    p = rq.seeded_weights(CFG, seed)                 # bfloat16, as served
    toks = _tokens(seed, 48)
    logits, choice = _forward(build_lm(CFG, dtype=jnp.bfloat16), p, toks)
    h_given, own, margins = rq.hidden_states(p, CFG, toks, choices=choice)
    ref = rq.logits_of(p, h_given)
    h8, _, _ = rq.hidden_states(p, CFG, toks, "fp8", choices=choice)
    program = float(jnp.max(jnp.abs(logits - ref)))
    control = float(jnp.max(jnp.abs(rq.logits_of(p, h8) - ref)))
    wide = np.asarray(margins) > TIE_MARGIN
    assert (_sets(choice) == _sets(own))[wide].all(), \
        "an expert set differs where the reference's margin is wide"
    assert program < BF16_GIVEN_SETS < control, (program, control)


@pytest.mark.parametrize("prompt_len", [130, 300])
def test_chunks_then_decode_equal_the_references_one_forward(prompt_len):
    """The same tokens through two (130 = 128 + 2) or three (300 = 128 +
    128 + 44) aligned chunks, the last one padded, and then token by token
    through decode - state, convolution tail and pages handed on each time
    - give the logits of the reference's one forward pass."""
    p = rq.seeded_weights(CFG, 4, jnp.float32)
    m = build_lm(CFG, dtype=jnp.float32)
    v = {"params": rq.program_tree(p)}
    toks = _tokens(4, prompt_len + 3)
    h, _, margins = rq.hidden_states(p, CFG, toks)
    ref = np.asarray(rq.logits_of(p, h))
    assert float(margins.min()) > 2e-5      # else pick another seed
    tol = 1e-4
    spec = CacheSpec.of(m)
    PL, slots, slot = 128, 2, 1
    pool = jnp.zeros((spec.page_layers, 4, spec.kv_heads, spec.head_dim, PL),
                     jnp.float32)
    pt = jnp.asarray([[1, 2, 3]], jnp.int32)
    # garbage where the slot's last tenant was: the first chunk starts
    # from zeros all the same
    blocks = {b.name: jnp.full((b.layers, slots) + b.shape, 0.5,
                               jnp.float32) for b in spec.state}
    kp = vp = pool

    @jax.jit
    def chunk(t, kp, vp, blocks, off, n):
        return m.apply(v, t, train=False, cache=(kp, vp, pt),
                       positions=off[None], state=blocks, n_valid=n[None],
                       addr=SlotAddr(slot=jnp.int32(slot), fresh=off == 0))

    @jax.jit
    def decode(t, kp, vp, blocks, pos):
        # row 1 is the slot; row 0 rides the batch inactive on the
        # sentinel page and must not move
        return m.apply(v, jnp.stack([t, t])[:, None], train=False,
                       cache=(kp, vp, jnp.concatenate([pt * 0, pt])),
                       positions=jnp.stack([pos, pos]), state=blocks,
                       addr=SlotAddr(active=jnp.asarray([False, True])))

    pad = jnp.concatenate([toks[:prompt_len],
                           jnp.zeros((-prompt_len % PL,), toks.dtype)])
    for off in range(0, prompt_len, PL):
        n = min(PL, prompt_len - off)
        lg, (kp, vp, blocks, _) = chunk(pad[None, off:off + PL], kp, vp,
                                        blocks, jnp.int32(off), jnp.int32(n))
        assert np.abs(np.asarray(lg[0, 0]) - ref[off + n - 1]).max() < tol
    for pos in range(prompt_len, prompt_len + 3):
        lg, (kp, vp, blocks, _) = decode(toks[pos], kp, vp, blocks,
                                         jnp.int32(pos))
        assert np.abs(np.asarray(lg[1, 0]) - ref[pos]).max() < tol
    # the idle neighbour's state never moved
    assert all(float(jnp.abs(b[:, 0] - 0.5).max()) == 0
               for b in blocks.values())


def test_a_serving_model_refuses_training_and_a_cache_without_state():
    m = build_lm(CFG, dtype=jnp.float32)
    v = {"params": rq.program_tree(rq.seeded_weights(CFG, 1, jnp.float32))}
    with pytest.raises(NotImplementedError, match="serving model"):
        m.apply(v, _tokens(0, 8)[None], train=True)
    with pytest.raises(NotImplementedError, match="state blocks"):
        m.apply(v, _tokens(0, 8)[None], train=False,
                cache=(jnp.zeros(1), jnp.zeros(1), jnp.zeros(1)),
                positions=jnp.zeros(1))


# ------------------------------------------------------- the expert layer
def _layer_inputs(seed, n=40):
    p = rq.seeded_weights(CFG, seed, jnp.float32)
    lp = {k: jnp.asarray(x, jnp.float32)
          for k, x in p["layers"][0].items()}
    u = jnp.asarray(np.random.default_rng(seed).normal(size=(n, 64)),
                    jnp.float32)
    return lp, u


def _program_groups(lp, held=None):
    """The layer's parameters as the model's groups, the experts sliced
    to a share."""
    ids = jnp.arange(16) if held is None else jnp.asarray(held)
    return {"router": {"w": lp["router/w"]},
            "experts": {"w_gate_up": lp["experts/w_gate_up"][ids],
                        "w_down": lp["experts/w_down"][ids]},
            "shared": {k: lp[f"shared/{k}"]
                       for k in ("w_gate_up", "w_down", "w_gate")}}


def test_the_top_k_layer_is_the_dense_loop_over_its_experts():
    """Drop-nothing top-4 of 16 with renormalised weights and the shared
    expert against the reference's loop of masked dense products: the
    same sets (margins are wide at this seed) and the same sum."""
    lp, u = _layer_inputs(7)
    m = build_lm(CFG, dtype=jnp.float32)
    y, choice, counts = jax.jit(lambda u: m._experts(
        u[None], _program_groups(lp), jnp.float32,
        jnp.ones((1, u.shape[0]), bool)))(u)
    own, weights, margin = rq.route(u, lp, CFG)
    assert float(margin.min()) > 1e-4
    assert (_sets(choice[0]) == _sets(own)).all()
    want = rq.experts(u, own, weights, lp, CFG) \
        + rq.shared_expert(u, lp, CFG)
    assert float(jnp.max(jnp.abs(y[0] - want))) < 2e-5
    # four experts a token, every token counted once for each
    assert int(counts.sum()) == 4 * u.shape[0]
    assert (np.bincount(np.asarray(own).ravel(), minlength=16)
            == np.asarray(counts)).all()


def test_the_parts_of_all_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: 8 chips hold 2 of the 16 experts each
    (ids in any order: the last share's are reversed); every chip routes
    over all 16 with weights normalised over all 4 chosen, computes its
    own experts' part, and the shared expert whole. The routed parts of
    all the shares, the shared expert counted once, are the uncut
    layer."""
    lp, u = _layer_inputs(8)
    valid = jnp.ones((1, u.shape[0]), bool)
    shares = [(2 * i, 2 * i + 1) for i in range(7)] + [(15, 14)]
    total = 0.0
    for held in shares:
        m = build_lm(CFG, dtype=jnp.float32, experts_held=held)
        y, _, _ = jax.jit(lambda u, m=m, held=held: m._experts(
            u[None], _program_groups(lp, held), jnp.float32, valid))(u)
        total = total + y[0]
    own, weights, _ = rq.route(u, lp, CFG)
    shared = rq.shared_expert(u, lp, CFG)
    whole = rq.experts(u, own, weights, lp, CFG) + shared
    assert float(jnp.max(jnp.abs(total - 7.0 * shared - whole))) < 5e-5
    # and one share alone is the reference's over the same experts
    m = build_lm(CFG, dtype=jnp.float32, experts_held=(4, 5))
    y, _, _ = jax.jit(lambda u: m._experts(
        u[None], _program_groups(lp, (4, 5)), jnp.float32, valid))(u)
    part = rq.experts(u, own, weights, lp, CFG, held=(4, 5)) + shared
    assert float(jnp.max(jnp.abs(y[0] - part))) < 2e-5


@pytest.mark.parametrize("block_rows", [512, 32])
def test_more_rows_than_a_block_go_through_as_many_blocks(block_rows):
    """``dropless_topk_experts`` alone, where the rows routed to the held
    experts outnumber a block (the loop) and where they do not."""
    from apex_tpu.transformer.moe import dropless_topk_experts

    lp, u = _layer_inputs(9, n=50)
    own, weights, _ = rq.route(u, lp, CFG)
    held = (3, 12, 7, 0, 9)
    ids = jnp.asarray(held)
    y = jax.jit(lambda u: dropless_topk_experts(
        u, weights, own.astype(jnp.int32), lp["experts/w_gate_up"][ids],
        lp["experts/w_down"][ids], num_experts=16, experts_held=held,
        block_rows=block_rows))(u)
    # the reference indexes its stacked weights by expert id
    want = rq.experts(u, own, weights, lp, CFG, held=held)
    assert float(jnp.max(jnp.abs(y - want))) < 2e-5
