"""The ``zaya`` architecture (models/zaya.py) against its float32
reference (benchmarks/lib/reference_zaya.py) at a small size on the CPU
(hidden 64, 4 query and 2 K/V heads of 16, 4 experts of width 32, 3
layers, vocabulary 256; Pallas in interpret mode). The kernels it rests on
are in test_zaya_kernels.py, the engine in test_zaya_serving.py.

Tolerances, and why. In float32 the program and the reference do the same
sums in another order: logits agree to 2e-5 of a logit scale of 0.2, and
the experts chosen agree wherever the reference's margin between its two
best experts exceeds 1e-4. In bfloat16 (the precision the configuration
states) a near-tie flips and the layer's output changes wholly, so the
logits are compared GIVEN the program's expert choices, at 0.02 (sound
readings reach 0.0096 over the seeds below; the float8 control reads 0.093
at the least), and the choices must agree wherever the reference's margin
exceeds TIE_MARGIN = 0.05.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import ZayaLM, build_lm
from apex_tpu.models.transformer_lm import TransformerLM
from benchmarks.checks.tiny_zaya import TINY_ZAYA_CFG
from benchmarks.lib import reference_zaya as rz

pytestmark = pytest.mark.serving

CFG = TINY_ZAYA_CFG
TIE_MARGIN = 0.05
BF16_GIVEN_CHOICES = 0.02


def _tokens(seed, n):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, n))


# ------------------------------------------------------------ the builder
def test_build_lm_returns_each_kind_and_refuses_the_rest():
    gpt2 = build_lm({"model_type": "gpt2", "vocab_size": 96, "n_embd": 32,
                     "n_layer": 2, "n_head": 2, "n_positions": 64})
    assert type(gpt2) is TransformerLM and gpt2.num_layers == 2
    zaya = build_lm(CFG, dtype=jnp.float32)
    assert type(zaya) is ZayaLM
    assert (zaya.num_heads, zaya.num_kv_heads, zaya.head_dim) == (4, 2, 16)
    assert zaya.rope_theta == 5e6 and zaya.slot_state_width == 2 * 96 + 16
    with pytest.raises(ValueError, match="model_type"):
        build_lm({"model_type": "mamba"})
    with pytest.raises(NotImplementedError, match="top-1"):
        build_lm(dict(CFG, num_experts_per_tok=2))
    with pytest.raises(NotImplementedError, match="kernel 2"):
        build_lm(dict(CFG, cca_time0=4))


def test_the_published_sizes_give_the_issues_state_and_cache_widths():
    m = ZayaLM()                      # defaults are ZAYA1-8B's
    assert m.slot_state_width == 2688
    assert 2 * m.num_kv_heads * m.head_dim * 2 == 1024     # bytes a token


def test_the_parameter_tree_is_the_references():
    m = build_lm(CFG, dtype=jnp.float32)
    init = jax.eval_shape(lambda: m.init(
        jax.random.PRNGKey(0), _tokens(0, 8)[None], train=False))
    tree = rz.program_tree(rz.seeded_weights(CFG, 1, jnp.float32))
    assert (jax.tree_util.tree_structure(init["params"])
            == jax.tree_util.tree_structure(tree))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape, init["params"], tree))


# ------------------------------------------- the model against the reference
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float32_forward_matches_the_reference(seed):
    p = rz.seeded_weights(CFG, seed, jnp.float32)
    m = build_lm(CFG, dtype=jnp.float32)
    toks = _tokens(seed, 40)
    logits, aux = jax.jit(lambda t: m.apply(
        {"params": rz.program_tree(p)}, t, train=False,
        mutable=["intermediates"]))(toks[None])
    h, used, margins = rz.hidden_states(p, CFG, toks)
    ref = rz.logits_of(p, h)
    choice = np.stack([np.asarray(c[0]) for c in
                       aux["intermediates"]["expert_choice"]])
    decisive = np.asarray(margins) > 1e-4
    assert (choice == np.asarray(used))[decisive].all()
    if decisive.all():
        assert float(jnp.max(jnp.abs(logits[0] - ref))) < 2e-5


def _bf16_readings(seed, n=48):
    """(widest logit gap to the reference given the program's choices,
    the same for the float8 control given ITS choices ... read against
    the float32 reference given the program's, choices agree off ties)."""
    p = rz.seeded_weights(CFG, seed)                 # bfloat16, as served
    m = build_lm(CFG, dtype=jnp.bfloat16)
    toks = _tokens(seed, n)
    logits, aux = jax.jit(lambda t: m.apply(
        {"params": rz.program_tree(p)}, t, train=False,
        mutable=["intermediates"]))(toks[None])
    choice = np.stack([np.asarray(c[0]) for c in
                       aux["intermediates"]["expert_choice"]])
    h_given, own, margins = rz.hidden_states(p, CFG, toks, choices=choice)
    ref = rz.logits_of(p, h_given)
    h8, _, _ = rz.hidden_states(p, CFG, toks, "fp8", choices=choice)
    ctrl = rz.logits_of(p, h8)
    agree = (choice == np.asarray(own))[np.asarray(margins) > TIE_MARGIN]
    return (float(jnp.max(jnp.abs(logits[0] - ref))),
            float(jnp.max(jnp.abs(ctrl - ref))), bool(agree.all()))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_logits_given_the_programs_choices(seed):
    program, control, agree = _bf16_readings(seed)
    assert agree, "an expert flipped where the reference's margin is wide"
    assert program < BF16_GIVEN_CHOICES < control, (program, control)


def test_rotary_is_by_absolute_position_in_every_mode():
    """The same 130 tokens through the plain forward, through two
    aligned chunks (the second at offset 128) and token by token through
    decode give the logits of the reference's one forward pass: a mode
    that rotated by a position of its own (the chunk's row, 0 for a
    decode step) would not."""
    p = rz.seeded_weights(CFG, 4, jnp.float32)
    m = build_lm(CFG, dtype=jnp.float32)
    v = {"params": rz.program_tree(p)}
    toks = _tokens(4, 133)
    h, _, margins = rz.hidden_states(p, CFG, toks)
    ref = np.asarray(rz.logits_of(p, h))
    assert float(margins.min()) > 1e-4      # else pick another seed
    tol = 5e-5
    # plain forward
    lg = jax.jit(lambda t: m.apply(v, t, train=False))(toks[None, :130])
    assert np.abs(np.asarray(lg[0]) - ref[:130]).max() < tol
    # two aligned chunks into a pool of 3 pages + sentinel, then decode
    L, pl_ = 3, 128
    pool = jnp.zeros((L, 4, 2, 16, pl_), jnp.float32)
    pt = jnp.asarray([[1, 2, 3]], jnp.int32)
    state = jnp.zeros((L, 1, m.slot_state_width), jnp.float32)
    kp = vp = pool
    pad = jnp.concatenate([toks[:130], jnp.zeros((126,), toks.dtype)])
    step = jax.jit(lambda t, kp, vp, state, pos, n: m.apply(
        v, t, train=False, cache=(kp, vp, pt), positions=pos, state=state,
        n_valid=n))
    for off, n in ((0, 128), (128, 2)):
        lg, (kp, vp, state, _) = step(
            pad[None, off:off + 128], kp, vp, state, jnp.asarray([off]),
            jnp.asarray([n]))
        assert np.abs(np.asarray(lg[0, 0]) - ref[off + n - 1]).max() < tol
    for pos in (130, 131, 132):
        lg, (kp, vp, state, _) = step(
            toks[None, pos:pos + 1], kp, vp, state, jnp.asarray([pos]),
            None)
        assert np.abs(np.asarray(lg[0, 0]) - ref[pos]).max() < tol


def test_a_serving_model_refuses_training_and_the_contiguous_cache():
    m = build_lm(CFG, dtype=jnp.float32)
    v = {"params": rz.program_tree(rz.seeded_weights(CFG, 1, jnp.float32))}
    with pytest.raises(NotImplementedError, match="serving model"):
        m.apply(v, _tokens(0, 8)[None], train=True)
    with pytest.raises(NotImplementedError, match="paged cache"):
        m.apply(v, _tokens(0, 8)[None], train=False,
                cache=(jnp.zeros(1), jnp.zeros(1)), positions=jnp.zeros(1))


def test_a_model_holding_half_the_experts_gives_its_share():
    """``experts_held`` on the model: the router still runs over all 4."""
    p = rz.seeded_weights(CFG, 5, jnp.float32)
    toks = _tokens(5, 24)
    outs = []
    for held in ((0, 1), (2, 3)):
        m = build_lm(dict(CFG, num_hidden_layers=1), dtype=jnp.float32,
                     experts_held=held)
        tree = rz.program_tree({**p, "layers": p["layers"][:1]})
        ex = tree["layer_0"]["experts"]
        tree["layer_0"]["experts"] = {
            k: v[jnp.asarray(held)] for k, v in ex.items()}
        outs.append(jax.jit(lambda t, m=m, tree=tree: m.apply(
            {"params": tree}, t, train=False))(toks[None]))
    m = build_lm(dict(CFG, num_hidden_layers=1), dtype=jnp.float32)
    whole = jax.jit(lambda t: m.apply(
        {"params": rz.program_tree({**p, "layers": p["layers"][:1]})}, t,
        train=False))(toks[None])
    # the expert sublayer enters the residual stream linearly and the one
    # layer's output goes through the final norm, so compare what can be
    # compared exactly: the shares differ from each other and a token's
    # logits equal the whole model's under the share that holds its expert
    a, b, w = (np.asarray(t[0]) for t in (*outs, whole))
    close_a = np.abs(a - w).max(-1) < 1e-5
    close_b = np.abs(b - w).max(-1) < 1e-5
    assert (close_a ^ close_b).all()
