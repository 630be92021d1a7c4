"""Context-parallel TRAINING test: ring attention inside an amp-O2 train
step over the ``context`` axis — the long-context story end-to-end, not just
the attention op.

Grad correctness note (why grad_average_axis="context" is right): params are
replicated per shard; shard r's local backward already accumulates the
k/v-path contributions of every shard (they flow back through the ring's
ppermute transposes), while q-path terms live only on their own shard —
each path term exists on exactly one shard's copy, so the psum-mean over
the axis reconstructs d(mean-over-shards loss)/dθ with no double counting.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from apex_tpu.utils.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.optimizers import fused_adam
from apex_tpu.transformer import ring_attention

# Heavy multi-device CPU-emulation tier: inert at the seed (shard_map
# import errors) until the apex_tpu.utils.compat shim made this file
# runnable on the hermetic jax, but too costly for the tier-1 wall-time
# budget. Deselect from the fast tier; run with -m slow.
pytestmark = pytest.mark.slow

B, H_HEADS, S_LOCAL, D, HID = 2, 4, 16, 8, 32


def _attn_model(p, x, axis_name):
    """One pre-LN-ish attention block over seq-sharded activations."""
    qkv = x @ p["w_qkv"]                                # [B, S_l, 3*HID]
    qkv = qkv.reshape(B, S_LOCAL, 3, H_HEADS, D)
    q, k, v = (jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3))
    o = ring_attention(q, k, v, axis_name=axis_name, causal=True)
    o = jnp.moveaxis(o, 1, 2).reshape(B, S_LOCAL, HID)
    return x + o @ p["w_out"]


def test_ring_attention_train_step_decreases_loss(eight_devices):
    mesh = Mesh(np.array(eight_devices), ("context",))
    rs = np.random.RandomState(0)
    params = {
        "w_qkv": jnp.asarray(rs.randn(HID, 3 * HID).astype(np.float32) * 0.1),
        "w_out": jnp.asarray(rs.randn(HID, HID).astype(np.float32) * 0.1),
    }
    policy = amp.resolve_policy(opt_level="O2", loss_scale="dynamic")

    def loss_fn(p, batch):
        x, t = batch
        y = _attn_model(p, jnp.asarray(x, policy.compute_dtype), "context")
        return jnp.mean((jnp.asarray(y, jnp.float32) - t) ** 2)

    init_fn, step_fn = amp.make_train_step(loss_fn, fused_adam(3e-3), policy,
                                           grad_average_axis="context")

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(), (P(None, "context"),
                                       P(None, "context"))),
                       out_specs=(P(), P()), check_vma=False)
    def run(state, batch):
        for _ in range(6):
            state, metrics = step_fn(state, batch)
        first = metrics  # last step's metrics
        return state.master_params, first["loss"]

    # global sequence 8*S_LOCAL = 128 tokens, sharded contiguously
    x = rs.randn(B, 8 * S_LOCAL, HID).astype(np.float32)
    t = np.tanh(x[:, ::-1].copy())  # nontrivial target
    state = init_fn(params)
    masters, final_loss = jax.jit(run)(state, (jnp.asarray(x),
                                              jnp.asarray(t)))

    # baseline: untouched params' loss on the same batch (single-shard ref)
    from apex_tpu.kernels.flash_attention import mha_reference

    def ref_loss(p):
        qkv = (x @ np.asarray(p["w_qkv"])).reshape(B, 8 * S_LOCAL, 3,
                                                   H_HEADS, D)
        q, k, v = (np.moveaxis(qkv[:, :, i], 1, 2) for i in range(3))
        o = np.asarray(mha_reference(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=True,
                                     scale=D ** -0.5))
        y = x + np.moveaxis(o, 1, 2).reshape(B, 8 * S_LOCAL, HID) \
            @ np.asarray(p["w_out"])
        return float(np.mean((y - t) ** 2))

    assert np.isfinite(float(final_loss))
    assert float(final_loss) < ref_loss(params), (
        float(final_loss), ref_loss(params))
    # trained masters evaluated on the FULL (unsharded) reference model also
    # improve — proving the sharded training optimized the real objective
    assert ref_loss(jax.tree_util.tree_map(np.asarray, masters)) \
        < ref_loss(params)


# -------------------------------------------------------- ring + dropout
def test_ring_attention_dropout_deterministic_and_unbiased():
    """Ring attention with fused prob-dropout: deterministic per seed,
    varies across seeds, unbiased in expectation vs the no-dropout ring,
    for both layouts."""
    from apex_tpu.transformer.context_parallel import (ring_attention,
                                                       zigzag_order)

    n = 4
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(devs[:n]), ("context",))
    B, H, S, D = 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.float32) for kk in ks)
    spec = P(None, None, "context", None)

    for layout in ("contiguous", "zigzag"):
        if layout == "zigzag":
            order = zigzag_order(S, n)
            q_, k_, v_ = (jnp.take(t, order, axis=2) for t in (q, k, v))
        else:
            q_, k_, v_ = q, k, v
        fn = jax.jit(shard_map(
            lambda q, k, v, s: ring_attention(
                q, k, v, causal=True, layout=layout,
                dropout_rate=0.3, dropout_seed=s),
            mesh=mesh, in_specs=(spec,) * 3 + (P(),), out_specs=spec))
        base_fn = jax.jit(shard_map(
            functools.partial(ring_attention, causal=True, layout=layout),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))

        d1 = fn(q_, k_, v_, jnp.int32(1))
        d1b = fn(q_, k_, v_, jnp.int32(1))
        d2 = fn(q_, k_, v_, jnp.int32(2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d1b))
        assert not np.allclose(np.asarray(d1), np.asarray(d2)), layout

        base = np.asarray(base_fn(q_, k_, v_))
        acc = np.zeros_like(base)
        m = 24
        for s in range(m):
            acc += np.asarray(fn(q_, k_, v_, jnp.int32(50 + s)))
        # Monte-Carlo bound on the MEAN deviation (the early causal rows
        # keep a single softmax entry, so the per-element variance is huge
        # and a max-norm bound would need thousands of samples)
        assert np.abs(acc / m - base).mean() < 0.08, layout


def test_ring_attention_dropout_grads_finite_and_deterministic():
    from apex_tpu.transformer.context_parallel import ring_attention

    n = 4
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(devs[:n]), ("context",))
    B, H, S, D = 1, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.float32) for kk in ks)
    spec = P(None, None, "context", None)
    fn = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=True,
                                       dropout_rate=0.2,
                                       dropout_seed=jnp.int32(9)),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))

    def loss(q, k, v):
        return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.isfinite(np.asarray(a)).all()
    # dropout must actually change the grads vs the clean path
    fn0 = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))

    def loss0(q, k, v):
        return (fn0(q, k, v).astype(jnp.float32) ** 2).sum()

    g0 = jax.grad(loss0, argnums=(0, 1, 2))(q, k, v)
    assert not np.allclose(np.asarray(g1[0]), np.asarray(g0[0]))


def test_ring_attention_dropout_rate_validation():
    from jax.sharding import Mesh as _M
    n = 4
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip("needs 4 devices")
    mesh = _M(np.array(devs[:n]), ("context",))
    q = jnp.zeros((1, 1, 4 * 8, 8))
    spec = P(None, None, "context", None)
    fn_bad = shard_map(
        lambda q: ring_attention(q, q, q, dropout_rate=1.0,
                                 dropout_seed=jnp.int32(0)),
        mesh=mesh, in_specs=(spec,), out_specs=spec)
    with pytest.raises(ValueError, match="dropout_rate"):
        jax.jit(fn_bad)(q)
    fn_noseed = shard_map(
        lambda q: ring_attention(q, q, q, dropout_rate=0.5),
        mesh=mesh, in_specs=(spec,), out_specs=spec)
    with pytest.raises(ValueError, match="dropout_seed"):
        jax.jit(fn_noseed)(q)
