"""TP layer/mapping/xent tests vs dense references on the 8-device CPU mesh.

Mirrors the reference's tests/L0/run_transformer/test_layers.py and
test_cross_entropy.py, which compare Megatron-parallel layers against plain
dense layers built from the gathered weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from apex_tpu.utils.compat import shard_map

from apex_tpu.transformer import tensor_parallel as tp

# Heavy multi-device CPU-emulation tier: inert at the seed (shard_map
# import errors) until the apex_tpu.utils.compat shim made this file
# runnable on the hermetic jax, but too costly for the tier-1 wall-time
# budget. Deselect from the fast tier; run with -m slow.
pytestmark = pytest.mark.slow

TPW = 2  # tensor-parallel world size used in these tests


@pytest.fixture()
def model_mesh(eight_devices):
    return Mesh(np.array(eight_devices[:TPW]), ("model",))


def _stacked_init(module, x_local, mesh):
    """Init inside shard_map; return params with a leading [world] dim so a
    plain P('model') out_spec works for every leaf."""

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(),),
                       out_specs=P("model"), check_vma=False)
    def init(x):
        v = module.init(jax.random.PRNGKey(0), x)
        return jax.tree_util.tree_map(lambda l: l[None], v)

    return init(x_local)


def test_column_parallel_linear_matches_dense(model_mesh):
    m = tp.ColumnParallelLinear(input_size=16, output_size=32,
                                world_size=TPW, gather_output=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    stacked = _stacked_init(m, x, model_mesh)

    @functools.partial(shard_map, mesh=model_mesh,
                       in_specs=(P("model"), P()), out_specs=P(),
                       check_vma=False)
    def fwd(sv, x):
        v = jax.tree_util.tree_map(lambda l: l[0], sv)
        y = m.apply(v, x)
        return y  # gathered → replicated

    y = fwd(stacked, x)
    # dense reference from gathered columns
    k = np.concatenate([np.asarray(stacked["params"]["kernel"][i])
                        for i in range(TPW)], axis=-1)
    b = np.concatenate([np.asarray(stacked["params"]["bias"][i])
                        for i in range(TPW)], axis=-1)
    ref = np.asarray(x) @ k + b
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-5)


def test_row_parallel_linear_matches_dense(model_mesh):
    m = tp.RowParallelLinear(input_size=32, output_size=16,
                             world_size=TPW, input_is_parallel=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    x_local_shape = jnp.zeros((4, 32 // TPW))
    stacked = _stacked_init(m, x_local_shape, model_mesh)

    @functools.partial(shard_map, mesh=model_mesh,
                       in_specs=(P("model"), P(None, "model")),
                       out_specs=P(), check_vma=False)
    def fwd(sv, x_local):
        v = jax.tree_util.tree_map(lambda l: l[0], sv)
        return m.apply(v, x_local)  # psum inside → replicated

    y = fwd(stacked, x)
    k = np.concatenate([np.asarray(stacked["params"]["kernel"][i])
                        for i in range(TPW)], axis=0)
    b = np.asarray(stacked["params"]["bias"][0])
    ref = np.asarray(x) @ k + b
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-5)


def test_column_row_grads_match_dense(model_mesh):
    """Megatron MLP block: column (no gather) → row (input parallel); grads
    of the local shards must equal the corresponding dense-grad slices."""
    col = tp.ColumnParallelLinear(input_size=8, output_size=16,
                                  world_size=TPW, gather_output=False,
                                  use_bias=False)
    row = tp.RowParallelLinear(input_size=16, output_size=8,
                               world_size=TPW, input_is_parallel=True,
                               use_bias=False)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8))

    @functools.partial(shard_map, mesh=model_mesh, in_specs=(P(),),
                       out_specs=P("model"), check_vma=False)
    def init(x):
        vc = col.init(jax.random.PRNGKey(0), x)
        h = col.apply(vc, x)
        vr = row.init(jax.random.PRNGKey(1), h)
        return jax.tree_util.tree_map(lambda l: l[None], (vc, vr))

    svc, svr = init(x)

    @functools.partial(shard_map, mesh=model_mesh,
                       in_specs=(P("model"), P("model"), P()),
                       out_specs=(P(), P("model"), P("model")),
                       check_vma=False)
    def lg(svc, svr, x):
        vc = jax.tree_util.tree_map(lambda l: l[0], svc)
        vr = jax.tree_util.tree_map(lambda l: l[0], svr)

        def loss_fn(args):
            vc, vr = args
            h = jax.nn.relu(col.apply(vc, x))
            y = row.apply(vr, h)
            return jnp.sum(y ** 2)

        l, (gc, gr) = jax.value_and_grad(loss_fn)((vc, vr))
        add = jax.tree_util.tree_map(lambda a: a[None], (gc, gr))
        return l, add[0], add[1]

    l, gc, gr = lg(svc, svr, x)

    # dense reference
    kc = np.concatenate([np.asarray(svc["params"]["kernel"][i])
                         for i in range(TPW)], axis=-1)
    kr = np.concatenate([np.asarray(svr["params"]["kernel"][i])
                         for i in range(TPW)], axis=0)

    def dense_loss(args):
        kc, kr = args
        h = jax.nn.relu(jnp.asarray(np.asarray(x)) @ kc)
        y = h @ kr
        return jnp.sum(y ** 2)

    lr, (gkc, gkr) = jax.value_and_grad(dense_loss)((jnp.asarray(kc),
                                                     jnp.asarray(kr)))
    np.testing.assert_allclose(float(l), float(lr), rtol=1e-5)
    half = 16 // TPW
    for i in range(TPW):
        np.testing.assert_allclose(
            np.asarray(gc["params"]["kernel"][i]),
            np.asarray(gkc)[:, i * half:(i + 1) * half], rtol=1e-5,
            atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(gr["params"]["kernel"][i]),
            np.asarray(gkr)[i * half:(i + 1) * half, :], rtol=1e-5,
            atol=1e-5)


def test_vocab_parallel_embedding(model_mesh):
    m = tp.VocabParallelEmbedding(num_embeddings=24, embedding_dim=8,
                                  world_size=TPW)
    ids = jnp.array([[0, 5, 11], [12, 17, 23]], jnp.int32)
    stacked = _stacked_init(m, ids, model_mesh)

    @functools.partial(shard_map, mesh=model_mesh,
                       in_specs=(P("model"), P()), out_specs=P(),
                       check_vma=False)
    def fwd(sv, ids):
        v = jax.tree_util.tree_map(lambda l: l[0], sv)
        return m.apply(v, ids)

    y = fwd(stacked, ids)
    table = np.concatenate([np.asarray(stacked["params"]["embedding"][i])
                            for i in range(TPW)], axis=0)
    np.testing.assert_allclose(np.asarray(y), table[np.asarray(ids)],
                               rtol=1e-6, atol=1e-6)


def test_vocab_parallel_cross_entropy(model_mesh):
    B, V = 6, 32
    logits = jax.random.normal(jax.random.PRNGKey(3), (B, V))
    target = jax.random.randint(jax.random.PRNGKey(4), (B,), 0, V)

    @functools.partial(shard_map, mesh=model_mesh,
                       in_specs=(P(None, "model"), P()), out_specs=P(),
                       check_vma=False)
    def xent(lg, t):
        return tp.vocab_parallel_cross_entropy(lg, t)

    loss = xent(logits, target)
    ref = -jax.nn.log_softmax(logits)[jnp.arange(B), target]
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_vocab_parallel_cross_entropy_grad(model_mesh):
    B, V = 4, 16
    logits = jax.random.normal(jax.random.PRNGKey(5), (B, V))
    target = jax.random.randint(jax.random.PRNGKey(6), (B,), 0, V)

    @functools.partial(shard_map, mesh=model_mesh,
                       in_specs=(P(None, "model"), P()),
                       out_specs=P(None, "model"), check_vma=False)
    def grad_fn(lg, t):
        return jax.grad(
            lambda l: jnp.mean(tp.vocab_parallel_cross_entropy(l, t)))(lg)

    g = grad_fn(logits, target)
    ref = jax.grad(lambda l: jnp.mean(
        -jax.nn.log_softmax(l)[jnp.arange(B), target]))(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_label_smoothing_cross_entropy():
    """world=1 path with smoothing vs optax reference."""
    import optax
    B, V = 5, 11
    logits = jax.random.normal(jax.random.PRNGKey(7), (B, V))
    target = jax.random.randint(jax.random.PRNGKey(8), (B,), 0, V)
    loss = tp.vocab_parallel_cross_entropy(logits, target,
                                           label_smoothing=0.1)
    onehot = jax.nn.one_hot(target, V)
    smoothed = onehot * 0.9 + 0.1 / V
    ref = optax.softmax_cross_entropy(logits, smoothed)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_mappings_roundtrip(model_mesh):
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 8))

    @functools.partial(shard_map, mesh=model_mesh, in_specs=(P(),),
                       out_specs=P(), check_vma=False)
    def roundtrip(x):
        local = tp.scatter_to_tensor_model_parallel_region(x, "model", -1)
        back = tp.gather_from_tensor_model_parallel_region(local, "model", -1)
        return back

    np.testing.assert_allclose(np.asarray(roundtrip(x)), np.asarray(x))


def test_copy_reduce_duality(model_mesh):
    """copy_to: identity fwd, psum bwd; reduce_from: psum fwd, identity bwd."""
    x = jnp.ones((3,))

    @functools.partial(shard_map, mesh=model_mesh, in_specs=(P(),),
                       out_specs=P(), check_vma=False)
    def f(x):
        y = tp.copy_to_tensor_model_parallel_region(x, "model")
        g = jax.grad(lambda v: jnp.sum(
            tp.copy_to_tensor_model_parallel_region(v, "model")))(x)
        r = tp.reduce_from_tensor_model_parallel_region(x, "model")
        gr = jax.grad(lambda v: jnp.sum(
            tp.reduce_from_tensor_model_parallel_region(v, "model")))(x)
        return y, g, r, gr

    y, g, r, gr = f(x)
    np.testing.assert_allclose(np.asarray(y), 1.0)       # identity fwd
    np.testing.assert_allclose(np.asarray(g), TPW * 1.0)  # psum bwd
    np.testing.assert_allclose(np.asarray(r), TPW * 1.0)  # psum fwd
    np.testing.assert_allclose(np.asarray(gr), 1.0)       # identity bwd


def test_sequence_parallel_pair(model_mesh):
    """reduce_scatter fwd + all_gather bwd and vice versa, on a seq dim."""
    x = jax.random.normal(jax.random.PRNGKey(10), (8, 4))

    @functools.partial(shard_map, mesh=model_mesh, in_specs=(P(),),
                       out_specs=P("model"), check_vma=False)
    def rs(x):
        return tp.reduce_scatter_to_sequence_parallel_region(x, "model", 0)

    out = rs(x)  # each shard: sum over ranks of its seq slice → stacked
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x) * TPW, rtol=1e-6)

    @functools.partial(shard_map, mesh=model_mesh,
                       in_specs=(P("model"),), out_specs=P(),
                       check_vma=False)
    def ag(xl):
        return tp.gather_from_sequence_parallel_region(xl, "model", 0)

    np.testing.assert_allclose(np.asarray(ag(out)), np.asarray(x) * TPW,
                               rtol=1e-6)


def test_utils():
    with pytest.raises(ValueError):
        tp.ensure_divisibility(7, 2)
    assert tp.divide(8, 2) == 4
    parts = tp.split_tensor_along_last_dim(jnp.ones((2, 8)), 4)
    assert len(parts) == 4 and parts[0].shape == (2, 2)
    assert tp.VocabUtility.vocab_range_from_global_vocab_size(100, 1, 4) == \
        (25, 50)


def test_rng_tracker():
    tr = tp.RNGStatesTracker()
    tr.add("a", 0)
    with pytest.raises(RuntimeError):
        tr.add("a", 1)
    with tr.fork("a") as k1:
        pass
    with tr.fork("a") as k2:
        pass
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    with pytest.raises(RuntimeError):
        with tr.fork("missing"):
            pass
    tp.model_parallel_manual_seed(123, tp_rank=0)
    with tp.get_rng_tracker().fork() as k:
        assert k is not None


def test_checkpoint_matches_plain():
    def f(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)

    w = jax.random.normal(jax.random.PRNGKey(0), (8, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    g_plain = jax.grad(f)(w, x)
    g_ckpt = jax.grad(lambda w, x: tp.checkpoint(f, w, x))(w, x)
    np.testing.assert_allclose(np.asarray(g_plain), np.asarray(g_ckpt),
                               rtol=1e-6)
