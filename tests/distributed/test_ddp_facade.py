"""End-to-end DDP facade tests (VERDICT round-1 item 9): N training steps
through DistributedDataParallel.reduce_gradients + the scaler facade must
match make_train_step's integrated path — the reference's recipe shape
(wrap the model, then train manually: examples/simple/distributed/ +
apex/amp README manual loop)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from apex_tpu.utils.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.amp import init_scaler, unscale, update_scale
from apex_tpu.amp.scaler import scale_loss as scale_loss_fn
from apex_tpu.parallel import DistributedDataParallel

# Heavy multi-device CPU-emulation tier: inert at the seed (shard_map
# import errors) until the apex_tpu.utils.compat shim made this file
# runnable on the hermetic jax, but too costly for the tier-1 wall-time
# budget. Deselect from the fast tier; run with -m slow.
pytestmark = pytest.mark.slow


@pytest.fixture()
def data_mesh(eight_devices):
    return Mesh(np.array(eight_devices), ("data",))


def _model(p, x):
    return jax.nn.relu(x @ p["w1"]) @ p["w2"]


def _loss(p, batch):
    x, y = batch
    return optax.softmax_cross_entropy_with_integer_labels(
        jnp.asarray(_model(p, x), jnp.float32), y).mean()


def _params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w1": jax.random.normal(k1, (16, 32)) * 0.1,
            "w2": jax.random.normal(k2, (32, 10)) * 0.1}


def _batches(steps, per_rank=4, world=8):
    ks = jax.random.split(jax.random.PRNGKey(1), steps)
    return [(jax.random.normal(k, (per_rank * world, 16)),
             jax.random.randint(jax.random.fold_in(k, 9),
                                (per_rank * world,), 0, 10))
            for k in ks]


@pytest.mark.parametrize("predivide", [1.0, 2.0])
def test_manual_ddp_loop_matches_make_train_step(data_mesh, predivide):
    params = _params()
    steps = 5
    batches = _batches(steps)

    # --- path A: the facade (DDP wrapper + functional scaler, hand loop)
    ddp = DistributedDataParallel(module=_model, axis_name="data",
                                  gradient_predivide_factor=predivide)
    tx = optax.sgd(0.1, momentum=0.9)

    def manual_step(params, opt_state, scaler, batch):
        def scaled(p):
            x, y = batch
            loss = optax.softmax_cross_entropy_with_integer_labels(
                jnp.asarray(ddp(p, x), jnp.float32), y).mean()
            return scale_loss_fn(loss, scaler), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        grads = ddp.reduce_gradients(grads)
        grads, found_inf = unscale(grads, scaler, jnp.float32)

        def do(_):
            upd, new_opt = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, upd), new_opt

        params2, opt2 = jax.lax.cond(
            found_inf, lambda _: (params, opt_state), do, operand=None)
        return params2, opt2, update_scale(scaler, found_inf)

    run_manual = jax.jit(functools.partial(
        shard_map, mesh=data_mesh,
        in_specs=(P(), P(), P(), (P("data"), P("data"))),
        out_specs=(P(), P(), P()), check_vma=False)(manual_step))

    p_a, opt_a, sc_a = params, tx.init(params), init_scaler("dynamic")
    for b in batches:
        p_a, opt_a, sc_a = run_manual(p_a, opt_a, sc_a, b)

    # --- path B: make_train_step integrated
    policy = amp.resolve_policy("O0", loss_scale="dynamic")
    init_fn, step_fn = amp.make_train_step(
        _loss, optax.sgd(0.1, momentum=0.9), policy,
        grad_average_axis="data", gradient_predivide_factor=predivide)
    run_b = jax.jit(functools.partial(
        shard_map, mesh=data_mesh,
        in_specs=(P(), (P("data"), P("data"))), out_specs=P(),
        check_vma=False)(step_fn))
    st = init_fn(params)
    for b in batches:
        st, _ = run_b(st, b)

    for k in params:
        np.testing.assert_allclose(np.asarray(p_a[k]),
                                   np.asarray(st.params[k]),
                                   rtol=1e-5, atol=1e-6)
    # scaler trajectories agree too (same unskipped count, same scale)
    np.testing.assert_array_equal(np.asarray(sc_a.loss_scale),
                                  np.asarray(st.scaler.loss_scale))


def test_scale_loss_context_facade():
    """The imperative amp.scale_loss context (apex/amp/handle.py) scales by
    the registered scaler's current scale and advances its schedule."""
    amp.initialize((None, None), optimizers=None, opt_level="O2",
                   loss_scale=128.0, verbosity=0)
    with amp.scale_loss(jnp.asarray(2.0)) as scaled:
        assert float(scaled) == 2.0 * 128.0


def test_ddp_allreduce_always_fp32(data_mesh):
    """apex's allreduce_always_fp32: half grads are reduced in fp32 and cast
    back; the result equals the fp32 mean within half precision."""
    ddp = DistributedDataParallel(module=_model, axis_name="data",
                                  allreduce_always_fp32=True)

    @functools.partial(shard_map, mesh=data_mesh, in_specs=P("data"),
                       out_specs=P(), check_vma=False)
    def reduce(gs):
        out = ddp.reduce_gradients({"g": gs[0]})
        return out["g"]

    gs = jnp.arange(8.0, dtype=jnp.bfloat16)[:, None] * jnp.ones(
        (8, 4), jnp.bfloat16)
    out = reduce(gs)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.full((4,), 3.5), rtol=1e-2)
