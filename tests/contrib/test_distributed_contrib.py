"""Distributed contrib tests on the 8-device CPU mesh: ZeRO-sharded
optimizers vs single-process fused Adam (mirrors
apex/contrib/test/optimizers/test_dist_adam.py) and halo exchange (mirrors
test_peer_halo_exchange_module.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from apex_tpu.utils.compat import shard_map

from apex_tpu import comm

# Heavy multi-device CPU-emulation tier: inert at the seed (shard_map
# import errors) until the apex_tpu.utils.compat shim made this file
# runnable on the hermetic jax, but too costly for the tier-1 wall-time
# budget. Deselect from the fast tier; run with -m slow.
pytestmark = pytest.mark.slow

WORLD = 4


@pytest.fixture()
def data_mesh(eight_devices):
    mesh = Mesh(np.array(eight_devices[:WORLD]), ("data",))
    comm.set_mesh(mesh)
    yield mesh
    comm.reset_mesh()


def _params():
    k = jax.random.PRNGKey(0)
    return {"w": jax.random.normal(k, (33, 7)),  # odd sizes force padding
            "b": jnp.zeros((5,))}


def test_dist_adam_matches_fused_adam(data_mesh):
    """Sharded-state Adam must produce the same params as unsharded Adam on
    the mean gradient (the reference test compares DistributedFusedAdam to
    FusedAdam the same way)."""
    from apex_tpu.contrib.optimizers import distributed_fused_adam
    from apex_tpu.optimizers.fused_adam import fused_adam

    params = _params()
    tx = distributed_fused_adam(1e-2, world_size=WORLD)
    state = tx.init(params)

    # per-rank grads: rank r gets grads scaled by (r+1); mean = 2.5x base
    base = {"w": jnp.ones((33, 7)), "b": jnp.full((5,), 2.0)}

    @functools.partial(shard_map, mesh=data_mesh,
                       in_specs=(P(), P(), P("data")), out_specs=P(),
                       check_vma=False)
    def sharded_step(params, state_and_base, rank_scale):
        state, base = state_and_base
        grads = jax.tree_util.tree_map(lambda g: g * rank_scale[0], base)
        upd, new_state = tx.update(grads, state, params)
        return optax.apply_updates(params, upd)

    scales = jnp.arange(1.0, WORLD + 1)  # mean 2.5
    new_params = jax.jit(sharded_step)(params, (state, base), scales)

    ref_tx = fused_adam(1e-2)
    ref_state = ref_tx.init(params)
    mean_grads = jax.tree_util.tree_map(lambda g: g * 2.5, base)
    ref_upd, _ = ref_tx.update(mean_grads, ref_state, params)
    ref_params = optax.apply_updates(params, ref_upd)

    for k in params:
        np.testing.assert_allclose(np.asarray(new_params[k]),
                                   np.asarray(ref_params[k]),
                                   rtol=1e-5, atol=1e-6)


def test_dist_adam_state_is_sharded(data_mesh):
    from apex_tpu.contrib.optimizers import distributed_fused_adam
    params = _params()
    n = 33 * 7 + 5
    tx = distributed_fused_adam(1e-2, world_size=WORLD)
    state = tx.init(params)
    padded = ((n + WORLD - 1) // WORLD) * WORLD
    assert state.m_shard.shape == (padded // WORLD,)  # 1/world of the state


def test_dist_lamb_runs_and_differs_by_trust_ratio(data_mesh):
    from apex_tpu.contrib.optimizers import distributed_fused_lamb
    params = {"w": jax.random.normal(jax.random.PRNGKey(1), (16, 8))}
    tx = distributed_fused_lamb(1e-2, world_size=WORLD) \
        if "world_size" in distributed_fused_lamb.__code__.co_varnames \
        else distributed_fused_lamb(1e-2)
    state = tx.init(params)

    @functools.partial(shard_map, mesh=data_mesh,
                       in_specs=(P(), P()), out_specs=P(),
                       check_vma=False)
    def step(params, state):
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        upd, _ = tx.update(grads, state, params)
        return optax.apply_updates(params, upd)

    out = jax.jit(step)(params, state)
    assert np.isfinite(np.asarray(out["w"])).all()
    assert not np.allclose(np.asarray(out["w"]), np.asarray(params["w"]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dist_lamb_matches_fused_lamb(data_mesh, dtype):
    """distributed_fused_lamb == fused_lamb on the mean gradient for the
    same constructor args (VERDICT: the two LAMBs must agree — same
    multi_tensor_lamb.cu math, different state placement). Grads are large
    enough that the global-norm clip stage engages, proving the distributed
    path has one. bf16 params exercise the update-stays-fp32-through-the-
    trust-ratio-stage requirement."""
    from apex_tpu.contrib.optimizers import distributed_fused_lamb
    from apex_tpu.optimizers.fused_lamb import fused_lamb

    kw = dict(learning_rate=1e-2, weight_decay=0.01, max_grad_norm=1.0,
              use_nvlamb=False)
    params = {"w": jax.random.normal(jax.random.PRNGKey(1),
                                     (16, 8)).astype(dtype),
              "b": jax.random.normal(jax.random.PRNGKey(2),
                                     (5,)).astype(dtype)}
    base = {"w": jnp.full((16, 8), 4.0), "b": jnp.full((5,), -3.0)}
    steps = 3

    tx = distributed_fused_lamb(axis_name="data", world_size=WORLD, **kw)
    state = tx.init(params)

    @functools.partial(shard_map, mesh=data_mesh,
                       in_specs=(P(), P(), P("data")), out_specs=P(),
                       check_vma=False)
    def run(params, state, rank_scale):
        for _ in range(steps):
            grads = jax.tree_util.tree_map(lambda g: g * rank_scale[0], base)
            upd, state = tx.update(grads, state, params)
            params = optax.apply_updates(params, upd)
        return params

    scales = jnp.arange(1.0, WORLD + 1)  # mean 2.5
    dist_params = jax.jit(run)(params, state, scales)

    ref_tx = fused_lamb(**kw)
    ref_state = ref_tx.init(params)
    ref_params = params
    mean_grads = jax.tree_util.tree_map(lambda g: g * 2.5, base)
    for _ in range(steps):
        upd, ref_state = ref_tx.update(mean_grads, ref_state, ref_params)
        ref_params = optax.apply_updates(ref_params, upd)

    # sanity: the clip stage must actually have engaged
    gn = float(jnp.sqrt(sum(jnp.sum((g * 2.5) ** 2)
                            for g in jax.tree_util.tree_leaves(base))))
    assert gn > 1.0
    for k in params:
        np.testing.assert_allclose(np.asarray(dist_params[k]),
                                   np.asarray(ref_params[k]),
                                   rtol=1e-5, atol=1e-6)


def test_dist_lamb_nvlamb_switch_matches_fused_lamb(data_mesh):
    """weight_decay=0 + use_nvlamb=False forces trust ratio 1.0 in BOTH
    LAMBs (the kernel's NVLAMB switch) — previously only fused_lamb did."""
    from apex_tpu.contrib.optimizers import distributed_fused_lamb
    from apex_tpu.optimizers.fused_lamb import fused_lamb

    params = {"w": jax.random.normal(jax.random.PRNGKey(3), (8, 4)) * 5.0}
    grads = {"w": jnp.full((8, 4), 0.1)}  # below max_grad_norm: no clip

    for nv in (False, True):
        kw = dict(learning_rate=1e-2, weight_decay=0.0, max_grad_norm=1e9,
                  use_nvlamb=nv)
        tx = distributed_fused_lamb(axis_name="data", world_size=WORLD, **kw)
        state = tx.init(params)

        @functools.partial(shard_map, mesh=data_mesh,
                           in_specs=(P(), P()), out_specs=P(),
                           check_vma=False)
        def run(params, state):
            upd, _ = tx.update(grads, state, params)
            return optax.apply_updates(params, upd)

        dist_out = jax.jit(run)(params, state)
        ref_tx = fused_lamb(**kw)
        upd, _ = ref_tx.update(grads, ref_tx.init(params), params)
        ref_out = optax.apply_updates(params, upd)
        np.testing.assert_allclose(np.asarray(dist_out["w"]),
                                   np.asarray(ref_out["w"]),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"use_nvlamb={nv}")


def test_zero_state_resharded_roundtrip(data_mesh, tmp_path):
    """ZeRO optimizer-state save/restore across a world-size change
    (reference: DistributedFusedAdam.state_dict reconstitution — SURVEY §6
    checkpoint (c)): train 2 steps at world 4, checkpoint via the sharded
    writer, restore under a world-2 mesh, train 2 more steps; the result
    must equal 4 uninterrupted steps (oracle: fused_lamb on mean grads)."""
    from jax.sharding import NamedSharding
    from apex_tpu.contrib.optimizers import (DistAdamState,
                                             distributed_fused_lamb,
                                             reshard_zero_state)
    from apex_tpu.optimizers.fused_lamb import fused_lamb
    from apex_tpu.utils.sharded_checkpoint import load_sharded, save_sharded

    kw = dict(learning_rate=1e-2, weight_decay=0.01, max_grad_norm=1.0)
    # n = 13*3 + 7 = 46: pads to 48 at world 4, 46 at world 2 — the repad
    # path is actually exercised
    params = {"w": jax.random.normal(jax.random.PRNGKey(5), (13, 3)),
              "b": jnp.zeros((7,))}
    n = 46
    base = {"w": jnp.full((13, 3), 2.0), "b": jnp.full((7,), -1.0)}

    def make_run(mesh, world, steps):
        tx = distributed_fused_lamb(axis_name="data", world_size=world, **kw)
        sspec = DistAdamState(count=P(), m_shard=P("data"),
                              v_shard=P("data"))

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(), sspec, P("data")),
                           out_specs=(P(), sspec), check_vma=False)
        def run(params, state, rank_scale):
            for _ in range(steps):
                grads = jax.tree_util.tree_map(
                    lambda g: g * rank_scale[0], base)
                upd, state = tx.update(grads, state, params)
                params = optax.apply_updates(params, upd)
            return params, state

        return jax.jit(run)

    # phase 1: world 4, concatenated state representation [48]
    state4 = DistAdamState(count=jnp.zeros((), jnp.int32),
                           m_shard=jnp.zeros((48,), jnp.float32),
                           v_shard=jnp.zeros((48,), jnp.float32))
    scales4 = jnp.arange(1.0, 5.0)  # mean 2.5
    p_mid, state_mid = make_run(data_mesh, 4, 2)(params, state4, scales4)

    # checkpoint: place the concatenated state sharded over the 4-dev mesh
    # and write through the real sharded writer
    sh4 = NamedSharding(data_mesh, P("data"))
    state_placed = DistAdamState(
        count=state_mid.count,
        m_shard=jax.device_put(state_mid.m_shard, sh4),
        v_shard=jax.device_put(state_mid.v_shard, sh4))
    save_sharded(str(tmp_path), state_placed, step=2)

    # restore under a DIFFERENT mesh (2 devices) — resharded restore
    mesh2 = Mesh(np.array(data_mesh.devices.flatten()[:2]), ("data",))
    sh2 = NamedSharding(mesh2, P("data"))
    template = DistAdamState(
        count=jnp.zeros((), jnp.int32),
        m_shard=jax.device_put(jnp.zeros((48,), jnp.float32), sh2),
        v_shard=jax.device_put(jnp.zeros((48,), jnp.float32), sh2))
    restored, step = load_sharded(str(tmp_path), template)
    assert step == 2
    state2 = reshard_zero_state(restored, n, 2)  # strip pad48 → pad46
    assert state2.m_shard.shape == (46,)

    # phase 2: world 2, same mean gradient (scales (2,3) → mean 2.5)
    p_mid = jax.tree_util.tree_map(np.asarray, p_mid)  # off the 4-dev mesh
    state2 = jax.tree_util.tree_map(np.asarray, state2)
    scales2 = jnp.asarray([2.0, 3.0])
    p_final, _ = make_run(mesh2, 2, 2)(p_mid, state2, scales2)

    # oracle: 4 uninterrupted fused_lamb steps on the mean grads
    ref_tx = fused_lamb(**kw)
    ref_state = ref_tx.init(params)
    ref_params = params
    mean_grads = jax.tree_util.tree_map(lambda g: g * 2.5, base)
    for _ in range(4):
        upd, ref_state = ref_tx.update(mean_grads, ref_state, ref_params)
        ref_params = optax.apply_updates(ref_params, upd)

    for k in params:
        np.testing.assert_allclose(np.asarray(p_final[k]),
                                   np.asarray(ref_params[k]),
                                   rtol=1e-5, atol=1e-6)


def test_wrapper_state_dict_semantics(data_mesh):
    """Wrapper checkpoint API: world-1 round-trips and rebuilds the
    transformation for the new world; a world>1 instance holding only its
    per-rank shard refuses to checkpoint (the concatenated state must be
    gathered first)."""
    from apex_tpu.contrib.optimizers import DistributedFusedLAMB

    params = {"w": jnp.ones((5, 7))}  # n=35: pads to 36 at world 2

    opt1 = DistributedFusedLAMB(params, lr=1e-2, world_size=1)
    sd = opt1.state_dict()
    assert sd["world"] == 1 and sd["num_params"] == 35
    opt1.load_state_dict(sd, new_world=2)
    assert opt1.state.m_shard.shape == (36,)
    assert opt1._world == 2  # tx rebuilt: next step's shard math uses 2

    opt4 = DistributedFusedLAMB(params, lr=1e-2, world_size=4)
    assert opt4.state.m_shard.shape == (9,)  # per-rank shard
    with pytest.raises(ValueError, match="gather shards"):
        opt4.state_dict()


def test_halo_exchange_1d(data_mesh):
    from apex_tpu.contrib.peer_memory import halo_exchange_1d
    # global [WORLD*4, 3] sharded along dim 0 (rows)
    x = jnp.arange(WORLD * 4 * 3, dtype=jnp.float32).reshape(WORLD * 4, 3)

    @functools.partial(shard_map, mesh=data_mesh,
                       in_specs=(P("data"),), out_specs=P("data"),
                       check_vma=False)
    def ex(xl):
        return halo_exchange_1d(xl, 1, "data", dim=0)

    out = ex(x)  # each shard: [1+4+1, 3] → gathered [WORLD*6, 3]
    out = np.asarray(out).reshape(WORLD, 6, 3)
    xg = np.asarray(x).reshape(WORLD, 4, 3)
    for r in range(WORLD):
        np.testing.assert_array_equal(out[r, 1:5], xg[r])
        if r > 0:
            np.testing.assert_array_equal(out[r, 0], xg[r - 1, -1])
        else:
            np.testing.assert_array_equal(out[r, 0], 0)
        if r < WORLD - 1:
            np.testing.assert_array_equal(out[r, 5], xg[r + 1, 0])
        else:
            np.testing.assert_array_equal(out[r, 5], 0)


def test_spatial_bottleneck_matches_dense(data_mesh):
    """SpatialBottleneck with H sharded over 4 ranks == Bottleneck on the
    full image (reference: bottleneck test comparing spatial vs serial)."""
    from apex_tpu.contrib.bottleneck import Bottleneck, SpatialBottleneck
    N, Hh, W, C = 1, 16, 8, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (N, Hh, W, C))

    dense = Bottleneck(in_channels=C, bottleneck_channels=4, out_channels=C)
    dv = dense.init(jax.random.PRNGKey(1), x, train=False)
    ref = dense.apply(dv, x, train=False)

    spatial = SpatialBottleneck(in_channels=C, bottleneck_channels=4,
                                out_channels=C, axis_name="data")

    @functools.partial(shard_map, mesh=data_mesh,
                       in_specs=(P(), P(None, "data")),
                       out_specs=P(None, "data"), check_vma=False)
    def run(variables, xl):
        return spatial.apply(variables, xl, train=False)

    out = run(dv, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_deprecated_optimizer_aliases():
    """The P32 deprecated wrappers stay importable and forward correctly
    (an eager package import would break ALL contrib.optimizers imports if
    a forwarding target moved)."""
    import warnings
    from apex_tpu.contrib.optimizers import FP16_Optimizer, FusedSGD
    from apex_tpu.fp16_utils import FP16_Optimizer as Real16
    from apex_tpu.optimizers import FusedSGD as RealSGD

    assert issubclass(FP16_Optimizer, Real16)
    assert issubclass(FusedSGD, RealSGD)
    params = {"w": jnp.ones((4,))}
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        opt = FusedSGD(params, lr=0.1)
        FP16_Optimizer(optax.sgd(0.1), params)
    assert sum("deprecated" in str(x.message) for x in w) >= 2
    out = opt.step({"w": jnp.full((4,), 0.5)})
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0 - 0.05, rtol=1e-6)
