"""The full-parallelism integration test: dp × tp(sp) × pp × ep in ONE
jitted amp-O2 train step — the driver's dryrun_multichip contract, kept
honest in CI on the 8-virtual-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from apex_tpu.utils.compat import shard_map

from apex_tpu.transformer.testing import (build_full_parallel_step,
                                          factor_mesh_axes,
                                          make_full_parallel_inputs)

# Heavy multi-device CPU-emulation tier: inert at the seed (shard_map
# import errors) until the apex_tpu.utils.compat shim made this file
# runnable on the hermetic jax, but too costly for the tier-1 wall-time
# budget. Deselect from the fast tier; run with -m slow.
pytestmark = pytest.mark.slow


def _run(devices, axes, *, opt_level="O2", n_steps=3, seed=0, seq=8,
         capacity_factor=1.25, num_chunks=1):
    dp, pp, tp = axes["data"], axes["pipe"], axes["model"]
    n = dp * pp * tp
    mesh = Mesh(np.array(devices[:n]).reshape(dp, pp, tp),
                ("data", "pipe", "model"))
    params, specs, mask, mb, tg, dims = make_full_parallel_inputs(
        n_stages=pp, tp=tp, dp=dp, n_experts=4, seed=seed, seq=seq,
        capacity_factor=capacity_factor, num_chunks=num_chunks)
    run = build_full_parallel_step(dims, mask, opt_level=opt_level,
                                   n_steps=n_steps)
    sharded = jax.jit(shard_map(
        run, mesh=mesh,
        in_specs=(specs, P(None, "data", "model"), P(None, "data", "model")),
        out_specs=P(), check_vma=False))
    return np.asarray(sharded(params, mb, tg))


def test_factor_mesh_axes():
    assert factor_mesh_axes(8) == {"data": 2, "pipe": 2, "model": 2}
    assert factor_mesh_axes(4) == {"data": 1, "pipe": 2, "model": 2}
    assert factor_mesh_axes(2) == {"data": 1, "pipe": 1, "model": 2}
    assert factor_mesh_axes(1) == {"data": 1, "pipe": 1, "model": 1}
    for n in (1, 2, 4, 8):
        f = factor_mesh_axes(n)
        assert f["data"] * f["pipe"] * f["model"] == n


@pytest.mark.parametrize("axes", [
    {"data": 2, "pipe": 2, "model": 2},
    {"data": 4, "pipe": 2, "model": 1},
    {"data": 1, "pipe": 2, "model": 4},
    {"data": 2, "pipe": 1, "model": 2},
])
def test_full_parallel_train_step(eight_devices, axes):
    losses = _run(eight_devices, axes)
    assert losses.shape == (3,)
    assert np.isfinite(losses).all(), losses
    # same batch each step: training must make progress
    assert losses[-1] < losses[0], losses


def test_tp_width_is_numerically_invisible(eight_devices):
    """Same seed → same GLOBAL model and batch; cutting it tp=2 vs tp=4
    (dp=1, pp=2 fixed) must produce the same fp32 loss trajectory — the
    parallel layout is an implementation detail, not a numerics change.

    capacity_factor is set high enough that no token drops: switch-MoE
    drops depend on which tokens share a shard, the one legitimately
    layout-dependent behavior."""
    l2 = _run(eight_devices, {"data": 1, "pipe": 2, "model": 2},
              opt_level="O0", n_steps=2, seed=11, capacity_factor=64)
    l4 = _run(eight_devices, {"data": 1, "pipe": 2, "model": 4},
              opt_level="O0", n_steps=2, seed=11, capacity_factor=64)
    np.testing.assert_allclose(l2, l4, rtol=1e-5, atol=1e-6)


# dp-width exact parity is intentionally NOT asserted: switch-MoE capacity
# is tokens-per-shard dependent, so changing dp legitimately changes which
# overflow tokens drop (a property of token-dropping routers, not a bug).
# The dispatch math itself is exactly parity-tested in test_moe.py; dp=2/4
# layouts are covered by the parametrized step test above.


def test_full_parallel_with_interleaved_pipeline(eight_devices):
    """dp2 × pp2(v=2 virtual chunks → 4 logical stages) × tp2 — the
    interleaved 1F1B schedule composed with every other axis."""
    losses = _run(eight_devices, {"data": 2, "pipe": 2, "model": 2},
                  seed=21, num_chunks=2)
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses
