"""The GSPMD/pjit tier of the LM recipe (VERDICT round-4 missing #2).

SURVEY §3.3's TP row names TWO idiomatic TPU mappings for Megatron TP:
explicit shard_map collectives (mappings.py) and "pjit with sharded
weight specs — the mappings collapse into sharding constraints". The
shard_map half has carried the recipe since round 2; this module proves
the other half: ``--partitioning gspmd`` runs the SAME 1-device program
under plain ``jax.jit`` with NamedShardings built from the TP modules'
own ``kernel_partition_spec()`` — no shard_map, no explicit collectives
— and XLA's SPMD partitioner must reproduce the trajectory of both the
shard_map path and the 1-device oracle, whole canonicalized param trees
leaf-for-leaf.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp

# Heavy multi-device CPU-emulation tier: inert at the seed (shard_map
# import errors) until the apex_tpu.utils.compat shim made this file
# runnable on the hermetic jax, but too costly for the tier-1 wall-time
# budget. Deselect from the fast tier; run with -m slow.
pytestmark = pytest.mark.slow


BASE = ["--size", "tiny", "--vocab-size", "128", "--seq-len", "16",
        "-b", "16", "--iters", "6", "--deterministic",
        "--microbatches", "4"]


def _run(lm, extra, opt_level="O0"):
    args = lm.parse_args(BASE + ["--opt-level", opt_level] + extra)
    policy = amp.resolve_policy(opt_level=opt_level,
                                loss_scale=args.loss_scale, verbose=False)
    m = lm.run_parallel(args, policy)
    m["args"] = args
    return m


def _canon(lm, m):
    return lm.canonicalize_from_args(m["final_state"].params, m["args"])


def test_gspmd_matches_shard_map_and_oracle(lm, eight_devices):
    """The VERDICT done-bar: TP(+DP) under plain jit + NamedSharding
    reproduces both the explicit-collectives path and the 1-device
    oracle — same losses, same whole final param tree. This is the
    proof that mappings.py's collectives and GSPMD's propagated
    shardings compute the same math (tensor_parallel/mappings.py's
    "under plain pjit/GSPMD these mappings collapse" claim)."""
    m_seq = _run(lm, ["--data-parallel", "1", "--tensor-parallel", "1",
                      "--pipeline-parallel", "1"])
    m_smap = _run(lm, ["--data-parallel", "2", "--tensor-parallel", "2"])
    m_gspmd = _run(lm, ["--partitioning", "gspmd",
                        "--data-parallel", "2", "--tensor-parallel", "2"])
    np.testing.assert_allclose(m_gspmd["loss_history"],
                               m_seq["loss_history"], rtol=2e-4)
    np.testing.assert_allclose(m_gspmd["loss_history"],
                               m_smap["loss_history"], rtol=2e-4)
    lm.assert_trees_close(_canon(lm, m_gspmd), _canon(lm, m_seq))
    lm.assert_trees_close(_canon(lm, m_gspmd), _canon(lm, m_smap))


def test_gspmd_params_actually_sharded(lm, eight_devices):
    """The NamedShardings must DISTRIBUTE, not replicate: every column/
    row kernel (and the vocab-sharded embedding) ends up with 'model' in
    its spec and its shards spread over all 4 mesh devices — otherwise
    the tier would be a replicated no-op wearing pjit clothes."""
    m = _run(lm, ["--partitioning", "gspmd",
                  "--data-parallel", "2", "--tensor-parallel", "2",
                  "--iters", "1"])
    params = m["final_state"].params
    col = params["stages"]["col"]
    for name in ("qkv_k", "proj_k", "mlp_in_k", "mlp_out_k"):
        sh = col[name].sharding
        assert "model" in tuple(sh.spec), \
            f"{name} spec {sh.spec} does not shard over 'model'"
        assert sh.num_devices == 4, f"{name} on {sh.num_devices} devices"
    emb_sh = params["emb"]["wte"].sharding
    assert emb_sh.spec[0] == "model", f"wte spec {emb_sh.spec}"
    head_sh = params["head"]["kernel"].sharding
    assert "model" in tuple(head_sh.spec), f"head spec {head_sh.spec}"
    # masters ride the same specs as their params (O0 has none; re-check
    # cheaply via the state spec tree on an O2 run in the test below)


def test_gspmd_o2_masters_and_scaler(lm, eight_devices):
    """O2 on the GSPMD tier: finite decreasing loss, and the apex O2
    invariant — the half model params ARE the cast fp32 masters — holds
    bitwise with both trees sharded."""
    m = _run(lm, ["--partitioning", "gspmd",
                  "--data-parallel", "2", "--tensor-parallel", "2"],
             opt_level="O2")
    assert np.isfinite(float(m["loss"]))
    assert not bool(m["found_inf"])
    hist = m["loss_history"]
    assert all(np.isfinite(hist)) and hist[-1] < hist[0], hist
    state = m["final_state"]
    cast = jax.tree_util.tree_map(
        lambda mp, p: jnp.asarray(mp, p.dtype),
        state.master_params, state.params)
    lm.assert_trees_close(state.params, cast, rtol=0, atol=0)
    # masters carry the module specs too — sharded, not gathered
    msh = state.master_params["stages"]["col"]["qkv_k"].sharding
    assert "model" in tuple(msh.spec)


def test_gspmd_flag_guards(lm, eight_devices):
    """gspmd is dp x tp only (the pipe/SP/vocab/ZeRO compositions run
    under shard_map); a mesh of 1 is refused with guidance."""
    with pytest.raises(SystemExit, match="shard_map"):
        _run(lm, ["--partitioning", "gspmd", "--tensor-parallel", "2",
                  "--pipeline-parallel", "2"])
    with pytest.raises(SystemExit, match="mesh"):
        lm.main(BASE + ["--partitioning", "gspmd"])


def test_gspmd_save_resume_bitwise(lm, eight_devices, tmp_path):
    """--save/--resume on the GSPMD tier: host-restored arrays re-shard
    through the jit boundary's NamedShardings, and the resumed
    trajectory continues the uninterrupted run bitwise (same bar as the
    shard_map tier's checkpoint test)."""
    ckpt = str(tmp_path / "gspmd.npz")
    extra = ["--partitioning", "gspmd", "--data-parallel", "2",
             "--tensor-parallel", "2"]
    m_full = _run(lm, extra, opt_level="O2")
    _run(lm, extra + ["--iters", "3", "--save", ckpt], opt_level="O2")
    m_res = _run(lm, extra + ["--resume", ckpt], opt_level="O2")
    np.testing.assert_array_equal(m_res["loss_history"],
                                  m_full["loss_history"][3:])
    full_s, res_s = m_full["final_state"], m_res["final_state"]
    lm.assert_trees_close(res_s.params, full_s.params, rtol=0, atol=0)
    lm.assert_trees_close(res_s.master_params, full_s.master_params,
                          rtol=0, atol=0)
    assert float(res_s.scaler.loss_scale) == \
        float(full_s.scaler.loss_scale)


def test_gspmd_zero_is_one_partition_spec(lm, eight_devices):
    """ZeRO-1 the GSPMD way (--zero under --partitioning gspmd): the
    flat Adam m/v superbuffers carry P('data') — no collective code —
    and each device holds 1/dp of the optimizer state. The trajectory
    must match the unsharded gspmd run (sharding is layout, not
    numerics), which transitively ties it to the shard_map ZeRO and the
    1-device oracle already proven equal."""
    m_plain = _run(lm, ["--partitioning", "gspmd",
                        "--data-parallel", "2", "--tensor-parallel", "2"])
    m_zero = _run(lm, ["--partitioning", "gspmd", "--zero",
                       "--data-parallel", "2", "--tensor-parallel", "2"])
    np.testing.assert_allclose(m_zero["loss_history"],
                               m_plain["loss_history"], rtol=2e-4)
    lm.assert_trees_close(_canon(lm, m_zero), _canon(lm, m_plain))

    m_buf = m_zero["final_state"].opt_state.m
    assert "data" in tuple(m_buf.sharding.spec), m_buf.sharding
    # 4 devices in the dp2 x tp2 mesh; 'data' splits the buffer in 2 —
    # every addressable shard holds half the elements
    shard_elems = {s.data.size for s in m_buf.addressable_shards}
    assert shard_elems == {m_buf.size // 2}, \
        (m_buf.size, shard_elems)
    # the non-zero run uses the round-5 TREE layout, where each moment
    # leaf inherits its parameter's spec through _finish_gspmd's path
    # rules — TP-sharded weights get TP-sharded moments for free (a
    # memory property the replicated flat buffer never had); 'data'
    # stays out of the specs (that split is exactly what --zero adds)
    import jax as _jax

    p_leaves = _jax.tree_util.tree_leaves_with_path(
        m_plain["final_state"].params)
    m_leaves = _jax.tree_util.tree_leaves_with_path(
        m_plain["final_state"].opt_state.m)
    assert m_leaves and len(p_leaves) == len(m_leaves)
    for (p_path, p_leaf), (m_path, m_leaf) in zip(p_leaves, m_leaves):
        assert m_leaf.sharding.spec == p_leaf.sharding.spec, \
            (m_path, m_leaf.sharding, p_leaf.sharding)
        assert "data" not in tuple(m_leaf.sharding.spec), m_path
