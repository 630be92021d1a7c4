"""End-to-end amp step semantics — the observable order apex tests check
(tests/L0/run_amp/test_checkpointing.py, amp_master_params): master weights,
skip-on-overflow with NO optimizer-state advance, scale schedule."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_tpu.amp import make_train_step, resolve_policy


def _loss_fn(params, batch):
    x, y = batch
    pred = x @ params["w"].astype(x.dtype) + params["b"].astype(x.dtype)
    return jnp.mean((pred.astype(jnp.float32) - y) ** 2)


def _setup(opt_level="O2", half=jnp.float16, **over):
    policy = resolve_policy(opt_level, half_dtype=half, verbose=False, **over)
    opt = optax.sgd(0.1)
    init_fn, step_fn = make_train_step(_loss_fn, opt, policy)
    params = {"w": jnp.ones((4, 2), jnp.float32),
              "b": jnp.zeros((2,), jnp.float32)}
    state = init_fn(params)
    if state.scaler.dynamic:
        # 2**16 would overflow this toy batch's fp16 grads on step one (real
        # amp behavior: halve until it fits); a small init scale keeps the
        # happy-path tests deterministic. Overflow paths are tested explicitly.
        from apex_tpu.amp import init_scaler
        state = state.replace(scaler=init_scaler("dynamic", init_scale=256.0))
    x = jnp.ones((8, 4), jnp.float32)
    y = jnp.zeros((8, 2), jnp.float32)
    return policy, jax.jit(step_fn), state, (x, y)


def test_o2_master_weights_exist_and_params_half():
    policy, step, state, batch = _setup("O2")
    assert state.master_params is not None
    assert state.master_params["w"].dtype == jnp.float32
    assert state.params["w"].dtype == jnp.float16
    new_state, metrics = step(state, batch)
    # params moved and stayed half; masters stayed fp32 and mirror params
    assert new_state.params["w"].dtype == jnp.float16
    assert new_state.master_params["w"].dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(new_state.params["w"], np.float32),
        np.asarray(new_state.master_params["w"]).astype(np.float16).astype(np.float32))
    assert not bool(metrics["found_inf"])


def test_o0_trains_fp32_no_masters():
    policy, step, state, batch = _setup("O0")
    assert state.master_params is None
    assert state.params["w"].dtype == jnp.float32
    new_state, metrics = step(state, batch)
    assert float(metrics["loss"]) > 0
    assert not np.allclose(np.asarray(new_state.params["w"]),
                           np.asarray(state.params["w"]))


def test_overflow_skips_step_and_halves_scale():
    policy, step, state, batch = _setup("O2")
    x, y = batch
    bad = (x.at[0, 0].set(jnp.float32(1e30)), y)  # overflows f16 grads via loss scale
    new_state, metrics = step(state, bad)
    assert bool(metrics["found_inf"])
    # optimizer state did not advance, params unchanged
    np.testing.assert_array_equal(np.asarray(new_state.master_params["w"]),
                                  np.asarray(state.master_params["w"]))
    np.testing.assert_array_equal(np.asarray(new_state.params["w"], np.float32),
                                  np.asarray(state.params["w"], np.float32))
    assert float(new_state.scaler.loss_scale) == 128.0  # halved from 256
    assert int(new_state.scaler.unskipped) == 0


def test_overflow_freezes_stateful_optimizer_bitwise():
    """Regression for the cond→select skip rewrite: with a STATEFUL
    optimizer (adam mu/nu + count), an overflow step must leave every
    opt-state leaf bitwise frozen — the select path computes the update
    on inf/NaN grads and must discard all of it, count increment
    included. sgd-based overflow tests can't see this (no state leaves)."""
    policy = resolve_policy("O2", half_dtype=jnp.float16, verbose=False,
                            loss_scale=256.0)
    opt = optax.adam(1e-2)
    init_fn, step_fn = make_train_step(_loss_fn, opt, policy)
    state = init_fn({"w": jnp.ones((4, 2), jnp.float32),
                     "b": jnp.zeros((2,), jnp.float32)})
    step = jax.jit(step_fn)
    x = jnp.ones((8, 4), jnp.float32)
    y = jnp.zeros((8, 2), jnp.float32)
    state, m = step(state, (x, y))           # clean step: state advances
    assert not bool(m["found_inf"])
    bad = (x.at[0, 0].set(jnp.float32(1e30)), y)
    new_state, m = step(state, bad)
    assert bool(m["found_inf"])
    before = jax.tree_util.tree_leaves(state.opt_state)
    after = jax.tree_util.tree_leaves(new_state.opt_state)
    assert before and len(before) == len(after)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(new_state.master_params["w"]),
                                  np.asarray(state.master_params["w"]))


def test_clean_steps_grow_scale():
    policy = resolve_policy("O2", half_dtype=jnp.float16, verbose=False)
    opt = optax.sgd(1e-4)
    init_fn, step_fn = make_train_step(_loss_fn, opt, policy)
    params = {"w": jnp.zeros((4, 2), jnp.float32),
              "b": jnp.zeros((2,), jnp.float32)}
    state = init_fn(params)
    x = jnp.ones((8, 4), jnp.float32) * 0.01
    y = jnp.zeros((8, 2), jnp.float32)
    step = jax.jit(step_fn)
    # shrink window via a fresh scaler config
    from apex_tpu.amp import init_scaler
    sc = init_scaler("dynamic", init_scale=1.0, scale_window=3)
    state = state.replace(scaler=sc)
    for _ in range(3):
        state, m = step(state, (x, y))
        assert not bool(m["found_inf"])
    assert float(state.scaler.loss_scale) == 2.0


def test_static_loss_scale_o3():
    policy, step, state, batch = _setup("O3")
    assert state.master_params is None
    assert state.params["w"].dtype == jnp.float16
    new_state, metrics = step(state, batch)
    assert float(new_state.scaler.loss_scale) == 1.0


def test_o3_stateful_optimizer_traces():
    """Regression: O3 (half params, no masters) + momentum must not hit a
    lax.cond branch dtype mismatch — optimizer state stays in param dtype."""
    policy = resolve_policy("O3", half_dtype=jnp.float16, verbose=False)
    opt = optax.sgd(0.1, momentum=0.9)
    init_fn, step_fn = make_train_step(_loss_fn, opt, policy)
    state = init_fn({"w": jnp.ones((4, 2), jnp.float32),
                     "b": jnp.zeros((2,), jnp.float32)})
    x = jnp.ones((8, 4), jnp.float32)
    y = jnp.zeros((8, 2), jnp.float32)
    new_state, m = jax.jit(step_fn)(state, (x, y))
    assert new_state.params["w"].dtype == jnp.float16
    assert not bool(m["found_inf"])


def test_o1_casts_batch_to_half_compute():
    """O1 leaves params fp32 but runs compute (and thus batch inputs) in the
    half dtype — the op-table policy's coarse-grained application."""
    policy = resolve_policy("O1", verbose=False)
    seen = {}

    def probe_loss(params, batch):
        x, y = batch
        seen["x_dtype"] = x.dtype
        pred = x @ params["w"].astype(x.dtype)
        return jnp.mean((pred.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)

    init_fn, step_fn = make_train_step(probe_loss, optax.sgd(0.1), policy)
    state = init_fn({"w": jnp.ones((4, 2), jnp.float32)})
    assert state.params["w"].dtype == jnp.float32  # O1 keeps model fp32
    state, m = step_fn(state, (jnp.ones((8, 4)), jnp.zeros((8, 2))))
    assert seen["x_dtype"] == jnp.bfloat16


def test_master_params_rejects_optimizer_object():
    import pytest as _pytest
    from apex_tpu import amp as _amp

    with _pytest.raises(TypeError):
        _amp.master_params(optax.sgd(0.1))


# ------------------------------------------------- microbatch accumulation

def _mlp_loss(params, batch):
    x, y = batch
    h = jnp.tanh(x @ params["w"].astype(x.dtype))
    pred = h @ params["v"].astype(x.dtype) + params["b"].astype(x.dtype)
    return jnp.mean((pred.astype(jnp.float32) - y) ** 2)


def _mlp_params(seed=0):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(4, 8) * 0.3, jnp.float32),
            "v": jnp.asarray(rng.randn(8, 2) * 0.3, jnp.float32),
            "b": jnp.zeros((2,), jnp.float32)}


def _microbatches(n, rows=2, seed=0):
    rng = np.random.RandomState(100 + seed)
    x = jnp.asarray(rng.randn(n * rows, 4), jnp.float32)
    y = jnp.asarray(rng.randn(n * rows, 2), jnp.float32)
    return (x.reshape(n, rows, 4), y.reshape(n, rows, 2))


def test_accum_bitwise_matches_manual_accumulation():
    """THE acceptance bar: accum_steps=N at scale 1 produces bitwise-
    identical params to N sequential single-microbatch grad computations
    accumulated in fp32, averaged, and fed to ONE optimizer application
    — apex's delay_unscale recipe done by hand. The one optimizer
    application reuses the step machinery via grad_fn (identical traced
    update program), so the assertion isolates the accumulation scan —
    any deviation in sum order, averaging, or dtype shows up bitwise."""
    n = 4
    params = _mlp_params()
    opt = optax.adam(1e-2)
    policy = resolve_policy("O0", verbose=False)
    init_fn, step_fn = make_train_step(_mlp_loss, opt, policy,
                                       accum_steps=n)
    state = init_fn(params)
    mb = _microbatches(n)
    new_state, m = jax.jit(step_fn)(state, mb)
    assert not bool(m["found_inf"])

    # manual reference: per-microbatch jitted grads (N independent
    # compilations — truly sequential single-microbatch backward passes),
    # sequential fp32 accumulation, sum/N ...
    grad_one = jax.jit(jax.grad(_mlp_loss))
    acc = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    loss_sum = 0.0
    for i in range(n):
        one = jax.tree_util.tree_map(lambda l: l[i], mb)
        g = grad_one(state.params, one)
        acc = jax.tree_util.tree_map(
            lambda a, gg: a + jnp.asarray(gg, a.dtype), acc, g)
        loss_sum += float(_mlp_loss(state.params, one))
    avg = jax.tree_util.tree_map(lambda a: a / n, acc)
    # ... then the optimizer applied ONCE on the averaged grads, through
    # the same step pipeline (grad_fn passes the grads through untouched)
    init_ref, step_ref = make_train_step(
        None, opt, policy, grad_fn=lambda p, g, scale: (jnp.float32(0.0), g))
    ref_state = init_ref(params)
    want, _ = jax.jit(step_ref)(ref_state, avg)
    for k in params:
        np.testing.assert_array_equal(np.asarray(new_state.params[k]),
                                      np.asarray(want.params[k]),
                                      err_msg=f"leaf {k} not bitwise")
    # the reported loss is the window mean
    assert float(m["loss"]) == pytest.approx(loss_sum / n, rel=1e-6)


def test_accum_overflow_any_microbatch_freezes_whole_window():
    """delay_unscale semantics: ONE poisoned microbatch anywhere in the
    window ⇒ the whole window is skipped — stateful (adam) optimizer
    state bitwise frozen, masters untouched, scale backed off ONCE
    (the stateful extension of
    test_overflow_freezes_stateful_optimizer_bitwise)."""
    n = 4
    policy = resolve_policy("O2", half_dtype=jnp.float16, verbose=False)
    init_fn, step_fn = make_train_step(_mlp_loss, optax.adam(1e-2), policy,
                                       accum_steps=n)
    from apex_tpu.amp import init_scaler
    state = init_fn(_mlp_params())
    state = state.replace(scaler=init_scaler("dynamic", init_scale=256.0))
    step = jax.jit(step_fn)
    mb = _microbatches(n)
    state, m = step(state, mb)                   # clean window: advances
    assert not bool(m["found_inf"])
    x, y = _microbatches(n)
    # poison microbatch 2 only — the overflow must survive accumulation
    bad = (x.at[2, 0, 0].set(jnp.float32(1e30)), y)
    new_state, m = step(state, bad)
    assert bool(m["found_inf"])
    before = jax.tree_util.tree_leaves(state.opt_state)
    after = jax.tree_util.tree_leaves(new_state.opt_state)
    assert before and len(before) == len(after)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(new_state.master_params["w"]),
                                  np.asarray(state.master_params["w"]))
    # backed off exactly once for the whole window, not once per microbatch
    assert float(new_state.scaler.loss_scale) == 128.0


def test_accum_scaler_trajectory_matches_single_step_path():
    """The scaler schedule counts OPTIMIZER steps: W windows at
    accum_steps=N move the scaler state exactly as W single-microbatch
    steps do (scale_window counts windows, steps counter +1 per window)."""
    windows, n = 3, 2
    policy = resolve_policy("O2", half_dtype=jnp.float16, verbose=False)

    def run(accum_steps):
        from apex_tpu.amp import init_scaler
        init_fn, step_fn = make_train_step(
            _mlp_loss, optax.sgd(1e-4), policy, accum_steps=accum_steps)
        state = init_fn(_mlp_params())
        state = state.replace(
            scaler=init_scaler("dynamic", init_scale=4.0, scale_window=3))
        step = jax.jit(step_fn)
        for i in range(windows):
            if accum_steps == 1:
                x, y = _microbatches(n, seed=i)
                batch = (x.reshape(-1, 4), y.reshape(-1, 2))
            else:
                batch = _microbatches(n, seed=i)
            state, m = step(state, batch)
            assert not bool(m["found_inf"])
        return state.scaler

    acc, single = run(n), run(1)
    assert float(acc.loss_scale) == float(single.loss_scale) == 8.0
    assert int(acc.steps) == int(single.steps) == windows
    assert int(acc.unskipped) == int(single.unskipped)
    assert int(acc.overflows) == int(single.overflows) == 0


def test_accum_model_state_threads_through_scan_and_aux_stacks():
    """model_state flows microbatch→microbatch through the scan carry
    (i+1 sees i's BatchNorm stats — N updates per window), and has_aux
    stacks the per-microbatch aux along a leading N axis."""
    n = 3

    def loss_fn(params, mstate, batch):
        x, y = batch
        pred = x @ params["w"].astype(x.dtype)
        loss = jnp.mean((pred.astype(jnp.float32) - y) ** 2)
        new_ms = {"count": mstate["count"] + 1,
                  "mean": jnp.mean(x.astype(jnp.float32))}
        return loss, (new_ms, {"batch_mean": jnp.mean(y)})

    policy = resolve_policy("O0", verbose=False)
    init_fn, step_fn = make_train_step(loss_fn, optax.sgd(0.1), policy,
                                       has_aux=True, with_model_state=True,
                                       accum_steps=n)
    state = init_fn({"w": jnp.ones((4, 2), jnp.float32)},
                    model_state={"count": jnp.int32(0),
                                 "mean": jnp.float32(0.0)})
    mb = _microbatches(n)
    new_state, m = jax.jit(step_fn)(state, mb)
    assert int(new_state.model_state["count"]) == n
    assert m["aux"]["batch_mean"].shape == (n,)
    np.testing.assert_allclose(
        np.asarray(m["aux"]["batch_mean"]),
        np.asarray(jnp.mean(mb[1], axis=(1, 2))), rtol=1e-6)


def test_accum_rejects_grad_fn_and_bad_counts():
    policy = resolve_policy("O0", verbose=False)
    with pytest.raises(ValueError, match="accum_steps must be >= 1"):
        make_train_step(_mlp_loss, optax.sgd(0.1), policy, accum_steps=0)
    with pytest.raises(ValueError, match="incompatible with grad_fn"):
        make_train_step(None, optax.sgd(0.1), policy, accum_steps=2,
                        grad_fn=lambda p, b, s: (0.0, p))


def test_accum_one_psum_per_window_trace_time():
    """The acceptance certificate, counter half: with accum_steps=N the
    whole-tree DDP grad reduction is traced ONCE per optimizer window —
    `comm.ddp.allreduce.calls` reads 1 (and leaves == n_params) after the
    jitted window step compiles, because the psum sits after the scan,
    not inside it. (The scheduled-HLO half lives in bench_schedule.py's
    ddp_accum leg.)"""
    import apex_tpu.telemetry as telemetry
    from jax.sharding import Mesh, PartitionSpec as P
    from apex_tpu.utils.compat import shard_map

    old = telemetry.get_registry()
    reg = telemetry.configure(sinks=[])
    try:
        n = 4
        policy = resolve_policy("O2", half_dtype=jnp.bfloat16,
                                verbose=False)
        init_fn, step_fn = make_train_step(_mlp_loss, optax.sgd(0.1),
                                           policy, grad_average_axis="data",
                                           accum_steps=n)
        state = init_fn(_mlp_params())
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        x, y = _microbatches(n)
        fn = shard_map(step_fn, mesh=mesh,
                       in_specs=(P(), (P(None, "data"), P(None, "data"))),
                       out_specs=(P(), P()))
        jax.jit(fn)(state, (x, y))
        assert reg.counters["comm.ddp.allreduce.calls"] == 1.0
        assert reg.counters["comm.ddp.allreduce.leaves"] == 3.0
    finally:
        telemetry.set_registry(old)


def test_training_converges_o2_vs_o0():
    """Convergence-parity smoke (the L1 bar scaled down): O2 loss tracks O0."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 4), jnp.float32)
    w_true = jnp.asarray(rng.randn(4, 2), jnp.float32)
    y = x @ w_true
    losses = {}
    for lvl in ("O0", "O2"):
        policy = resolve_policy(lvl, half_dtype=jnp.bfloat16, verbose=False)
        init_fn, step_fn = make_train_step(_loss_fn, optax.sgd(0.05), policy)
        state = init_fn({"w": jnp.zeros((4, 2), jnp.float32),
                         "b": jnp.zeros((2,), jnp.float32)})
        step = jax.jit(step_fn)
        for _ in range(60):
            state, m = step(state, (x, y))
        losses[lvl] = float(m["loss"])
    assert losses["O0"] < 0.05
    assert abs(losses["O2"] - losses["O0"]) < 0.05
