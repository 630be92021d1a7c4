"""Multi-tensor fused update kernels over a flat superbuffer.

TPU-native equivalent of the reference's ``amp_C`` extension — the
multi_tensor_apply harness (csrc/multi_tensor_apply.cuh) plus its functors:
ScaleFunctor (multi_tensor_scale_kernel.cu), AxpbyFunctor
(multi_tensor_axpby_kernel.cu), L2NormFunctor (multi_tensor_l2norm_kernel.cu),
AdamFunctor (multi_tensor_adam.cu), SGDFunctor (multi_tensor_sgd_kernel.cu).

The CUDA harness exists to update hundreds of small tensors in O(1) kernel
launches. The TPU translation keeps the *semantics* — one whole-model update
pass per step with an overflow (``noop``) flag — via a single Pallas kernel
over the model flattened into one fp32 superbuffer (see
apex_tpu.multi_tensor_apply for the tensor-list plumbing, and
apex_tpu.utils.pytree for flatten/unflatten). Chunking happens through the
Pallas grid instead of the CUDA TensorListMetadata chunk tables.

All kernels run on (rows, 128) lane-aligned views of the zero-padded flat
buffer; zero padding is a fixed point of every functor here, so padded tails
never perturb real entries. Off-TPU they fall back to jnp (one fused jaxpr).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels import vmem as _vmem

_LANES = 128
# the adam kernel touches 7 blocked buffers (+pipelining double-buffers and
# fp32 temporaries); the shared scoped-VMEM heuristic (kernels/vmem.py) gives
# 1024 rows of 128 lanes — 2048 overflowed Mosaic's 16MB stack at LM scale
_BLOCK_ROWS = _vmem.block_rows(1 << 30, row_bytes=4 * _LANES, n_bufs=8,
                               max_rows=2048)


def _as_rows(flat):
    n = flat.shape[0]
    rows = max(1, -(-n // _LANES))
    rows_p = -(-rows // 8) * 8
    padded = jnp.pad(flat, (0, rows_p * _LANES - n))
    return padded.reshape(rows_p, _LANES), n


def _grid_rows(rows):
    bm = min(_vmem.get_override("multi_tensor.block_rows", _BLOCK_ROWS,
                                multiple=8), rows)
    rows_p = -(-rows // bm) * bm
    return bm, rows_p, rows_p // bm


def _use_pallas(interpret, *xs):
    from . import mosaic_dtype_ok, on_tpu

    return interpret or (on_tpu() and mosaic_dtype_ok(*xs))


def _row_spec(bm):
    return pl.BlockSpec((bm, _LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _acc_spec():
    return pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)


# ------------------------------------------------------------------- scale
def _scale_kernel(scale_ref, x_ref, out_ref, flag_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    out_ref[:] = (x * scale_ref[0, 0]).astype(out_ref.dtype)

    @pl.when(i == 0)
    def _():
        flag_ref[0, 0] = 0

    bad = jnp.logical_not(jnp.all(jnp.isfinite(x)))
    flag_ref[0, 0] = jnp.maximum(flag_ref[0, 0], bad.astype(jnp.int32))


def fused_scale(flat, scale, interpret: bool = False):
    """out = flat * scale, plus found_inf — amp_C.multi_tensor_scale."""
    scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    if not _use_pallas(interpret, flat):
        x32 = flat.astype(jnp.float32)
        out = (x32 * scale[0, 0]).astype(flat.dtype)
        return out, jnp.logical_not(jnp.all(jnp.isfinite(x32)))
    x2, n = _as_rows(flat)
    bm, rows_p, g = _grid_rows(x2.shape[0])
    x2 = jnp.pad(x2, ((0, rows_p - x2.shape[0]), (0, 0)))
    out, flag = pl.pallas_call(
        _scale_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
                  _row_spec(bm)],
        out_specs=[_row_spec(bm), _acc_spec()],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, flat.dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret, name="multi_tensor_scale",
    )(scale, x2)
    return out.reshape(-1)[:n], flag[0, 0] > 0


# ------------------------------------------------------------------- axpby
def _axpby_kernel(ab_ref, x_ref, y_ref, out_ref, flag_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    y = y_ref[:].astype(jnp.float32)
    out_ref[:] = (ab_ref[0, 0] * x + ab_ref[0, 1] * y).astype(out_ref.dtype)

    @pl.when(i == 0)
    def _():
        flag_ref[0, 0] = 0

    bad = jnp.logical_not(jnp.logical_and(jnp.all(jnp.isfinite(x)),
                                          jnp.all(jnp.isfinite(y))))
    flag_ref[0, 0] = jnp.maximum(flag_ref[0, 0], bad.astype(jnp.int32))


def fused_axpby(flat_x, flat_y, a, b, interpret: bool = False):
    """out = a*x + b*y with overflow check — amp_C.multi_tensor_axpby
    (grad accumulation fused with unscale).

    Hot-path wiring: ``amp.scaler.unscale_with_stashed`` routes flat 1-D
    buffer pairs here with ``a=1/scale, b=1`` — the delayed-unscale
    accumulate-with-unscale primitive for the superbuffer layout (the
    in-jit ``make_train_step(accum_steps=N)`` path accumulates per-leaf
    trees instead and lets XLA fuse the equivalent axpby)."""
    ab = jnp.stack([jnp.asarray(a, jnp.float32),
                    jnp.asarray(b, jnp.float32)]).reshape(1, 2)
    if not _use_pallas(interpret, flat_x, flat_y):
        x32, y32 = flat_x.astype(jnp.float32), flat_y.astype(jnp.float32)
        out = (ab[0, 0] * x32 + ab[0, 1] * y32).astype(flat_x.dtype)
        found = jnp.logical_not(jnp.logical_and(
            jnp.all(jnp.isfinite(x32)), jnp.all(jnp.isfinite(y32))))
        return out, found
    x2, n = _as_rows(flat_x)
    y2, _ = _as_rows(flat_y)
    bm, rows_p, g = _grid_rows(x2.shape[0])
    x2 = jnp.pad(x2, ((0, rows_p - x2.shape[0]), (0, 0)))
    y2 = jnp.pad(y2, ((0, rows_p - y2.shape[0]), (0, 0)))
    out, flag = pl.pallas_call(
        _axpby_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
                  _row_spec(bm), _row_spec(bm)],
        out_specs=[_row_spec(bm), _acc_spec()],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, flat_x.dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret, name="multi_tensor_axpby",
    )(ab, x2, y2)
    return out.reshape(-1)[:n], flag[0, 0] > 0


# ------------------------------------------------------------------- l2norm
def _l2norm_kernel(x_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[0, 0] = 0.0

    x = x_ref[:].astype(jnp.float32)
    acc_ref[0, 0] += jnp.sum(x * x)


def fused_l2norm(flat, interpret: bool = False):
    """||flat||_2 in fp32 — amp_C.multi_tensor_l2norm (used by FusedLAMB's
    global-norm stage and contrib clip_grad)."""
    if not _use_pallas(interpret, flat):
        x32 = flat.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x32 * x32))
    x2, _ = _as_rows(flat)
    bm, rows_p, g = _grid_rows(x2.shape[0])
    x2 = jnp.pad(x2, ((0, rows_p - x2.shape[0]), (0, 0)))
    acc = pl.pallas_call(
        _l2norm_kernel,
        grid=(g,),
        in_specs=[_row_spec(bm)],
        out_specs=_acc_spec(),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret, name="multi_tensor_l2norm",
    )(x2)
    return jnp.sqrt(acc[0, 0])


# --------------------------------------------------------------------- adam
def _adam_kernel(sc_ref, p_ref, m_ref, v_ref, g_ref,
                 p_out, m_out, v_out, *, adam_w):
    lr = sc_ref[0, 0]
    b1 = sc_ref[0, 1]
    b2 = sc_ref[0, 2]
    eps = sc_ref[0, 3]
    wd = sc_ref[0, 4]
    bc1 = sc_ref[0, 5]   # 1 - b1**t
    bc2 = sc_ref[0, 6]   # 1 - b2**t
    inv_scale = sc_ref[0, 7]

    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * inv_scale
    if not adam_w:
        g = g + wd * p  # ADAM_MODE_0: L2 regularization folded into grad
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w:
        update = update + wd * p  # ADAM_MODE_1: decoupled weight decay
    p_out[:] = (p - lr * update).astype(p_out.dtype)
    m_out[:] = m
    v_out[:] = v


def fused_adam_step(flat_p, flat_m, flat_v, flat_g, *, lr, beta1, beta2, eps,
                    weight_decay, step, adam_w_mode=True, inv_scale=1.0,
                    bias_correction=True, interpret: bool = False):
    """One whole-model Adam/AdamW step — amp_C.multi_tensor_adam
    (csrc/multi_tensor_adam.cu — AdamFunctor; bias correction via step count,
    adam_w selects decoupled decay).

    Buffers are flat fp32 (m, v always fp32, matching apex's fp32 optimizer
    state). ``step`` is the 1-based step count (traced ok).
    """
    step = jnp.asarray(step, jnp.float32)
    b1 = jnp.asarray(beta1, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    if bias_correction:
        bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    else:  # apex FusedAdam(bias_correction=False)
        bc1 = bc2 = jnp.float32(1.0)
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32), b1, b2,
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        bc1, bc2,
        jnp.asarray(inv_scale, jnp.float32),
    ]).reshape(1, 8)
    if not _use_pallas(interpret, flat_p, flat_g):
        lr_, b1_, b2_, eps_, wd_, bc1, bc2, inv = [scalars[0, i]
                                                   for i in range(8)]
        p = flat_p.astype(jnp.float32)
        g = flat_g.astype(jnp.float32) * inv
        if not adam_w_mode:
            g = g + wd_ * p
        m = b1_ * flat_m + (1 - b1_) * g
        v = b2_ * flat_v + (1 - b2_) * g * g
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps_)
        if adam_w_mode:
            upd = upd + wd_ * p
        return (p - lr_ * upd).astype(flat_p.dtype), m, v

    p2, n = _as_rows(flat_p)
    m2, _ = _as_rows(flat_m)
    v2, _ = _as_rows(flat_v)
    g2, _ = _as_rows(flat_g)
    bm, rows_p, grid = _grid_rows(p2.shape[0])
    pad = ((0, rows_p - p2.shape[0]), (0, 0))
    p2, m2, v2, g2 = (jnp.pad(a, pad) for a in (p2, m2, v2, g2))
    kernel = functools.partial(_adam_kernel, adam_w=adam_w_mode)
    p_new, m_new, v_new = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, 8), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)] + [_row_spec(bm)] * 4,
        out_specs=[_row_spec(bm)] * 3,
        out_shape=[jax.ShapeDtypeStruct((rows_p, _LANES), flat_p.dtype),
                   jax.ShapeDtypeStruct((rows_p, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows_p, _LANES), jnp.float32)],
        interpret=interpret, name="multi_tensor_adam",
    )(scalars, p2, m2, v2, g2)
    return (p_new.reshape(-1)[:n], m_new.reshape(-1)[:n],
            v_new.reshape(-1)[:n])


def adam_tree_step(tree_p, tree_m, tree_v, tree_g, *, lr, beta1, beta2, eps,
                   weight_decay, step, adam_w_mode=True, inv_scale=1.0,
                   bias_correction=True):
    """AdamFunctor applied PER LEAF under one jit — the TPU-native layout.

    Same per-element math as :func:`fused_adam_step`'s superbuffer kernel
    (asserted bitwise-identical in tests/L0/test_multi_tensor.py), but over
    the parameter pytree directly. The CUDA multi_tensor harness exists to
    amortize kernel LAUNCHES, which jit does not pay; the superbuffer
    translation instead pays two whole-model flatten/unflatten copies per
    step. Measured on v5e at 125M params (BASELINE.md round-5 kernel tier):
    flat+Pallas 18.7 ms, flat+jnp 15.1 ms, this path 5.2 ms — XLA fuses the
    per-leaf updates to the HBM roofline. The flat kernels remain for
    callers whose SHARDING is buffer-level (contrib ZeRO optimizers
    psum_scatter the superbuffer).

    Returns (new_p tree in param dtype, new_m tree fp32, new_v tree fp32).
    """
    step = jnp.asarray(step, jnp.float32)
    b1 = jnp.asarray(beta1, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    if bias_correction:
        bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    else:
        bc1 = bc2 = jnp.float32(1.0)
    lr = jnp.asarray(lr, jnp.float32)
    eps = jnp.asarray(eps, jnp.float32)
    wd = jnp.asarray(weight_decay, jnp.float32)
    inv = jnp.asarray(inv_scale, jnp.float32)

    def leaf(p, m, v, g):
        p32 = p.astype(jnp.float32)
        g32 = g.astype(jnp.float32) * inv
        if not adam_w_mode:
            g32 = g32 + wd * p32       # ADAM_MODE_0: L2 folded into grad
        m2 = b1 * m + (1.0 - b1) * g32
        v2 = b2 * v + (1.0 - b2) * g32 * g32
        upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
        if adam_w_mode:
            upd = upd + wd * p32       # ADAM_MODE_1: decoupled decay
        return (p32 - lr * upd).astype(p.dtype), m2, v2

    lp, td = jax.tree_util.tree_flatten(tree_p)
    lm = jax.tree_util.tree_leaves(tree_m)
    lv = jax.tree_util.tree_leaves(tree_v)
    lg = jax.tree_util.tree_leaves(tree_g)
    outs = [leaf(p, m, v, g) for p, m, v, g in zip(lp, lm, lv, lg)]

    def unf(i):
        return jax.tree_util.tree_unflatten(td, [o[i] for o in outs])

    return unf(0), unf(1), unf(2)


def sgd_tree_step(tree_p, tree_buf, tree_g, *, lr, momentum=0.0,
                  dampening=0.0, weight_decay=0.0, nesterov=False,
                  wd_after_momentum=False):
    """SGDFunctor applied PER LEAF under one jit — the TPU-native layout
    (same rationale and bitwise contract as :func:`adam_tree_step`; the
    superbuffer's flatten/unflatten copies are the dominant cost of
    :func:`fused_sgd_step` under jit).

    Returns (new_p tree in param dtype, new_buf tree fp32)."""
    lr = jnp.asarray(lr, jnp.float32)
    mom = jnp.asarray(momentum, jnp.float32)
    damp = jnp.asarray(dampening, jnp.float32)
    wd = jnp.asarray(weight_decay, jnp.float32)
    momentum_on = True if hasattr(momentum, "dtype") \
        else float(momentum) != 0.0

    def leaf(p, buf, g):
        p32 = p.astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        if not wd_after_momentum:
            g32 = g32 + wd * p32
        if momentum_on:
            buf2 = mom * buf + (1 - damp) * g32
            upd = g32 + mom * buf2 if nesterov else buf2
        else:
            buf2 = buf
            upd = g32
        if wd_after_momentum:
            upd = upd + wd * p32
        return (p32 - lr * upd).astype(p.dtype), buf2

    lp, td = jax.tree_util.tree_flatten(tree_p)
    lb = jax.tree_util.tree_leaves(tree_buf)
    lg = jax.tree_util.tree_leaves(tree_g)
    outs = [leaf(p, b, g) for p, b, g in zip(lp, lb, lg)]

    def unf(i):
        return jax.tree_util.tree_unflatten(td, [o[i] for o in outs])

    return unf(0), unf(1)


# ---------------------------------------------------------------------- sgd
def _sgd_kernel(sc_ref, p_ref, buf_ref, g_ref, p_out, buf_out, *,
                momentum_on, nesterov, wd_after_momentum):
    lr = sc_ref[0, 0]
    momentum = sc_ref[0, 1]
    dampening = sc_ref[0, 2]
    wd = sc_ref[0, 3]

    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    if not wd_after_momentum:
        g = g + wd * p
    if momentum_on:
        buf = momentum * buf_ref[:] + (1.0 - dampening) * g
        upd = g + momentum * buf if nesterov else buf
        buf_out[:] = buf
    else:
        upd = g
        buf_out[:] = buf_ref[:]
    if wd_after_momentum:
        upd = upd + wd * p
    p_out[:] = (p - lr * upd).astype(p_out.dtype)


def fused_sgd_step(flat_p, flat_buf, flat_g, *, lr, momentum=0.0,
                   dampening=0.0, weight_decay=0.0, nesterov=False,
                   wd_after_momentum=False, interpret: bool = False):
    """One whole-model SGD step — amp_C.multi_tensor_sgd
    (csrc/multi_tensor_sgd_kernel.cu — SGDFunctor, incl. the
    wd_after_momentum variant apex exposes on FusedSGD).

    Note: with zero-initialized momentum buffers and dampening==0 the first
    step equals torch/apex's buf=grad initialization.
    """
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(momentum, jnp.float32),
        jnp.asarray(dampening, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
    ]).reshape(1, 4)
    momentum_on = float(momentum) != 0.0 if not hasattr(momentum, "dtype") \
        else True
    if not _use_pallas(interpret, flat_p, flat_g):
        lr_, mom, damp, wd_ = [scalars[0, i] for i in range(4)]
        p = flat_p.astype(jnp.float32)
        g = flat_g.astype(jnp.float32)
        if not wd_after_momentum:
            g = g + wd_ * p
        if momentum_on:
            buf = mom * flat_buf + (1 - damp) * g
            upd = g + mom * buf if nesterov else buf
        else:
            buf = flat_buf
            upd = g
        if wd_after_momentum:
            upd = upd + wd_ * p
        return (p - lr_ * upd).astype(flat_p.dtype), buf

    p2, n = _as_rows(flat_p)
    b2, _ = _as_rows(flat_buf)
    g2, _ = _as_rows(flat_g)
    bm, rows_p, grid = _grid_rows(p2.shape[0])
    pad = ((0, rows_p - p2.shape[0]), (0, 0))
    p2, b2, g2 = (jnp.pad(a, pad) for a in (p2, b2, g2))
    kernel = functools.partial(_sgd_kernel, momentum_on=momentum_on,
                               nesterov=nesterov,
                               wd_after_momentum=wd_after_momentum)
    p_new, buf_new = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, 4), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)] + [_row_spec(bm)] * 3,
        out_specs=[_row_spec(bm)] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows_p, _LANES), flat_p.dtype),
                   jax.ShapeDtypeStruct((rows_p, _LANES), jnp.float32)],
        interpret=interpret, name="multi_tensor_sgd",
    )(scalars, p2, b2, g2)
    return p_new.reshape(-1)[:n], buf_new.reshape(-1)[:n]
