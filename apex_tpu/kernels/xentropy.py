"""Fused softmax cross-entropy Pallas kernel with label smoothing.

TPU-native equivalent of the reference's ``xentropy_cuda`` extension
(apex/contrib/csrc/xentropy/xentropy_kernel.cu —
cunn_SoftMaxXEntropyForward/Backward). Semantics preserved:

- forward computes per-row loss and saves only (losses, max_log_sum_exp)
  for backward ("bprop-in-fprop" memory shape: no softmax tensor saved);
- label smoothing folded into both passes (in-place smoothing in the
  reference);
- half I/O with fp32 math.

Rows are blocked over a 1-D grid with the full vocab row in VMEM per block
(same layout choice as the LN kernel); unaligned vocab falls back to the jnp
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.kernels import vmem

__all__ = ["softmax_cross_entropy_loss", "xent_reference"]


def xent_reference(logits, labels, smoothing: float = 0.0):
    """fp32 composed reference (the reference tests compare against
    F.log_softmax + nll with manual smoothing).

    Out-of-range labels (ignore-index ``-100``, ids ``>= V``) produce a
    NaN loss and drop the onehot cotangent — explicitly, for EVERY
    out-of-range id: a raw ``take_along_axis`` would numpy-wrap
    negatives in ``[-V, -1]`` onto real vocab rows (``-100`` at
    ``V > 100`` silently trains on token ``V-100``), which torch's
    ``nll_loss`` would never do (it raises). NaN is the loud jax-side
    equivalent; mask the returned losses to ignore such positions."""
    lg = jnp.asarray(logits, jnp.float32)
    logp = jax.nn.log_softmax(lg, axis=-1)
    valid = (labels >= 0) & (labels < lg.shape[-1])
    safe = jnp.where(valid, labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, jnp.float32(jnp.nan))
    if smoothing > 0.0:
        mean_logp = jnp.mean(logp, axis=-1)
        return (1.0 - smoothing) * nll - smoothing * mean_logp
    return nll


def _fwd_kernel(lg_ref, lb_ref, loss_ref, mlse_ref, *, smoothing):
    # per-row tensors ride the SUBLANE dim as [br, 1] blocks — lane-dim
    # dynamic stores at non-128-aligned offsets don't lower on Mosaic
    lg = lg_ref[:].astype(jnp.float32)              # [br, V]
    labels = lb_ref[:, 0]                           # [br]
    m = jnp.max(lg, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1, keepdims=True)) + m
    # gather-by-label as a masked reduction (Mosaic has no 1-slice gather)
    cols = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    onehot_logit = jnp.sum(
        jnp.where(cols == labels[:, None], lg, 0.0), axis=-1, keepdims=True)
    # out-of-range labels (ignore-index -100, ids >= V): the masked
    # reduction matches no column, so nll would silently read as lse —
    # finite but WRONG. Match xent_reference: NaN, loudly.
    valid = (labels >= 0) & (labels < lg.shape[-1])
    nll = jnp.where(valid[:, None], lse - onehot_logit,
                    jnp.float32(jnp.nan))           # [br, 1]
    if smoothing > 0.0:
        mean_logp = jnp.mean(lg - lse, axis=-1, keepdims=True)
        loss = (1.0 - smoothing) * nll - smoothing * mean_logp
    else:
        loss = nll
    loss_ref[:] = loss
    mlse_ref[:] = lse


def _bwd_kernel(lg_ref, lb_ref, mlse_ref, g_ref, out_ref, *, smoothing):
    lg = lg_ref[:].astype(jnp.float32)              # [br, V]
    labels = lb_ref[:, 0]
    lse = mlse_ref[:]                               # [br, 1]
    g = g_ref[:]                                    # [br, 1]
    V = lg.shape[-1]
    softmax = jnp.exp(lg - lse)
    cols = jax.lax.broadcasted_iota(jnp.int32, softmax.shape, 1)
    onehot = (cols == labels[:, None]).astype(jnp.float32)
    # out-of-range labels: the reference drops the onehot cotangent (its
    # NaN-masked nll contributes nothing) but keeps the smoothing
    # mean-logp path flowing — d/dlogits of -s*mean_logp is
    # s*(softmax - 1/V). Same algebra as lm_head_loss._fused_bwd.
    valid = (labels >= 0) & (labels < V)
    if smoothing > 0.0:
        target = (1.0 - smoothing) * onehot + smoothing / V
        inv_dl = smoothing * (softmax - 1.0 / V)
    else:
        target = onehot
        inv_dl = jnp.float32(0.0)
    dl = jnp.where(valid[:, None], softmax - target, inv_dl)
    out_ref[:] = (dl * g).astype(out_ref.dtype)


def _col(x, n):
    return x.reshape(n, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _xent(logits, labels, smoothing, interpret):
    loss, _ = _xent_fwd(logits, labels, smoothing, interpret)
    return loss


def _block_rows(n, v, n_bufs=4):
    # fp32 logits block + ~3 same-size temporaries (exp, iota/onehot,
    # output); shared scoped-VMEM budget lives in kernels/vmem.py.
    # The BACKWARD passes n_bufs=8 ONLY for fp32 residuals: its logits
    # residual arrives in the caller's dtype, and at fp32 the 4*v-byte
    # rows plus the same-width dlogits block overflowed Mosaic's 16MB
    # scoped-VMEM stack (21MB at the tuned 32-row block, [8192, 32768]
    # fp32 — caught by the round-5 LM run). Half-precision callers keep
    # the fwd accounting: their 2*v-byte residual fits the full tuned
    # block (bench-verified at 32 rows bf16).
    return vmem.block_rows(n, row_bytes=4 * v, n_bufs=n_bufs, max_rows=128,
                           divisor_of=n, key="xentropy.block_rows")


def _xent_fwd(logits, labels, smoothing, interpret):
    n, v = logits.shape
    br = _block_rows(n, v)
    kernel = functools.partial(_fwd_kernel, smoothing=smoothing)
    loss, mlse = pl.pallas_call(
        kernel,
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, v), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret, name="xentropy_fwd",
    )(logits, _col(labels, n))
    return loss.reshape(n), (logits, labels, mlse)


def _xent_bwd(smoothing, interpret, res, g):
    logits, labels, mlse = res
    n, v = logits.shape
    # 8 buffers only when the residual actually IS fp32 (4*v-byte rows);
    # half-precision callers keep the full tuned block — their 2*v-byte
    # residual fits the fwd accounting (bench-verified at 32 rows bf16)
    br = _block_rows(n, v,
                     n_bufs=8 if logits.dtype == jnp.float32 else 4)
    kernel = functools.partial(_bwd_kernel, smoothing=smoothing)
    dlogits = pl.pallas_call(
        kernel,
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, v), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, v), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, v), logits.dtype),
        interpret=interpret, name="xentropy_bwd",
    )(logits, _col(labels, n), _col(mlse, n),
      _col(g.astype(jnp.float32), n))
    return dlogits, None


_xent.defvjp(_xent_fwd, _xent_bwd)


def softmax_cross_entropy_loss(logits, labels, smoothing: float = 0.0,
                               interpret: bool = False):
    """Per-example fused CE. logits: [..., V] (half ok), labels: [...] int.

    Reference: apex/contrib/xentropy/softmax_xentropy.py —
    SoftmaxCrossEntropyLoss(logits, labels, smoothing).

    Out-of-range labels (ignore-index ``-100``, ids ``>= V``) follow
    :func:`xent_reference` on EVERY dispatch path (Pallas kernel and jnp
    fallback alike): NaN loss, onehot cotangent dropped. To ignore such
    positions, mask the returned per-example losses before reducing —
    ``jnp.where(labels != -100, losses, 0.0)``.
    """
    shape = logits.shape[:-1]
    v = logits.shape[-1]
    n = 1
    for s in shape:
        n *= s
    lg2 = logits.reshape(n, v)
    lb = labels.reshape(n)
    aligned = v % 128 == 0 and (n % 128 == 0 or n % 8 == 0)
    if not aligned:
        return xent_reference(logits, labels, smoothing)
    if jax.default_backend() == "cpu":
        interpret = True
    from . import mosaic_dtype_ok

    if not interpret and not mosaic_dtype_ok(lg2):
        return xent_reference(logits, labels, smoothing)
    return _xent(lg2, lb, smoothing, interpret).reshape(shape)
