"""Fused LayerNorm / RMSNorm Pallas kernels with custom VJP.

TPU-native equivalent of the reference's ``fused_layer_norm_cuda`` extension
(csrc/layer_norm_cuda_kernel.cu — cuApplyLayerNorm, cuWelfordMuSigma2,
cuComputeGradInput, cuComputePartGradGammaBeta) and the contrib "fast layer
norm" (apex/contrib/csrc/layer_norm/ln_fwd_kernels.cuh). Semantics preserved:

- forward saves (mean, invvar) in fp32 for backward — not the normalized
  output (memory_efficient=False semantics, the apex default);
- ``memory_efficient=True`` mirrors apex's flag of the same name
  (fused_layer_norm.py — memory_efficient forward): the backward keeps the
  OUTPUT y (plus rstd) instead of the input x and reconstructs
  xhat = (y - beta)/gamma, so a mid-graph x dies right after the forward —
  the round-5 answer to the priced LN residency negative (BASELINE.md).
  Like apex, it requires gamma nonzero everywhere (the reconstruction
  divides by it);
- all statistics and grad reductions accumulate in fp32 whatever the I/O
  dtype (apex computes Welford in accscalar_t = float);
- gamma/beta gradients are column reductions accumulated across row blocks
  (apex's two-stage cuComputePartGradGammaBeta/cuComputeGradGammaBeta
  becomes a grid-revisited accumulator block).

Design notes (TPU): rows are blocked over a 1-D grid; the full hidden dim
sits in VMEM per block (lane-aligned H). Unaligned hidden sizes fall back to
the jnp reference path — XLA fuses that chain well; the Pallas win is for the
transformer-shaped (H % 128 == 0) hot path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.kernels import vmem
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_rows(n_rows: int, hidden: int, n_bufs: int) -> int:
    # shared scoped-VMEM budget heuristic (kernels/vmem.py) clamps to n_rows
    return vmem.block_rows(n_rows, row_bytes=4 * hidden, n_bufs=n_bufs,
                           key="layer_norm.block_rows")


def _pallas_ok(n: int, h: int, dtype=None) -> bool:
    from . import mosaic_dtype_ok, on_tpu

    return on_tpu() and h % 128 == 0 and mosaic_dtype_ok(dtype)


# ----------------------------------------------------------------- references
def layer_norm_reference(x, weight=None, bias=None, eps=1e-5):
    """Composed-op oracle (the reference tests compare against
    torch.nn.LayerNorm; here: pure jnp in fp32)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_reference(x, weight=None, eps=1e-5):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(ms + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


# -------------------------------------------------------------------- kernels
def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps,
                   affine, rms):
    x = x_ref[:].astype(jnp.float32)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(x * x, axis=1, keepdims=True)
    else:
        mean = jnp.mean(x, axis=1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    if affine:
        y = y * g_ref[:].astype(jnp.float32)
        if not rms:
            y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _bwd_from_xhat(dy, xhat, dyg, rstd, rms):
    """cuComputeGradInput: dx = rstd*(dyg - mean(dyg) - xhat*mean(dyg*xhat))
    (RMS: no mean(dyg) term — no mean was subtracted in fwd). Shared by
    the save-x and save-y (memory_efficient) backwards, Pallas and jnp —
    the two variants differ ONLY in how xhat is derived. Returns
    (dx, dg_rows, db_rows) in fp32; dg/db still need the column
    reduction."""
    c2 = jnp.mean(dyg * xhat, axis=-1, keepdims=True)
    if rms:
        dx = rstd * (dyg - xhat * c2)
    else:
        c1 = jnp.mean(dyg, axis=-1, keepdims=True)
        dx = rstd * (dyg - c1 - xhat * c2)
    return dx, dy * xhat, dy


def _ln_bwd_kernel(dy_ref, src_ref, g_ref, aux_ref, rstd_ref,
                   dx_ref, dg_ref, db_ref, *, affine, rms, mem_eff):
    """One backward kernel for both residual layouts. Default (save-x):
    ``src`` is the input x, ``aux`` its per-row mean, xhat=(x-mean)*rstd.
    memory_efficient (save-y, apex's flag): ``src`` is the OUTPUT y,
    ``aux`` is beta broadcast as a (1, h) row, xhat=(y-beta)/gamma —
    gamma must be nonzero, as in apex."""
    i = pl.program_id(0)
    dy = dy_ref[:].astype(jnp.float32)
    src = src_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    g = g_ref[:].astype(jnp.float32) if affine else None
    if mem_eff:
        if affine:
            xhat = (src / g if rms
                    else (src - aux_ref[:].astype(jnp.float32)) / g)
        else:
            xhat = src
    else:
        xhat = (src - aux_ref[:]) * rstd
    dyg = dy * g if affine else dy
    dx, dg_rows, db_rows = _bwd_from_xhat(dy, xhat, dyg, rstd, rms)
    dx_ref[:] = dx.astype(dx_ref.dtype)

    if affine:
        # grid-revisited accumulator block — the two-stage gamma/beta grad
        # reduction (cuComputePartGradGammaBeta) collapses to this.
        @pl.when(i == 0)
        def _():
            dg_ref[:] = jnp.zeros_like(dg_ref)
            if not rms:
                db_ref[:] = jnp.zeros_like(db_ref)

        dg_ref[:] += jnp.sum(dg_rows, axis=0, keepdims=True)
        if not rms:
            db_ref[:] += jnp.sum(db_rows, axis=0, keepdims=True)


def _pad_rows(arr, rows_p):
    n = arr.shape[0]
    if n == rows_p:
        return arr
    return jnp.pad(arr, ((0, rows_p - n), (0, 0)))


def _ln_fwd_pallas(x2, gamma, beta, eps, rms, interpret):
    n, h = x2.shape
    affine = gamma is not None
    nbufs = 3 + (2 if affine else 0)
    bm = _block_rows(n, h, nbufs)
    rows_p = ((n + bm - 1) // bm) * bm
    xp = _pad_rows(x2, rows_p)
    g2 = (gamma if affine else jnp.zeros((h,), x2.dtype)).reshape(1, h)
    # beta may be None even with a weight (weight-only affine)
    b2 = (beta if (affine and not rms and beta is not None)
          else jnp.zeros((h,), x2.dtype)).reshape(1, h)
    grid = (rows_p // bm,)
    kernel = functools.partial(_ln_fwd_kernel, eps=eps, affine=affine, rms=rms)
    y, mean, rstd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, h), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, h), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, h), x2.dtype),
            jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
        ],
        interpret=interpret, name="layer_norm_fwd",
    )(xp, g2, b2)
    return y[:n], mean[:n], rstd[:n]


def _ln_bwd_pallas(dy2, src2, gamma, aux, rstd, rms, interpret,
                   mem_eff=False):
    """Shared backward wrapper. Default: ``src2``=x, ``aux``=mean [n,1].
    memory_efficient: ``src2``=y, ``aux``=beta (h,) or None."""
    n, h = src2.shape
    affine = gamma is not None
    nbufs = 4 + (3 if affine else 0)
    bm = _block_rows(n, h, nbufs)
    rows_p = ((n + bm - 1) // bm) * bm
    dyp, srcp = _pad_rows(dy2, rows_p), _pad_rows(src2, rows_p)
    rstdp = _pad_rows(rstd, rows_p)
    g2 = (gamma if affine else jnp.zeros((h,), src2.dtype)).reshape(1, h)
    if mem_eff:
        aux_arr = (aux if (affine and not rms and aux is not None)
                   else jnp.zeros((h,), src2.dtype)).reshape(1, h)
        aux_spec = pl.BlockSpec((1, h), lambda i: (0, 0),
                                memory_space=pltpu.VMEM)
    else:
        aux_arr = _pad_rows(aux, rows_p)
        aux_spec = pl.BlockSpec((bm, 1), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)
    grid = (rows_p // bm,)
    kernel = functools.partial(_ln_bwd_kernel, affine=affine, rms=rms,
                               mem_eff=mem_eff)
    dx, dg, db = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, h), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, h), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
            aux_spec,
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, h), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, h), src2.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=interpret, name="layer_norm_bwd",
    )(dyp, srcp, g2, aux_arr, rstdp)
    return dx[:n], dg.reshape(h), db.reshape(h)


# ----------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _layer_norm(x2, gamma, beta, eps, rms, interpret, mem_eff=False):
    y, _, _ = _ln_fwd(x2, gamma, beta, eps, rms, interpret)
    return y


def _ln_fwd(x2, gamma, beta, eps, rms, interpret):
    n, h = x2.shape
    if _pallas_ok(n, h, x2.dtype) or interpret:
        return _ln_fwd_pallas(x2, gamma, beta, eps, rms, interpret)
    # jnp fallback still saves (mean, rstd) so bwd matches
    x32 = x2.astype(jnp.float32)
    if rms:
        mean = jnp.zeros((n, 1), jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x32 - mean) * rstd
    if gamma is not None:
        y = y * gamma.astype(jnp.float32)
        if beta is not None and not rms:
            y = y + beta.astype(jnp.float32)
    return y.astype(x2.dtype), mean, rstd


def _layer_norm_fwd(x2, gamma, beta, eps, rms, interpret, mem_eff=False):
    y, mean, rstd = _ln_fwd(x2, gamma, beta, eps, rms, interpret)
    if mem_eff:
        # keep the OUTPUT, drop the input: x can die after the forward
        # (apex memory_efficient=True residuals: output + invvar)
        return y, (y, gamma, beta, rstd)
    return y, (x2, gamma, mean, rstd)


def _finish_affine(dx, dg, db, gamma, rms, affine):
    if not affine:
        return dx, None, None
    dgamma = dg.astype(gamma.dtype)
    dbeta = None if rms else db.astype(gamma.dtype)
    return dx, dgamma, dbeta


def _layer_norm_bwd(eps, rms, interpret, mem_eff, res, dy):
    if mem_eff:
        src2, gamma, beta, rstd = res      # src = the saved OUTPUT y
        aux = beta
    else:
        src2, gamma, aux, rstd = res       # src = the saved input x, aux = mean
    n, h = src2.shape
    affine = gamma is not None
    if _pallas_ok(n, h, src2.dtype) or interpret:
        dx, dg, db = _ln_bwd_pallas(dy, src2, gamma, aux, rstd, rms,
                                    interpret, mem_eff=mem_eff)
    else:
        dy32 = dy.astype(jnp.float32)
        src32 = src2.astype(jnp.float32)
        if mem_eff:
            if affine:
                g32 = gamma.astype(jnp.float32)
                # bias may be None with a weight (public API allows it;
                # the Pallas branch zero-fills the same way)
                b32 = (beta.astype(jnp.float32)
                       if (beta is not None and not rms) else 0.0)
                xhat = (src32 - b32) / g32
            else:
                xhat = src32
        else:
            xhat = (src32 - aux) * rstd
        dyg = dy32 * gamma.astype(jnp.float32) if affine else dy32
        dx, dg_rows, db_rows = _bwd_from_xhat(dy32, xhat, dyg, rstd, rms)
        dx = dx.astype(src2.dtype)
        dg = jnp.sum(dg_rows, axis=0)
        db = jnp.sum(db_rows, axis=0)
    return _finish_affine(dx, dg, db, gamma, rms, affine)


_layer_norm.defvjp(_layer_norm_fwd, _layer_norm_bwd)


def layer_norm(x, weight: Optional[jnp.ndarray] = None,
               bias: Optional[jnp.ndarray] = None, eps: float = 1e-5,
               interpret: bool = False, memory_efficient: bool = False):
    """Fused layer norm over the last dim (apex FusedLayerNormAffineFunction).

    ``weight``/``bias`` of shape (H,) or None (non-affine variant,
    apex FusedLayerNormFunction). ``memory_efficient`` keeps the OUTPUT
    (not the input) for backward, reconstructing xhat=(y-beta)/gamma —
    apex's flag of the same name; requires nonzero gamma."""
    shape = x.shape
    h = shape[-1]
    x2 = x.reshape(-1, h)
    y = _layer_norm(x2, weight, bias, float(eps), False, interpret,
                    memory_efficient)
    return y.reshape(shape)


def rms_norm(x, weight: Optional[jnp.ndarray] = None, eps: float = 1e-5,
             interpret: bool = False, memory_efficient: bool = False):
    """Fused RMS norm (apex FusedRMSNormAffineFunction); see
    :func:`layer_norm` for ``memory_efficient``."""
    shape = x.shape
    h = shape[-1]
    x2 = x.reshape(-1, h)
    y = _layer_norm(x2, weight, None, float(eps), True, interpret,
                    memory_efficient)
    return y.reshape(shape)
