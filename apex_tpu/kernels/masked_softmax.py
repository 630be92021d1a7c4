"""Fused scale + arbitrary-mask + softmax Pallas kernel.

TPU-native equivalent of the reference's ``scaled_masked_softmax_cuda``
extension (apex/contrib → csrc/megatron/scaled_masked_softmax.h —
scaled_masked_softmax_warp_forward/backward; SURVEY N8 — this is the
SECOND kernel N8 names, the arbitrary-mask variant the padded-mask BERT
path hits; the causal one is kernels/causal_softmax.py). Semantics
preserved: half I/O allowed, softmax math in fp32, masked entries get the
additive ``-10000`` the CUDA kernel applies (probabilities underflow to
exactly zero in fp32 except for the degenerate all-masked row, which —
like the reference kernel — softmaxes to uniform).

Layout: rows ride a (batch, q-block) grid with the full key row block and
its MASK TILE in VMEM (same layout as causal_softmax; no tile-skip is
possible for arbitrary masks — the CUDA generic kernel also walks full
rows). The mask rides its own BlockSpec whose index map folds the
reference's broadcast pattern (mask ``[b, 1, sq, sk]`` against
``x [b, h, sq, sk]``): batch index ``i`` reads mask block ``i // rep``,
so the h-fold broadcast costs no HBM duplication.

Backward: dx = scale * p * (g - sum(g*p, -1)) — the CUDA backward's
formula, which does not re-apply the mask (masked p are exact zeros, so
masked dx are zeros, except in the all-masked-row corner where the CUDA
kernel also lets gradient flow).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.kernels import vmem

__all__ = ["masked_softmax", "masked_softmax_reference"]

_MASK_VALUE = -10000.0


def masked_softmax_reference(x, mask, scale: float = 1.0):
    """fp32 composed reference (the jnp fallback path). ``mask`` bool,
    True = masked out, broadcastable against x."""
    out_dtype = x.dtype
    x32 = jnp.asarray(x, jnp.float32) * scale
    if mask is not None:
        x32 = jnp.where(mask, _MASK_VALUE, x32)
    y = jnp.exp(x32 - jnp.max(x32, axis=-1, keepdims=True))
    y = y / jnp.sum(y, axis=-1, keepdims=True)
    return jnp.asarray(y, out_dtype)


def _fwd_kernel(x_ref, m_ref, out_ref, *, scale):
    x = x_ref[0].astype(jnp.float32) * scale          # [bq, sk]
    masked = m_ref[0] != 0
    x = jnp.where(masked, _MASK_VALUE, x)
    mx = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - mx)
    out_ref[0] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(
        out_ref.dtype)


def _bwd_kernel(p_ref, g_ref, out_ref, *, scale):
    p = p_ref[0].astype(jnp.float32)                  # [bq, sk]
    g = g_ref[0].astype(jnp.float32)
    dot = jnp.sum(g * p, axis=-1, keepdims=True)
    out_ref[0] = (scale * p * (g - dot)).astype(out_ref.dtype)


def _block_q(sq, sk):
    # fp32 row block + mask tile + ~3 temporaries
    return vmem.block_rows(sq, row_bytes=4 * sk, n_bufs=5, max_rows=128,
                           divisor_of=sq, key="masked_softmax.block_q")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _masked_softmax(x, mask_i8, scale, rep, interpret):
    out, _ = _masked_fwd(x, mask_i8, scale, rep, interpret)
    return out


def _masked_fwd(x, mask_i8, scale, rep, interpret):
    n, sq, sk = x.shape
    bq = _block_q(sq, sk)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(n, sq // bq),
        in_specs=[pl.BlockSpec((1, bq, sk), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((1, bq, sk),
                               lambda i, j: (i // rep, j, 0))],
        out_specs=pl.BlockSpec((1, bq, sk), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, sq, sk), x.dtype),
        interpret=interpret, name="masked_softmax_fwd",
    )(x, mask_i8)
    return out, out


def _masked_bwd(scale, rep, interpret, p, g):
    n, sq, sk = p.shape
    bq = _block_q(sq, sk)
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=(n, sq // bq),
        in_specs=[pl.BlockSpec((1, bq, sk), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((1, bq, sk), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((1, bq, sk), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, sq, sk), p.dtype),
        interpret=interpret, name="masked_softmax_bwd",
    )(p, g)
    return (dx, None)


_masked_softmax.defvjp(_masked_fwd, _masked_bwd)


def _broadcast_rep(x_shape, mask_shape):
    """rep such that flat batch i of x reads flat mask batch i // rep, or
    None when the broadcast pattern isn't prefix-contiguous."""
    lead_x = x_shape[:-2]
    lead_m = mask_shape[:-2]
    if len(lead_m) > len(lead_x):
        return None
    lead_m = (1,) * (len(lead_x) - len(lead_m)) + tuple(lead_m)
    seen_one = False
    rep = 1
    for dx, dm in zip(lead_x, lead_m):
        if dm == dx and not seen_one:
            continue
        if dm == 1:
            seen_one = True
            rep *= dx
            continue
        return None
    return rep


def masked_softmax(x, mask, scale: float = 1.0, interpret: bool = False):
    """probs = softmax(scale * x + (-10000 where mask)) over the last dim.

    ``x``: [..., sq, sk], half or fp32; ``mask``: bool (True = masked
    out), trailing dims (sq, sk), leading dims equal to x's or a prefix
    of them followed by 1s (the reference's [b, 1, sq, sk] head
    broadcast). Returns probs in the input dtype with fp32 softmax math.
    Unaligned shapes or non-prefix broadcasts fall back to the jnp
    reference.
    """
    if mask is None:
        return masked_softmax_reference(x, None, scale)
    shape = x.shape
    sq, sk = shape[-2], shape[-1]
    n = 1
    for s in shape[:-2]:
        n *= s
    rep = None
    if mask.shape[-2:] == (sq, sk):
        rep = _broadcast_rep(shape, mask.shape)
    aligned = sk % 128 == 0 and sq % 8 == 0
    if not aligned or rep is None:
        return masked_softmax_reference(x, mask, scale)
    if jax.default_backend() == "cpu":
        interpret = True
    nm = n // rep
    mask_i8 = jnp.asarray(mask, jnp.int8).reshape(nm, sq, sk)
    return _masked_softmax(x.reshape(n, sq, sk), mask_i8, scale, rep,
                           interpret).reshape(shape)
