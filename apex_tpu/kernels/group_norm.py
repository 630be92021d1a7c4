"""NHWC GroupNorm (+ fused SiLU) Pallas kernels.

TPU-native equivalent of the reference's ``group_norm_cuda`` extension
(apex/contrib/csrc/group_norm/group_norm_nhwc_fwd/bwd_*.cu — SURVEY N23:
NHWC GroupNorm with fused SiLU for diffusion UNets). Design:

- NHWC is the TPU-native layout: channels ride the LANE dimension, spatial
  rows the sublane/grid dimensions. Nothing is ever transposed.
- Stats are two-pass like the CUDA kernels (sum-pass → normalize-pass):
  a per-(sample, channel) (sum, sumsq) reduction kernel accumulates across
  spatial blocks (the LN kernel's grid-revisited-accumulator pattern), the
  tiny [N, C] → [N, G] group combine happens in plain jnp between passes,
  and the normalize kernel applies per-channel (mean, rstd, gamma, beta)
  with the SiLU epilogue fused — one VMEM round trip each pass.
- Backward mirrors it: one reduction kernel produces the per-(n, c) sums
  that yield BOTH the group terms (c1, c2) and, summed over n, dgamma /
  dbeta; a second kernel computes dx. SiLU's chain rule re-derives z from
  (x, mean, rstd, gamma, beta) — residuals are just (x, mean, rstd), the
  reference's memory shape.

Channels not a lane multiple (C % 128 != 0, e.g. diffusion's 320) and
non-TPU backends use the jnp fallback (XLA fuses it well; the Pallas win
is the guaranteed two-pass HBM traffic).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels import vmem

__all__ = ["group_norm_nhwc", "group_norm_reference"]


def _silu(z):
    return z * jax.nn.sigmoid(z)


def _dsilu(z):
    s = jax.nn.sigmoid(z)
    return s * (1.0 + z * (1.0 - s))


def group_norm_reference(x, num_groups: int, weight=None, bias=None,
                         eps: float = 1e-5, act: Optional[str] = None):
    """fp32 composed oracle (and the fallback path). x: [N, H, W, C] or
    [N, S, C]."""
    if act not in (None, "", "identity", "silu"):
        raise ValueError(f"unsupported act {act!r}")
    act = act if act == "silu" else None
    shape = x.shape
    n, c = shape[0], shape[-1]
    x32 = jnp.asarray(x, jnp.float32).reshape(n, -1, num_groups,
                                              c // num_groups)
    mean = jnp.mean(x32, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=(1, 3), keepdims=True)
    y = ((x32 - mean) * jax.lax.rsqrt(var + eps)).reshape(n, -1, c)
    if weight is not None:
        y = y * jnp.asarray(weight, jnp.float32)
    if bias is not None:
        y = y + jnp.asarray(bias, jnp.float32)
    if act == "silu":
        y = _silu(y)
    return jnp.asarray(y, x.dtype).reshape(shape)


# ------------------------------------------------------------------ kernels
def _stats_kernel(x_ref, mean_ref, m2_ref, *, bs, s):
    """Per-(n, channel) running (mean, M2) via Chan's parallel combine —
    the numerically stable form (csrc/welford.cu — welford_parallel_CUDA);
    a sum/sumsq formulation cancels catastrophically for large-mean
    inputs. Padded tail rows are masked out of the block statistics."""
    j = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)                    # [bs, C]
    valid = jnp.minimum(bs, s - j * bs).astype(jnp.float32)
    mask = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            < valid.astype(jnp.int32))
    xm = jnp.where(mask, x, 0.0)
    bmean = jnp.sum(xm, axis=0, keepdims=True) / valid
    xc = jnp.where(mask, x - bmean, 0.0)
    bm2 = jnp.sum(xc * xc, axis=0, keepdims=True)

    @pl.when(j == 0)
    def _():
        mean_ref[0] = jnp.zeros_like(mean_ref[0])
        m2_ref[0] = jnp.zeros_like(m2_ref[0])

    na = (j * bs).astype(jnp.float32)
    delta = bmean - mean_ref[0]
    total = na + valid
    mean_ref[0] += delta * (valid / total)
    m2_ref[0] += bm2 + delta * delta * (na * valid / total)


def _norm_kernel(x_ref, mean_ref, rstd_ref, g_ref, b_ref, y_ref, *, act):
    x = x_ref[0].astype(jnp.float32)                    # [bs, C]
    z = (x - mean_ref[0]) * rstd_ref[0]
    z = z * g_ref[0] + b_ref[0]
    if act == "silu":
        z = _silu(z)
    y_ref[0] = z.astype(y_ref.dtype)


def _bwd_sums_kernel(dy_ref, x_ref, mean_ref, rstd_ref, g_ref, b_ref,
                     sdz_ref, sdzx_ref, *, act):
    j = pl.program_id(1)
    dy = dy_ref[0].astype(jnp.float32)
    x = x_ref[0].astype(jnp.float32)
    xhat = (x - mean_ref[0]) * rstd_ref[0]
    if act == "silu":
        z = xhat * g_ref[0] + b_ref[0]
        dy = dy * _dsilu(z)
    # dz = d(loss)/d(pre-activation affine output)

    @pl.when(j == 0)
    def _():
        sdz_ref[0] = jnp.zeros_like(sdz_ref[0])
        sdzx_ref[0] = jnp.zeros_like(sdzx_ref[0])

    sdz_ref[0] += jnp.sum(dy, axis=0, keepdims=True)
    sdzx_ref[0] += jnp.sum(dy * xhat, axis=0, keepdims=True)


def _bwd_dx_kernel(dy_ref, x_ref, mean_ref, rstd_ref, g_ref, b_ref,
                   c1_ref, c2_ref, dx_ref, *, act):
    dy = dy_ref[0].astype(jnp.float32)
    x = x_ref[0].astype(jnp.float32)
    xhat = (x - mean_ref[0]) * rstd_ref[0]
    if act == "silu":
        z = xhat * g_ref[0] + b_ref[0]
        dy = dy * _dsilu(z)
    dxhat = dy * g_ref[0]
    # dx = rstd * (dxhat - mean_g(dxhat) - xhat * mean_g(dxhat·xhat));
    # the per-group means arrive broadcast per channel as c1, c2
    dx = rstd_ref[0] * (dxhat - c1_ref[0] - xhat * c2_ref[0])
    dx_ref[0] = dx.astype(dx_ref.dtype)


# ------------------------------------------------------------------ plumbing
def _block_spatial(srows, c, nbufs, key="group_norm.block_spatial"):
    # fwd and bwd carry separate tuned keys: on v5e the forward wants the
    # largest block that fits (fewer grid steps over the Welford state)
    # while the backward — five live buffers and two reduction outputs —
    # prefers a small one (swept readings in BASELINE.md round-5 tier)
    return vmem.block_rows(srows, row_bytes=4 * c, n_bufs=nbufs,
                           max_rows=256, key=key)


def _pad_s(x3, sp):
    n, s, c = x3.shape
    if s == sp:
        return x3
    return jnp.pad(x3, ((0, 0), (0, sp - s), (0, 0)))


def _row_specs(count, bs, c):
    """count spatial-blocked [1, bs, C] input specs."""
    return [pl.BlockSpec((1, bs, c), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM) for _ in range(count)]


def _vec_spec(c):
    """per-sample [1, 1, C] row-vector spec (constant over j)."""
    return pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0),
                        memory_space=pltpu.VMEM)


def _group_stats(mean_c, m2_c, groups, s, eps):
    """Per-channel (mean, M2) with count s each → per-channel-broadcast
    group (mean, rstd) [N, 1, C], via Chan's combine across the group's
    channels (equal counts simplify it)."""
    n, c = mean_c.shape
    gc = c // groups
    mc = mean_c.reshape(n, groups, gc)
    mean_g = jnp.mean(mc, axis=-1)                           # [N, G]
    m2_g = jnp.sum(m2_c.reshape(n, groups, gc), axis=-1) \
        + s * jnp.sum(jnp.square(mc - mean_g[..., None]), axis=-1)
    var_g = m2_g / (s * gc)
    rstd_g = jax.lax.rsqrt(var_g + eps)
    rep = lambda a: jnp.repeat(a, gc, axis=-1).reshape(n, 1, c)
    return rep(mean_g), rep(rstd_g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _group_norm(x3, gamma, beta, groups, eps, act, interpret):
    y, _ = _gn_fwd(x3, gamma, beta, groups, eps, act, interpret)
    return y


def _gn_fwd(x3, gamma, beta, groups, eps, act, interpret):
    n, s, c = x3.shape
    bs = _block_spatial(s, c, 3)
    sp = ((s + bs - 1) // bs) * bs
    xp = _pad_s(x3, sp)
    grid = (n, sp // bs)
    mean_ch, m2_ch = pl.pallas_call(
        functools.partial(_stats_kernel, bs=bs, s=s),
        grid=grid,
        in_specs=_row_specs(1, bs, c),
        out_specs=[_vec_spec(c), _vec_spec(c)],
        out_shape=[jax.ShapeDtypeStruct((n, 1, c), jnp.float32)] * 2,
        interpret=interpret, name="group_norm_fwd_stats",
    )(xp)
    mean_c, rstd_c = _group_stats(mean_ch[:, 0], m2_ch[:, 0], groups, s,
                                  eps)
    g2 = gamma.astype(jnp.float32).reshape(1, 1, c)
    b2 = beta.astype(jnp.float32).reshape(1, 1, c)
    y = pl.pallas_call(
        functools.partial(_norm_kernel, act=act),
        grid=grid,
        in_specs=_row_specs(1, bs, c) + [
            _vec_spec(c), _vec_spec(c),
            pl.BlockSpec((1, 1, c), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, c), lambda i, j: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bs, c), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, sp, c), x3.dtype),
        interpret=interpret, name="group_norm_fwd",
    )(xp, mean_c, rstd_c, g2, b2)
    return y[:, :s], (x3, gamma, beta, mean_c, rstd_c)


def _gn_bwd(groups, eps, act, interpret, res, dy):
    x3, gamma, beta, mean_c, rstd_c = res
    n, s, c = x3.shape
    bs = _block_spatial(s, c, 5, key="group_norm.bwd_block_spatial")
    sp = ((s + bs - 1) // bs) * bs
    xp, dyp = _pad_s(x3, sp), _pad_s(dy, sp)
    grid = (n, sp // bs)
    g2 = gamma.astype(jnp.float32).reshape(1, 1, c)
    b2 = beta.astype(jnp.float32).reshape(1, 1, c)
    const_vec = pl.BlockSpec((1, 1, c), lambda i, j: (0, 0, 0),
                             memory_space=pltpu.VMEM)
    sdz, sdzx = pl.pallas_call(
        functools.partial(_bwd_sums_kernel, act=act),
        grid=grid,
        in_specs=_row_specs(2, bs, c) + [_vec_spec(c), _vec_spec(c),
                                         const_vec, const_vec],
        out_specs=[_vec_spec(c), _vec_spec(c)],
        out_shape=[jax.ShapeDtypeStruct((n, 1, c), jnp.float32)] * 2,
        interpret=interpret, name="group_norm_bwd_stats",
    )(dyp, xp, mean_c, rstd_c, g2, b2)
    sdz2, sdzx2 = sdz[:, 0], sdzx[:, 0]                     # [N, C]
    dgamma = jnp.sum(sdzx2, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(sdz2, axis=0).astype(beta.dtype)

    # group means of dxhat and dxhat·xhat, broadcast per channel. The sums
    # above are of dz (pre-affine grads); dxhat = dz * gamma, so fold gamma
    # in before the group reduction.
    m = s * (c // groups)
    gc = c // groups
    g32 = gamma.astype(jnp.float32)[None]                    # [1, C]
    c1_g = jnp.sum((sdz2 * g32).reshape(n, groups, gc), axis=-1) / m
    c2_g = jnp.sum((sdzx2 * g32).reshape(n, groups, gc), axis=-1) / m
    rep = lambda a: jnp.repeat(a, gc, axis=-1).reshape(n, 1, c)
    c1_c, c2_c = rep(c1_g), rep(c2_g)

    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, act=act),
        grid=grid,
        in_specs=_row_specs(2, bs, c) + [_vec_spec(c), _vec_spec(c),
                                         const_vec, const_vec,
                                         _vec_spec(c), _vec_spec(c)],
        out_specs=pl.BlockSpec((1, bs, c), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, sp, c), x3.dtype),
        interpret=interpret, name="group_norm_bwd",
    )(dyp, xp, mean_c, rstd_c, g2, b2, c1_c, c2_c)
    return dx[:, :s], dgamma, dbeta


_group_norm.defvjp(_gn_fwd, _gn_bwd)


def _pallas_ok(c, dtype=None):
    from . import mosaic_dtype_ok, on_tpu

    return on_tpu() and c % 128 == 0 and mosaic_dtype_ok(dtype)


def group_norm_nhwc(x, num_groups: int, weight=None, bias=None,
                    eps: float = 1e-5, act: Optional[str] = None,
                    interpret: bool = False):
    """Fused NHWC GroupNorm(+SiLU). x: [N, H, W, C] (or [N, S, C]);
    stats per (sample, group) in fp32 (reference: group_norm_nhwc kernels).

    Affine weight/bias are required for the Pallas path's fused backward
    (the reference kernels are affine-only too); pass None to use the
    composed fallback.
    """
    if act not in (None, "", "identity", "silu"):
        raise ValueError(f"unsupported act {act!r}")
    c = x.shape[-1]
    if c % num_groups:
        raise ValueError(
            f"channels {c} not divisible by groups {num_groups}")
    act = act if act == "silu" else None
    usable = weight is not None and bias is not None and \
        (_pallas_ok(c, x.dtype) or interpret)
    if not usable:
        return group_norm_reference(x, num_groups, weight, bias, eps, act)
    shape = x.shape
    x3 = x.reshape(shape[0], -1, c)
    y = _group_norm(x3, weight, bias, num_groups, float(eps), act,
                    interpret)
    return y.reshape(shape)
