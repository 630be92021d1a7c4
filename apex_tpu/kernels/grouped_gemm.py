"""Grouped GEMM over token rows sorted by expert — the drop-nothing
expert layer's matrix product (:func:`apex_tpu.transformer.moe
.dropless_top1_experts`).

``x`` ``[M, K]`` holds the tokens sorted by the expert they chose;
``w`` ``[G, K, N]`` the weights of the ``G`` experts HELD here (all of
them, or one chip's share); ``starts``/``ends`` ``[G]`` int32 name each
held expert's row range ``[starts[g], ends[g])`` in ``x``. Row ``i`` of
the result is ``x[i] @ w[g]`` for the held expert whose range holds it,
and zero for a row no held expert owns (a token routed to an expert
that lives on another chip: its part of the result is that chip's to
give). Ranges may be empty and of any unevenness; no capacity exists and
no row is dropped.

The serving shapes are SMALL in ``M`` (96 tokens a decode beat, 256 a
chunk) and LARGE in weights (16 experts x 2048 x 4096): the product is
bound by streaming every held expert's weights through VMEM once, so
the kernel is built around that stream. Grid ``(N / tn, G, K / tk)``:
the whole sorted token block ``[M, tk]`` and the output block ``[M,
tn]`` stay resident while the ``g`` axis walks the experts; each step
DMAs one ``[tk, tn]`` weight tile and multiplies only the row tiles
(``tm`` = 128 rows, the MXU's height) that overlap the expert's range,
then stores the range's rows under a mask. Every weight byte crosses
HBM once per call whatever the routing; an expert with no token costs
its DMA and no MXU work.

fp32 accumulation regardless of I/O dtype; a pure-jnp reference doubles
as the oracle and as the fallback for shapes the kernel's tiling does
not take (``K`` or ``N`` not a multiple of 128).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels import mosaic_dtype_ok, vmem

__all__ = ["grouped_gemm", "grouped_gemm_reference", "group_ranges"]

KERNEL_NAME = "moe_grouped_gemm"
ROW_TILE = 128
DEFAULT_BLOCK_K = 2048
DEFAULT_BLOCK_N = 512


def group_ranges(choice, num_experts: int, held=None):
    """Row ranges of tokens SORTED by ``choice`` ``[T]``: ``(sizes [E],
    starts [G], ends [G])`` with ``G`` the ``held`` experts (a static
    tuple of expert ids; None = all ``E``)."""
    sizes = jnp.zeros((num_experts,), jnp.int32).at[choice].add(1)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    if held is not None:
        idx = jnp.asarray(held, jnp.int32)
        starts, ends = starts[idx], ends[idx]
    return sizes, starts, ends


def grouped_gemm_reference(x, w, starts, ends, *, out_dtype=None):
    """The one-hot dense form: every held expert's product over every
    row, masked to its range. ``O(G * M * K * N)`` — the oracle and the
    small-shape fallback."""
    out_dtype = out_dtype or x.dtype
    rows = jnp.arange(x.shape[0], dtype=jnp.int32)[:, None]
    if jax.default_backend() == "cpu":   # its dot takes no bf16 x bf16 -> f32
        x, w = jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32)
    full = jnp.einsum("mk,gkn->gmn", x, w,
                      preferred_element_type=jnp.float32)
    mask = (rows[None] >= starts[:, None, None]) \
        & (rows[None] < ends[:, None, None])
    return jnp.sum(jnp.where(mask, full, 0.0), 0).astype(out_dtype)


def _kernel(starts_ref, ends_ref, x_ref, w_ref, o_ref, acc_ref, *, tm,
            n_row_tiles, nk, widen):
    # ``widen`` (interpret mode): the CPU's dot takes no bf16 x bf16 ->
    # f32, so the operands are widened first; the MXU takes them as are
    wide = (lambda t: t.astype(jnp.float32)) if widen else (lambda t: t)
    g = pl.program_id(1)
    ki = pl.program_id(2)
    start, end = starts_ref[g], ends_ref[g]

    @pl.when((g == 0) & (ki == 0))
    def _zero_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    for t in range(n_row_tiles):                     # static: M / tm tiles
        lo = t * tm

        @pl.when((start < lo + tm) & (end > lo) & (end > start))
        def _tile(lo=lo):
            prod = jax.lax.dot_general(
                wide(x_ref[lo:lo + tm, :]), wide(w_ref[...]),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [tm, tn]
            def _store(val):
                rows = lo + jax.lax.broadcasted_iota(jnp.int32, val.shape, 0)
                mine = (rows >= start) & (rows < end)
                o_ref[lo:lo + tm, :] = jnp.where(
                    mine, val.astype(o_ref.dtype), o_ref[lo:lo + tm, :])

            if nk == 1:
                _store(prod)
                return

            @pl.when(ki == 0)
            def _first():
                acc_ref[lo:lo + tm, :] = prod

            @pl.when(ki > 0)
            def _rest():
                acc_ref[lo:lo + tm, :] += prod

            @pl.when(ki == nk - 1)
            def _last():
                _store(acc_ref[lo:lo + tm, :])


def _pallas(x, w, starts, ends, tm, tk, tn, out_dtype, interpret):
    M, K = x.shape
    G, _, N = w.shape
    nk = K // tk
    kernel = functools.partial(_kernel, tm=tm, n_row_tiles=M // tm, nk=nk,
                               widen=interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                        # starts, ends
        grid=(N // tn, G, nk),
        in_specs=[
            pl.BlockSpec((M, tk), lambda n, g, k, s, e: (0, k)),
            pl.BlockSpec((None, tk, tn), lambda n, g, k, s, e: (g, k, n)),
        ],
        out_specs=pl.BlockSpec((M, tn), lambda n, g, k, s, e: (0, n)),
        scratch_shapes=[pltpu.VMEM((M if nk > 1 else 8, tn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=KERNEL_NAME,
    )(starts, ends, x, w)


def _fit(block: int, size: int) -> int:
    """The largest multiple of 128 that is at most ``block`` and divides
    ``size`` (itself a multiple of 128)."""
    block = max(128, min(block, size) // 128 * 128)
    while size % block:
        block -= 128
    return block


def grouped_gemm(x, w, starts, ends, *, out_dtype=None,
                 block_k: Optional[int] = None,
                 block_n: Optional[int] = None, interpret: bool = False):
    """``out[i] = x[i] @ w[g]`` for ``starts[g] <= i < ends[g]``, zero
    elsewhere (module docstring). ``x`` ``[M, K]``, ``w`` ``[G, K, N]``,
    ``starts``/``ends`` ``[G]`` int32; returns ``[M, N]`` in
    ``out_dtype`` (default ``x.dtype``).

    Tuned geometry: ``moe.block_k`` / ``moe.block_n`` in the
    :mod:`apex_tpu.kernels.vmem` override registry (multiples of 128,
    clamped to divisors of ``K`` / ``N``). ``M`` is padded to the row
    tile inside (16 rows up to 128, then whole tiles of 128)."""
    M, K = x.shape
    G, Kw, N = w.shape
    if Kw != K or starts.shape != (G,) or ends.shape != (G,):
        raise ValueError(f"grouped_gemm: x {x.shape}, w {w.shape}, ranges "
                         f"{starts.shape}/{ends.shape} do not agree")
    out_dtype = out_dtype or x.dtype
    if jax.default_backend() == "cpu":
        interpret = True
    from apex_tpu.kernels.flash_attention import _has_vma
    if K % 128 or N % 128 or (interpret and _has_vma(x)) \
            or (not interpret and not mosaic_dtype_ok(x, w)):
        return grouped_gemm_reference(x, w, starts, ends,
                                      out_dtype=out_dtype)
    if block_k is None:
        block_k = vmem.get_override("moe.block_k", DEFAULT_BLOCK_K,
                                    multiple=128)
    if block_n is None:
        block_n = vmem.get_override("moe.block_n", DEFAULT_BLOCK_N,
                                    multiple=128)
    tm = ROW_TILE if M > ROW_TILE else -(-M // 16) * 16
    Mp = -(-M // tm) * tm
    # the token block [Mp, tk] and the output block [Mp, tn] are resident:
    # a tall M (a monolithic prefill) takes narrower tiles to stay in VMEM
    cap = lambda b, budget: min(b, max(128, budget // Mp // 128 * 128))  # noqa: E731,E501
    tk = _fit(cap(block_k, 1 << 20), K)
    tn = _fit(cap(block_n, 1 << 19), N)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    out = _pallas(x, w, jnp.asarray(starts, jnp.int32),
                  jnp.asarray(ends, jnp.int32), tm, tk, tn, out_dtype,
                  interpret)
    return out[:M] if Mp != M else out
