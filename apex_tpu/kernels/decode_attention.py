"""Cached-K/V decode attention — the serving tier's single-token kernel.

Training attention (:mod:`apex_tpu.kernels.flash_attention`) answers
"every query attends to every earlier key"; decode answers a different
question: ONE new query per sequence against a **preallocated KV cache**
of which only the first ``lengths[b]`` positions are valid. This is the
same move the flash-attention kernel lineage makes from training kernels
to cached inference: the blockwise online-softmax inner loop is
unchanged, but the query block degenerates to a single row and the
causal-block skip becomes a *length* skip — KV blocks entirely past the
sequence's valid length are never touched, so a request of length 37 in
a 1024-slot cache pays for ceil(38/block_k) blocks, not 8.

Layouts (matching the serving cache, one slot per batch row):

- ``q``: ``[batch, heads, head_dim]`` — the current token's query.
- ``k``/``v``: ``[batch, heads, max_len, head_dim]`` — the cache view.
- ``lengths``: ``[batch]`` int32 — valid positions per row (the current
  token's K/V must already be written at ``lengths-1``).

Numerics follow the kernel tier's contract: fp32 accumulation regardless
of I/O dtype (the cache is normally bf16 via the amp cast policies), and
a pure-jnp reference that doubles as the CPU/unaligned fallback and the
test oracle. Rows with ``lengths == 0`` return zeros (a defined value for
inactive serving slots — their output is discarded by the engine).

Block geometry rides the shared tuned-override registry
(:mod:`apex_tpu.kernels.vmem`) under new ``decode.*`` keys:
``decode.block_k`` (KV positions per grid step, lane-multiple 128) here,
and ``decode.prefill_block_q``/``decode.prefill_block_k`` consumed by
``serving.Engine`` for its prefill flash-attention geometry (prefill
shapes — short sequences, single-request batch — want different blocks
than the training sweep).

**Paged variant** (:func:`paged_decode_attention`): the serving tier's
block-table refactor replaces the per-slot cache row with a dense pool
of fixed-size pages plus a ``[batch, max_pages]`` page table. The
kernel is the same online-softmax recurrence with ONE structural
change: the KV block index is no longer an affine function of the grid
position — block ``j`` of batch row ``b`` lives wherever
``page_table[b, j]`` says. Pallas expresses exactly that through
scalar-prefetch block index maps (``PrefetchScalarGridSpec``): the page
table rides SMEM ahead of the grid, and each (b, h, j) step DMAs pool
page ``page_table[b, j]`` instead of row offset ``j``. The length skip
is unchanged — pages wholly past ``lengths[b]`` are masked to the
sentinel page and their compute skipped.

**Tensor parallelism** (``serving.Engine(mesh=...)``): the kernels need
NO sharded variant. The grid iterates ``batch x heads`` (flattened to
``b*h`` rows here, an explicit heads dimension in the paged grid), so a
heads-sharded pool — ``[num_pages, heads/tp, page_len, head_dim]`` per
shard, the serving tier's TP layout — simply hands each shard a grid
with fewer heads-axis blocks over its own pool slice: the index maps
never mix heads, every DMA stays shard-local, and the per-shard math is
bit-identical to the single-chip kernel over that head subset.
Attention therefore contributes ZERO collectives to the sharded serving
programs (the psums live in the projection GEMMs; see
:mod:`apex_tpu.serving.sharding`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels import mosaic_dtype_ok, vmem

__all__ = ["decode_attention", "decode_attention_reference",
           "paged_decode_attention", "paged_decode_attention_reference",
           "gather_pages"]

_NEG_INF = -1e30
DEFAULT_BLOCK_K = 256


# --------------------------------------------------------------- jnp reference
def _group_size(name, h, h_kv):
    """Query heads per K/V head (grouped-query attention): query head
    ``i`` reads K/V head ``i // G``. 1 is plain multi-head attention."""
    if h_kv < 1 or h % h_kv:
        raise ValueError(f"{name}: {h} query heads are not a multiple of "
                         f"{h_kv} K/V heads")
    return h // h_kv


def _repeat_kv_heads(h, k, v):
    """``k``/``v`` ``[b, h_kv, L, d]`` with each K/V head repeated for the
    query heads of its group (the references' way; the kernels index)."""
    G = h // k.shape[1]
    if G == 1:
        return k, v
    return jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)


def decode_attention_reference(q, k, v, lengths, *, scale: float = 1.0,
                               k_scale=None, v_scale=None):
    """fp32-math oracle: masked softmax over the valid cache prefix.

    ``q`` [b, h, d]; ``k``/``v`` [b, h_kv, L, d] with ``h_kv`` dividing
    ``h`` (grouped heads: query head ``i`` reads K/V head ``i // (h //
    h_kv)``); ``lengths`` [b] int32.
    Returns [b, h, d] in ``q.dtype``; rows with ``lengths == 0`` are 0.
    ``k_scale``/``v_scale`` ([h] fp32) are the quantized-cache tier's
    per-head dequantization scales: when given, ``k``/``v`` hold int8
    codes and are dequantized (cast + scale multiply) before the exact
    fp32 math — the gather-dequant oracle the in-kernel path is tested
    against.
    """
    out_dtype = q.dtype
    q32, k32, v32 = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    if k_scale is not None:
        k32 = k32 * jnp.asarray(k_scale, jnp.float32)[None, :, None, None]
    if v_scale is not None:
        v32 = v32 * jnp.asarray(v_scale, jnp.float32)[None, :, None, None]
    k32, v32 = _repeat_kv_heads(q.shape[1], k32, v32)
    s = jnp.einsum("bhd,bhld->bhl", q32, k32) * scale
    L = k.shape[2]
    valid = (jnp.arange(L, dtype=jnp.int32)[None, None, :]
             < lengths[:, None, None])
    s = jnp.where(valid, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhl,bhld->bhd", p, v32)
    live = (lengths > 0)[:, None, None]
    return jnp.asarray(jnp.where(live, out, 0.0), out_dtype)


# -------------------------------------------------------------------- kernel
def _decode_kernel(len_ref, *refs, scale, block_k, quant, G=1):
    """Grid (bh, nk): one batch·K/V-head row, blockwise over cached KV;
    the ``G`` query heads of the row's group are the rows of one
    ``[G, d] x [d, block_k]`` product against the one fetched block
    (``G`` = 1: plain multi-head attention, one query row).

    Online softmax identical to the training forward kernel's (m, l)
    recurrence, with the causal tile-skip replaced by a length skip:
    a block whose first position is already past this row's valid
    length contributes nothing and is skipped entirely.

    ``quant`` (static) threads the int8-cache tier through: two extra
    SMEM refs carry the per-row K/V dequantization scales, the K scale
    folds into the existing logit multiply and the V scale into the
    accumulator update — dequantization fused with the attend, the
    int8 block never expanding outside VMEM. The non-quant trace is
    byte-identical to before the tier existed.
    """
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, \
            l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    length = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(ki * block_k < length)
    def _body():
        q = q_ref[0].astype(jnp.float32)                      # [G, d]
        k = k_ref[0].astype(jnp.float32)                      # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [G, bk]
        if quant:
            # dequant-in-kernel: the per-head K scale is constant over
            # the row, so it factors out of the int8 dot product
            s = s * ks_ref[b]
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(cols < length, s, _NEG_INF)
        m_prev = m_ref[:G, :1]                                # [G, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # [G, bk]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:G, :1] = alpha * l_ref[:G, :1] + jnp.sum(
            p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quant:
            pv = pv * vs_ref[b]
        acc_ref[:G, :] = acc_ref[:G, :] * alpha + pv
        m_ref[:G, :1] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:G, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:G, :] / l_safe).astype(o_ref.dtype)


def _scratch_rows(G):
    """Sublane rows of the (acc, m, l) scratch: the group's ``G`` live
    rows rounded up to a whole tile of 8."""
    return -(-G // 8) * 8


def _decode_pallas(q3, k3, v3, len3, scale, bk, interpret, ks3=None,
                   vs3=None):
    bh, G, d = q3.shape              # rows: batch x K/V heads; G per group
    L = k3.shape[1]
    quant = ks3 is not None
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=bk,
                               quant=quant, G=G)
    R = _scratch_rows(G)
    scale_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2 \
        if quant else []
    scale_ops = (ks3, vs3) if quant else ()
    out = pl.pallas_call(
        kernel,
        grid=(bh, L // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # lengths
            *scale_specs,                         # k/v dequant scales
            pl.BlockSpec((1, G, d), lambda b, j: (b, 0, 0)),      # q
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),     # k
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),     # v
        ],
        out_specs=pl.BlockSpec((1, G, d), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, G, d), q3.dtype),
        scratch_shapes=[
            pltpu.VMEM((R, d), jnp.float32),      # acc (rows :G live)
            pltpu.VMEM((R, 128), jnp.float32),    # m
            pltpu.VMEM((R, 128), jnp.float32),    # l
        ],
        interpret=interpret, name="decode_attention",
    )(len3, *scale_ops, q3, k3, v3)
    return out


# ------------------------------------------------------------------ dispatch
def _resolve_block(block_k):
    if block_k is None:
        block_k = vmem.get_override("decode.block_k", DEFAULT_BLOCK_K,
                                    multiple=128)
    return block_k


def _check_head_scales(name, h, k_scale, v_scale):
    """Quantized-cache scale validation shared by the four dispatchers:
    scales come as a pair of [heads] fp32 vectors or not at all."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError(f"{name}: k_scale and v_scale must be given "
                         f"together (int8 K and V are stored with "
                         f"independent per-head scales)")
    if k_scale is not None:
        for nm, s in (("k_scale", k_scale), ("v_scale", v_scale)):
            if s.shape != (h,):
                raise ValueError(f"{name}: {nm} {s.shape} must be "
                                 f"[{h}] (one scale per head)")


def decode_attention(q, k, v, lengths, *, scale: Optional[float] = None,
                     block_k: Optional[int] = None,
                     k_scale=None, v_scale=None,
                     interpret: bool = False):
    """Single-token attention against a length-masked KV cache.

    ``q`` [batch, heads, head_dim]; ``k``/``v`` [batch, kv_heads, max_len,
    head_dim] (the serving cache's per-layer view; ``kv_heads`` divides
    ``heads`` — grouped-query attention, query head ``i`` reading K/V
    head ``i // (heads // kv_heads)``, the group's query heads sharing
    each fetched block); ``lengths`` [batch]
    int32 — positions ``[0, lengths[b])`` are attended, everything past
    is masked. The current token's own K/V must already be written at
    position ``lengths[b] - 1`` (the serving engine's write-then-attend
    order). ``scale`` defaults to ``1/sqrt(head_dim)``.

    Inference-only (no VJP — decode never backprops). The Pallas path
    skips KV blocks past ``lengths[b]`` entirely, so short sequences in
    a long cache cost O(length), not O(max_len); unaligned shapes and
    non-Mosaic dtypes fall back to the jnp reference, which XLA fuses
    acceptably at decode's tiny per-step footprint.

    Quantized cache (``k_scale``/``v_scale``, both ``[heads]`` fp32):
    ``k``/``v`` hold int8 codes dequantized IN-KERNEL — the K scale
    rides the logit multiply, the V scale the accumulator update — so
    the half-width cache bytes stream through VMEM and never expand in
    HBM. The fallback path dequantizes in the jnp oracle instead (same
    math, materialised).

    Tuned geometry: ``decode.block_k`` in the
    :mod:`apex_tpu.kernels.vmem` override registry (lane-multiple 128,
    clamped to the largest aligned divisor of ``max_len``).
    """
    b, h, d = q.shape
    h_kv, L = k.shape[1], k.shape[2]
    if k.shape != (b, h_kv, L, d) or v.shape != k.shape:
        raise ValueError(f"decode_attention: k/v {k.shape}/{v.shape} do "
                         f"not match q {q.shape} + max_len")
    G = _group_size("decode_attention", h, h_kv)
    if lengths.shape != (b,):
        raise ValueError(f"decode_attention: lengths {lengths.shape} must "
                         f"be [{b}]")
    _check_head_scales("decode_attention", h_kv, k_scale, v_scale)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    from apex_tpu.kernels.flash_attention import _fit_block, _has_vma
    bk = _fit_block(_resolve_block(block_k), L, 128)
    if jax.default_backend() == "cpu":
        interpret = True
    pallas_ok = (L % bk == 0 and d % 8 == 0 and bk % 128 == 0)
    if not pallas_ok or (interpret and _has_vma(q)) \
            or (not interpret and not mosaic_dtype_ok(q, k, v)):
        return decode_attention_reference(q, k, v, lengths, scale=scale,
                                          k_scale=k_scale,
                                          v_scale=v_scale)
    q3 = q.reshape(b * h_kv, G, d)
    k3 = k.reshape(b * h_kv, L, d)
    v3 = v.reshape(b * h_kv, L, d)
    len3 = jnp.repeat(jnp.asarray(lengths, jnp.int32), h_kv)
    ks3 = vs3 = None
    if k_scale is not None:
        # flattened bh rows walk heads fastest: row b*h + hh -> head hh
        ks3 = jnp.tile(jnp.asarray(k_scale, jnp.float32), b)
        vs3 = jnp.tile(jnp.asarray(v_scale, jnp.float32), b)
    out = _decode_pallas(q3, k3, v3, len3, scale, bk, interpret, ks3,
                         vs3)
    live = (lengths > 0)[:, None, None]
    return jnp.where(live, out.reshape(b, h, d), 0).astype(q.dtype)


# ------------------------------------------------------------ paged variant
def _layer_pool_shape(name, k_pool, v_pool, layer):
    """One layer's ``(num_pages, heads, page_len, head_dim)`` of a paged
    pool handed over in either of its two forms — shared by the two
    paged dispatchers:

    - one layer alone, ``[num_pages, heads, page_len, head_dim]``
      (``layer=None``);
    - the serving engine's whole STACKED pool ``[layers, num_pages,
      heads, head_dim, page_len]`` with the static ``layer`` to read.
      Its pages are held transposed (``page_len`` is the lane
      dimension) because that is the one form the chip stores without
      padding AND the kernels' page DMA reads as it lies: with
      ``head_dim`` 64 in the lanes a row-major page pads every tile to
      128, so the compiler keeps such a pool the other way round and
      relays every layer of it out (and back) around each kernel call —
      the pool-sized copies that were three quarters of a decode step.
    """
    if v_pool.shape != k_pool.shape or k_pool.ndim not in (4, 5):
        raise ValueError(f"{name}: pools {k_pool.shape}/{v_pool.shape} "
                         f"must be equal-shaped [num_pages, heads, "
                         f"page_len, head_dim], or stacked [layers, "
                         f"num_pages, heads, head_dim, page_len]")
    if (k_pool.ndim == 5) != (layer is not None):
        raise ValueError(f"{name}: layer={layer!r} with a "
                         f"{k_pool.ndim}-D pool; a stacked "
                         f"[layers, num_pages, ...] pool takes the layer "
                         f"to read, a single layer's pool takes none")
    if layer is None:
        return k_pool.shape
    if not 0 <= int(layer) < k_pool.shape[0]:
        raise ValueError(f"{name}: layer {layer} outside the pool's "
                         f"{k_pool.shape[0]} layers")
    _, P, h, d, page_len = k_pool.shape
    return P, h, page_len, d


def _page_block_spec(page_len, d, page_idx, layer):
    """The K/V ``BlockSpec`` of the two paged kernels: one pool page of
    one head, chosen by the scalar-prefetch index map ``page_idx``. On a
    stacked pool the static ``layer`` is one more (squeezed) block index
    in front and the page arrives as it is stored, ``[d, page_len]``
    (the kernel bodies' ``kt`` form): it is DMA'd out of the pool where
    it lives, and no layer of the pool is ever sliced out in HBM."""
    if layer is None:
        return pl.BlockSpec((1, 1, page_len, d), page_idx)
    return pl.BlockSpec((None, 1, 1, d, page_len),
                        lambda *a: (layer,) + page_idx(*a))


def _page_dots(kt: bool):
    """``dot_general`` dimension numbers of a page's two products, for a
    page held ``[page_len, d]`` or (``kt``, the stacked pool's form)
    ``[d, page_len]``: (q·K^T contracting ``d``, p·V contracting
    ``page_len``). Same products either way; only which axis of the
    page block is contracted moves."""
    if kt:
        return (((1,), (0,)), ((), ())), (((1,), (1,)), ((), ()))
    return (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ()))


def gather_pages(pool, page_table, layer=None):
    """Materialise a contiguous per-row cache view from a paged pool:
    ``pool`` [num_pages, heads, page_len, d] + ``page_table``
    [batch, max_pages] int32 -> [batch, heads, max_pages * page_len, d].
    With ``layer`` the pool is the stacked ``[layers, num_pages, heads,
    d, page_len]`` one (see :func:`_layer_pool_shape`) and only that
    layer's pages are gathered, straight out of it.

    The paged kernels' oracle building block (and the CPU/unaligned
    fallback's first step): positions ``[j*page_len, (j+1)*page_len)``
    of row ``b`` are pool page ``page_table[b, j]``. Entries past a
    row's allocated pages point at the sentinel page — garbage the
    length/causal masks keep out of every softmax."""
    B, P = page_table.shape
    if layer is None:
        h, page_len, d = pool.shape[1:]
        gathered = pool[page_table]          # [B, P, h, page_len, d]
        return gathered.transpose(0, 2, 1, 3, 4).reshape(
            B, h, P * page_len, d)
    h, d, page_len = pool.shape[2:]
    gathered = pool[layer, page_table]       # [B, P, h, d, page_len]
    return gathered.transpose(0, 2, 1, 4, 3).reshape(
        B, h, P * page_len, d)


def paged_decode_attention_reference(q, k_pool, v_pool, page_table,
                                     lengths, *, scale: float = 1.0,
                                     k_scale=None, v_scale=None,
                                     layer=None):
    """fp32-math oracle: gather the page-table view, then the exact
    contiguous decode reference. ``q`` [b, h, d]; pools
    [num_pages, h, page_len, d] (or the stacked pool, with ``layer``);
    ``page_table`` [b, max_pages];
    ``lengths`` [b] int32. With ``k_scale``/``v_scale`` ([h] fp32) the
    gathered int8 pages are dequantized before the exact math — the
    gather-dequant oracle of the quantized-cache tier."""
    k = gather_pages(k_pool, page_table, layer)
    v = gather_pages(v_pool, page_table, layer)
    return decode_attention_reference(q, k, v, lengths, scale=scale,
                                      k_scale=k_scale, v_scale=v_scale)


def _paged_decode_kernel(pt_ref, len_ref, *refs, scale, page_len, quant,
                         kt=False, G=1):
    """Grid (b, h_kv, max_pages): one batch row x K/V head, one pool page
    per step; the ``G`` query heads of the head's group are the rows of
    one ``[G, d] x [d, page_len]`` product against the one fetched page
    (``G`` = 1: plain multi-head attention, one query row). The (m, l) recurrence is :func:`_decode_kernel`'s; the page
    the DMA fetched was chosen by the scalar-prefetch index map
    (``pt_ref[b, j]``), so the kernel body only needs the length skip/
    mask on GLOBAL positions ``j * page_len + lane``. ``quant``
    (static) adds two scalar-prefetch scale refs and the same fused
    per-head dequant multiplies as :func:`_decode_kernel`. ``kt``
    (static): the page blocks are ``[d, page_len]``, the stacked pool's
    form — only the contracted axis of the two products moves."""
    qk_dims, pv_dims = _page_dots(kt)
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, \
            l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    hh = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(j * page_len < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                   # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)       # [pl, d] (kt: [d, pl])
        s = jax.lax.dot_general(
            q, k, qk_dims,
            preferred_element_type=jnp.float32) * scale       # [G, pl]
        if quant:
            s = s * ks_ref[hh]
        cols = j * page_len + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_len), 1)
        s = jnp.where(cols < length, s, _NEG_INF)
        m_prev = m_ref[:G, :1]                                # [G, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # [G, pl]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:G, :1] = alpha * l_ref[:G, :1] + jnp.sum(
            p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), pv_dims,
            preferred_element_type=jnp.float32)
        if quant:
            pv = pv * vs_ref[hh]
        acc_ref[:G, :] = acc_ref[:G, :] * alpha + pv
        m_ref[:G, :1] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:G, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:G, :] / l_safe).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pool, v_pool, pt, lengths, scale,
                         interpret, ks=None, vs=None, layer=None):
    B, h, d = q.shape
    kt = layer is not None           # stacked pool: pages [d, page_len]
    page_len = k_pool.shape[-1 if kt else -2]
    h_kv = k_pool.shape[2 if kt else 1]
    G = h // h_kv                    # query heads per K/V head
    R = _scratch_rows(G)
    max_pages = pt.shape[1]
    quant = ks is not None
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               page_len=page_len, quant=quant, kt=kt, G=G)
    # the dequant scales ride as two extra scalar-prefetch operands (the
    # variadic tail absorbs them — only the kernel body reads them).
    # q/out carry the group's rows as their own axis ([B, h_kv, G, d];
    # G = 1 is a unit row axis): Mosaic wants a block's last two dims to
    # tile (8, 128) or EQUAL the array's, and a (G, d) block over
    # [.., G, d] is the latter where (1, d) over [.., h, d] is neither
    # (the contiguous kernel's [bh, G, d] does the same).
    def _q_idx(b, hh, j, pt, ln, *_scales):
        return (b, hh, 0, 0)

    def _kv_idx(b, hh, j, pt, ln, *_scales):
        return (pt[b, j], hh, 0, 0)

    kv_spec = _page_block_spec(page_len, d, _kv_idx, layer)
    n_prefetch, extra_ops = (4, (ks, vs)) if quant else (2, ())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,   # page_table, lengths[, ks, vs]
        grid=(B, h_kv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), _q_idx),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, G, d), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((R, d), jnp.float32),      # acc (rows :G live)
            pltpu.VMEM((R, 128), jnp.float32),    # m
            pltpu.VMEM((R, 128), jnp.float32),    # l
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h_kv, G, d), q.dtype),
        interpret=interpret, name="paged_decode_attention",
    )(pt, lengths, *extra_ops, q.reshape(B, h_kv, G, d), k_pool, v_pool)
    return out.reshape(B, h, d)


def paged_decode_attention(q, k_pool, v_pool, page_table, lengths, *,
                           scale: Optional[float] = None,
                           k_scale=None, v_scale=None,
                           layer: Optional[int] = None,
                           interpret: bool = False):
    """Single-token attention against a PAGED, length-masked KV pool.

    ``q`` [batch, heads, head_dim]; ``k_pool``/``v_pool``
    [num_pages, kv_heads, page_len, head_dim] (one layer of the serving
    pool — pages are shared across batch rows; ``kv_heads`` divides
    ``heads``: grouped-query attention, query head ``i`` reading K/V
    head ``i // (heads // kv_heads)``, a group's query heads sharing ONE
    fetch of each page), or the whole stacked
    pool [layers, num_pages, kv_heads, head_dim, page_len] with the static
    ``layer`` to attend (the serving engine's form, pages transposed:
    :func:`_layer_pool_shape` says why): the layer is then one more
    block index of the page DMA, so the serving programs hand over the
    pool they write in place and never slice a layer out of it;
    ``page_table``
    [batch, max_pages] int32 maps row ``b``'s logical block ``j`` to a
    pool page (sentinel ids for unallocated blocks — masked, never
    attended); ``lengths`` [batch] int32 as in
    :func:`decode_attention`. The current token's K/V must already be
    written at logical position ``lengths[b] - 1`` of its row's pages.
    ``scale`` defaults to ``1/sqrt(head_dim)``.

    Inference-only. The Pallas path walks each row's page list through
    scalar-prefetch index maps — one pool-page DMA per grid step, with
    pages past ``lengths[b]`` skipping their compute — so a short
    request in a big pool costs O(length) MXU work exactly like the
    contiguous kernel, while the pool itself stays dense and shared.
    Unaligned shapes and non-Mosaic dtypes fall back to the
    gather-then-reference oracle.
    """
    B, h, d = q.shape
    P, hp, page_len, dp = _layer_pool_shape("paged_decode_attention",
                                            k_pool, v_pool, layer)
    if dp != d:
        raise ValueError(f"paged_decode_attention: pools "
                         f"{k_pool.shape}/{v_pool.shape} do not match q "
                         f"{q.shape}")
    _group_size("paged_decode_attention", h, hp)
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(f"paged_decode_attention: page_table "
                         f"{page_table.shape} must be [{B}, max_pages]")
    if lengths.shape != (B,):
        raise ValueError(f"paged_decode_attention: lengths "
                         f"{lengths.shape} must be [{B}]")
    _check_head_scales("paged_decode_attention", hp, k_scale, v_scale)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    from apex_tpu.kernels.flash_attention import _has_vma
    if jax.default_backend() == "cpu":
        interpret = True
    pallas_ok = (d % 8 == 0 and page_len % 128 == 0)
    if not pallas_ok or (interpret and _has_vma(q)) \
            or (not interpret and not mosaic_dtype_ok(q, k_pool, v_pool)):
        return paged_decode_attention_reference(
            q, k_pool, v_pool, page_table, lengths, scale=scale,
            k_scale=k_scale, v_scale=v_scale, layer=layer)
    pt = jnp.asarray(page_table, jnp.int32)
    len32 = jnp.asarray(lengths, jnp.int32)
    ks = vs = None
    if k_scale is not None:
        ks = jnp.asarray(k_scale, jnp.float32)
        vs = jnp.asarray(v_scale, jnp.float32)
    out = _paged_decode_pallas(q, k_pool, v_pool, pt, len32, scale,
                               interpret, ks, vs, layer)
    live = (lengths > 0)[:, None, None]
    return jnp.where(live, out, 0).astype(q.dtype)
