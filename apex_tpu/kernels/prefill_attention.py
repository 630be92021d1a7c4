"""Chunked-prefill attention — a block of queries against the KV cache.

:mod:`~apex_tpu.kernels.decode_attention` answers "ONE new token per
sequence against the cached prefix"; chunked prefill (Sarathi-style)
asks the in-between question: a CHUNK of ``C`` consecutive prompt tokens
per sequence, already written into the cache at positions
``[offset, offset + C)``, each attending causally over everything before
and including itself. The serving engine runs one such chunk per decode
heartbeat, so in-flight decodes never wait for a whole prompt — the
monolithic ``[1, prefill_len]`` prefill's head-of-line blocking becomes
at most one chunk of latency.

Geometry is the flash kernel's blockwise online softmax with the causal
diagonal shifted by a per-row *cache offset*: query row ``i`` of batch
row ``b`` sits at global position ``offsets[b] + i`` and attends cache
positions ``[0, offsets[b] + i]``. KV blocks entirely past the chunk's
last query position skip their compute (the decode kernel's length
skip, lifted to a q-block × k-block skip), so an early chunk of a long
prompt pays MXU work for the prefix it can see, not for ``max_len``
(the block pipeline still streams the full cache row through VMEM —
bounding the DMA extent too needs a trace-time cap on offsets, a
future lever).

Layouts (matching the serving cache, one slot per batch row):

- ``q``: ``[batch, heads, C, d]`` — the chunk's queries.
- ``k``/``v``: ``[batch, heads, max_len, d]`` — the cache view; the
  chunk's own K/V must already be written at ``[offset, offset + C)``
  (the serving tier's write-then-attend order).
- ``offsets``: ``[batch]`` int32 — valid cache positions before the
  chunk (= the slot's pre-chunk length).

Numerics follow the kernel tier's contract: fp32 accumulation regardless
of I/O dtype, and a pure-jnp reference that doubles as the CPU/unaligned
fallback and the test oracle. Pad rows of a final partial chunk compute
garbage that the engine never samples from.

Block geometry rides the shared tuned-override registry
(:mod:`apex_tpu.kernels.vmem`) under ``decode.chunk_block_q``
(sublane-multiple 8) and ``decode.chunk_block_k`` (lane-multiple 128).

**Paged variant** (:func:`paged_prefill_attention`): the block-table
refactor's chunk-ingestion kernel. Same shifted-causal online softmax,
but K/V arrive from a dense page pool through a ``[batch, max_pages]``
page table rather than a contiguous cache row: the KV grid dimension
walks the row's page list via scalar-prefetch block index maps (page
``j`` of row ``b`` DMAs pool page ``page_table[b, j]``, clamped at the
row's last reachable page ``(offsets[b] + C - 1) // page_len`` so grid
steps past the chunk's extent re-issue the same block index and cost
no new DMA — the fetch walk is O(offset + C) like the compute, not
O(max_pages)), the q-block × page skip runs on global positions
exactly as the contiguous kernel's q-block × k-block skip. The q-block
knob is ``decode.page_block_q`` (the KV block is pinned to one page —
the pool's DMA granule).

**Tensor parallelism** (``serving.Engine(mesh=...)``): no sharded
variant needed — the grid's heads dimension simply shrinks. A
heads-sharded pool (``heads/tp`` per shard) gives each shard the same
index maps over fewer heads-axis blocks of its own pool slice; no DMA
or mask ever crosses heads, so the per-shard kernel is unchanged math
over its head subset and attention adds no collectives to the sharded
serving programs (the block knobs above tune per-shard exactly as they
do single-chip — same shapes per head, fewer heads).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels import mosaic_dtype_ok, vmem
from apex_tpu.kernels.decode_attention import (_check_head_scales,
                                               _group_size,
                                               _layer_pool_shape,
                                               _page_block_spec,
                                               _page_dots, _repeat_kv_heads,
                                               gather_pages)

__all__ = ["prefill_attention", "prefill_attention_reference",
           "paged_prefill_attention", "paged_prefill_attention_reference",
           "mla_prefill_attention", "mla_prefill_attention_reference"]

_NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 256


# --------------------------------------------------------------- jnp reference
def prefill_attention_reference(q, k, v, offsets, *, scale: float = 1.0,
                                k_scale=None, v_scale=None):
    """fp32-math oracle: per-row shifted-causal softmax over the cache.

    ``q`` [b, h, C, d]; ``k``/``v`` [b, h_kv, L, d] with ``h_kv``
    dividing ``h`` (grouped heads: query head ``i`` reads K/V head ``i //
    (h // h_kv)``); ``offsets`` [b] int32.
    Query row ``i`` attends cache positions ``j <= offsets[b] + i``.
    Returns [b, h, C, d] in ``q.dtype``. ``k_scale``/``v_scale`` ([h]
    fp32) dequantize an int8 cache before the exact math (the
    quantized tier's oracle — see
    :func:`~apex_tpu.kernels.decode_attention.decode_attention_reference`).
    """
    out_dtype = q.dtype
    q32, k32, v32 = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    if k_scale is not None:
        k32 = k32 * jnp.asarray(k_scale, jnp.float32)[None, :, None, None]
    if v_scale is not None:
        v32 = v32 * jnp.asarray(v_scale, jnp.float32)[None, :, None, None]
    k32, v32 = _repeat_kv_heads(q.shape[1], k32, v32)
    s = jnp.einsum("bhqd,bhld->bhql", q32, k32) * scale
    C, L = q.shape[2], k.shape[2]
    rows = (offsets[:, None, None, None]
            + jnp.arange(C, dtype=jnp.int32)[None, None, :, None])
    cols = jnp.arange(L, dtype=jnp.int32)[None, None, None, :]
    s = jnp.where(cols <= rows, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.asarray(jnp.einsum("bhql,bhld->bhqd", p, v32), out_dtype)


# -------------------------------------------------------------------- kernel
def _prefill_kernel(off_ref, *refs, scale, block_q, block_k, quant):
    """Grid (bh, nq, nk): one batch·head row, q-blocked chunk, blockwise
    over cached KV. The (m, l) recurrence is the flash forward kernel's;
    the causal skip/mask runs on GLOBAL query positions ``offset + row``
    instead of chunk-local ones, which is the whole difference between
    training attention and chunked prefill. ``quant`` (static) adds two
    per-row SMEM scale refs and fuses the int8-cache dequant multiplies
    into the logit/accumulator updates (the decode kernel's pattern)."""
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, \
            l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    offset = off_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # skip KV blocks entirely past this q-block's LAST global position
    @pl.when(ki * block_k <= offset + qi * block_q + block_q - 1)
    def _body():
        q = q_ref[0].astype(jnp.float32)                     # [bq, d]
        k = k_ref[0].astype(jnp.float32)                     # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bq, bk]
        if quant:
            s = s * ks_ref[b]
        rows = offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
        m_prev = m_ref[:, :1]                                # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                               # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if quant:
            pv = pv * vs_ref[b]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        # every row attends at least its own position, so l > 0 always;
        # the guard only keeps a mis-called kernel finite
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _prefill_pallas(q3, k3, v3, off3, scale, bq, bk, interpret,
                    ks3=None, vs3=None, G=1):
    bh, C, d = q3.shape
    L = k3.shape[1]
    quant = ks3 is not None
    # grouped heads: query row r (batch x query heads, heads fastest)
    # reads K/V row r // G (batch x K/V heads); G = 1 keeps the plain map
    kv_row = (lambda b: b) if G == 1 else (lambda b: b // G)
    kernel = functools.partial(_prefill_kernel, scale=scale, block_q=bq,
                               block_k=bk, quant=quant)
    scale_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2 \
        if quant else []
    scale_ops = (ks3, vs3) if quant else ()
    return pl.pallas_call(
        kernel,
        grid=(bh, C // bq, L // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # offsets
            *scale_specs,                          # k/v dequant scales
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (kv_row(b), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, C, d), q3.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),      # acc
            pltpu.VMEM((bq, 128), jnp.float32),    # m (col 0 live)
            pltpu.VMEM((bq, 128), jnp.float32),    # l (col 0 live)
        ],
        interpret=interpret, name="prefill_attention",
    )(off3, *scale_ops, q3, k3, v3)


# ------------------------------------------------------------------ dispatch
def _resolve_blocks(block_q, block_k):
    if block_q is None:
        block_q = vmem.get_override("decode.chunk_block_q",
                                    DEFAULT_BLOCK_Q, multiple=8)
    if block_k is None:
        block_k = vmem.get_override("decode.chunk_block_k",
                                    DEFAULT_BLOCK_K, multiple=128)
    return block_q, block_k


def prefill_attention(q, k, v, offsets, *, scale: Optional[float] = None,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None,
                      k_scale=None, v_scale=None,
                      interpret: bool = False):
    """Chunk-of-queries attention against a cached, offset prefix.

    ``q`` [batch, heads, C, head_dim] — C consecutive prompt tokens whose
    K/V are already written into the cache at ``[offsets[b],
    offsets[b] + C)``; ``k``/``v`` [batch, kv_heads, max_len, head_dim]
    (the serving cache's per-layer view; ``kv_heads`` divides ``heads``,
    query head ``i`` reading K/V head ``i // (heads // kv_heads)``);
    ``offsets`` [batch] int32. Query
    row ``i`` attends cache positions ``[0, offsets[b] + i]`` — the
    shifted-causal mask of chunked prefill. ``scale`` defaults to
    ``1/sqrt(head_dim)``.

    Inference-only (no VJP — prefill never backprops). The Pallas path
    skips the compute of KV blocks past each q-block's last global
    position, so chunk ``n`` of a prompt costs O(offset + C) MXU work
    rather than O(max_len) (block DMA still covers the cache row);
    unaligned shapes and non-Mosaic dtypes fall back to the jnp
    reference.

    Tuned geometry: ``decode.chunk_block_q`` / ``decode.chunk_block_k``
    in the :mod:`apex_tpu.kernels.vmem` override registry (clamped to
    aligned divisors of the chunk / cache lengths).
    """
    b, h, C, d = q.shape
    h_kv, L = k.shape[1], k.shape[2]
    if k.shape != (b, h_kv, L, d) or v.shape != k.shape:
        raise ValueError(f"prefill_attention: k/v {k.shape}/{v.shape} do "
                         f"not match q {q.shape} + max_len")
    G = _group_size("prefill_attention", h, h_kv)
    if offsets.shape != (b,):
        raise ValueError(f"prefill_attention: offsets {offsets.shape} "
                         f"must be [{b}]")
    _check_head_scales("prefill_attention", h_kv, k_scale, v_scale)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    from apex_tpu.kernels.flash_attention import _fit_block, _has_vma
    bq, bk = _resolve_blocks(block_q, block_k)
    bq = _fit_block(bq, C, 8)
    bk = _fit_block(bk, L, 128)
    if jax.default_backend() == "cpu":
        interpret = True
    pallas_ok = (C % bq == 0 and L % bk == 0 and d % 8 == 0
                 and bq % 8 == 0 and bk % 128 == 0)
    if not pallas_ok or (interpret and _has_vma(q)) \
            or (not interpret and not mosaic_dtype_ok(q, k, v)):
        return prefill_attention_reference(q, k, v, offsets, scale=scale,
                                           k_scale=k_scale,
                                           v_scale=v_scale)
    q3 = q.reshape(b * h, C, d)
    k3 = k.reshape(b * h_kv, L, d)
    v3 = v.reshape(b * h_kv, L, d)
    off3 = jnp.repeat(jnp.asarray(offsets, jnp.int32), h)
    ks3 = vs3 = None
    if k_scale is not None:
        # one scale per flattened QUERY row: its K/V head's
        per_q = (lambda t: t) if G == 1 else (lambda t: jnp.repeat(t, G))
        ks3 = jnp.tile(per_q(jnp.asarray(k_scale, jnp.float32)), b)
        vs3 = jnp.tile(per_q(jnp.asarray(v_scale, jnp.float32)), b)
    out = _prefill_pallas(q3, k3, v3, off3, scale, bq, bk, interpret,
                          ks3, vs3, G)
    return out.reshape(b, h, C, d).astype(q.dtype)


# ------------------------------------------------------------ paged variant
def paged_prefill_attention_reference(q, k_pool, v_pool, page_table,
                                      offsets, *, scale: float = 1.0,
                                      k_scale=None, v_scale=None,
                                      layer=None):
    """fp32-math oracle: gather the page-table view, then the exact
    contiguous chunk-prefill reference. ``q`` [b, h, C, d]; pools
    [num_pages, h, page_len, d] (or the stacked pool, with ``layer``);
    ``page_table`` [b, max_pages];
    ``offsets`` [b] int32. With ``k_scale``/``v_scale`` ([h] fp32) the
    gathered int8 pages are dequantized before the exact math — the
    quantized tier's gather-dequant oracle."""
    k = gather_pages(k_pool, page_table, layer)
    v = gather_pages(v_pool, page_table, layer)
    return prefill_attention_reference(q, k, v, offsets, scale=scale,
                                       k_scale=k_scale, v_scale=v_scale)


def _paged_prefill_kernel(pt_ref, off_ref, *refs, scale, block_q,
                          page_len, quant, kt=False, G=1):
    """Grid (b, h, nq, max_pages): one batch row x head, q-blocked
    chunk, one pool page per KV step. :func:`_prefill_kernel`'s (m, l)
    recurrence and global-position shifted-causal mask; the page the
    DMA fetched was chosen by the scalar-prefetch index map. ``quant``
    (static) adds two scalar-prefetch scale refs and the fused per-head
    dequant multiplies. ``kt`` (static): the page blocks are
    ``[d, page_len]``, the stacked pool's form. ``G`` (static): query
    heads per K/V head; the grid walks QUERY heads and the index map
    fetched K/V head ``hh // G``'s page."""
    qk_dims, pv_dims = _page_dots(kt)
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, \
            l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    hh = pl.program_id(1)
    kv = hh if G == 1 else hh // G    # the K/V head of this query head
    qi = pl.program_id(2)
    ji = pl.program_id(3)
    nj = pl.num_programs(3)
    offset = off_ref[b]

    @pl.when(ji == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # skip pages entirely past this q-block's LAST global position
    @pl.when(ji * page_len <= offset + qi * block_q + block_q - 1)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)      # [pl, d] (kt: [d, pl])
        s = jax.lax.dot_general(
            q, k, qk_dims,
            preferred_element_type=jnp.float32) * scale      # [bq, pl]
        if quant:
            s = s * ks_ref[kv]
        rows = offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_len), 0)
        cols = ji * page_len + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_len), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
        m_prev = m_ref[:, :1]                                # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                               # [bq, pl]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), pv_dims,
            preferred_element_type=jnp.float32)
        if quant:
            pv = pv * vs_ref[kv]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ji == nj - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _paged_prefill_pallas(q, k_pool, v_pool, pt, offsets, scale, bq,
                          interpret, ks=None, vs=None, layer=None):
    B, h, C, d = q.shape
    kt = layer is not None           # stacked pool: pages [d, page_len]
    page_len = k_pool.shape[-1 if kt else -2]
    max_pages = pt.shape[1]
    quant = ks is not None
    G = h // k_pool.shape[2 if kt else 1]    # query heads per K/V head
    kv_head = (lambda hh: hh) if G == 1 else (lambda hh: hh // G)
    kernel = functools.partial(_paged_prefill_kernel, scale=scale,
                               block_q=bq, page_len=page_len,
                               quant=quant, kt=kt, G=G)

    # the dequant scales ride as two extra scalar-prefetch operands;
    # the index maps' variadic tails absorb them (only the kernel body
    # reads them)
    def _q_idx(b, hh, i, j, pt, off, *_scales):
        return (b, hh, i, 0)

    def _kv_page(b, hh, i, j, pt, off, *_scales):
        # Bound the DMA extent by the chunk's offset: row b's queries
        # reach global position off[b] + C - 1 at most, so pages past
        # index (off[b] + C - 1) // page_len are never computed over
        # (the kernel's q-block × page skip). Clamping the page walk
        # there makes every later grid step re-issue the SAME block
        # index, which the Pallas pipeline does not re-fetch — the
        # kernel stops paying DMA for the max_pages tail just as it
        # already stopped paying MXU for it. Computed steps always have
        # j <= last, so the clamp never changes what the compute reads
        # (outputs stay bitwise identical to the oracle).
        last = (off[b] + (C - 1)) // page_len
        return (pt[b, jnp.minimum(j, last)], kv_head(hh), 0, 0)

    kv_spec = _page_block_spec(page_len, d, _kv_page, layer)
    n_prefetch, extra_ops = (4, (ks, vs)) if quant else (2, ())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,  # page_table, offsets[, ks, vs]
        grid=(B, h, C // bq, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), _q_idx),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), _q_idx),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),      # acc
            pltpu.VMEM((bq, 128), jnp.float32),    # m (col 0 live)
            pltpu.VMEM((bq, 128), jnp.float32),    # l (col 0 live)
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, C, d), q.dtype),
        interpret=interpret, name="paged_prefill_attention",
    )(pt, offsets, *extra_ops, q, k_pool, v_pool)


def _resolve_page_block_q(block_q):
    if block_q is None:
        block_q = vmem.get_override("decode.page_block_q",
                                    DEFAULT_BLOCK_Q, multiple=8)
    return block_q


def paged_prefill_attention(q, k_pool, v_pool, page_table, offsets, *,
                            scale: Optional[float] = None,
                            block_q: Optional[int] = None,
                            k_scale=None, v_scale=None,
                            layer: Optional[int] = None,
                            interpret: bool = False):
    """Chunk-of-queries attention against a PAGED cached prefix.

    ``q`` [batch, heads, C, head_dim] — C consecutive prompt tokens
    whose K/V are already written into the pool at logical positions
    ``[offsets[b], offsets[b] + C)`` of row ``b``'s pages; ``k_pool``/
    ``v_pool`` [num_pages, kv_heads, page_len, head_dim] (one layer of
    the serving pool; ``kv_heads`` divides ``heads``, query head ``i``
    reading K/V head ``i // (heads // kv_heads)``), or the whole stacked
    pool [layers, num_pages, kv_heads,
    head_dim, page_len] with the static ``layer`` to attend (the
    serving engine's form, pages transposed; the layer is one more
    block index of the page DMA — no layer is sliced out);
    ``page_table`` [batch, max_pages] int32;
    ``offsets`` [batch] int32. Query row ``i`` attends logical cache
    positions ``[0, offsets[b] + i]`` — the shifted-causal mask of
    chunked prefill, unchanged by the paging. ``scale`` defaults to
    ``1/sqrt(head_dim)``.

    Inference-only. The Pallas path walks each row's page list via
    scalar-prefetch index maps and skips pages past each q-block's last
    global position — O(offset + C) MXU work per chunk, same as the
    contiguous kernel, over a pool that is dense and shared instead of
    slot-partitioned. The DMA extent is bounded the same way: the page
    index map clamps at each row's last reachable page
    (``(offsets[b] + C - 1) // page_len``), so grid steps past the
    prefix re-issue the same block index and the pipeline fetches
    nothing new — an early chunk of a long prompt pays O(offset + C)
    DMA, not O(max_pages) (the clamp only ever retargets steps whose
    compute is skipped, so outputs are bitwise unchanged). Unaligned
    shapes and non-Mosaic dtypes fall back to the gather-then-reference
    oracle.

    Tuned geometry: ``decode.page_block_q`` in the
    :mod:`apex_tpu.kernels.vmem` override registry (the KV block is one
    pool page by construction).
    """
    B, h, C, d = q.shape
    P, hp, page_len, dp = _layer_pool_shape("paged_prefill_attention",
                                            k_pool, v_pool, layer)
    if dp != d:
        raise ValueError(f"paged_prefill_attention: pools "
                         f"{k_pool.shape}/{v_pool.shape} do not match q "
                         f"{q.shape}")
    _group_size("paged_prefill_attention", h, hp)
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(f"paged_prefill_attention: page_table "
                         f"{page_table.shape} must be [{B}, max_pages]")
    if offsets.shape != (B,):
        raise ValueError(f"paged_prefill_attention: offsets "
                         f"{offsets.shape} must be [{B}]")
    _check_head_scales("paged_prefill_attention", hp, k_scale, v_scale)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    from apex_tpu.kernels.flash_attention import _fit_block, _has_vma
    bq = _fit_block(_resolve_page_block_q(block_q), C, 8)
    if jax.default_backend() == "cpu":
        interpret = True
    pallas_ok = (C % bq == 0 and bq % 8 == 0 and d % 8 == 0
                 and page_len % 128 == 0)
    if not pallas_ok or (interpret and _has_vma(q)) \
            or (not interpret and not mosaic_dtype_ok(q, k_pool, v_pool)):
        return paged_prefill_attention_reference(
            q, k_pool, v_pool, page_table, offsets, scale=scale,
            k_scale=k_scale, v_scale=v_scale, layer=layer)
    pt = jnp.asarray(page_table, jnp.int32)
    off32 = jnp.asarray(offsets, jnp.int32)
    ks = vs = None
    if k_scale is not None:
        ks = jnp.asarray(k_scale, jnp.float32)
        vs = jnp.asarray(v_scale, jnp.float32)
    return _paged_prefill_pallas(q, k_pool, v_pool, pt, off32, scale, bq,
                                 interpret, ks, vs, layer).astype(q.dtype)


# ------------------------------------------------- latent pages (MLA)
MLA_BLOCK_TOKENS = 32       # chunk tokens (x heads rows) a q block
MLA_PAGES_PER_STEP = 4      # pool pages a KV step


def mla_prefill_attention_reference(q, pool, page_table, offsets, *,
                                    value_dim: int, scale: float = 1.0,
                                    layer=None):
    """fp32-math oracle of :func:`mla_prefill_attention`: ``q [b, C, h,
    d]`` against the gathered latent rows, shifted-causal, the value the
    rows' first ``value_dim`` columns. Returns ``[b, C, h, value_dim]``
    float32."""
    rows = gather_pages(pool, page_table, layer)          # [B, 1, L, d]
    out = prefill_attention_reference(
        jnp.moveaxis(jnp.asarray(q, jnp.float32), 1, 2), rows,
        rows[..., :value_dim], offsets, scale=scale)
    return jnp.moveaxis(out, 1, 2)


def _mla_prefill_kernel(pt_ref, off_ref, q_ref, *refs, scale, heads,
                        block_tokens, page_len, pages, vdim, widen):
    """Grid (b, q block, KV step). A q block is ``block_tokens``
    consecutive chunk tokens x ALL heads (row ``r`` is token ``r //
    heads``), a KV step ``pages`` pool pages, each fetched once for every
    head and for both products: scores against the whole ``[d, page_len]``
    page, values from its first ``vdim`` rows. The (m, l) recurrence and
    the global-position mask are :func:`_paged_prefill_kernel`'s; the
    operands go to the products as stored (bfloat16), ``p`` rounded to
    the page's type."""
    kv_refs, (o_ref, acc_ref, m_ref, l_ref) = refs[:pages], refs[pages:]
    b, qi, ji = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    T = pages * page_len
    R = block_tokens * heads
    first = off_ref[b] + qi * block_tokens        # the block's first token

    def dot(a, bb, dims):
        if widen:
            a, bb = a.astype(jnp.float32), bb.astype(jnp.float32)
        return jax.lax.dot_general(a, bb, (dims, ((), ())),
                                   preferred_element_type=jnp.float32)

    @pl.when(ji == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # skip steps entirely past this q block's LAST global position
    @pl.when(ji * T <= first + block_tokens - 1)
    def _body():
        kv = kv_refs[0][...] if pages == 1 else jnp.concatenate(
            [r[...] for r in kv_refs], axis=1)               # [d, T]
        s = dot(q_ref[0], kv, ((1,), (0,))) * scale          # [R, T]
        rows = first + jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (R, T), 0), heads)
        cols = ji * T + jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + dot(
            p.astype(kv.dtype), kv[:vdim, :], ((1,), (1,)))  # [R, vdim]

    @pl.when(ji == nj - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def mla_prefill_attention(q, pool, page_table, offsets, *, value_dim: int,
                          scale: float = 1.0, layer: int = 0,
                          block_tokens: Optional[int] = None,
                          pages_per_step: Optional[int] = None,
                          interpret: bool = False):
    """Chunk-of-queries ABSORBED latent attention against a paged pool of
    latent rows (the chunk's own rows already written at ``[offsets[b],
    offsets[b] + C)``).

    ``q`` ``[batch, C, heads, d]``: the chunk's queries in the latent's
    coordinates (``[W_kvb,k^T q_nope | q_rope]``, token-major); ``pool``
    the stacked ``[layers, num_pages, 1, d, page_len]`` pool of the
    latent page kind; the other operands as
    :func:`paged_prefill_attention`. Returns ``[batch, C, heads,
    value_dim]`` float32, ``sum_t p_t latent_t`` a head, for the caller
    to take through ``W_kvb,v``.

    Why absorbed here too, and not the live latents expanded to per-head
    keys and values in front of the grouped-head kernel: the expansion is
    a ``[context, heads x (d_nope + d_v)]`` temporary of the WHOLE table's
    span (the chunk program is one fixed shape: 0.7 GB at 33,792
    positions x 32 heads) written and read every chunk whatever the
    offset, where this kernel's pages are read once a q block as they
    lie and its walk stops at the chunk's own extent. It pays for that in
    operations - 2 (d + value_dim) a position and head against 2 (d_nope +
    d_rope + d_v) expanded, 3.4 times at 576/512 against 192/128 - on
    the MXU with every head sharing the page (``heads x block_tokens``
    rows a product). Runs under the name ``mla_prefill_attention``.
    Unaligned shapes fall back to the oracle."""
    B, C, h, d = q.shape
    if pool.ndim != 5 or pool.shape[2] != 1 or pool.shape[3] != d \
            or not 0 < value_dim <= d:
        raise ValueError(f"mla_prefill_attention: pool {pool.shape} must "
                         f"be [layers, num_pages, 1, {d}, page_len] with "
                         f"the value its first {value_dim} columns")
    page_len = pool.shape[4]
    from apex_tpu.kernels.flash_attention import _fit_block, _has_vma
    bt = _fit_block(block_tokens or MLA_BLOCK_TOKENS, C, 8)
    pt = jnp.asarray(page_table, jnp.int32)
    off32 = jnp.asarray(offsets, jnp.int32)
    max_pages = pt.shape[1]
    pages = max(1, min(pages_per_step or MLA_PAGES_PER_STEP, max_pages))
    if jax.default_backend() == "cpu":
        interpret = True
    rows = 8 if interpret else 32 // pool.dtype.itemsize
    pallas_ok = (C % bt == 0 and (bt * h) % 8 == 0 and d % rows == 0
                 and value_dim % rows == 0 and page_len % 128 == 0)
    if not pallas_ok or (interpret and _has_vma(q)) \
            or (not interpret and not mosaic_dtype_ok(q, pool)):
        return mla_prefill_attention_reference(
            q, pool, pt, off32, value_dim=value_dim, scale=scale,
            layer=layer)
    kernel = functools.partial(
        _mla_prefill_kernel, scale=float(scale), heads=h, block_tokens=bt,
        page_len=page_len, pages=pages, vdim=int(value_dim),
        widen=interpret)

    def page_spec(x):
        def index(b, i, j, pt, off):
            # the walk stops at the chunk's last reachable page: later
            # steps re-issue that block index and fetch nothing
            last = (off[b] + (C - 1)) // page_len
            return (layer, pt[b, jnp.minimum(j * pages + x, last)], 0, 0, 0)
        return pl.BlockSpec((None, None, None, d, page_len), index)

    R = bt * h
    q_spec = pl.BlockSpec((1, R, d), lambda b, i, j, pt, off: (b, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # page_table, offsets
        grid=(B, C // bt, -(-max_pages // pages)),
        in_specs=[q_spec] + [page_spec(x) for x in range(pages)],
        out_specs=pl.BlockSpec((1, R, value_dim),
                               lambda b, i, j, pt, off: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((R, value_dim), jnp.float32),   # acc
            pltpu.VMEM((R, 128), jnp.float32),         # m (col 0 live)
            pltpu.VMEM((R, 128), jnp.float32),         # l (col 0 live)
        ])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C * h, value_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret, name="mla_prefill_attention",
    )(pt, off32, jnp.asarray(q, pool.dtype).reshape(B, C * h, d),
      *([pool] * pages))
    return out.reshape(B, C, h, value_dim)
