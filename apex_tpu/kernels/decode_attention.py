"""Cached-K/V decode attention — the serving tier's single-token kernel.

Training attention (:mod:`apex_tpu.kernels.flash_attention`) answers
"every query attends to every earlier key"; decode answers a different
question: ONE new query per sequence against a **paged KV pool** of
which only the first ``lengths[b]`` positions of each row are valid.
This is the same move the flash-attention kernel lineage makes from
training kernels to cached inference: the blockwise online-softmax
inner loop is unchanged, but the query block degenerates to a single
row and the causal-block skip becomes a *length* skip — pages entirely
past the sequence's valid length are never touched.

Numerics follow the kernel tier's contract: fp32 accumulation regardless
of I/O dtype (the cache is normally bf16 via the amp cast policies), and
a pure-jnp reference that doubles as the CPU/unaligned fallback and the
test oracle. Rows with ``lengths == 0`` return zeros (a defined value for
inactive serving slots — their output is discarded by the engine).

:func:`decode_attention_reference` is that oracle over a contiguous
``[batch, heads, max_len, head_dim]`` view; the paged oracle is the
same function over the pool gathered through the page table
(:func:`gather_pages`).

**The kernel** (:func:`paged_decode_attention`): the cache is a dense
pool of fixed-size pages plus a ``[batch, max_pages]`` page table: block
``j`` of batch row ``b`` lives wherever ``page_table[b, j]`` says. The
pool stays in HBM, the page table and the lengths ride SMEM
(scalar prefetch), and ONE invocation walks every row's *live* pages -
``ceil(lengths[b] / page_len)`` of them, never the rest of the table -
fetching ``decode.paged_step_bytes`` (the tuned-override registry,
:mod:`apex_tpu.kernels.vmem`) worth of whole pages a step, every
K/V head of a page in one DMA (in the stacked pool a page of all heads
of a layer is one contiguous stretch), double-buffered, the next row's
first step in flight while this row's last is multiplied. All heads of
a step are one product against the row's query laid block-diagonally
(:func:`_paged_decode_kernel`).

**Tensor parallelism** (``serving.Engine(mesh=...)``): the kernels need
NO sharded variant. A heads-sharded pool - ``[layers, num_pages,
heads/tp, head_dim, page_len]`` per shard, the serving tier's TP
layout - hands each shard the same kernels over its own pool slice
and its own query heads: the paged decode kernel's page is the local
heads' page (and its pages a step follow from that page's bytes); no
index map or product mixes heads across shards, every DMA stays shard-local, and
the per-shard math is the single-chip kernel's over that head subset.
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

__all__ = ["decode_attention_reference",
           "paged_decode_attention", "paged_decode_attention_reference",
           "mla_decode_attention", "mla_decode_attention_reference",
           "gather_pages"]

_NEG_INF = -1e30


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


# ------------------------------------------------------------------ dispatch
def _check_head_scales(name, h, k_scale, v_scale):
    """Quantized-cache scale validation shared by the three dispatchers:
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
    """The K/V ``BlockSpec`` of the paged PREFILL kernel: one pool page
    of one head, chosen by the scalar-prefetch index map ``page_idx``
    (the paged decode kernel takes no block of the pool: it leaves it in
    HBM and fetches whole pages of all heads itself). On a
    stacked pool the static ``layer`` is one more (squeezed) block index
    in front and the page arrives as it is stored, ``[d, page_len]``
    (the kernel body's ``kt`` form): it is DMA'd out of the pool where
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


def _pool_write_tokens(pool, layer, page_ids, off, new):
    """Write one token per batch row into the stacked paged pool, IN
    PLACE, in plain XLA: ``pool`` ``[layers, num_pages, h, d,
    page_len]``, ``new`` ``[B, h, d]`` (already in the pool's storage
    dtype) lands at ``pool[layer, page_ids[b], :, :, off[b]]``; a row
    whose page id is ``num_pages`` (past the pool) writes nothing.

    The decode program does not run this any more: its one token a row
    is written by :func:`paged_decode_attention` itself, from the page
    its kernel holds in VMEM anyway. This is the write of the programs
    without that kernel - speculative verify's few unaligned positions,
    and the reference the kernel gives way to - and its oracle.

    Written as read-modify-write of each row's whole write page — gather
    the B pages, replace lane ``off[b]``, scatter the pages back —
    because a scatter whose window is a whole page runs on the pool as
    it lies, while a scatter of the ``[h, d]`` column alone makes the
    TPU compiler re-lay the entire pool out (the window dims must be its
    minor ones) and back: three fusions over ``B`` pages to store ``B``
    columns, which is why the decode program left it. A live row's write
    page is its own (shared pages are full); inactive rows all name the
    sentinel page, whose contents nothing reads."""
    pages = pool[layer, page_ids]                     # [B, h, d, pl]
    lane = jax.lax.broadcasted_iota(jnp.int32, pages.shape, 3)
    pages = jnp.where(lane == off[:, None, None, None], new[..., None],
                      pages)
    return pool.at[layer, page_ids].set(pages)


def _write_slots(pt, lengths, page_len, num_pages):
    """Where the XLA write of a decode token lands: ``(page ids [B], lane
    [B])`` of position ``lengths - 1`` (at most the table's span). A row
    of length 0 writes nothing: its page id is past the pool, and the
    scatter drops it."""
    pos = jnp.clip(lengths, 1, pt.shape[1] * page_len) - 1
    page_ids = jnp.take_along_axis(pt, (pos // page_len)[:, None],
                                   axis=1)[:, 0]
    return jnp.where(lengths > 0, page_ids, num_pages), pos % page_len


DEFAULT_PAGED_STEP_BYTES = 512 * 1024
_SCOPED_VMEM_ROOM = 8 * 1024 * 1024


def _pages_per_step(page_bytes, max_pages):
    """Pool pages one step of the paged decode kernel fetches: as many
    as fit the tuned bytes in flight per buffer
    (``decode.paged_step_bytes``), by the page's own bytes (all K/V
    heads of one layer, ``heads x head_dim x page_len`` elements) - at
    least one, at most a row's table."""
    target = vmem.get_override("decode.paged_step_bytes",
                               DEFAULT_PAGED_STEP_BYTES)
    return max(1, min(max_pages, target // page_bytes))


def _p_rows(h):
    """Rows one bfloat16 piece of ``p`` takes in its stacked scratch:
    the query heads rounded up to the packed tile of 16."""
    return -(-h // 16) * 16


def _paged_decode_vmem(k_pool, q, pages, write=False):
    """``(working set, scoped limit asked for)`` in bytes of the paged
    decode kernel on the stacked pool ``k_pool`` at ``pages`` pages a
    step: K and V, two buffers each, of ``pages`` whole pages; the
    query and the output of every row; the stacked pieces of ``p``; the
    float32 accumulator, (m, l) and one step's logits; for the call
    that writes, every row's new K and V as columns. The limit leaves
    the compiler room for the products' operands beside it."""
    _, _, h_kv, d, page_len = k_pool.shape
    B, h, _ = q.shape
    T, C = pages * page_len, h_kv * d
    buffers = 2 * 2 * h_kv * d * T * k_pool.dtype.itemsize
    rows = -(-h // 8) * 8
    lanes = -(-d // 128) * 128
    q_and_out = 2 * B * rows * lanes * q.dtype.itemsize
    state = 3 * _p_rows(h) * T * 2 + rows * (C + 2 * 128 + 2 * T) * 4
    working = buffers + q_and_out + state
    if write:
        working += 2 * C * -(-B // page_len) * page_len * \
            k_pool.dtype.itemsize
    return working, 2 * working + _SCOPED_VMEM_ROOM


def _paged_decode_kernel(pt_ref, len_ref, layer_ref, *refs, scale, pages,
                         G, quant, write, widen, vdim=None):
    """One invocation walks every batch row's LIVE pages, ``pages`` of
    them a step, every K/V head of a page in one fetch.

    The pool stays in HBM (``k_hbm``/``v_hbm``, the stacked
    ``[layers, num_pages, h_kv, d, page_len]`` form). A step of row
    ``b`` is the next ``pages`` entries of its table below
    ``ceil(lengths[b] / page_len)``: each page ``[h_kv, d, page_len]``
    of layer ``layer_ref[0]`` (a scalar operand, so every layer of a
    model runs the SAME kernel: traced, lowered and compiled once, not
    once a layer) is one contiguous stretch of HBM and one
    ``make_async_copy`` into its ``page_len`` lanes of a
    ``[h_kv, d, pages * page_len]`` VMEM buffer; K and V have two such
    buffers each, and while one step is multiplied the next is in
    flight - the next row's first step included, so a row's last pages
    never wait on a cold fetch. A table entry past the length costs
    neither a DMA nor a step: rows of length 0 (and the sentinel page)
    are never fetched.

    Arithmetic (the (m, l) recurrence of :func:`_decode_kernel`, float32
    state): the page buffer read as ``[h_kv * d, T]`` (``T = pages *
    page_len``) is ONE product for all heads against the row's query
    laid block-diagonally, ``qbd[i, hh * d + c] = q[i, c]`` where query
    head ``i`` reads K/V head ``hh = i // G`` and 0 elsewhere: ``s =
    qbd @ K`` is ``[h, T]``, every query head against its own K/V
    head's keys, the operands as stored (bfloat16 x bfloat16 is exact in
    the float32 sum). ``p @ V^T`` gives ``[h, h_kv * d]``, each query
    head against EVERY K/V head's values; the accumulator keeps that
    form and the finish reads each head's own ``d`` columns. ``p``
    stays float32: against a bfloat16 (or int8) page it is handed to
    the product as its three bfloat16 pieces ``hi + mid + lo`` (24
    bits of mantissa, exact), stacked on the row axis so the page is
    loaded once. Table slots of a step past the row's last live page
    hold stale bytes: K's are masked with the positions past the
    length, V's are zeroed before the product (0 x NaN is NaN).

    VMEM working set (:func:`_paged_decode_vmem` counts it, and the
    call asks for a scoped limit of twice that plus 8 MB): the four page
    buffers, ``4 x pages x`` a page's bytes - 1.3 MB at GPT-2 large's
    320 KB page, 2.1 MB at eight of ZAYA1-8B's 64 KB pages - plus every
    row's query and output, ``p``'s pieces, the accumulator and one
    step's logits: 1.8 MB and 2.7 MB in all; the call that writes
    holds every row's new K and V as columns beside them, 0.66 MB and
    0.13 MB.

    ``quant`` (static): int8 pages widen to bfloat16 (exact) and the
    per-head scales, ``[h, 1]`` float32 columns, multiply after each
    product as in :func:`_decode_kernel`. ``widen`` (static, the CPU's
    interpreter: its dot takes no bfloat16 x bfloat16 -> float32):
    operands are widened to float32 at the product, the same numbers.

    ``write`` (static: the call was handed the rows' new K/V): the
    kernel also WRITES the decode token. ``nk_ref``/``nv_ref`` hold the
    new K and V of every row as columns, ``[blocks, h_kv * d,
    page_len]`` in the pool's storage type (row ``b`` is lane ``b %
    page_len`` of block ``b // page_len``), and the pools are outputs
    aliased to their inputs, read and written through the one (output)
    reference. Position ``lengths[b] - 1`` lies in row ``b``'s LAST
    live page, which the row's last step fetches anyway: once that
    step's pages have landed, lane ``(lengths[b] - 1) % page_len`` of
    that page is replaced in ``kbuf``/``vbuf`` by the row's new column
    - its block of ``nk_ref`` rotated along the lanes so that the row's
    lane lies on the write lane, and a select over that one page in
    VMEM, both on 32-bit words (data moved, no value computed) - the
    page's slice of the buffer is DMA'd back to where it came from, and
    the products run on the buffer while it goes: write-then-attend on
    the bytes the pool will hold. What makes that safe:

    (a) While row ``b`` writes its page back, the next row's first step
        is already being fetched. They never meet: a live row's write
        page is its own (shared pages are full ones, copy-on-write), and
        the sentinel page, which every inactive slot both writes and
        reads, is read by nobody who keeps the result.
    (b) No page written in a call is fetched again in it: a write page
        sits in one row's table, and a row fetches its last page once.
    (c) The write-back is waited on at the end of its row, before its
        buffer can be the target of the next fetch into it (the first
        step of the row after next, or later). A later wait buys
        nothing where it counts: copied back from two staging pages of
        its own and waited on two rows on, the call alone ran 6% faster
        and the decode program it sits in not at all (that program is
        bound by the bytes it moves, PERF.md section 6, PR 35).
    (d) Table slots of a partial step past the row's last live page are
        neither fetched nor written; rows of length 0 write nothing.

    ``vdim`` (static; :func:`mla_decode_attention`): the LATENT page
    kind. There is ONE pool of one row ``[d]`` a token that every query
    head reads (``h_kv`` 1, no block-diagonal query), and the value is
    the first ``vdim`` rows of the page as it lies in the K buffer: one
    fetch of a page serves both products, one write-back the token's
    row. No V operand, buffer or new-V exists; everything else - the
    walk, the double buffer, the write's hazards - is the text above.
    """
    refs = list(refs)
    latent = vdim is not None
    n_pools = 1 if latent else 2
    q_ref = refs.pop(0)
    ks_ref, vs_ref = (refs.pop(0), refs.pop(0)) if quant else (None, None)
    news = [refs.pop(0) for _ in range(n_pools)] if write else []
    hbms = [refs.pop(0) for _ in range(n_pools)]
    o_ref = refs.pop(0)
    if write:
        # the pools as outputs, aliased to the inputs: one reference to
        # read and to write
        hbms = [refs.pop(0) for _ in range(n_pools)]
    bufs = [refs.pop(0) for _ in range(n_pools)]
    kbuf, vbuf = bufs[0], bufs[-1]
    pools = list(zip(hbms, bufs))
    sem, p_ref, acc_ref, m_ref, l_ref = refs[:5]
    wsem = refs[5] if write else None
    B, h, d = q_ref.shape
    h_kv, page_len = h // G, kbuf.shape[-1] // pages
    C, T = h_kv * d, pages * page_len
    max_pages = pt_ref.shape[1]
    layer = layer_ref[0]
    f32, bf16 = jnp.float32, jnp.bfloat16
    split = kbuf.dtype != f32            # p as three bfloat16 pieces
    # the K product's operands: as stored (int8 widened) where q is
    # bfloat16 too, else float32
    k_dtype = bf16 if q_ref.dtype == bf16 and split else f32
    Rp = _p_rows(h)

    def dot(a, b, dims):
        if widen:
            a, b = a.astype(f32), b.astype(f32)
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=f32)

    def live_pages(b):
        return jnp.minimum(
            jax.lax.div(jnp.maximum(len_ref[b], 0) + (page_len - 1),
                        page_len), max_pages)

    def next_row(b):
        """The first row after ``b`` with a live page, or ``B``."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < B, len_ref[jnp.minimum(r, B - 1)] <= 0),
            lambda r: r + 1, b + 1)

    def write_back(b, slot, jj, act):
        """Start or wait (``act``) the copies of row ``b``'s write page,
        slice ``jj`` of buffer ``slot``, back into the pool."""
        page = pt_ref[b, jnp.clip(live_pages(b) - 1, 0, max_pages - 1)]
        lanes = pl.ds(jj * page_len, page_len)
        for x, (hbm, buf) in enumerate(pools):
            act(pltpu.make_async_copy(buf.at[slot, :, :, lanes],
                                      hbm.at[layer, page], wsem.at[x]))

    def step_copies(b, i, slot, act):
        """Start or wait (``act``) the DMAs of step ``i`` of row ``b``
        on buffer ``slot``: its live pages only."""
        n = live_pages(b)
        for jj in range(pages):
            j = i * pages + jj

            @pl.when(j < n)
            def _page():
                page = pt_ref[b, jnp.minimum(j, max_pages - 1)]
                lanes = pl.ds(jj * page_len, page_len)
                for x, (hbm, buf) in enumerate(pools):
                    act(pltpu.make_async_copy(
                        hbm.at[layer, page], buf.at[slot, :, :, lanes],
                        sem.at[x, slot]))

    # the block-diagonal query: q tiled h_kv times along the lanes by a
    # product with [I I .. I] (exact: one term a sum), masked to each
    # query head's own K/V head
    if not latent:
        tile = (jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (d, C), 1),
                            d)
                == jax.lax.broadcasted_iota(jnp.int32, (d, C), 0)
                ).astype(q_ref.dtype)
        own = (jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (h, C), 1),
                           d)
               == jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (h, C),
                                                       0), G))
    if split:
        p_ref[...] = jnp.zeros_like(p_ref)

    first = next_row(-1)

    @pl.when(first < B)
    def _warm():
        step_copies(jnp.minimum(first, B - 1), 0, 0,
                    lambda c: c.start())

    def row(b, slot):
        length = len_ref[b]
        n = live_pages(b)
        steps = jax.lax.div(n + (pages - 1), pages)
        after = next_row(b)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        if latent:
            qbd = q_ref[b].astype(k_dtype)                    # [h, d]
        else:
            qbd = jnp.where(own, dot(q_ref[b], tile, ((1,), (0,))),
                            0.0).astype(k_dtype)              # [h, C]

        def step(i, slot):
            last = i + 1 >= steps
            nb = jnp.where(last, after, b)

            @pl.when(nb < B)
            def _prefetch():
                step_copies(jnp.minimum(nb, B - 1),
                            jnp.where(last, 0, i + 1), 1 - slot,
                            lambda c: c.start())

            step_copies(b, i, slot, lambda c: c.wait())
            for jj in range(1, pages):
                @pl.when(i * pages + jj >= n)
                def _stale():
                    vbuf[slot, :, :, jj * page_len:(jj + 1) * page_len] = \
                        jnp.zeros((h_kv, d, page_len), vbuf.dtype)
            for jj in range(pages if write else 0):
                @pl.when(i * pages + jj == n - 1)
                def _write():
                    # the row's last live page has landed: the new
                    # token's column goes into it, and the page goes
                    # back (hazards (a)-(d) of the docstring) while the
                    # products below read the buffer. Row b's column is
                    # lane b % page_len of its block of new_ref: rotated
                    # to the write lane and selected into the page, as
                    # 32-bit words (whole sublanes: no value is touched)
                    off = jax.lax.rem(
                        jnp.minimum(length, max_pages * page_len) - 1,
                        page_len)
                    shift = jax.lax.rem(
                        off - jax.lax.rem(b, page_len) + page_len, page_len)
                    lane = jax.lax.broadcasted_iota(
                        jnp.int32, (1, page_len), 1) == off
                    at = (slot, slice(None), slice(None),
                          slice(jj * page_len, (jj + 1) * page_len))
                    for new_ref, buf in zip(news, bufs):
                        col = pltpu.roll(
                            pltpu.bitcast(new_ref[jax.lax.div(b, page_len)],
                                          jnp.uint32), shift, 1)
                        page = pltpu.bitcast(buf[at].reshape(C, page_len),
                                             jnp.uint32)
                        buf[at] = pltpu.bitcast(
                            jnp.where(lane, col, page),
                            buf.dtype).reshape(h_kv, d, page_len)
                    write_back(b, slot, jj, lambda c: c.start())
            k = kbuf[slot].reshape(C, T).astype(k_dtype)
            v = kbuf[slot, 0, :vdim, :] if latent \
                else vbuf[slot].reshape(C, T)
            if quant:
                v = v.astype(bf16)
            s = dot(qbd, k, ((1,), (0,))) * scale             # [h, T]
            if quant:
                s = s * ks_ref[...]
            cols = i * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
            s = jnp.where(cols < length, s, _NEG_INF)
            m_prev = m_ref[:, :1]                             # [h, 1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)                            # [h, T]
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = jnp.broadcast_to(
                alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
                l_ref.shape)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            if split:
                hi = p.astype(bf16)
                rest = p - hi.astype(f32)
                mid = rest.astype(bf16)
                lo = (rest - mid.astype(f32)).astype(bf16)
                for x, piece in enumerate((hi, mid, lo)):
                    p_ref[x * Rp:x * Rp + h, :] = piece
                pv3 = dot(p_ref[...], v, ((1,), (1,)))        # [3 Rp, C]
                pv = pv3[:h] + pv3[Rp:Rp + h] + pv3[2 * Rp:2 * Rp + h]
            else:
                pv = dot(p, v, ((1,), (1,)))                  # [h, C]
            if quant:
                pv = pv * vs_ref[...]
            acc_ref[...] = acc_ref[...] * alpha + pv
            return 1 - slot

        slot = jax.lax.fori_loop(0, steps, step, slot)
        if write:
            @pl.when(n > 0)
            def _written():
                # any slice of either buffer: a wait counts bytes
                write_back(b, 0, 0, lambda c: c.wait())
        l = l_ref[:, :1]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)      # [h, C]
        if latent:
            o_ref[b] = out.astype(o_ref.dtype)                # [h, vdim]
        else:
            for hh in range(h_kv):
                o_ref[b, hh * G:(hh + 1) * G, :] = out[
                    hh * G:(hh + 1) * G,
                    hh * d:(hh + 1) * d].astype(o_ref.dtype)
        return slot

    jax.lax.fori_loop(0, B, row, 0)


@functools.partial(jax.jit, static_argnames=("scale", "pages", "interpret",
                                             "vdim"))
def _paged_decode_pallas(q, k_pool, v_pool, pt, lengths, layer, ks=None,
                         vs=None, new_k=None, new_v=None, *, scale, pages,
                         interpret, vdim=None):
    """The kernel's call on the stacked pool, ``layer`` a traced scalar.
    Jitted: a model's layers differ in ``layer`` alone, so they share
    one trace and one lowered function (36 traces of this body were
    seconds of every process's start). With ``new_k``/``new_v`` ``[B,
    h_kv, d]`` the call writes them too and returns ``(out, k_pool,
    v_pool)``, the pools aliased to the ones handed in. ``vdim``: the
    latent page kind (``v_pool`` and ``new_v`` None; the output is ``[B,
    h, vdim]`` float32 and the call returns ``(out, pool)``)."""
    B, h, d = q.shape
    _, _, h_kv, _, page_len = k_pool.shape
    T, C = pages * page_len, h_kv * d
    quant, write = ks is not None, new_k is not None
    latent = vdim is not None
    pools = (k_pool,) if latent else (k_pool, v_pool)
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               pages=pages, G=h // h_kv, quant=quant,
                               write=write, widen=interpret, vdim=vdim)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q]
    if quant:
        # one scale a QUERY head, as a column the products' rows take
        operands += [jnp.repeat(s, h // h_kv)[:, None] for s in (ks, vs)]
    if write:
        # a row's new K (V) as a column, the form of a page's lane:
        # [blocks, h_kv * d, page_len], row b lane b % page_len of
        # block b // page_len
        blocks = -(-B // page_len)
        operands += [jnp.pad(t.reshape(B, C),
                             ((0, blocks * page_len - B), (0, 0)))
                     .reshape(blocks, page_len, C).swapaxes(1, 2)
                     for t in ((new_k,) if latent else (new_k, new_v))]
    out_shape = [jax.ShapeDtypeStruct((B, h, vdim), jnp.float32) if latent
                 else jax.ShapeDtypeStruct((B, h, d), q.dtype)]
    out_specs, scratch, aliases = [whole], [], {}
    if write:
        first = 3 + len(operands)         # the scalars count
        aliases = {first + x: 1 + x for x in range(len(pools))}
        out_shape += [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in pools]
        out_specs += [in_hbm] * len(pools)
        scratch = [pltpu.SemaphoreType.DMA((2,))]         # K|V write-back
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # page_table, lengths, layer
        grid=(1,),
        in_specs=[whole] * len(operands) + [in_hbm] * len(pools),
        out_specs=out_specs,
        scratch_shapes=[           # K (and V): two buffers each
            pltpu.VMEM((2, h_kv, d, T), t.dtype) for t in pools] + [
            pltpu.SemaphoreType.DMA((2, 2)),              # (K|V, buffer)
            pltpu.VMEM((3 * _p_rows(h), T), jnp.bfloat16),  # p's pieces
            pltpu.VMEM((h, vdim if latent else C), jnp.float32),  # acc
            pltpu.VMEM((h, 128), jnp.float32),            # m
            pltpu.VMEM((h, 128), jnp.float32),            # l
        ] + scratch,
    )
    _, limit = _paged_decode_vmem(k_pool, q, pages, write)
    outs = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=limit),
        interpret=interpret,
        name="mla_decode_attention" if latent else "paged_decode_attention",
    )(pt, lengths, jnp.reshape(layer, (1,)).astype(jnp.int32), *operands,
      *pools)
    return tuple(outs) if write else outs[0]


def paged_decode_attention(q, k_pool, v_pool, page_table, lengths, *,
                           new_k=None, new_v=None,
                           scale: Optional[float] = None,
                           k_scale=None, v_scale=None,
                           layer: Optional[int] = None,
                           interpret: bool = False):
    """Single-token attention against a PAGED, length-masked KV pool,
    and the write of that token's K/V into it.

    ``q`` [batch, heads, head_dim]; ``k_pool``/``v_pool``
    [num_pages, kv_heads, page_len, head_dim] (one layer of the serving
    pool — pages are shared across batch rows; ``kv_heads`` divides
    ``heads``: grouped-query attention, query head ``i`` reading K/V
    head ``i // (heads // kv_heads)``, a group's query heads sharing ONE
    fetch of each page), or the whole stacked
    pool [layers, num_pages, kv_heads, head_dim, page_len] with the static
    ``layer`` to attend (the serving engine's form, pages transposed:
    :func:`_layer_pool_shape` says why): the layer is then one more
    index of the page DMA (handed to the kernel as a scalar, so a
    model's layers share ONE traced and lowered kernel), and the serving
    programs hand over the pool they write in place and never slice a
    layer out of it;
    ``page_table``
    [batch, max_pages] int32 maps row ``b``'s logical block ``j`` to a
    pool page (sentinel ids for unallocated blocks — masked, never
    attended); ``lengths`` [batch] int32 as in
    :func:`decode_attention`. ``scale`` defaults to
    ``1/sqrt(head_dim)``.

    **The current token's K/V.** Handed over as ``new_k``/``new_v``
    ``[batch, kv_heads, head_dim]``, already in the pool's storage type
    (the int8 tier quantises before the call), the call WRITES them at
    logical position ``lengths[b] - 1`` of each row with ``lengths[b] >
    0`` (at most the table's span) - in the stacked pool, layer
    ``layer`` - then attends, and returns ``(out, k_pool, v_pool)``: the
    decode program's form. The Pallas path does both in the kernel (the
    pools are outputs aliased to their inputs; the write page is the
    row's last live page, edited in the VMEM the kernel fetched it into
    and copied back: :func:`_paged_decode_kernel`); the fallback writes
    with :func:`_pool_write_tokens` and then runs the oracle - the same
    bytes in the pool and the same operands under the products either
    way. Without them the call only reads - the K/V must already be in
    the pool - and returns ``out`` alone.

    Inference-only. The Pallas path leaves the pool in HBM and walks
    each row's LIVE pages (``ceil(lengths[b] / page_len)``; the table
    past them costs neither a DMA nor a step), several pages a step and
    every K/V head of a page in one fetch, double-buffered across steps
    and rows (:func:`_paged_decode_kernel`) - a short request in a big
    pool costs O(length), and the pool itself stays dense and shared.
    Pages a step follow from the page's own bytes and ONE tuned number,
    ``decode.paged_step_bytes`` (bytes in flight per buffer): no
    per-model setting. A single layer's 4-D pool is relaid to the
    stacked form first (a pool-sized copy: the form of tests and smoke
    runs, not of serving, and read-only: it takes no ``new_k``).
    Unaligned shapes (``page_len`` not a
    multiple of 128, a head's rows not whole tiles of the page's type)
    and non-Mosaic dtypes fall back to the gather-then-reference oracle.
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
    write = new_k is not None
    if write != (new_v is not None):
        raise ValueError("paged_decode_attention: new_k and new_v must be "
                         "given together (a token's K and V are written "
                         "as one)")
    if write:
        if layer is None:
            raise ValueError(
                "paged_decode_attention: new_k/new_v with a single "
                "layer's [num_pages, heads, page_len, head_dim] pool; the "
                "call writes only the stacked [layers, num_pages, heads, "
                "head_dim, page_len] pool (with its layer)")
        for nm, t, pool in (("new_k", new_k, k_pool),
                            ("new_v", new_v, v_pool)):
            if t.shape != (B, hp, d) or t.dtype != pool.dtype:
                raise ValueError(
                    f"paged_decode_attention: {nm} {t.dtype}{t.shape} "
                    f"must be {pool.dtype}[{B}, {hp}, {d}], the pool's "
                    f"storage type")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    from apex_tpu.kernels.flash_attention import _has_vma
    if jax.default_backend() == "cpu":
        interpret = True
    # the kernel reads a page as [heads * head_dim, page_len]: compiled,
    # a head's rows must fill whole tiles of the stored type (8 rows of
    # float32, 16 of bfloat16, 32 of int8)
    rows = 8 if interpret else 32 // k_pool.dtype.itemsize
    pallas_ok = (d % rows == 0 and page_len % 128 == 0)
    pt = jnp.asarray(page_table, jnp.int32)
    len32 = jnp.asarray(lengths, jnp.int32)
    if not pallas_ok or (interpret and _has_vma(q)) \
            or (not interpret and not mosaic_dtype_ok(q, k_pool, v_pool)):
        if write:
            page_ids, off = _write_slots(pt, len32, page_len, P)
            k_pool = _pool_write_tokens(k_pool, layer, page_ids, off, new_k)
            v_pool = _pool_write_tokens(v_pool, layer, page_ids, off, new_v)
        out = paged_decode_attention_reference(
            q, k_pool, v_pool, page_table, lengths, scale=scale,
            k_scale=k_scale, v_scale=v_scale, layer=layer)
        return (out, k_pool, v_pool) if write else out
    ks = vs = None
    if k_scale is not None:
        ks = jnp.asarray(k_scale, jnp.float32)
        vs = jnp.asarray(v_scale, jnp.float32)
    if layer is None:
        # one layer's [num_pages, heads, page_len, d] pool is relaid to
        # the stacked form the kernel reads: a pool-sized copy, the
        # price of the form tests and smoke runs use; serving hands over
        # the stacked pool
        k_pool, v_pool = (jnp.swapaxes(t, -1, -2)[None]
                          for t in (k_pool, v_pool))
    _, _, h_kv, _, _ = k_pool.shape
    pages = _pages_per_step(h_kv * d * page_len * k_pool.dtype.itemsize,
                            pt.shape[1])
    out = _paged_decode_pallas(q, k_pool, v_pool, pt, len32,
                               jnp.int32(layer or 0), ks, vs, new_k, new_v,
                               scale=float(scale), pages=pages,
                               interpret=interpret)
    if write:
        out, k_pool, v_pool = out
    live = (lengths > 0)[:, None, None]
    out = jnp.where(live, out, 0).astype(q.dtype)
    return (out, k_pool, v_pool) if write else out


# ------------------------------------------------- latent pages (MLA)
def mla_decode_attention_reference(q, pool, page_table, lengths, *,
                                   value_dim: int, scale: float = 1.0,
                                   layer=None):
    """fp32-math oracle of :func:`mla_decode_attention` without the
    write: gather the rows' latent pages, score every head's query
    against the whole row, take the value from its first ``value_dim``
    columns. Returns ``[b, h, value_dim]`` float32."""
    rows = gather_pages(pool, page_table, layer)          # [B, 1, L, d]
    return decode_attention_reference(
        jnp.asarray(q, jnp.float32), rows, rows[..., :value_dim], lengths,
        scale=scale)


def mla_decode_attention(q, pool, page_table, lengths, *, value_dim: int,
                         new_row=None, scale: float = 1.0, layer: int = 0,
                         interpret: bool = False):
    """Single-token ABSORBED latent attention against a paged pool of
    latent rows, and the write of that token's row into it.

    ``q`` ``[batch, heads, d]``: each head's query in the LATENT's
    coordinates, ``[W_kvb,k^T q_nope | q_rope]``; ``pool`` the stacked
    ``[layers, num_pages, 1, d, page_len]`` pool of the latent page kind
    (:class:`~apex_tpu.serving.kv_cache.CacheSpec`, ``value_dim`` > 0):
    one row ``[latent (value_dim) | rotary key]`` a token, key and value
    both, for all heads. Scores are ``scale * q . row``; the value is the
    row's first ``value_dim`` columns, so the result ``[batch, heads,
    value_dim]`` (float32) is ``sum_t p_t latent_t``, which the caller
    takes through ``W_kvb,v``. ``new_row`` ``[batch, d]`` (the pool's
    dtype) is written at position ``lengths[b] - 1`` first, in the
    kernel, as :func:`paged_decode_attention` writes K/V; the call then
    returns ``(out, pool)``.

    The Pallas path is :func:`_paged_decode_kernel` with ``vdim``: ONE
    fetch of a page serves both products (about ``4 heads`` operations a
    byte: 60 at 32 heads, where a grouped-head cell has 8). It runs
    under the name ``mla_decode_attention``. Unaligned shapes fall back
    to the XLA write and the oracle."""
    B, h, d = q.shape
    if pool.ndim != 5 or pool.shape[2] != 1 or pool.shape[3] != d \
            or not 0 < value_dim <= d:
        raise ValueError(f"mla_decode_attention: pool {pool.shape} must be "
                         f"[layers, num_pages, 1, {d}, page_len] with the "
                         f"value its first {value_dim} columns")
    P, page_len = pool.shape[1], pool.shape[4]
    write = new_row is not None
    if write and (new_row.shape != (B, d) or new_row.dtype != pool.dtype):
        raise ValueError(f"mla_decode_attention: new_row {new_row.dtype}"
                         f"{new_row.shape} must be {pool.dtype}[{B}, {d}]")
    from apex_tpu.kernels.flash_attention import _has_vma
    if jax.default_backend() == "cpu":
        interpret = True
    rows = 8 if interpret else 32 // pool.dtype.itemsize
    pallas_ok = d % rows == 0 and value_dim % rows == 0 \
        and page_len % 128 == 0
    pt = jnp.asarray(page_table, jnp.int32)
    len32 = jnp.asarray(lengths, jnp.int32)
    new = None if not write else new_row[:, None, :]
    if not pallas_ok or (interpret and _has_vma(q)) \
            or (not interpret and not mosaic_dtype_ok(q, pool)):
        if write:
            pool = _pool_write_tokens(
                pool, layer, *_write_slots(pt, len32, page_len, P), new)
        out = mla_decode_attention_reference(
            q, pool, pt, len32, value_dim=value_dim, scale=scale,
            layer=layer)
        return (out, pool) if write else out
    pages = _pages_per_step(d * page_len * pool.dtype.itemsize, pt.shape[1])
    out = _paged_decode_pallas(
        jnp.asarray(q, pool.dtype), pool, None, pt, len32, jnp.int32(layer),
        None, None, new, None, scale=float(scale), pages=pages,
        interpret=interpret, vdim=int(value_dim))
    if write:
        out, pool = out
    out = jnp.where((len32 > 0)[:, None, None], out, 0.0)
    return (out, pool) if write else out
