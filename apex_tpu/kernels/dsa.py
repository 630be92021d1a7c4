"""DeepSeek-Sparse-Attention (DSA) kernels: a lightning indexer over a
paged index-key cache, an exact top-k selection a query, and grouped-query
attention restricted to the selection.

The mechanism (DeepSeek-V3.2; :class:`~apex_tpu.models.keye.KeyeLM`):
every position ``s`` keeps ONE index key ``kI_s [d]`` shared by the
indexer's heads; query ``t`` brings ``nh`` index queries ``qI_{t,j}`` and
weights ``w_{t,j}``, scores every causal position

    ``I_{t,s} = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s * scale)``,

keeps the ``k`` largest (ties to the LOWER position: ``lax.top_k``'s
order) and attends only those with its ordinary query heads.

Layouts (the serving engine's, :class:`~apex_tpu.serving.kv_cache
.CacheSpec` with ``index_dim`` and ``row_pages``):

- the index pool ``[layers, num_pages, d, page_len]``: a page of index
  keys held transposed, ``page_len`` in the lanes (a 64-wide key there
  would pad every lane tile to twice its bytes);
- the K and V pools in the CONTIGUOUS-ROW layout ``[layers, num_pages,
  page_len, kv_heads * head_dim]``: one position's keys (values) of all
  heads are one row, so a selected position is one row to fetch, and at
  ``head_dim`` 128 every lane tile is full.

Kernels, each under its own name in a device trace. Each walks the
program's LIVE span, not its page table's: ``ceil((max_b q_pos[b] + Q) /
T)`` steps of ``T`` positions (:func:`live_steps`), the bound computed on
the device from ``q_pos``, so a context that fills a quarter of the table
costs a quarter of its steps:

- :func:`dsa_index` (``dsa_index``): the scores ``[B, Q, page_table
  span]`` float32 of ``Q`` queries a row against the row's LIVE index
  pages, read through the page table ``pages_per_step`` pages at a time
  (the pool handed once per page of a step, each with its own index map;
  steps past the row's last query position re-issue the last live page
  and fetch nothing). The ``nh`` heads are the rows of one product (row
  ``t * nh + j``), ReLU'd, weighted and summed in VMEM: no head's
  ``[Q, context]`` array reaches HBM, only their weighted sum. A score
  is ``-inf`` past its query's position up to the walk's end; the
  columns at or past ``live_steps x T`` are UNSPECIFIED (never written):
  every consumer below reads a column only at or before its query's
  position.
- :func:`dsa_select` (``dsa_select``): the exact k-th largest score of a
  row as a threshold, by bisection over the float's order bits (32
  counting passes over the row's live columns), then - only where a
  block's row has more ties at it than it keeps - the position of the
  last tie kept (bisection over the positions). A selection is the pair
  ``(thr, cut)``: position ``s <= pos`` is kept iff ``key(I_s) > thr`` or
  ``key(I_s) == thr and s <= cut`` (:func:`selected_mask`). A row with no
  more than ``k`` live positions keeps them all (``thr`` the least int).
  ``lax.top_k`` at 64k columns sorts; this fetches and converts a row
  block's live tiles only and reads them 34 times in VMEM (50 with ties
  to cut); a column past its row's position is never read as a score.
- :func:`dsa_attend` (``dsa_attend``): one token a row (decode) against
  the ``k`` positions gathered from the pool (:func:`gather_selected`),
  every query head of a K/V head in one product.
- :func:`dsa_attend_chunk` (``dsa_attend_chunk``): a chunk's queries
  against the chunk's LIVE context pages read once, the selection
  re-derived in VMEM from the scores as a mask (exact; its operations are
  the dense causal count, the work the result needs is the selected
  count - the roofline of the selected work says what a per-query
  gather would win).

Every kernel has a plain ``jnp`` twin (``*_reference``) that is its
oracle and its fallback where the geometry does not tile.

Their work as the benchmark counts it (``benchmarks/lib/work_keye.py``):
the indexer ``2 nh di`` operations a causal position scored and its index
keys once a decode row or a chunk (``2 di`` bytes a position), the
selection none; the attention ``4 nq d`` operations a KEPT position, a
decode row's kept K/V rows (``4 nk d`` bytes a position) or a chunk's live
context once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["order_key", "live_steps", "table_walk",
           "index_scores_reference", "dsa_index", "select_reference",
           "dsa_select", "selected_mask", "selected_positions",
           "gather_selected", "attend_reference", "dsa_attend",
           "attend_chunk_reference", "dsa_attend_chunk"]

_NEG_INF = -1e30                # the attention kernels' mask (finite)
_INT_MIN = np.int32(-2 ** 31)
INDEX_PAGES_PER_STEP = 4        # chunk; a decode row takes 16
INDEX_BLOCK_Q = 128
SELECT_ROWS = 8
SELECT_TILE = 2048
ATTEND_BLOCK_Q = 128
ATTEND_PAGES_PER_STEP = 4


def _interpret(interpret: bool) -> bool:
    return interpret or jax.default_backend() == "cpu"


def _dot(a, b, dims, widen):
    if widen:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def order_key(x):
    """float32 -> int32 with the same order (``-0.0`` taken as ``+0.0``):
    the selection's comparisons are on these keys."""
    b = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32) + 0.0,
                                     jnp.int32)
    return b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))


def live_steps(top, queries, step):
    """``ceil((top + queries) / step)``: the steps of ``step`` positions
    that hold every causal position of ``queries`` queries a row whose
    deepest row starts at position ``top`` (a host int or a traced
    scalar)."""
    return (top + queries + step - 1) // step


def _index_geometry(Q, mp, pages_per_step=None, block_q=None):
    """:func:`dsa_index`'s ``(pages a step, query block)``."""
    pages = pages_per_step or (INDEX_PAGES_PER_STEP if Q > 1 else 16)
    while mp % pages:
        pages //= 2
    return pages, min(block_q or INDEX_BLOCK_Q, Q)


def _attend_geometry(C, mp, pages_per_step=None, block_q=None):
    """:func:`dsa_attend_chunk`'s ``(pages a step, query block)``."""
    pages = pages_per_step or ATTEND_PAGES_PER_STEP
    while mp % pages:
        pages //= 2
    return pages, min(block_q or ATTEND_BLOCK_Q, C)


def table_walk(top, rows, queries, max_pages, page_len):
    """``(walked, tabled)``: the grid steps one layer's :func:`dsa_index`
    and, for a chunk (``queries > 1``), :func:`dsa_attend_chunk` take for
    ``rows`` rows of ``queries`` queries whose deepest row starts at
    position ``top``, and the steps a walk of the whole table of
    ``max_pages`` would take. Host integers (the engine's
    ``serving.dsa.steps_walked`` / ``steps_tabled``)."""
    geometries = [_index_geometry(queries, max_pages)]
    if queries > 1:
        geometries.append(_attend_geometry(queries, max_pages))
    walked = tabled = 0
    for pages, bq in geometries:
        steps = max_pages // pages
        blocks = rows * (queries // bq)
        walked += blocks * min(live_steps(top, queries, pages * page_len),
                               steps)
        tabled += blocks * steps
    return walked, tabled


# ------------------------------------------------------------------ indexer

def _span_keys(pool, page_table, layer):
    """The index keys of every row's table span ``[B, span, d]`` float32."""
    B, mp = page_table.shape
    d, pl_ = pool.shape[2], pool.shape[3]
    pages = pool[layer][page_table]                    # [B, mp, d, pl]
    return jnp.swapaxes(pages, 2, 3).reshape(B, mp * pl_, d).astype(
        jnp.float32)


def index_scores_reference(q, w, pool, page_table, q_pos, *, layer,
                           scale):
    """``q [B, Q, nh, d]``, ``w [B, Q, nh]`` float32, ``pool [layers, P,
    d, page_len]``, ``page_table [B, max_pages]``, ``q_pos [B]`` (query
    ``t`` of row ``b`` is at position ``q_pos[b] + t``) -> ``I [B, Q,
    max_pages * page_len]`` float32, ``-inf`` past each query's
    position."""
    keys = _span_keys(pool, page_table, layer)
    s = jnp.einsum("bqhd,bsd->bqhs", jnp.asarray(q, jnp.float32), keys,
                   precision=jax.lax.Precision.HIGHEST) * scale
    score = jnp.einsum("bqhs,bqh->bqs", jnp.maximum(s, 0.0),
                       jnp.asarray(w, jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    Q, span = score.shape[1], score.shape[2]
    pos = jnp.asarray(q_pos, jnp.int32)[:, None, None] \
        + jnp.arange(Q, dtype=jnp.int32)[None, :, None]
    cols = jnp.arange(span, dtype=jnp.int32)[None, None, :]
    return jnp.where(cols <= pos, score, -jnp.inf)


def _index_kernel(lyr_ref, pt_ref, pos_ref, q_ref, w_ref, *refs, scale,
                  heads, block_q, page_len, pages, widen):
    page_refs, o_ref = refs[:pages], refs[pages]
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    T = pages * page_len
    first = pos_ref[b] + i * block_q          # the block's first query

    @pl.when(j * T <= first + block_q - 1)
    def _live():
        kp = page_refs[0][...] if pages == 1 else jnp.concatenate(
            [r[...] for r in page_refs], axis=1)             # [d, T]
        s = _dot(q_ref[0], kp, ((1,), (0,)), widen) * scale  # [R, T]
        s = jnp.maximum(s, 0.0) * w_ref[0]                   # [R, 1]
        score = jnp.sum(s.reshape(block_q, heads, T), axis=1)
        rows = first + jax.lax.broadcasted_iota(jnp.int32, (block_q, T), 0)
        cols = j * T + jax.lax.broadcasted_iota(jnp.int32, (block_q, T), 1)
        o_ref[0] = jnp.where(cols <= rows, score, -jnp.inf)

    @pl.when(j * T > first + block_q - 1)
    def _dead():
        o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, o_ref.dtype)


def dsa_index(q, w, pool, page_table, q_pos, *, layer: int, scale: float,
              pages_per_step: Optional[int] = None,
              block_q: Optional[int] = None, interpret: bool = False):
    """The indexer's scores (module docstring): ``q [B, Q, nh, d]`` (the
    pool's dtype), ``w [B, Q, nh]``, ``pool [layers, P, d, page_len]``,
    ``page_table [B, max_pages]``, ``q_pos [B]`` -> ``[B, Q, max_pages *
    page_len]`` float32 (``-inf`` past a query's position; the columns at
    or past ``live_steps(max(q_pos), Q, T) x T``, ``T`` the positions of
    a step, unspecified). Runs under the name ``dsa_index``."""
    B, Q, nh, d = q.shape
    page_len = pool.shape[3]
    pages, bq = _index_geometry(Q, page_table.shape[1], pages_per_step,
                                block_q)
    interpret = _interpret(interpret)
    if Q % bq or (bq * nh) % 8 or page_len % 128 or d % 8:
        return index_scores_reference(q, w, pool, page_table, q_pos,
                                      layer=layer, scale=scale)
    return _index_pallas(
        jnp.full((1,), layer, jnp.int32), jnp.asarray(page_table, jnp.int32),
        jnp.asarray(q_pos, jnp.int32),
        jnp.asarray(q, pool.dtype).reshape(B, Q * nh, d),
        jnp.asarray(w, jnp.float32).reshape(B, Q * nh, 1), pool,
        scale=float(scale), heads=nh, block_q=bq, pages=pages,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "heads", "block_q",
                                             "pages", "interpret"))
def _index_pallas(layer, pt, pos, q, w, pool, *, scale, heads, block_q,
                  pages, interpret):
    """:func:`dsa_index`'s call, the layer an operand: one trace for all
    of a model's layers."""
    B, R, d = q.shape
    Q, bq = R // heads, block_q
    mp, page_len = pt.shape[1], pool.shape[3]
    steps = jnp.minimum(live_steps(jnp.max(pos), Q, pages * page_len),
                        mp // pages)
    kernel = functools.partial(
        _index_kernel, scale=scale, heads=heads, block_q=bq,
        page_len=page_len, pages=pages, widen=interpret)

    def page_spec(x):
        def index(b, i, j, lyr, pt, pos):
            last = (pos[b] + (i + 1) * bq - 1) // page_len
            return (lyr[0], pt[b, jnp.minimum(j * pages + x, last)], 0, 0)
        return pl.BlockSpec((None, None, d, page_len), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # layer, page_table, q_pos
        grid=(B, Q // bq, steps),
        in_specs=[pl.BlockSpec((1, bq * heads, d),
                               lambda b, i, j, lyr, pt, pos: (b, i, 0)),
                  pl.BlockSpec((1, bq * heads, 1),
                               lambda b, i, j, lyr, pt, pos: (b, i, 0))]
        + [page_spec(x) for x in range(pages)],
        out_specs=pl.BlockSpec((1, bq, pages * page_len),
                               lambda b, i, j, lyr, pt, pos: (b, i, j)))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q, mp * page_len), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret, name="dsa_index",
    )(layer, pt, pos, q, w, *([pool] * pages))


# ---------------------------------------------------------------- selection

def select_reference(scores, pos, k: int):
    """The exact top-``k`` of each row of ``scores [N, span]`` over its
    live columns ``s <= pos[n]``, as ``(thr, cut)`` int32 ``[N]``
    (:func:`selected_mask`): ``lax.top_k`` (which keeps the lower index of
    a tie) and the k-th value's key; a row of ``k`` or fewer live columns
    keeps them all."""
    scores = jnp.asarray(scores, jnp.float32)
    N, span = scores.shape
    pos = jnp.asarray(pos, jnp.int32)
    cols = jnp.arange(span, dtype=jnp.int32)[None]
    live = cols <= pos[:, None]
    x = jnp.where(live, scores, -jnp.inf)
    kk = min(k, span)
    vals, idx = jax.lax.top_k(x, kk)
    thr = order_key(vals[:, -1])
    # the kept ties are the lowest positions: the last kept one is the
    # largest index among the selected ties
    ties = jnp.where(order_key(vals) == thr[:, None], idx, -1)
    cut = jnp.max(ties, axis=1)
    few = pos + 1 <= k
    return jnp.where(few, _INT_MIN, thr), jnp.where(few, span, cut)


def selected_mask(scores, thr, cut, pos):
    """``[..., span]`` bool: the kept columns of each row (``scores [...,
    span]``, ``thr, cut, pos [...]``)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, scores.shape,
                                    scores.ndim - 1)
    key = order_key(scores)
    thr, cut, pos = (jnp.asarray(a, jnp.int32)[..., None]
                     for a in (thr, cut, pos))
    return (cols <= pos) & ((key > thr) | ((key == thr) & (cols <= cut)))


def _select_kernel(pos_s, pos_ref, s_hbm, thr_ref, cut_ref, buf, key_ref,
                   sem, *, k, rows, tile, nbits):
    """Grid ``(N // rows,)``, in order: a step selects one block of
    ``rows`` rows. Its live tiles (those up to the block's deepest
    position) are DMAed from HBM into ``buf`` as at most ``log2(span /
    tile) + 1`` runs of ``2^x`` tiles, a semaphore each - the next
    block's while this block's passes run - and each run is converted to
    order keys in ``key_ref``, a column past its row's position taken as
    the least int: nothing past a row's position is read as a score."""
    i = pl.program_id(0)
    span = key_ref.shape[1]
    sizes = [1 << x for x in reversed(range((span // tile).bit_length()))]

    def live_tiles(blk):
        top = pos_s[blk * rows]
        for r in range(1, rows):
            top = jnp.maximum(top, pos_s[blk * rows + r])
        return jnp.minimum(top // tile + 1, span // tile)

    def runs(blk, act):
        """``act(first column, width, x)`` for each run of block
        ``blk``'s live tiles: run ``x`` is ``sizes[x]`` tiles wide."""
        n, lo = live_tiles(blk), jnp.int32(0)
        for x, size in enumerate(sizes):
            @pl.when((n & size) != 0)
            def _run(lo=lo, x=x, size=size):
                act(lo, size * tile, x)
            lo = lo + jnp.where((n & size) != 0, size * tile, 0)

    def copy(blk, lo, width, x):
        cols = pl.ds(pl.multiple_of(lo, tile), width)
        return pltpu.make_async_copy(s_hbm.at[pl.ds(blk * rows, rows), cols],
                                     buf.at[:, cols], sem.at[x])

    @pl.when(i == 0)
    def _first():
        runs(0, lambda lo, width, x: copy(0, lo, width, x).start())

    def fill(lo, width, x):
        copy(i, lo, width, x).wait()
        cols = pl.ds(pl.multiple_of(lo, tile), width)
        at = lo + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        key_ref[:, cols] = jnp.where(at <= pos_ref[...],
                                     order_key(buf[:, cols]), _INT_MIN)

    runs(i, fill)

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        runs(i + 1, lambda lo, width, x: copy(i + 1, lo, width, x).start())

    ntiles = live_tiles(i)

    def count(pred):
        def body(c, acc):
            start = pl.multiple_of(c * tile, tile)
            kt = key_ref[:, pl.ds(start, tile)]
            cols = start + jax.lax.broadcasted_iota(jnp.int32, kt.shape, 1)
            return acc + pred(kt, cols).astype(jnp.float32)
        # float32 counts: exact below 2^24 columns
        acc = jax.lax.fori_loop(0, ntiles, body,
                                jnp.zeros((rows, tile), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)            # [rows, 1]

    t = jnp.where(count(lambda kt, c: kt >= 0) >= k, jnp.int32(0),
                  jnp.int32(_INT_MIN))

    def value_bit(i, t):             # the largest t with >= k keys >= t
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(lambda kt, c: kt >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 31, value_bit, t)
    m = k - count(lambda kt, c: kt > t)            # ties to keep (>= 1)
    few = pos_ref[...] + 1 <= k

    def position_bit(i, p):          # the position of the m-th tie
        cand = p | jnp.left_shift(jnp.int32(1), nbits - 1 - i)
        n = count(lambda kt, c: (kt == t) & (c < cand))
        return jnp.where(n < m, cand, p)

    # a row whose ties at t are all kept needs no cut; the bisection over
    # positions runs only where some row of the block has more
    over = (count(lambda kt, c: kt == t) > m) & ~few
    p = jax.lax.cond(
        jnp.max(over.astype(jnp.float32)) > 0,
        lambda: jnp.where(over, jax.lax.fori_loop(
            0, nbits, position_bit, jnp.zeros_like(t)), span),
        lambda: jnp.full_like(t, span))
    thr_ref[...] = jnp.where(few, jnp.int32(_INT_MIN), t)
    cut_ref[...] = jnp.where(few, jnp.int32(span), p)


def dsa_select(scores, pos, k: int, *, interpret: bool = False):
    """:func:`select_reference` as a kernel: ``scores [N, span]`` float32,
    read only at or before each row's position ``pos [N]`` (what lies past
    it, ``-inf``, NaN or never written, is not read as a score) -> ``(thr,
    cut)`` int32 ``[N]``. Runs under the name ``dsa_select``."""
    N, span = scores.shape
    interpret = _interpret(interpret)
    rows = SELECT_ROWS if N % SELECT_ROWS == 0 else N
    tile = min(SELECT_TILE, span)
    if span % tile or tile % 128 or span < k:
        return select_reference(scores, pos, k)
    return _select_pallas(jnp.asarray(pos, jnp.int32),
                          jnp.asarray(scores, jnp.float32), k=int(k),
                          rows=rows, tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "rows", "tile",
                                             "interpret"))
def _select_pallas(pos, scores, *, k, rows, tile, interpret):
    N, span = scores.shape
    kernel = functools.partial(_select_kernel, k=k, rows=rows,
                               tile=tile, nbits=max(1, (span - 1)
                                                    .bit_length()))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // rows,),
        in_specs=[pl.BlockSpec((rows, 1), lambda i, p: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((rows, 1), lambda i, p: (i, 0))] * 2,
        scratch_shapes=[pltpu.VMEM((rows, span), jnp.float32),
                        pltpu.VMEM((rows, span), jnp.int32),
                        pltpu.SemaphoreType.DMA(
                            ((span // tile).bit_length(),))])
    thr, cut = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            # in order: a step starts the next block's DMAs
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret, name="dsa_select",
    )(pos, pos[:, None], scores)
    return thr[:, 0], cut[:, 0]


def selected_positions(scores, thr, cut, pos, k: int, tile: int = 128):
    """A decode row's kept positions in order, ``([N, k] int32, [N]
    count)``, in two levels: the ``tile``-wide stretch that holds the
    ``i``-th kept position (counts of the stretches before it), then its
    place in that stretch (a running count over the stretch alone) - a
    few dense operations over ``[N, k, span / tile]`` and ``[N, k,
    tile]``, where a search over the row's running count is a loop of
    gathers. Slots past the count hold a position past the last kept one
    (clipped to the span) and are masked by the caller."""
    mask = selected_mask(scores, thr, cut, pos).astype(jnp.int32)
    N, span = mask.shape
    tile = min(tile, span)
    bits = mask.reshape(N, span // tile, tile)
    per = jnp.sum(bits, -1)                                  # [N, tiles]
    before = jnp.cumsum(per, -1) - per                       # nondecreasing
    rank = jnp.arange(k, dtype=jnp.int32)[None, :, None]     # 0-based
    le = before[:, None, :] <= rank                          # [N, k, tiles]
    where = jnp.sum(le, -1) - 1
    # before[where], as a reduction (a gather of single values is slow)
    r = rank[..., 0] - jnp.max(jnp.where(le, before[:, None, :], 0), -1)
    run = jnp.cumsum(jnp.take_along_axis(bits, where[..., None], 1), -1)
    idx = where * tile + jnp.sum(run <= r[..., None], -1)
    return jnp.minimum(idx, span - 1).astype(jnp.int32), jnp.sum(per, -1)


def gather_selected(pool, page_table, idx, layer):
    """Rows ``[B, k, width]`` of the contiguous-row ``pool [layers, P,
    page_len, width]`` at the logical positions ``idx [B, k]`` of each
    row's table: only the selected positions are read."""
    L, P, page_len, width = pool.shape
    # page_table[b, idx // page_len], as a reduction over the table (a
    # gather of single values is slow)
    slots = jnp.arange(page_table.shape[1], dtype=jnp.int32)
    page = jnp.sum(jnp.where((idx // page_len)[..., None] == slots,
                             page_table[:, None, :], 0), -1)
    flat = (layer * P + page) * page_len + idx % page_len
    return pool.reshape(L * P * page_len, width)[flat]


# ---------------------------------------------------------------- attention

def attend_reference(q, k_sel, v_sel, n_sel, *, scale):
    """``q [B, nq, d]`` against ``k_sel, v_sel [B, K, nk * d]`` of which
    the first ``n_sel [B]`` are live -> ``[B, nq, d]`` float32 (query head
    ``h`` reads K/V head ``h // (nq // nk)``)."""
    B, nq, d = q.shape
    K, W = k_sel.shape[1], k_sel.shape[2]
    nk = W // d
    qf = jnp.asarray(q, jnp.float32).reshape(B, nk, nq // nk, d)
    kf = jnp.asarray(k_sel, jnp.float32).reshape(B, K, nk, d)
    vf = jnp.asarray(v_sel, jnp.float32).reshape(B, K, nk, d)
    s = jnp.einsum("bgrd,bkgd->bgrk", qf, kf,
                   precision=jax.lax.Precision.HIGHEST) * scale
    live = jnp.arange(K)[None, :] < jnp.asarray(n_sel)[:, None]
    s = jnp.where(live[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", p, vf,
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(B, nq, d)


def _attend_kernel(n_ref, q_ref, k_ref, v_ref, o_ref, *, scale, groups,
                   d, widen):
    b = pl.program_id(0)
    K = k_ref.shape[1]
    live = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1) < n_ref[b]
    for g in range(groups):
        kg = k_ref[0, :, g * d:(g + 1) * d]                  # [K, d]
        vg = v_ref[0, :, g * d:(g + 1) * d]
        s = _dot(q_ref[0, g], kg, ((1,), (1,)), widen) * scale   # [G, K]
        s = jnp.where(live, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = _dot(p.astype(vg.dtype), vg, ((1,), (0,)), widen)    # [G, d]
        o_ref[0, g] = (o / l).astype(o_ref.dtype)


def dsa_attend(q, k_sel, v_sel, n_sel, *, scale: float,
               interpret: bool = False):
    """:func:`attend_reference` as a kernel (one grid step a row, every
    K/V head's query heads one product). Runs under the name
    ``dsa_attend``."""
    B, nq, d = q.shape
    K, W = k_sel.shape[1], k_sel.shape[2]
    nk = W // d
    G = nq // nk
    interpret = _interpret(interpret)
    if d % 128 or K % 128 or G % 8:
        return attend_reference(q, k_sel, v_sel, n_sel, scale=scale)
    out = _attend_pallas(jnp.asarray(n_sel, jnp.int32),
                         jnp.asarray(q, k_sel.dtype).reshape(B, nk, G, d),
                         k_sel, v_sel, scale=float(scale),
                         interpret=interpret)
    return out.reshape(B, nq, d)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _attend_pallas(n_sel, q, k_sel, v_sel, *, scale, interpret):
    B, nk, G, d = q.shape
    K, W = k_sel.shape[1], k_sel.shape[2]
    kernel = functools.partial(_attend_kernel, scale=scale, groups=nk, d=d,
                               widen=interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, nk, G, d), lambda b, n: (b, 0, 0, 0)),
                  pl.BlockSpec((1, K, W), lambda b, n: (b, 0, 0)),
                  pl.BlockSpec((1, K, W), lambda b, n: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, nk, G, d), lambda b, n: (b, 0, 0, 0)))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nk, G, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=64 << 20),
        interpret=interpret, name="dsa_attend",
    )(n_sel, q, k_sel, v_sel)


def attend_chunk_reference(q, k_pool, v_pool, page_table, q_pos, scores,
                           thr, cut, *, layer, scale):
    """``q [B, C, nq, d]`` against each row's table span of the
    contiguous-row pools, restricted to the selection ``(thr, cut)
    [B, C]`` over ``scores [B, C, span]`` -> ``[B, C, nq, d]``
    float32."""
    B, C, nq, d = q.shape
    L, P, page_len, W = k_pool.shape
    nk = W // d
    span = page_table.shape[1] * page_len
    kf = k_pool[layer][page_table].reshape(B, span, nk, d).astype(
        jnp.float32)
    vf = v_pool[layer][page_table].reshape(B, span, nk, d).astype(
        jnp.float32)
    pos = jnp.asarray(q_pos, jnp.int32)[:, None] \
        + jnp.arange(C, dtype=jnp.int32)[None]
    keep = selected_mask(scores, thr, cut, pos)               # [B, C, S]
    qf = jnp.asarray(q, jnp.float32).reshape(B, C, nk, nq // nk, d)
    s = jnp.einsum("bcgrd,bsgd->bcgrs", qf, kf,
                   precision=jax.lax.Precision.HIGHEST) * scale
    s = jnp.where(keep[:, :, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bcgrs,bsgd->bcgrd", p, vf,
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(B, C, nq, d)


def _attend_chunk_kernel(lyr_ref, pos_ref, pt_ref, q_ref, s_ref, thr_ref,
                         cut_ref, *refs, scale, groups, G, d, block_q,
                         page_len, pages, widen):
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * pages:]
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    T = pages * page_len
    first = pos_ref[b] + i * block_q

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * T <= first + block_q - 1)
    def _body():
        kk = k_refs[0][...] if pages == 1 else jnp.concatenate(
            [r[...] for r in k_refs], axis=0)                # [T, W]
        vv = v_refs[0][...] if pages == 1 else jnp.concatenate(
            [r[...] for r in v_refs], axis=0)
        key = order_key(s_ref[0])                            # [bq, T]
        rows = first + jax.lax.broadcasted_iota(jnp.int32, (block_q, T), 0)
        cols = j * T + jax.lax.broadcasted_iota(jnp.int32, (block_q, T), 1)
        thr, cut = thr_ref[0], cut_ref[0]                    # [bq, 1]
        keep = (cols <= rows) & ((key > thr) | ((key == thr)
                                                & (cols <= cut)))
        # the kept columns as an additive bias, added to every head's
        # rows of a group at once (no [G bq, T] mask is built)
        bias = jnp.where(keep, 0.0, _NEG_INF)[None]          # [1, bq, T]
        for g in range(groups):
            s = _dot(q_ref[0, g], kk[:, g * d:(g + 1) * d], ((1,), (1,)),
                     widen) * scale                          # [G bq, T]
            s = (s.reshape(G, block_q, T) + bias).reshape(G * block_q, T)
            m_prev = m_ref[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = jnp.broadcast_to(
                alpha * l_ref[g][:, :1] + jnp.sum(p, axis=-1,
                                                  keepdims=True),
                l_ref.shape[1:])
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            acc_ref[g] = acc_ref[g] * alpha + _dot(
                p.astype(vv.dtype), vv[:, g * d:(g + 1) * d],
                ((1,), (0,)), widen)

    @pl.when(j == nj - 1)
    def _finish():
        for g in range(groups):
            l = l_ref[g][:, :1]
            o_ref[0, g] = (acc_ref[g] / jnp.where(l == 0.0, 1.0, l)
                           ).astype(o_ref.dtype)


def dsa_attend_chunk(q, k_pool, v_pool, page_table, q_pos, scores, thr,
                     cut, *, layer: int, scale: float,
                     block_q: Optional[int] = None,
                     pages_per_step: Optional[int] = None,
                     interpret: bool = False):
    """:func:`attend_chunk_reference` as a kernel: grid (row, query
    block, page step); a step reads ``pages_per_step`` pages of K and V
    once for every query head, the kept columns re-derived from the
    step's scores, online softmax across steps; the walk ends at the
    program's live span (:func:`live_steps`), and steps past the block's
    last query fetch nothing. ``scores`` is read only at or before each
    query's position. Runs under the name ``dsa_attend_chunk``."""
    B, C, nq, d = q.shape
    page_len, W = k_pool.shape[2], k_pool.shape[3]
    nk = W // d
    G = nq // nk
    pages, bq = _attend_geometry(C, page_table.shape[1], pages_per_step,
                                 block_q)
    interpret = _interpret(interpret)
    if C % bq or bq % 8 or d % 128 or page_len % 128:
        return attend_chunk_reference(
            q, k_pool, v_pool, page_table, q_pos, scores, thr, cut,
            layer=layer, scale=scale)
    # query head h = g G + r of token t is row r bq + t of group g's block
    qg = jnp.asarray(q, k_pool.dtype).reshape(B, C // bq, bq, nk, G, d)
    qg = qg.transpose(0, 1, 3, 4, 2, 5).reshape(B, C // bq * nk, G * bq, d)
    out = _attend_chunk_pallas(
        jnp.full((1,), layer, jnp.int32), jnp.asarray(q_pos, jnp.int32),
        jnp.asarray(page_table, jnp.int32), qg,
        jnp.asarray(scores, jnp.float32),
        jnp.asarray(thr, jnp.int32)[..., None],
        jnp.asarray(cut, jnp.int32)[..., None], k_pool, v_pool,
        scale=float(scale), groups=nk, block_q=bq, pages=pages,
        interpret=interpret)
    out = out.reshape(B, C // bq, nk, G, bq, d).transpose(0, 1, 4, 2, 3, 5)
    return out.reshape(B, C, nq, d)


@functools.partial(jax.jit, static_argnames=("scale", "groups", "block_q",
                                             "pages", "interpret"))
def _attend_chunk_pallas(layer, pos, pt, qg, scores, thr, cut, k_pool,
                         v_pool, *, scale, groups, block_q, pages,
                         interpret):
    """:func:`dsa_attend_chunk`'s call, the layer an operand: one trace
    for all of a model's layers."""
    B, _, GB, d = qg.shape
    nk, bq = groups, block_q
    G, C = GB // bq, scores.shape[1]
    mp = pt.shape[1]
    page_len, W = k_pool.shape[2], k_pool.shape[3]
    T = pages * page_len
    steps = jnp.minimum(live_steps(jnp.max(pos), C, T), mp // pages)
    kernel = functools.partial(
        _attend_chunk_kernel, scale=scale, groups=nk, G=G, d=d,
        block_q=bq, page_len=page_len, pages=pages, widen=interpret)

    def page_spec(x):
        def index(b, i, j, lyr, pos, pt):
            last = (pos[b] + (i + 1) * bq - 1) // page_len
            return (lyr[0], pt[b, jnp.minimum(j * pages + x, last)], 0, 0)
        return pl.BlockSpec((None, None, page_len, W), index)

    def score_index(b, i, j, lyr, pos, pt):
        last = (pos[b] + (i + 1) * bq - 1) // T
        return (b, i, jnp.minimum(j, last))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # layer, q_pos, page_table
        grid=(B, C // bq, steps),
        in_specs=[pl.BlockSpec((1, nk, G * bq, d),
                               lambda b, i, j, lyr, pos, pt: (b, i, 0, 0)),
                  pl.BlockSpec((1, bq, T), score_index),
                  pl.BlockSpec((1, bq, 1), lambda b, i, j, lyr, pos, pt:
                               (b, i, 0)),
                  pl.BlockSpec((1, bq, 1), lambda b, i, j, lyr, pos, pt:
                               (b, i, 0))]
        + [page_spec(x) for x in range(pages)] * 2,
        out_specs=pl.BlockSpec((1, nk, G * bq, d),
                               lambda b, i, j, lyr, pos, pt: (b, i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((nk, G * bq, d), jnp.float32),
                        pltpu.VMEM((nk, G * bq, 128), jnp.float32),
                        pltpu.VMEM((nk, G * bq, 128), jnp.float32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C // bq * nk, G * bq, d),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=96 << 20),
        interpret=interpret, name="dsa_attend_chunk",
    )(layer, pos, pt, qg, scores, thr, cut, *([k_pool] * pages),
      *([v_pool] * pages))
