"""The DeepSeek-Sparse-Attention kernels (kernels/dsa.py) in interpret mode
against their plain twins, at small shapes that keep every kernel on its
Pallas path: a chunk of 128 queries and single decode rows over tables of
four 128-position pages, live context ending mid-table, and over tables
of sixteen whose live context ends in page 3, so each kernel's walk stops
at the live span and leaves the indexer's tail unwritten (the
interpreter leaves an unwritten output NaN). The selection kernel is held
to ``lax.top_k`` here and in test_keye_model.py: its ``(thr, cut)`` are
``select_reference``'s bit for bit.

Tolerances, and why: the indexer's scores are the same float32 sums of
float32 products (agreement to 1e-6); the attention kernels add
probabilities a page step at a time (online softmax) where the twin adds
them in one softmax - outputs (scale 1) agree to 1e-5 in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.kernels import dsa

L, P, D, PL = 2, 9, 64, 128
PT = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)


def _normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


def _walked(Q, q_pos, pt, pages=None):
    """The columns the indexer's walk defines: ``live_steps x T``."""
    pages = dsa._index_geometry(Q, pt.shape[1], pages)[0]
    return dsa.live_steps(max(q_pos), Q, pages * PL) * pages * PL


def _index(Q, q_pos, pt, pages=None):
    pool = _normal(0, (L, 2 * P, D, PL))
    q, w = _normal(1, (2, Q, 8, D)), _normal(2, (2, Q, 8))
    pos = jnp.asarray(q_pos, jnp.int32)
    got = dsa.dsa_index(q, w, pool, pt, pos, layer=1, scale=0.125,
                        pages_per_step=pages)
    want = dsa.index_scores_reference(q, w, pool, pt, pos, layer=1,
                                      scale=0.125)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("Q, q_pos", [(128, (0, 256)), (1, (5, 400))])
def test_the_indexer_scores_the_live_pages_of_its_table(Q, q_pos):
    got, want = _index(Q, q_pos, PT)
    end = _walked(Q, q_pos, PT)
    got, want = got[..., :end], want[..., :end]
    live = np.isfinite(want)
    assert (np.isneginf(got) == ~live).all()
    np.testing.assert_allclose(got[live], want[live], rtol=1e-6, atol=1e-6)


# sixteen pages a row, the live context ending in page 3 (positions
# 384-511): the walk's tail is twelve pages
PT16 = jnp.asarray([np.arange(1, 17), np.arange(17, 33)], jnp.int32)
CHUNK, DECODE = (128, (0, 300)), (1, (5, 400))


@pytest.mark.parametrize("top, queries, step, want", [
    (300, 128, 512, 1),           # a chunk inside the first step
    (384, 128, 512, 1),           # ... ending on its last position
    (385, 128, 512, 2),           # ... one position into the next
    (0, 1024, 512, 2),            # a whole first chunk
    (16384, 1024, 512, 34),       # a chunk at 16k of 64k
    (64512, 1024, 512, 128),      # the table's last chunk: every step
    (400, 1, 2048, 1),            # decode rows inside the first step
    (2047, 1, 2048, 1),
    (2048, 1, 2048, 2),
    (65535, 1, 2048, 32)])
def test_the_walk_is_the_steps_that_hold_the_deepest_query(top, queries,
                                                           step, want):
    assert dsa.live_steps(top, queries, step) == want
    assert int(dsa.live_steps(jnp.int32(top), queries, step)) == want


@pytest.mark.parametrize("top, rows, queries, want", [
    # a chunk at 16k of a 512-page table: 8 query blocks x 34 of 128
    # steps, the indexer's and the attention's
    (16384, 1, 1024, (2 * 8 * 34, 2 * 8 * 128)),
    # 8 decode rows, the deepest at 16k - 1: the indexer alone, 8 of 32
    (16383, 8, 1, (8 * 8, 8 * 32))])
def test_the_table_walk_counts_the_kernels_grid_steps(top, rows, queries,
                                                      want):
    assert dsa.table_walk(top, rows, queries, 512, 128) == want


@pytest.mark.parametrize("Q, q_pos, pages", [CHUNK + (None,),
                                             DECODE + (None,),
                                             DECODE + (4,)])
def test_the_indexer_walks_only_the_live_span(Q, q_pos, pages):
    """Exact agreement and ``-inf`` past each query's position on every
    column the walk defines; beyond it (twelve pages, or none where a
    decode step of sixteen pages covers the table) nothing is written."""
    got, want = _index(Q, q_pos, PT16, pages)
    end = _walked(Q, q_pos, PT16, pages)
    assert end == (512 if pages or Q > 1 else 2048)
    head, tail = got[..., :end], got[..., end:]
    live = np.isfinite(want[..., :end])
    assert (np.isneginf(head) == ~live).all()
    np.testing.assert_allclose(head[live], want[..., :end][live],
                               rtol=1e-6, atol=1e-6)
    assert not np.isneginf(tail).any()
    assert np.isneginf(want[..., end:]).all()


def _top_k_mask(scores, pos, k):
    _, idx = jax.lax.top_k(jnp.where(
        jnp.arange(scores.shape[-1])[None] <= pos[:, None], scores,
        -jnp.inf), k)
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, 1)
    return want & (np.arange(scores.shape[-1])[None]
                   <= np.asarray(pos)[:, None])


@pytest.mark.parametrize("Q, q_pos", [CHUNK, DECODE])
def test_the_selection_over_the_live_span_is_top_ks(Q, q_pos):
    """On the indexer's own scores, unwritten tail and all: ``thr`` is
    ``select_reference``'s (``lax.top_k``'s k-th key) exactly, ``cut`` its
    last kept tie where a row has more ties at ``thr`` than it keeps and
    the span elsewhere, and the pair keeps ``lax.top_k``'s positions."""
    got, _ = _index(Q, q_pos, PT16)
    scores = jnp.asarray(got.reshape(2 * Q, -1))
    span = scores.shape[1]
    pos = (jnp.asarray(q_pos, jnp.int32)[:, None]
           + jnp.arange(Q, dtype=jnp.int32)[None]).reshape(-1)
    thr, cut = dsa.dsa_select(scores, pos, 64)
    want_thr, want_cut = dsa.select_reference(scores, pos, 64)
    np.testing.assert_array_equal(np.asarray(thr), np.asarray(want_thr))
    live = np.arange(span)[None] <= np.asarray(pos)[:, None]
    key = np.where(live, np.asarray(dsa.order_key(scores)), dsa._INT_MIN)
    t = np.asarray(want_thr)[:, None]
    cuts = ((key == t).sum(1) > 64 - (key > t).sum(1)) \
        & (np.asarray(pos) >= 64)
    np.testing.assert_array_equal(
        np.asarray(cut), np.where(cuts, np.asarray(want_cut), span))
    kept = np.asarray(dsa.selected_mask(scores, thr, cut, pos))
    assert (kept == _top_k_mask(scores, pos, 64)).all()


def _selection(scores, pos, k=64):
    B, Q, span = scores.shape
    rows = (pos[:, None] + jnp.arange(Q)[None]).reshape(-1)
    thr, cut = dsa.select_reference(scores.reshape(B * Q, span), rows, k)
    return thr.reshape(B, Q), cut.reshape(B, Q)


def test_the_chunk_attends_its_selection_over_the_live_span():
    """The chunk against a sixteen-page table on the indexer kernel's own
    scores (their tail unwritten): the kernel walks one step of four
    pages, the twin the whole table."""
    pool_k = _normal(4, (L, 2 * P, PL, 128))
    pool_v = _normal(5, (L, 2 * P, PL, 128))
    q = _normal(6, (2, 128, 8, 128))
    Q, q_pos = CHUNK
    scores, _ = _index(Q, q_pos, PT16)
    pos = jnp.asarray(q_pos, jnp.int32)
    thr, cut = _selection(jnp.asarray(scores), pos)
    got = dsa.dsa_attend_chunk(q, pool_k, pool_v, PT16, pos, scores, thr,
                               cut, layer=1, scale=0.088)
    want = dsa.attend_chunk_reference(q, pool_k, pool_v, PT16, pos, scores,
                                      thr, cut, layer=1, scale=0.088)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def _poisoned(scores, pos, value, where):
    """``scores [B, Q, span]`` with ``value`` past the walk's end (the
    live span) or past each query's own position."""
    B, Q, span = scores.shape
    rows = np.asarray(pos)[:, None, None] + np.arange(Q)[None, :, None]
    cols = np.arange(span)[None, None]
    past = cols >= _walked(Q, tuple(np.asarray(pos)), PT16) \
        if where == "span" else cols > rows
    return jnp.asarray(np.where(past, np.float32(value), scores))


@pytest.mark.parametrize("where", ["span", "position"])
@pytest.mark.parametrize("value", [np.nan, np.inf])
def test_what_lies_past_the_live_span_is_never_read(value, where):
    """The consumers of the indexer's scores - the selection, a decode
    row's kept positions and the chunk's masked attention - give results
    bit for bit those of ``-inf`` when the columns past the live span
    (or past each query's position) hold NaN or ``+inf``."""
    pool_k = _normal(4, (L, 2 * P, PL, 128))
    pool_v = _normal(5, (L, 2 * P, PL, 128))
    for (Q, q_pos), k in ((CHUNK, 64), (DECODE, 64)):
        pos = jnp.asarray(q_pos, jnp.int32)
        clean = jnp.asarray(_index(Q, q_pos, PT16)[1])    # -inf tails
        dirty = _poisoned(clean, pos, value, where)
        rows = (pos[:, None] + jnp.arange(Q)[None]).reshape(-1)
        sel = [dsa.dsa_select(s.reshape(2 * Q, -1), rows, k)
               for s in (clean, dirty)]
        for a, b in zip(*sel):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        thr, cut = (x.reshape(2, Q) for x in sel[0])
        if Q == 1:
            got = [dsa.selected_positions(s[:, 0], thr[:, 0], cut[:, 0],
                                          pos, k) for s in (clean, dirty)]
        else:
            q = _normal(6, (2, Q, 8, 128))
            got = [dsa.dsa_attend_chunk(q, pool_k, pool_v, PT16, pos, s,
                                        thr, cut, layer=1, scale=0.088)
                   for s in (clean, dirty)]
        for a, b in zip(jax.tree_util.tree_leaves(got[0]),
                        jax.tree_util.tree_leaves(got[1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_chunk_attends_its_selection_over_the_live_pages():
    pool_k = _normal(4, (L, P, PL, 128))
    pool_v = _normal(5, (L, P, PL, 128))
    q = _normal(6, (2, 128, 8, 128))
    pos = jnp.asarray([0, 256], jnp.int32)
    scores = dsa.index_scores_reference(
        _normal(1, (2, 128, 8, D)), _normal(2, (2, 128, 8)),
        _normal(0, (L, P, D, PL)), PT, pos, layer=1, scale=0.125)
    thr, cut = _selection(scores, pos)
    got = dsa.dsa_attend_chunk(q, pool_k, pool_v, PT, pos, scores, thr, cut,
                               layer=1, scale=0.088)
    want = dsa.attend_chunk_reference(q, pool_k, pool_v, PT, pos, scores,
                                      thr, cut, layer=1, scale=0.088)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_a_decode_row_gathers_and_attends_only_its_selection():
    """The kept positions in order, their rows gathered from the pool, and
    the kernel over them equal the chunk twin's one-query answer."""
    pool_k = _normal(4, (L, P, PL, 256))
    pool_v = _normal(5, (L, P, PL, 256))
    q = _normal(6, (2, 1, 16, 128))
    pos = jnp.asarray([30, 400], jnp.int32)
    scores = dsa.index_scores_reference(
        _normal(1, (2, 1, 8, D)), _normal(2, (2, 1, 8)),
        _normal(0, (L, P, D, PL)), PT, pos, layer=1, scale=0.125)
    thr, cut = _selection(scores, pos)
    idx, n = dsa.selected_positions(scores[:, 0], thr[:, 0], cut[:, 0], pos,
                                    64)
    assert np.asarray(n).tolist() == [31, 64]
    assert (np.diff(np.asarray(idx)[1]) > 0).all()
    ks = dsa.gather_selected(pool_k, PT, idx, 1)
    vs = dsa.gather_selected(pool_v, PT, idx, 1)
    got = dsa.dsa_attend(q[:, 0], ks, vs, n, scale=0.088)
    want = dsa.attend_chunk_reference(q, pool_k, pool_v, PT, pos, scores,
                                      thr, cut, layer=1, scale=0.088)[:, 0]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
