"""The DeepSeek-Sparse-Attention kernels (kernels/dsa.py) in interpret mode
against their plain twins, at small shapes that keep every kernel on its
Pallas path: a chunk of 128 queries and single decode rows over tables of
four 128-position pages, live context ending mid-table. The selection
kernel is held to ``lax.top_k`` in test_keye_model.py.

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


@pytest.mark.parametrize("Q, q_pos", [(128, (0, 256)), (1, (5, 400))])
def test_the_indexer_scores_the_live_pages_of_its_table(Q, q_pos):
    pool = _normal(0, (L, P, D, PL))
    q, w = _normal(1, (2, Q, 8, D)), _normal(2, (2, Q, 8))
    pos = jnp.asarray(q_pos, jnp.int32)
    got = dsa.dsa_index(q, w, pool, PT, pos, layer=1, scale=0.125)
    want = dsa.index_scores_reference(q, w, pool, PT, pos, layer=1,
                                      scale=0.125)
    live = np.isfinite(np.asarray(want))
    assert (np.isfinite(np.asarray(got)) == live).all()
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], rtol=1e-6, atol=1e-6)


def _selection(scores, pos, k=64):
    B, Q, span = scores.shape
    rows = (pos[:, None] + jnp.arange(Q)[None]).reshape(-1)
    thr, cut = dsa.select_reference(scores.reshape(B * Q, span), rows, k)
    return thr.reshape(B, Q), cut.reshape(B, Q)


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
