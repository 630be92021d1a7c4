"""The latent page kind and its two kernels (``mla_decode_attention`` in
kernels/decode_attention.py: the paged decode kernel with ONE pool, the
value a prefix of the key row, the token's row written in the kernel;
``mla_prefill_attention`` in kernels/prefill_attention.py) in interpret
mode on the CPU, at a small latent (32 + 16) in float32 and at the
published one (512 + 64) in bfloat16.

The oracle is written here and shares nothing with the kernels: EXPANDED
multi-head attention, every head's key ``[W_k c | k_r]`` and value ``W_v
c`` made from the latent rows, against which the ABSORBED form
(``W_k^T q`` scored on the rows themselves, ``W_v`` applied to ``sum p
c``) must agree - 1e-5 in float32 (the same sums in another order); in
bfloat16 2e-2 on outputs of order 1 (the page and ``p`` are rounded to 8
bits of mantissa: 4e-3 a term).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving.kv_cache import CacheSpec, PagedKVCache

da = importlib.import_module("apex_tpu.kernels.decode_attention")
pa = importlib.import_module("apex_tpu.kernels.prefill_attention")

PL = 128
SIZES = {"small_f32": (jnp.float32, 32, 16, 8, 8, 4, 1e-5),
         "published_bf16": (jnp.bfloat16, 512, 64, 128, 128, 4, 2e-2)}


@pytest.fixture(scope="module", params=sorted(SIZES))
def case(request):
    dtype, r, dr, dn, dv, h, tol = SIZES[request.param]
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    pool = jnp.asarray(f(2, 9, 1, r + dr, PL) * 0.5, dtype)
    return dict(dtype=dtype, r=r, dr=dr, dn=dn, dv=dv, h=h, tol=tol,
                pool=pool, wk=f(r, h, dn) / np.sqrt(r),
                wv=f(r, h, dv) / np.sqrt(r), rng=rng,
                scale=1.0 / np.sqrt(dn + dr))


def _rows(pool, layer, pages):
    """The latent rows ``[len(pages) * PL, d]`` of a page list, float32."""
    got = jnp.asarray(pool, jnp.float32)[layer, jnp.asarray(pages), 0]
    return jnp.moveaxis(got, 1, 2).reshape(len(pages) * PL, -1)


def _expanded(c, q_n, q_r, rows, n_keys, causal_from=None):
    """Multi-head attention with every head's keys and values expanded
    from the latent ``rows``: ``q_n [T, h, dn]``, ``q_r [T, h, dr]`` ->
    ``[T, h, dv]``; query ``t`` sees keys ``< n_keys`` (or ``<=
    causal_from + t``)."""
    lat, k_r = rows[:, :c["r"]], rows[:, c["r"]:]
    k_n = jnp.einsum("lr,rhd->lhd", lat, c["wk"])
    v = jnp.einsum("lr,rhd->lhd", lat, c["wv"])
    s = (jnp.einsum("thd,lhd->htl", q_n, k_n)
         + jnp.einsum("thd,ld->htl", q_r, k_r)) * c["scale"]
    cols = jnp.arange(rows.shape[0])[None, None, :]
    limit = n_keys if causal_from is None else \
        (causal_from + jnp.arange(q_n.shape[0]) + 1)[None, :, None]
    s = jnp.where(cols < limit, s, -jnp.inf)
    return jnp.einsum("htl,lhd->thd", jax.nn.softmax(s, -1), v)


def _absorb(c, q_n, q_r):
    return jnp.concatenate(
        [jnp.einsum("thd,rhd->thr", q_n, c["wk"]), q_r], -1)


def test_absorbed_decode_is_expanded_attention_and_writes_its_row(case):
    c = case
    f = lambda *s: jnp.asarray(c["rng"].normal(size=s), jnp.float32)  # noqa: E731,E501
    B, d = 3, c["r"] + c["dr"]
    pt = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([300, 129, 0], jnp.int32)
    q_n, q_r = f(B, c["h"], c["dn"]), f(B, c["h"], c["dr"])
    new = jnp.asarray(f(B, d) * 0.5, c["dtype"])
    out, pool = jax.jit(lambda q, pool, new: da.mla_decode_attention(
        q, pool, pt, lengths, value_dim=c["r"], new_row=new,
        scale=c["scale"], layer=1))(
            jnp.asarray(_absorb(c, q_n, q_r), c["dtype"]), c["pool"], new)
    # the write: the row of the token at lengths - 1 in its page, in the
    # kernel, and nothing else anywhere (row 2 has length 0)
    want = np.array(jnp.asarray(c["pool"], jnp.float32))
    want[1, 3, 0, :, 299 - 256] = np.asarray(new[0], np.float32)
    want[1, 5, 0, :, 0] = np.asarray(new[1], np.float32)
    assert np.array_equal(np.asarray(pool, np.float32), want)
    # the read: absorbed over the written pool == expanded attention
    q_used = jnp.asarray(jnp.asarray(_absorb(c, q_n, q_r), c["dtype"]),
                         jnp.float32)
    for b, pages in ((0, [1, 2, 3]), (1, [4, 5])):
        rows = _rows(pool, 1, pages)
        # the query the kernel saw: its latent part is W_k^T q_n rounded
        # to the pool's type, so expand from that rounding's point of view
        lat = jnp.einsum("hr,lr->hl", q_used[b, :, :c["r"]], rows[:, :c["r"]])
        s = (lat + jnp.einsum("hd,ld->hl", q_used[b, :, c["r"]:],
                              rows[:, c["r"]:])) * c["scale"]
        s = jnp.where(jnp.arange(rows.shape[0])[None] < lengths[b], s,
                      -jnp.inf)
        direct = jnp.einsum("hl,lr->hr", jax.nn.softmax(s, -1),
                            rows[:, :c["r"]])
        assert float(jnp.abs(out[b] - direct).max()) < c["tol"]
        got = jnp.einsum("hr,rhd->hd", out[b], c["wv"])
        exp = _expanded(c, q_n[b][None], q_r[b][None], rows,
                        int(lengths[b]))[0]
        assert float(jnp.abs(got - exp).max()) < 3 * c["tol"], b
    assert not np.asarray(out[2]).any()         # a row of length 0


@pytest.mark.parametrize("offset", [0, 384])
def test_absorbed_prefill_is_expanded_causal_attention(case, offset):
    c = case
    f = lambda *s: jnp.asarray(c["rng"].normal(size=s), jnp.float32)  # noqa: E731,E501
    C = 256
    pt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7]], jnp.int32)
    q_n, q_r = f(C, c["h"], c["dn"]), f(C, c["h"], c["dr"])
    lat = jax.jit(lambda q, pool: pa.mla_prefill_attention(
        q, pool, pt, jnp.asarray([offset], jnp.int32), value_dim=c["r"],
        scale=c["scale"], layer=1, block_tokens=32, pages_per_step=2))(
            jnp.asarray(_absorb(c, q_n, q_r), c["dtype"])[None], c["pool"])
    assert lat.shape == (1, C, c["h"], c["r"]) and lat.dtype == jnp.float32
    got = jnp.einsum("thr,rhd->thd", lat[0], c["wv"])
    exp = _expanded(c, q_n, q_r, _rows(c["pool"], 1, [1, 2, 3, 4, 5, 6, 7]),
                    None, causal_from=offset)
    assert float(jnp.abs(got - exp).max()) < 3 * c["tol"]
    # and the module's own oracle (the fallback) says the same
    ref = pa.mla_prefill_attention_reference(
        jnp.asarray(_absorb(c, q_n, q_r), c["dtype"])[None], c["pool"], pt,
        jnp.asarray([offset], jnp.int32), value_dim=c["r"],
        scale=c["scale"], layer=1)
    assert float(jnp.abs(lat - ref).max()) < c["tol"]


def test_the_latent_page_kind_is_one_pool_and_says_what_it_cannot_be():
    spec = CacheSpec(page_layers=1, kv_heads=1, head_dim=576, value_dim=512)
    c = PagedKVCache.create(layers=spec.page_layers, num_pages=5,
                            heads=spec.kv_heads, page_len=128,
                            head_dim=spec.head_dim,
                            value_dim=spec.value_dim)
    assert c.k.shape == (1, 5, 1, 576, 128) and c.v.shape[2] == 0
    assert c.nbytes() == 5 * 576 * 128 * 2        # no V pool
    assert c.bytes_per_token() == 1152
    both = PagedKVCache.create(layers=2, num_pages=5, heads=2, page_len=128,
                               head_dim=64)
    assert both.v.shape == both.k.shape
    assert both.bytes_per_token() == 2 * 2 * 2 * 64 * 2
    for bad in (dict(kv_heads=2, head_dim=576, value_dim=512),
                dict(kv_heads=1, head_dim=64, value_dim=128)):
        with pytest.raises(ValueError, match="latent page"):
            CacheSpec(page_layers=1, **bad)
    with pytest.raises(ValueError, match="value its first"):
        da.mla_decode_attention(jnp.zeros((1, 2, 48)),
                                jnp.zeros((1, 3, 2, 48, 128)),
                                jnp.zeros((1, 2), jnp.int32),
                                jnp.ones((1,), jnp.int32), value_dim=32)
