"""Paged KV cache — block-table pool + copy-on-write sharing, hermetic.

The acceptance bar from the block-table issue, as tests:

- the paged kernels (``paged_decode_attention`` /
  ``paged_prefill_attention``) match their jnp oracles, and the oracles
  are BITWISE identical to the contiguous references over the gathered
  page view (same math, indirected storage);
- through an identity page table the paged decode kernel holds the
  contract of a contiguous cache row (zero-length rows are zero,
  nothing past a row's length is read, int8 dequantised in the kernel,
  bf16 in and out, under ``jit``);
- the engine is token-exact against a teacher-forcing recompute
  (greedy) over a mixed hit/miss/evict request
  stream with prompt lengths below / at / straddling page boundaries;
- a prefix-cache hit performs ZERO KV data movement:
  the engine compiles exactly TWO programs (chunk prefill + decode)
  across a stream that includes hits, pinned by trace counters;
- copy-on-write refcount pinning: a shared page is never freed while
  any slot or prefix entry references it, and the first write past a
  shared prefix lands on a freshly allocated page (never the donor's);
- pool-exhaustion degradation: admission blocks (requests queue, FIFO
  holds, ``serving.pool.admit_blocked`` counts) instead of failing
  mid-decode, prefix entries are LRU-evicted under reservation
  pressure, and the engine constructor refuses pools too small for one
  ``max_len`` request — so the drain loop can never deadlock;
- the ``serving.pool.*`` telemetry gauges (pages_in_use / pages_free /
  cow_shares / fragmentation) land in the registry every step.

Everything runs on CPU with a tiny model at policy O0 (exact fp32);
the paged kernels take their interpret/reference paths here (Mosaic
lowering is the tests/tpu tier's job).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import telemetry
from apex_tpu.amp.policy import resolve_policy
from apex_tpu.kernels.decode_attention import (
    _pool_write_tokens, decode_attention_reference, gather_pages,
    paged_decode_attention, paged_decode_attention_reference)
from apex_tpu.kernels.prefill_attention import (
    paged_prefill_attention, paged_prefill_attention_reference,
    prefill_attention_reference)
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import (Engine, PagedKVCache, PagePool, Request,
                              Scheduler)

pytestmark = pytest.mark.serving

VOCAB = 101
CHUNK = 8     # engine chunk_len == page_len below: every chunk is 1 page


# ------------------------------------------------------------ page pool
def test_page_pool_alloc_share_release_refcounts():
    pool = PagePool(num_pages=5, page_len=8)
    assert pool.free_pages == 4 and pool.pages_in_use == 0   # page 0 = sentinel
    a, b = pool.alloc(), pool.alloc()
    assert a != b and 0 not in (a, b)
    assert pool.pages_in_use == 2 and pool.cow_shares == 0
    pool.share([a])                       # second reader: COW share
    assert pool.cow_shares == 1
    pool.release([a])                     # first reader gone: page lives
    assert pool.pages_in_use == 2 and pool.cow_shares == 0
    pool.release([a, b])                  # last readers: both freed
    assert pool.pages_in_use == 0 and pool.free_pages == 4
    with pytest.raises(ValueError, match="already free"):
        pool.release([a])
    with pytest.raises(ValueError, match="cannot share"):
        pool.share([a])
    with pytest.raises(ValueError, match="out of range"):
        pool.share([0])                   # the sentinel is never shared


def test_page_pool_reservation_ledger():
    pool = PagePool(num_pages=6, page_len=4)      # 5 usable
    assert pool.available == 5
    assert pool.reserve(3)
    assert pool.available == 2 and pool.free_pages == 5
    assert not pool.reserve(3)                    # over-promise refused
    assert pool.reserve(2) and pool.available == 0
    # a reserved alloc draws the ledger down with the page
    p = pool.alloc(reserved=True)
    assert p is not None and pool.reserved_total == 4
    pool.unreserve(4)
    assert pool.available == pool.free_pages == 4
    # exhaustion returns None, never raises
    for _ in range(4):
        assert pool.alloc() is not None
    assert pool.alloc() is None
    assert pool.pages_for(0) == 0 and pool.pages_for(1) == 1
    assert pool.pages_for(4) == 1 and pool.pages_for(5) == 2


def test_page_pool_fragmentation_and_validation():
    pool = PagePool(num_pages=4, page_len=8)
    # 2 slots, 3 pages allocated, 20/24 positions valid
    assert pool.fragmentation([12, 8], [2, 1]) == pytest.approx(1 - 20 / 24)
    assert pool.fragmentation([], []) == 0.0
    with pytest.raises(ValueError, match="sentinel"):
        PagePool(num_pages=1, page_len=8)
    with pytest.raises(ValueError, match="page_len"):
        PagePool(num_pages=4, page_len=0)
    with pytest.raises(ValueError, match="sentinel"):
        PagedKVCache.create(layers=1, num_pages=1, heads=1, page_len=8,
                            head_dim=4)


def test_paged_kv_cache_geometry():
    c = PagedKVCache.create(layers=2, num_pages=5, heads=3, page_len=16,
                            head_dim=8, dtype=jnp.bfloat16)
    assert (c.layers, c.num_pages, c.heads, c.page_len, c.head_dim) \
        == (2, 5, 3, 16, 8)
    assert c.dtype == jnp.bfloat16
    assert c.nbytes() == 2 * 5 * 3 * 16 * 8 * 2 * 2


# -------------------------------------------------------- paged kernels
def test_paged_decode_kernel_matches_oracle_and_contiguous_reference():
    rng = np.random.default_rng(0)
    B, H, D, PL, NP, MAXP = 3, 2, 16, 128, 7, 4
    scale = 1.0 / D ** 0.5
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NP, H, PL, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NP, H, PL, D)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, NP, size=(B, MAXP)), jnp.int32)
    # below / at / straddling page boundaries, plus 0 (dead slot) + full
    for L in ([5, 128, 130], [0, 200, 512], [1, 127, 129]):
        lengths = jnp.asarray(L, jnp.int32)
        ref = paged_decode_attention_reference(q, kp, vp, pt, lengths,
                                               scale=scale)
        out = paged_decode_attention(q, kp, vp, pt, lengths,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-6)
        # the oracle IS the contiguous reference over the gathered view
        # — bitwise, which is what makes paged-vs-contiguous engine
        # parity a storage claim rather than a numerics claim
        kg, vg = gather_pages(kp, pt), gather_pages(vp, pt)
        contig = decode_attention_reference(q, kg, vg, lengths,
                                            scale=scale)
        assert (np.asarray(ref) == np.asarray(contig)).all()
    # rows with length 0 return exactly zero (dead serving slots)
    out = paged_decode_attention(q, kp, vp, pt,
                                 jnp.asarray([0, 3, 0], jnp.int32),
                                 interpret=True)
    assert (np.asarray(out)[[0, 2]] == 0).all()


def test_paged_decode_kernel_bf16_and_fallback():
    rng = np.random.default_rng(1)
    B, H, D, PL, NP, MAXP = 2, 2, 16, 128, 5, 2
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(NP, H, PL, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(NP, H, PL, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.integers(0, NP, size=(B, MAXP)), jnp.int32)
    lengths = jnp.asarray([100, 256], jnp.int32)
    ref = paged_decode_attention_reference(q, kp, vp, pt, lengths,
                                           scale=0.25)
    out = paged_decode_attention(q, kp, vp, pt, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)
    # unaligned page_len (not a lane multiple) falls back to the oracle
    out_fb = paged_decode_attention(q[:, :, :], kp[:, :, :24],
                                    vp[:, :, :24], pt,
                                    jnp.asarray([10, 40], jnp.int32))
    assert out_fb.shape == (B, H, D)
    with pytest.raises(ValueError, match="page_table"):
        paged_decode_attention(q, kp, vp, pt[0], lengths)
    with pytest.raises(ValueError, match="lengths"):
        paged_decode_attention(q, kp, vp, pt, lengths[:1])
    with pytest.raises(ValueError, match="pools"):
        paged_decode_attention(q, kp, vp[:, :1], pt, lengths)


def test_paged_prefill_kernel_matches_oracle_across_offsets():
    rng = np.random.default_rng(2)
    B, H, C, D, PL, NP, MAXP = 2, 2, 16, 16, 128, 7, 4
    scale = 1.0 / D ** 0.5
    q = jnp.asarray(rng.normal(size=(B, H, C, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NP, H, PL, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NP, H, PL, D)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, NP, size=(B, MAXP)), jnp.int32)
    for offs in ([0, 0], [128, 200], [496, 3]):
        off = jnp.asarray(offs, jnp.int32)
        ref = paged_prefill_attention_reference(q, kp, vp, pt, off,
                                                scale=scale)
        out = paged_prefill_attention(q, kp, vp, pt, off, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-6)
        kg, vg = gather_pages(kp, pt), gather_pages(vp, pt)
        contig = prefill_attention_reference(q, kg, vg, off, scale=scale)
        assert (np.asarray(ref) == np.asarray(contig)).all()
    # q-block override exercises the multi-q-block grid
    q2 = jnp.asarray(rng.normal(size=(B, H, 256, D)), jnp.float32)
    off = jnp.asarray([128, 200], jnp.int32)
    ref = paged_prefill_attention_reference(q2, kp, vp, pt, off,
                                            scale=scale)
    out = paged_prefill_attention(q2, kp, vp, pt, off, block_q=64,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6)
    with pytest.raises(ValueError, match="offsets"):
        paged_prefill_attention(q, kp, vp, pt, off[:1])


def _identity_pool(x, page_len):
    """A contiguous cache ``[B, h, L, d]`` as pool pages ``[B * L /
    page_len, h, page_len, d]`` and the identity table ``[B, L /
    page_len]`` that reads it back row by row."""
    B, h, L, d = x.shape
    n = L // page_len
    pool = x.reshape(B, h, n, page_len, d).transpose(0, 2, 1, 3, 4)
    return pool.reshape(B * n, h, page_len, d), jnp.arange(
        B * n, dtype=jnp.int32).reshape(B, n)


_ROW_CASES = {
    # name: (B, h, L, d, lengths, dtype, tolerance)
    "short_and_full": (3, 4, 256, 64, [1, 5, 256], "f32", 2e-5),
    "dead_and_page_edge": (3, 4, 256, 64, [0, 37, 128], "f32", 2e-5),
    "all_full": (3, 4, 256, 64, [256, 256, 256], "f32", 2e-5),
    "zero_length_rows_are_zero": (2, 2, 128, 8, [0, 4], "f32", 2e-5),
    "nothing_past_length_is_read": (2, 4, 256, 16, [9, 200], "f32", 1e-6),
    "bf16_in_and_out": (2, 4, 256, 32, [17, 256], "bf16", 0.05),
    "under_jit": (1, 2, 256, 8, [129], "f32", 2e-5),
    "int8_dequantised_in_kernel": (3, 4, 256, 16, [1, 37, 256], "int8",
                                   2e-5),
    "int8_codes_past_length": (3, 4, 256, 16, [1, 37, 40], "int8", 2e-5),
}


@pytest.mark.parametrize("case", sorted(_ROW_CASES))
def test_paged_decode_through_an_identity_table_is_a_contiguous_row(case):
    """What the contiguous decode kernel's tests held, held of the paged
    kernel: a cache row laid out as consecutive pages and read through
    an identity table gives ``decode_attention_reference`` over the row
    (float32 oracle), whatever lies past the row's length."""
    B, h, L, d, lengths, dtype, tol = _ROW_CASES[case]
    rng = np.random.default_rng(sorted(_ROW_CASES).index(case))
    lens = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, h, d)), jnp.float32)
    scales = {}
    if dtype == "int8":
        k = jnp.asarray(rng.integers(-127, 128, (B, h, L, d)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, (B, h, L, d)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.06, h), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.06, h), jnp.float32)
        scales = {"k_scale": ks, "v_scale": vs}
        k32 = jnp.asarray(k, jnp.float32) * ks[None, :, None, None]
        v32 = jnp.asarray(v, jnp.float32) * vs[None, :, None, None]
    else:
        k32 = jnp.asarray(rng.standard_normal((B, h, L, d)), jnp.float32)
        v32 = jnp.asarray(rng.standard_normal((B, h, L, d)), jnp.float32)
        k, v = k32, v32
        if dtype == "bf16":
            q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    want = np.asarray(decode_attention_reference(
        jnp.asarray(q, jnp.float32), k32, v32, lens, scale=1.0 / d ** 0.5))
    # garbage past each row's length: the result may not move
    past = jnp.arange(L)[None, None, :, None] >= lens[:, None, None, None]
    k = jnp.where(past, jnp.asarray(127 if dtype == "int8" else 1e4,
                                    k.dtype), k)
    v = jnp.where(past, jnp.asarray(-127 if dtype == "int8" else -1e4,
                                    v.dtype), v)
    kp, pt = _identity_pool(k, 128)
    vp, _ = _identity_pool(v, 128)
    fn = lambda q, kp, vp, pt, lens: paged_decode_attention(   # noqa: E731
        q, kp, vp, pt, lens, interpret=True, **scales)
    if case == "under_jit":
        fn = jax.jit(fn)
    out = fn(q, kp, vp, pt, lens)
    assert out.dtype == q.dtype
    got = np.asarray(out, np.float32)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    dead = np.asarray(lens) == 0
    assert (got[dead] == 0).all()


# ------------------------------------ the stacked pool, read where it lies
def _stacked_case(kernel, dtype, page_len):
    """(call on the stacked pool at ``layer``, call on one layer's 4-D
    pool) for the decode or prefill kernel: a 3-layer pool as the
    serving engine holds it, ``[layers, pages, heads, d, page_len]``,
    bf16 or int8 with per-head scales. ``page_len`` 128 takes the
    (interpreted) Pallas kernel, 24 its jnp fallback."""
    rng = np.random.default_rng(7)
    LYR, B, H, D, NP, MAXP, C = 3, 2, 2, 16, 6, 3, 16
    shape = (LYR, NP, H, D, page_len)
    scales = {}
    if dtype == "int8":
        kp = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        scales = {"k_scale": jnp.asarray(rng.uniform(.01, .03, H),
                                         jnp.float32),
                  "v_scale": jnp.asarray(rng.uniform(.01, .03, H),
                                         jnp.float32)}
    else:
        kp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    pt = jnp.asarray(rng.integers(0, NP, size=(B, MAXP)), jnp.int32)
    if kernel == "decode":
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
        where = jnp.asarray([page_len + 3, 3 * page_len], jnp.int32)
        fn = paged_decode_attention
    else:
        q = jnp.asarray(rng.normal(size=(B, H, C, D)), jnp.bfloat16)
        where = jnp.asarray([0, 2 * page_len - 5], jnp.int32)
        fn = paged_prefill_attention

    def stacked(layer):
        return fn(q, kp, vp, pt, where, layer=layer, **scales)

    def one_layer(layer):       # that layer's pages, each [page_len, d]
        return fn(q, kp[layer].swapaxes(-1, -2),
                  vp[layer].swapaxes(-1, -2), pt, where, **scales)
    return stacked, one_layer, (q, kp, vp, pt, where)


@pytest.mark.parametrize("page_len", [128, 24], ids=["pallas", "fallback"])
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_stacked_pool_with_layer_equals_that_layers_pool(kernel, dtype,
                                                         page_len):
    """The serving programs hand the kernels the whole stacked pool and
    a layer; that reads exactly what the 4-D call reads on that layer
    alone — first, middle and last layer."""
    stacked, one_layer, _ = _stacked_case(kernel, dtype, page_len)
    outs = []
    for layer in (0, 1, 2):
        got, want = stacked(layer), one_layer(layer)
        assert got.shape == want.shape and got.dtype == want.dtype
        assert (np.asarray(got, np.float32)
                == np.asarray(want, np.float32)).all()
        outs.append(np.asarray(got, np.float32))
    # and the layers differ, so the index was not ignored
    assert not (outs[0] == outs[1]).all()
    assert not (outs[1] == outs[2]).all()


def test_stacked_pool_needs_its_layer_and_a_layer_its_stacked_pool():
    stacked, _, (q, kp, vp, pt, where) = _stacked_case("decode", "bf16",
                                                       128)
    with pytest.raises(ValueError, match="layer"):
        paged_decode_attention(q, kp, vp, pt, where)
    with pytest.raises(ValueError, match="layer"):
        paged_decode_attention(q, kp[0], vp[0], pt, where, layer=0)
    with pytest.raises(ValueError, match="outside"):
        stacked(3)
    with pytest.raises(ValueError, match="layer"):
        paged_prefill_attention(q[:, :, None].repeat(8, 2), kp, vp, pt,
                                where)
    # the reference's gather reads the stacked pool the same way
    assert (np.asarray(gather_pages(kp, pt, 1), np.float32)
            == np.asarray(gather_pages(kp[1].swapaxes(-1, -2), pt),
                          np.float32)).all()


# ------------------------- the decode kernel's walk over a row's live pages
# (model geometry: G query heads a K/V head x head_dim, page dtype, pool
# form) x (what a row's length and table can look like). Every case reads
# table entries past a row's last live page as the SENTINEL page 0, which
# is filled with NaN: the kernel must never bring it into a result.
_GEOMETRIES = {
    "g1_d64_bf16": (1, 64, "bf16", True),
    "g4_d128_bf16": (4, 128, "bf16", True),
    "g1_d64_int8": (1, 64, "int8", True),
    "g8_d256_bf16": (8, 256, "bf16", True),
    "g4_d64_bf16_one_layer": (4, 64, "bf16", False),
    "g1_d128_int8_one_layer": (1, 128, "int8", False),
}
_MAXP = 5
# name -> (lengths, pages a step the tuned bytes are set to give)
_WALKS = {
    "page_boundary_and_one_past": ([128, 129, 256, 257], 2),
    "one_token_beside_a_full_table": ([1, _MAXP * 128, 1, 640], 2),
    "live_pages_not_a_multiple_of_the_step": ([100, 300, 600, 385], 2),
    "three_pages_a_step": ([129, 385, 640, 512], 3),
    "one_page_a_step": ([5, 130, 300, 640], 1),
    "empty_rows_between_live_ones": ([0, 300, 0, 5], 2),
    "all_rows_empty": ([0, 0, 0, 0], 2),
}


def _walk_case(geometry, lengths, shared):
    G, d, dtype, stacked = _GEOMETRIES[geometry]
    rng = np.random.default_rng(11)
    h_kv, B, PL = 2, len(lengths), 128
    NP_ = 1 + B * _MAXP
    shape = (NP_, h_kv, d, PL)
    scales = {}
    if dtype == "int8":
        kp, vp = (rng.integers(-127, 128, size=shape).astype(np.float32)
                  for _ in range(2))
        scales = {"k_scale": jnp.asarray(rng.uniform(.01, .03, h_kv),
                                         jnp.float32),
                  "v_scale": jnp.asarray(rng.uniform(.01, .03, h_kv),
                                         jnp.float32)}
    else:
        kp, vp = (rng.normal(size=shape).astype(np.float32)
                  for _ in range(2))
    # every row its own pages, in a shuffled order; entries past the
    # live pages are the sentinel
    pt = rng.permutation(np.arange(1, NP_)).reshape(B, _MAXP)
    if shared:
        # the prefix cache's copy-on-write form: rows 1.. read row 0's
        # first two (full) pages
        pt[1:, :2] = pt[0, :2]
    live = -(-np.asarray(lengths) // PL)
    for b in range(B):
        pt[b, live[b]:] = 0
    q = jnp.asarray(rng.normal(size=(B, h_kv * G, d)), jnp.bfloat16)
    return q, kp, vp, jnp.asarray(pt, jnp.int32), scales, stacked, dtype


def _as_pool(pages, dtype, stacked):
    """``pages`` [num_pages, heads, d, page_len] as the kernel's pool:
    the middle layer of a stacked one (the other layers NaN, or -128 in
    int8: never this layer's business), or one layer's 4-D form."""
    store = jnp.int8 if dtype == "int8" else jnp.bfloat16
    if not stacked:
        return jnp.asarray(pages.swapaxes(-1, -2), store)
    other = np.full_like(pages, -128 if dtype == "int8" else np.nan)
    return jnp.asarray(np.stack([other, pages, other]), store)


@pytest.fixture
def step_bytes():
    from apex_tpu.kernels import vmem
    saved = vmem.overrides().get("decode.paged_step_bytes")

    def set_pages(pages, page_bytes):
        vmem.set_override("decode.paged_step_bytes", pages * page_bytes)
    yield set_pages
    vmem.remove_override("decode.paged_step_bytes")
    if saved is not None:
        vmem.set_override("decode.paged_step_bytes", saved)


def _bits(x):
    """An array's bytes as integers: equality that NaN cannot fail."""
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.itemsize])


def _write_then_read(q, kp, vp, pt, lens, new_k, new_v, layer, **kw):
    """What the writing call must equal bit for bit: the XLA write of
    each live row's token (``_pool_write_tokens``; a row of length 0
    names a page past the pool and is dropped), then the read-only
    kernel on that pool. -> (out, k_pool, v_pool)."""
    page_len, num_pages = kp.shape[-1], kp.shape[1]
    lengths = np.asarray(lens)
    pos = np.maximum(lengths - 1, 0)
    ids = np.asarray(pt)[np.arange(len(lengths)), pos // page_len]
    ids = jnp.asarray(np.where(lengths > 0, ids, num_pages), jnp.int32)
    off = jnp.asarray(pos % page_len, jnp.int32)
    kp = _pool_write_tokens(kp, layer, ids, off, new_k)
    vp = _pool_write_tokens(vp, layer, ids, off, new_v)
    out = paged_decode_attention(q, kp, vp, pt, lens, layer=layer,
                                 interpret=True, **kw)
    return out, kp, vp


# a shared prefix under the writing call: where a write page follows the
# shared pages at once, and with rows of length 0 between the writers
_SHARED_WRITES = ("page_boundary_and_one_past",
                  "empty_rows_between_live_ones")


@pytest.mark.parametrize("walk,shared,write", [
    (w, sh, wr) for w in sorted(_WALKS) for sh in (False, True)
    for wr in (False, True)
    if not (sh and w == "all_rows_empty")     # nothing read, nothing shared
    and not (sh and wr and w not in _SHARED_WRITES)])
@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
def test_paged_decode_walks_only_a_rows_live_pages(geometry, walk, shared,
                                                   write, step_bytes):
    """``write``: the call is also handed the rows' new K/V and must
    equal ``_pool_write_tokens`` followed by the read-only call in BOTH
    results, the attention output and the whole pool, bit for bit - the
    write page in any slot of a step (``one_page_a_step`` .. three), its
    lane 0 (a fresh page: lengths 1, 129, 257, 385), mid-page and 127
    (128, 256, 640), rows of length 0 in between, and a shared full
    prefix in front of each row's own write page."""
    lengths, pages = _WALKS[walk]
    if shared:
        # a shared prefix is whole pages its readers attend in full; a
        # row that writes has its write page past them (copy-on-write)
        lengths = [n and max(n, 256 + write + i)
                   for i, n in enumerate(lengths)]
    q, kp, vp, pt, scales, stacked, dtype = _walk_case(geometry, lengths,
                                                      shared)
    lens = jnp.asarray(lengths, jnp.int32)
    layer = 1 if stacked else None
    h_kv, d = kp.shape[1], kp.shape[2]
    step_bytes(pages, h_kv * d * 128 * (1 if dtype == "int8" else 2))
    store = jnp.int8 if dtype == "int8" else jnp.bfloat16
    rng = np.random.default_rng(5)
    new = [jnp.asarray(rng.integers(-127, 128, size=(len(lengths), h_kv, d))
                       if dtype == "int8" else
                       rng.normal(size=(len(lengths), h_kv, d)), store)
           for _ in range(2)]
    if write and not stacked:
        with pytest.raises(ValueError, match="new_k.*single layer"):
            paged_decode_attention(
                q, *(_as_pool(t, dtype, stacked) for t in (kp, vp)), pt,
                lens, new_k=new[0], new_v=new[1], **scales)
        return
    if write:
        # the oracle's pool holds the new columns
        pos = np.maximum(np.asarray(lengths) - 1, 0)
        for b in np.flatnonzero(np.asarray(lengths)):
            page = int(pt[b, pos[b] // 128])
            kp[page, :, :, pos[b] % 128] = np.asarray(new[0][b], np.float32)
            vp[page, :, :, pos[b] % 128] = np.asarray(new[1][b], np.float32)
    # the oracle reads a pool whose sentinel page is zeros; the kernel
    # one whose sentinel is NaN (int8 codes have no NaN: theirs is noise)
    clean = [_as_pool(t, dtype, stacked) for t in (kp, vp)]
    kp[0], vp[0] = (101, 101) if dtype == "int8" else (np.nan, np.nan)
    dirty = [_as_pool(t, dtype, stacked) for t in (kp, vp)]
    want = paged_decode_attention_reference(
        q, *clean, pt, lens, scale=1 / d ** 0.5, layer=layer, **scales)
    if not write:
        got = jax.jit(lambda *a: paged_decode_attention(
            *a, layer=layer, interpret=True, **scales))(q, *dirty, pt, lens)
    else:
        # the pool the call is handed lacks the new columns
        before = [_pool_write_tokens(
            t, layer, pt[jnp.arange(len(lengths)), jnp.asarray(pos // 128)],
            jnp.asarray(pos % 128, jnp.int32),
            jnp.full_like(new[0], 77 if dtype == "int8" else 1e4))
            for t in dirty]
        got, k_got, v_got = jax.jit(lambda q, k, v, pt, lens, nk, nv:
                                    paged_decode_attention(
            q, k, v, pt, lens, new_k=nk, new_v=nv, layer=layer,
            interpret=True, **scales))(q, *before, pt, lens, *new)
        out, k_want, v_want = _write_then_read(q, *before, pt, lens, *new,
                                               layer, **scales)
        assert (_bits(got) == _bits(out)).all()
        assert (_bits(k_got) == _bits(k_want)).all()
        assert (_bits(v_got) == _bits(v_want)).all()
        live = np.asarray(lengths) > 0
        if live.any():
            # and the write is there: the pool moved, by the new columns
            assert not (_bits(k_got) == _bits(before[0])).all()
        for b in np.flatnonzero(live):
            page, lane = int(pt[b, pos[b] // 128]), pos[b] % 128
            assert (_bits(k_got[layer, page, :, :, lane])
                    == _bits(new[0][b])).all()
            assert (_bits(v_got[layer, page, :, :, lane])
                    == _bits(new[1][b])).all()
        if shared:
            for t_got, t_in in ((k_got, before[0]), (v_got, before[1])):
                assert (_bits(t_got[:, np.asarray(pt[0, :2])])
                        == _bits(t_in[:, np.asarray(pt[0, :2])])).all()
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=3e-2)
    assert (got[np.asarray(lengths) == 0] == 0).all()


def test_a_rows_nan_page_never_reaches_another_rows_result(step_bytes):
    """Two pages a step: row 0's second page holds NaN and stays behind
    in the buffer row 2's one live page is fetched into next. Row 0 is
    NaN (the engine quarantines it); rows 1-3 must read as if it were
    not there."""
    lengths = [256, 100, 100, 100]
    q, kp, vp, pt, _, _, _ = _walk_case("g1_d64_bf16", lengths, False)
    step_bytes(2, kp.shape[1] * kp.shape[2] * 128 * 2)
    lens = jnp.asarray(lengths, jnp.int32)
    clean = [_as_pool(t, "bf16", True) for t in (kp, vp)]
    kp[pt[0, 1]], vp[pt[0, 1]] = np.nan, np.nan
    dirty = [_as_pool(t, "bf16", True) for t in (kp, vp)]
    want = paged_decode_attention_reference(q, *clean, pt, lens,
                                            scale=0.125, layer=1)
    got = paged_decode_attention(q, *dirty, pt, lens, layer=1,
                                 interpret=True)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isnan(got[0]).all()
    np.testing.assert_allclose(got[1:], want[1:], atol=3e-2)


# -------------------------- the chunk kernel's walk over a row's live pages
# (query heads a K/V head, head_dim, page dtype, stacked pool): B's 20 / 20
# heads of 64, D's 8 / 2 of 128, E's 16 / 2 of 256, the int8 tier, and one
# layer's float32 pool. Two K/V heads each. Table entries past a row's live
# pages are the SENTINEL page 0, filled with NaN.
_CHUNK_GEOMETRIES = {
    "g1_d64_bf16": (1, 64, "bf16", True),
    "g4_d128_bf16": (4, 128, "bf16", True),
    "g8_d256_bf16": (8, 256, "bf16", True),
    "g1_d64_int8": (1, 64, "int8", True),
    "g2_d32_f32_one_layer": (2, 32, "f32", False),
}
_CHUNK_MAXP = 6
# name -> (two rows' offsets, chunk length, pages a step)
_CHUNK_WALKS = {
    # 4 and 1 live pages of a table of 6: the dead tail is NaN
    "deep_offset_short_walk": ([384, 0], 64, 1),
    # unaligned offsets (a verify's): the last live page partly live
    "last_page_partly_live": ([200, 131], 32, 2),
    # q blocks of 128 rows a head (G = 1): a block before a page's first
    # position skips it
    "q_blocks_skip_pages": ([0, 128], 256, 1),
    # 3 and 5 live pages at 3 a step: a step whose last slots are dead
    "several_pages_a_step": ([256, 448], 128, 3),
    "whole_table": ([704, 0], 64, 2),
}


def _chunk_case(geometry, offsets, C, seed=13):
    """``(q, clean pools, dirty pools, table, offsets, scales, layer)``:
    every row its own pages in a shuffled order, entries past its live
    pages the sentinel - zeros in the oracle's pools, NaN in the
    kernel's (int8 has no NaN: noise)."""
    G, d, dtype, stacked = _CHUNK_GEOMETRIES[geometry]
    rng = np.random.default_rng(seed)
    h_kv, B, PL = 2, len(offsets), 128
    NP_ = 1 + B * _CHUNK_MAXP
    shape = (NP_, h_kv, d, PL)
    scales = {}
    if dtype == "int8":
        kp, vp = (rng.integers(-127, 128, size=shape).astype(np.float32)
                  for _ in range(2))
        scales = {"k_scale": jnp.asarray(rng.uniform(.01, .03, h_kv),
                                         jnp.float32),
                  "v_scale": jnp.asarray(rng.uniform(.01, .03, h_kv),
                                         jnp.float32)}
    else:
        kp, vp = (rng.normal(size=shape).astype(np.float32)
                  for _ in range(2))
    pt = rng.permutation(np.arange(1, NP_)).reshape(B, _CHUNK_MAXP)
    live = -(-(np.asarray(offsets) + C) // PL)
    for b in range(B):
        pt[b, live[b]:] = 0
    qdt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    q = jnp.asarray(rng.normal(size=(B, h_kv * G, C, d)), qdt)
    store = {"int8": jnp.int8, "bf16": jnp.bfloat16, "f32": jnp.float32}[
        dtype]

    def pool(pages):
        if not stacked:
            return jnp.asarray(pages.swapaxes(-1, -2), store)
        other = np.full_like(pages, -128 if dtype == "int8" else np.nan)
        return jnp.asarray(np.stack([other, pages, other]), store)
    clean = [pool(t) for t in (kp, vp)]
    kp[0], vp[0] = (101, 101) if dtype == "int8" else (np.nan, np.nan)
    dirty = [pool(t) for t in (kp, vp)]
    return (q, clean, dirty, jnp.asarray(pt, jnp.int32),
            jnp.asarray(offsets, jnp.int32), scales, 1 if stacked else None)


@pytest.mark.parametrize("walk", sorted(_CHUNK_WALKS))
@pytest.mark.parametrize("geometry", sorted(_CHUNK_GEOMETRIES))
def test_paged_prefill_walks_only_a_chunks_live_pages(geometry, walk,
                                                      step_bytes):
    """The chunk kernel walks ``ceil((offset + C) / page_len)`` pages of
    a row's table, every head of a page in one fetch and ``pages`` of
    them a step, and gives the gather-then-reference oracle's result:
    grouped heads folded into a product's rows, the int8 tier's per-head
    scales, a page partly live, q blocks that skip a page, a step whose
    last slots are dead - and never a NaN from the table's dead tail."""
    offsets, C, pages = _CHUNK_WALKS[walk]
    q, clean, dirty, pt, offs, scales, layer = _chunk_case(geometry,
                                                           offsets, C)
    G, d, dtype, _ = _CHUNK_GEOMETRIES[geometry]
    step_bytes(pages, 2 * d * 128 * {"int8": 1, "bf16": 2, "f32": 4}[dtype])
    want = paged_prefill_attention_reference(
        q, *clean, pt, offs, scale=1 / d ** 0.5, layer=layer, **scales)
    got = jax.jit(lambda *a: paged_prefill_attention(
        *a, layer=layer, interpret=True, **scales))(q, *dirty, pt, offs)
    assert got.shape == want.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want,
                               atol=1e-5 if dtype == "f32" else 3e-2)


def test_a_rows_nan_page_never_reaches_another_rows_chunk(step_bytes):
    """Two pages a step: row 0's second page holds NaN and stays behind
    in the buffer row 1's one live page is fetched into next. Row 0 is
    NaN; row 1 must read as if it were not there."""
    q, clean, dirty, pt, offs, _, layer = _chunk_case(
        "g1_d64_bf16", [128, 0], 64)
    step_bytes(2, 2 * 64 * 128 * 2)
    k = np.asarray(dirty[0], np.float32)
    v = np.asarray(dirty[1], np.float32)
    k[layer, int(pt[0, 1])], v[layer, int(pt[0, 1])] = np.nan, np.nan
    want = paged_prefill_attention_reference(q, *clean, pt, offs,
                                             scale=0.125, layer=layer)
    got = paged_prefill_attention(q, jnp.asarray(k, jnp.bfloat16),
                                  jnp.asarray(v, jnp.bfloat16), pt, offs,
                                  layer=layer, interpret=True)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isnan(got[0]).any()
    np.testing.assert_allclose(got[1], want[1], atol=3e-2)


def test_paged_prefill_layer_is_an_operand():
    """``layer`` reaches the kernel as a scalar operand: under ``jit`` a
    traced layer reads that layer of the stacked pool, two layers of one
    pool give each its own result, one trace serving both."""
    rng = np.random.default_rng(3)
    LYR, B, H, D, NP_, MAXP, C = 3, 1, 2, 64, 5, 4, 128
    kp, vp = (jnp.asarray(rng.normal(size=(LYR, NP_, H, D, 128)),
                          jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, H, C, D)), jnp.bfloat16)
    pt = jnp.asarray([[3, 1, 4, 2]], jnp.int32)
    offs = jnp.asarray([256], jnp.int32)
    call = jax.jit(lambda layer: paged_prefill_attention(
        q, kp, vp, pt, offs, layer=layer, interpret=True))
    outs = []
    for layer in (0, 2):
        got = np.asarray(call(jnp.int32(layer)), np.float32)
        want = np.asarray(paged_prefill_attention_reference(
            q, kp, vp, pt, offs, scale=0.125, layer=layer), np.float32)
        np.testing.assert_allclose(got, want, atol=3e-2)
        outs.append(got)
    assert not (outs[0] == outs[1]).all()
    assert call._cache_size() == 1


# ------------------------------------------------------------ engines
def _tiny_lm(max_seq_len=64, **kw):
    return TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                         num_heads=4, max_seq_len=max_seq_len, **kw)


@pytest.fixture(scope="module")
def lm_and_params():
    m = _tiny_lm()
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _mk_engine(lm_and_params, *, pool=2, slots=3, seed=5, **kw):
    m, params = lm_and_params
    return Engine(m, params, slots=slots, max_len=64, prefill_len=24,
                  chunk_len=CHUNK, prefix_pool=pool,
                  policy=resolve_policy("O0", verbose=False), seed=seed,
                  **kw)


@pytest.fixture(scope="module")
def engine(lm_and_params):
    """The module's shared engine (jit caches warm across it)."""
    return _mk_engine(lm_and_params)


def test_paged_engine_geometry_and_defaults(engine):
    ep = engine
    assert ep.page_len == CHUNK           # min(chunk, 128) -> chunk
    assert ep.max_pages == 64 // CHUNK
    # default pool budget: every slot and prefix at full length (+ sentinel)
    assert ep.num_pages == (3 + 2) * ep.max_pages + 1
    assert ep.pool.free_pages == ep.num_pages - 1


def test_paged_engine_validation(lm_and_params):
    with pytest.raises(ValueError, match="divide chunk_len"):
        _mk_engine(lm_and_params, page_len=5)
    with pytest.raises(ValueError, match="cannot hold even one"):
        _mk_engine(lm_and_params, num_pages=4)
    eng = _mk_engine(lm_and_params, pool=0)
    assert eng.prefix_cache is None
    with pytest.raises(RuntimeError, match="prefix cache"):
        eng.retain_prefix(0, [1] * 8)
    with pytest.raises(ValueError, match="page-aligned"):
        eng.prefill_chunk(0, [1, 2], 3)


def _boundary_cases():
    """(prompt_a, prompt_b, expected_reuse) with shared-prefix lengths
    below / at / straddling page boundaries (page_len == CHUNK == 8) and
    spanning two pages — the same sweep test_prefix_cache runs."""
    rng = np.random.default_rng(42)
    out = []
    for pre_len, want in [(5, 0), (8, 8), (13, 8), (16, 16)]:
        pre = list(rng.integers(1, VOCAB, size=pre_len))
        out.append((pre + list(rng.integers(1, VOCAB, size=3)),
                    pre + list(rng.integers(1, VOCAB, size=3)), want))
    return out


def test_paged_token_exact_vs_recompute_over_hit_miss_evict_stream(
        engine, lm_and_params):
    """THE acceptance pin: greedy tokens from the engine (with
    copy-on-write prefix retention on) match one teacher-forcing
    recompute request-for-request across a stream that drives misses,
    hits and boundary-length prompts — the hit served from shared pages
    as exactly as the miss that filled them."""
    m, params = lm_and_params
    ep = engine
    ep.reset(clear_prefixes=True)
    sp = Scheduler(ep, retain_prefixes=True)
    for prompt_a, prompt_b, want_reuse in _boundary_cases():
        for prompt in (prompt_a, prompt_b):
            (rp,) = sp.run([Request(prompt=list(prompt),
                                    max_new_tokens=5)])
            assert rp.chunks == ep.chunks_for(len(prompt)) \
                - rp.reused_tokens // CHUNK
            # teacher-forcing recompute re-derives every greedy step
            seq = jnp.asarray([list(prompt) + rp.output_tokens],
                              jnp.int32)
            full = m.apply({"params": params}, seq, train=False)
            want = np.asarray(jnp.argmax(full[0], axis=-1))
            for i, tok in enumerate(rp.output_tokens):
                assert tok == int(want[len(prompt) - 1 + i]), \
                    f"recompute divergence at token {i} (prompt len " \
                    f"{len(prompt)}, reused {rp.reused_tokens})"
        assert rp.reused_tokens == want_reuse


def test_exactly_two_compiled_programs_with_zero_copy_hits(engine):
    """The program pin: a hit/miss stream compiles chunk + decode and
    nothing else — a prefix hit is host bookkeeping plus
    the existing programs, never a copy dispatch — across the whole
    module (every earlier test rode this engine)."""
    ep = engine
    ep.reset(clear_prefixes=True)
    sched = Scheduler(ep, retain_prefixes=True)
    rng = np.random.default_rng(1)
    pre = list(rng.integers(1, VOCAB, size=16))
    sched.run([Request(prompt=pre + [7, 8], max_new_tokens=3)])   # miss
    (hit,) = sched.run([Request(prompt=pre + [9], max_new_tokens=3)])
    assert hit.reused_tokens == 16
    ep.prefill_chunked(0, [5, 9, 2])  # scheduler-less callers: same program
    assert (ep.chunk_traces, ep.decode_traces) == (1, 1)
    assert ep.compiled_programs == 2


def test_cow_shared_page_never_freed_while_referenced(engine):
    """Copy-on-write refcount pinning, observed at the page level: the
    donor entry's pages are shared into the hitting slot's table (one
    page, >= 2 readers, ZERO copies); releasing either reader alone
    keeps the page resident; write-after-share lands on a FRESH page —
    the donor's pages are never written by the borrower."""
    ep = engine
    ep.reset(clear_prefixes=True)
    sched = Scheduler(ep, retain_prefixes=True)
    rng = np.random.default_rng(9)
    pre = list(rng.integers(1, VOCAB, size=8))     # exactly one page
    sched.run([Request(prompt=pre + [1], max_new_tokens=2)])
    stats = ep.pool_stats()
    assert stats["pages_in_use"] == 1              # the retained page
    assert stats["cow_shares"] == 0
    # b hits pre and stays live (manual stepping)
    b = Request(prompt=pre + [2, 3], max_new_tokens=50)
    sched.submit(b)
    while b.status != "running":
        sched.step()
    assert b.reused_tokens == 8
    shared = int(ep._page_table[ [s for s, r in
                                  enumerate(sched._running)
                                  if r is b][0], 0])
    assert ep.pool.refcount[shared] == 2           # entry + b's slot
    assert ep.pool_stats()["cow_shares"] == 1
    # write-after-share: b's tail page (holding its unique tokens and
    # decode writes) is NOT the shared page
    slot = [s for s, r in enumerate(sched._running) if r is b][0]
    tail = int(ep._page_table[slot, 1])
    assert tail != shared and ep.pool.refcount[tail] == 1
    # evicting the donor entry mid-flight is harmless: the page's slot
    # refcount keeps it resident
    assert ep.prefix_cache.evict_lru()
    assert ep.pool.refcount[shared] == 1
    while sched.pending:
        sched.step()
    assert b.status == "finished"
    # last reader gone: page freed NOW (immediate reclamation)
    assert ep.pool.refcount[shared] == 0
    assert ep.pool_stats()["pages_in_use"] == 0


def test_pool_exhaustion_queues_admissions_and_degrades_gracefully(
        lm_and_params):
    """A pool sized for ONE max-budget request at a time: three such
    requests serve back-to-back (admission blocks on reservation, FIFO
    holds, admit_blocked counts) — exhaustion is a queueing signal,
    never a mid-decode failure. Prefix entries give way under pressure
    (LRU eviction at reservation time)."""
    # max_len 64, page 8 -> 8 pages/request worst case; 9 usable pages
    eng = _mk_engine(lm_and_params, pool=2, slots=3,
                     num_pages=10)
    reg = telemetry.MetricsRegistry()
    sched = Scheduler(eng, retain_prefixes=True, registry=reg)
    rng = np.random.default_rng(4)
    reqs = [Request(prompt=list(rng.integers(1, VOCAB, size=8)),
                    max_new_tokens=56) for _ in range(3)]
    done = sched.run(reqs)
    assert len(done) == 3
    assert all(r.status == "finished" for r in reqs)
    snap = reg.snapshot()
    assert snap["counters"].get("serving.pool.admit_blocked", 0) > 0
    # the first request's retained prefix was evicted to make room
    # for a later reservation (pressure valve) — pool back to empty
    sched_stats = eng.pool_stats()
    assert sched_stats["pages_reserved"] == 0
    assert eng.prefix_cache.evictions >= 1
    # direct (scheduler-less) overcommit fails loudly, not silently
    eng.reset(clear_prefixes=True)
    eng.prefill_chunked(0, list(rng.integers(1, VOCAB, size=24)))
    eng.prefill_chunked(1, list(rng.integers(1, VOCAB, size=24)))
    with pytest.raises(RuntimeError, match="pool exhausted"):
        # 9 usable pages; two 24-token prompts hold 6, a third needs 3
        # more for its padded window plus decode growth past it
        eng.prefill_chunked(2, list(rng.integers(1, VOCAB, size=24)))
        for _ in range(60):
            eng.decode_step([1, 1, 1], [True, True, True],
                            [0.0, 0.0, 0.0])


def test_cold_start_paths_keep_the_admission_reservation(lm_and_params):
    """Regression (review finding): every cold-start release inside an
    admitted request — the first chunk's offset-0 branch, through
    ``prefill_chunk`` or ``prefill_chunked`` — must pass
    keep_reservation, or the admission
    promise silently evaporates and a later admission can steal the
    pages, resurrecting the mid-decode exhaustion the reservation
    design exists to prevent."""
    eng = _mk_engine(lm_and_params, pool=0, slots=2)
    assert eng.try_reserve_slot(0, 5)
    assert eng.pool.reserved_total == 5
    eng.prefill_chunk(0, [1, 2, 3], 0)            # offset-0 cold start
    # one page drawn FROM the reservation, the rest still promised
    assert int(eng._slot_reserved[0]) == 4
    assert eng.pool.reserved_total == 4
    eng.release_slot(0)
    assert eng.pool.reserved_total == 0
    assert eng.try_reserve_slot(1, 5)
    eng.prefill_chunked(1, list(range(1, 12)))    # two chunks, cold start
    assert int(eng._slot_reserved[1]) == 5 - 2
    assert eng.pool.reserved_total == int(eng._slot_reserved[1])
    eng.release_slot(1)


def test_paged_pool_telemetry_gauges_and_request_records(engine):
    ep = engine
    ep.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    ep.set_registry(reg)
    sched = Scheduler(ep, retain_prefixes=True, registry=reg)
    rng = np.random.default_rng(11)
    pre = list(rng.integers(1, VOCAB, size=16))
    reqs = [Request(prompt=pre + [1], max_new_tokens=3),
            Request(prompt=pre + [2, 3], max_new_tokens=3)]
    try:
        sched.run([reqs[0]])
        sched.run([reqs[1]])
    finally:
        ep.set_registry(None)
    snap = reg.snapshot()
    g = snap["gauges"]
    for key in ("serving.pool.pages_in_use", "serving.pool.pages_free",
                "serving.pool.cow_shares", "serving.pool.fragmentation"):
        assert key in g, f"missing gauge {key}"
    assert g["serving.pool.pages_in_use"] >= 0
    assert 0.0 <= g["serving.pool.fragmentation"] <= 1.0
    c = snap["counters"]
    assert c["serving.prefix.hits"] == 1
    assert c["serving.prefix.tokens_reused"] == 16
    recs = {rec["uid"]: rec for rec in reg.records
            if rec.get("tag") == "serving.request"}
    assert recs[reqs[0].uid]["reused_tokens"] == 0
    assert recs[reqs[1].uid]["reused_tokens"] == 16


def test_decode_page_counters_follow_the_rows_lengths(engine):
    """``serving.decode.pages_live`` / ``pages_tabled``: the share of
    the page table the decode kernel walks, from the lengths of the rows
    that decode. A request of prompt ``p`` and ``n`` tokens gets its
    first token from prefill and decodes ``n - 1`` steps, the step at
    position ``p + i`` attending ``p + i + 1`` keys."""
    from apex_tpu.telemetry.summarize import (render_summary,
                                              summarize_records)
    ep = engine
    ep.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    ep.set_registry(reg)
    sched = Scheduler(ep, registry=reg)
    jobs = [(5, 12), (17, 9), (8, 1)]        # (prompt length, new tokens)
    try:
        for p, n in jobs:                     # one at a time: rows alone
            sched.run([Request(prompt=list(range(1, p + 1)),
                               max_new_tokens=n)])
    finally:
        ep.set_registry(None)
    c = reg.snapshot()["counters"]
    lengths = [p + i + 1 for p, n in jobs for i in range(n - 1)]
    assert c["serving.decode.pages_live"] == sum(
        -(-length // ep.page_len) for length in lengths)
    assert c["serving.decode.pages_tabled"] == len(lengths) * ep.max_pages
    # a K and a V page a layer of the pool, written back by the kernel
    assert c["serving.decode.pages_written"] == \
        len(lengths) * ep.cache.k.shape[0] * 2
    assert c["serving.decode.steps"] == len(lengths)
    share = c["serving.decode.pages_live"] / c["serving.decode.pages_tabled"]
    assert share == pytest.approx(
        np.mean([-(-length // ep.page_len) for length in lengths])
        / ep.max_pages)
    text = render_summary(summarize_records([reg.snapshot()]))
    assert "serving.decode.pages_live" in text
    assert "serving.decode.pages_tabled" in text
    assert "serving.decode.pages_written" in text
    # several rows a step: every decoding row counts its own table
    ep.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    ep.set_registry(reg)
    try:
        Scheduler(ep, registry=reg).run(
            [Request(prompt=list(range(1, p + 1)), max_new_tokens=n)
             for p, n in jobs])
    finally:
        ep.set_registry(None)
    c = reg.snapshot()["counters"]
    assert c["serving.decode.pages_tabled"] == len(lengths) * ep.max_pages
    assert c["serving.decode.pages_written"] == \
        len(lengths) * ep.cache.k.shape[0] * 2
    assert c["serving.decode.pages_live"] == sum(
        -(-length // ep.page_len) for length in lengths)


def test_chunk_page_counters_follow_the_rows_offsets(engine):
    """``serving.chunk.pages_live`` / ``pages_tabled``: the share of the
    page table the chunk kernel walks, from the offsets the chunks were
    dispatched at. A prompt of ``p`` tokens is ``ceil(p / chunk_len)``
    chunks at offsets ``0, chunk_len, ...``; the program attends its
    padded chunk, so a chunk at ``o`` walks ``ceil((o + chunk_len) /
    page_len)`` pages of a table of ``max_pages``."""
    from apex_tpu.telemetry.summarize import (render_summary,
                                              summarize_records)
    ep = engine
    ep.reset(clear_prefixes=True)
    reg = telemetry.MetricsRegistry()
    ep.set_registry(reg)
    jobs = [(5, 3), (17, 2), (24, 1), (8, 4)]  # (prompt length, new tokens)
    try:
        Scheduler(ep, registry=reg).run(
            [Request(prompt=list(range(1, p + 1)), max_new_tokens=n)
             for p, n in jobs])
    finally:
        ep.set_registry(None)
    c = reg.snapshot()["counters"]
    offsets = [o for p, _ in jobs for o in range(0, p, ep.chunk_len)]
    assert c["serving.prefill.chunks"] == len(offsets)
    assert c["serving.chunk.pages_live"] == sum(
        -(-(o + ep.chunk_len) // ep.page_len) for o in offsets)
    assert c["serving.chunk.pages_tabled"] == len(offsets) * ep.max_pages
    share = c["serving.chunk.pages_live"] / c["serving.chunk.pages_tabled"]
    assert 0 < share < 1
    text = render_summary(summarize_records([reg.snapshot()]))
    assert f"chunk kernel's walk of the table (serving.chunk.pages_live " \
        f"/ serving.chunk.pages_tabled) {share:.4f}" in text


def test_paged_reset_keeps_warm_prefix_pages_unless_cleared(engine):
    ep = engine
    ep.reset(clear_prefixes=True)
    sched = Scheduler(ep, retain_prefixes=True)
    pre = list(np.random.default_rng(13).integers(1, VOCAB, size=8))
    sched.run([Request(prompt=pre + [1], max_new_tokens=2)])
    ep.reset()                    # warm: the entry keeps its page
    assert ep.pool_stats()["pages_in_use"] == 1
    (r,) = Scheduler(ep, retain_prefixes=True).run(
        [Request(prompt=pre + [2], max_new_tokens=2)])
    assert r.reused_tokens == 8, "reset() must not drop warm prefixes"
    ep.reset(clear_prefixes=True)
    assert ep.pool_stats()["pages_in_use"] == 0
    assert ep.prefix_cache.size == 0


def test_logical_requests_outlive_physical_rows(lm_and_params):
    """The capacity unlock in miniature: a pool holding the bytes of
    THREE contiguous rows serves a 9-request short-prompt stream
    through 3 slots with room to spare, because each request only ever
    holds the pages it uses and frees them at completion — the
    contiguous layout would spend 3 full rows regardless of length."""
    eng = _mk_engine(lm_and_params, pool=0, slots=3,
                     num_pages=3 * 8 + 1)
    reg = telemetry.MetricsRegistry()
    sched = Scheduler(eng, registry=reg)
    rng = np.random.default_rng(8)
    reqs = [Request(prompt=list(rng.integers(1, VOCAB, size=4)),
                    max_new_tokens=3) for _ in range(9)]
    done = sched.run(reqs)
    assert len(done) == 9 and all(r.status == "finished" for r in reqs)
    # worst-case page use per request: 1 page (4+3 tokens < page 8),
    # but the reservation is chunk-padded — still far under a row
    assert eng.pool_stats()["pages_in_use"] == 0
    snap = reg.snapshot()
    assert snap["counters"].get("serving.pool.admit_blocked", 0) == 0
