"""Mosaic-lowering parity tier: every Pallas kernel vs its fp32 jnp oracle
ON THE REAL CHIP (VERDICT round-1 item 4 / SURVEY §5.4 inverse).

The hermetic suite runs these kernels in interpret mode only; this tier is
the proof the compiled Mosaic code computes the same numbers. Tolerances
follow the reference's L0 kernel tests (fp32 tight, bf16 ~1e-2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _close(a, b, tol, atol=None):
    # On silicon, fp32 matmuls run through the MXU at default precision
    # (bf16 passes), so near-zero outputs show large RELATIVE error while
    # absolute error stays at bf16-epsilon scale — compare atol-dominant.
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=tol, atol=tol if atol is None else atol)


# ------------------------------------------------------------ layer norm
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_layer_norm_fwd_bwd(tpu_backend, dtype, tol):
    from apex_tpu.kernels.layer_norm import layer_norm, layer_norm_reference

    n, h = 256, 512
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h), dtype) * 2.0
    w = jax.random.normal(jax.random.PRNGKey(1), (h,), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(2), (h,), jnp.float32)

    _close(jax.jit(layer_norm)(x, w, b),
           layer_norm_reference(x, w, b), tol)

    def loss_k(x, w, b):
        return jnp.sum(jnp.square(layer_norm(x, w, b)))

    def loss_r(x, w, b):
        return jnp.sum(jnp.square(layer_norm_reference(
            jnp.asarray(x, jnp.float32), w, b)))

    gk = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2)))(
        x.astype(jnp.float32), w, b)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(x.astype(jnp.float32), w, b)
    for a, r in zip(gk, gr):
        _close(a, r, 1e-3)


def test_rms_norm(tpu_backend):
    from apex_tpu.kernels.layer_norm import rms_norm, rms_norm_reference

    x = jax.random.normal(jax.random.PRNGKey(3), (128, 384), jnp.float32)
    w = jnp.ones((384,)) * 1.5
    _close(jax.jit(rms_norm)(x, w), rms_norm_reference(x, w), 2e-5)


# ------------------------------------------------------------- xentropy
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_xentropy_fwd_bwd(tpu_backend, smoothing):
    from apex_tpu.kernels.xentropy import (softmax_cross_entropy_loss,
                                           xent_reference)

    n, v = 128, 1024
    logits = jax.random.normal(jax.random.PRNGKey(4), (n, v),
                               jnp.float32) * 4.0
    labels = jax.random.randint(jax.random.PRNGKey(5), (n,), 0, v)

    _close(jax.jit(lambda l: softmax_cross_entropy_loss(
        l, labels, smoothing=smoothing))(logits),
        xent_reference(logits, labels, smoothing), 1e-5)

    gk = jax.jit(jax.grad(lambda l: jnp.sum(softmax_cross_entropy_loss(
        l, labels, smoothing=smoothing))))(logits)
    gr = jax.grad(lambda l: jnp.sum(xent_reference(
        l, labels, smoothing)))(logits)
    # compiled exp/sum reassociation differs from the composed oracle at
    # ~1e-4 relative on the smallest softmax entries
    _close(gk, gr, 5e-4, atol=1e-5)


# -------------------------------------------------------- multi-tensor
def test_multi_tensor_ops(tpu_backend):
    from apex_tpu.kernels.multi_tensor import (fused_adam_step, fused_axpby,
                                               fused_l2norm, fused_scale)

    n = 8192
    x = jax.random.normal(jax.random.PRNGKey(6), (n,), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(7), (n,), jnp.float32)

    out, inf = jax.jit(fused_scale)(x, 0.5)
    _close(out, x * 0.5, 1e-6)
    assert not bool(inf)

    ax, inf = jax.jit(fused_axpby)(x, y, 2.0, -1.0)
    _close(ax, 2.0 * x - y, 1e-6)

    _close(jax.jit(fused_l2norm)(x), jnp.sqrt(jnp.sum(x * x)), 1e-5)

    # inf detection must survive lowering
    bad = x.at[17].set(jnp.inf)
    _, inf = jax.jit(fused_scale)(bad, 1.0)
    assert bool(inf)

    # one adam step vs the composed update
    m = jnp.zeros((n,))
    v = jnp.zeros((n,))
    p2, m2, v2 = jax.jit(lambda p, m, v, g: fused_adam_step(
        p, m, v, g, lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-8,
        weight_decay=0.0, step=1, adam_w_mode=True))(x, m, v, y)
    m_ref = 0.1 * y
    v_ref = 0.001 * y * y
    update = (m_ref / 0.1) / (jnp.sqrt(v_ref / 0.001) + 1e-8)
    _close(p2, x - 1e-2 * update, 1e-5)
    _close(m2, m_ref, 1e-4, atol=1e-6)
    _close(v2, v_ref, 1e-4, atol=1e-6)


# ------------------------------------------------------ flash attention
@pytest.mark.parametrize("case", ["plain", "causal", "segments", "bias"])
def test_flash_attention_fwd_bwd(tpu_backend, case):
    from apex_tpu.kernels.flash_attention import (flash_attention,
                                                  mha_reference)

    b, h, s, d = 2, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    kw = {"scale": d ** -0.5}
    if case == "causal":
        kw["causal"] = True
    elif case == "segments":
        kw["segment_ids"] = jnp.concatenate(
            [jnp.zeros((b, s // 2), jnp.int32),
             jnp.ones((b, s - s // 2), jnp.int32)], axis=1)
    elif case == "bias":
        kw["bias"] = jax.random.normal(ks[3], (b, 1, s, s),
                                       jnp.float32) * 0.5

    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, **kw))(q, k, v)
    ref = mha_reference(q, k, v, **kw)
    _close(out, ref, 2e-2)  # MXU default-precision scale (see _close)

    def lk(q, k, v):
        return jnp.sum(jnp.square(flash_attention(q, k, v, **kw)))

    def lr(q, k, v):
        return jnp.sum(jnp.square(mha_reference(q, k, v, **kw)))

    gk = jax.jit(jax.grad(lk, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(gk, gr):
        _close(a, r, 2e-2, atol=1e-1)  # grad magnitudes are O(seq)
    if case == "bias":
        gbk = jax.jit(jax.grad(
            lambda bb: jnp.sum(jnp.square(flash_attention(
                q, k, v, scale=d ** -0.5, bias=bb)))))(kw["bias"])
        gbr = jax.grad(
            lambda bb: jnp.sum(jnp.square(mha_reference(
                q, k, v, scale=d ** -0.5, bias=bb))))(kw["bias"])
        _close(gbk, gbr, 2e-2, atol=1e-1)


def test_flash_attention_bf16(tpu_backend):
    from apex_tpu.kernels.flash_attention import (flash_attention,
                                                  mha_reference)

    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
               for kk in ks)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v)
    assert out.dtype == jnp.bfloat16
    # flash_attention defaults scale to 1/sqrt(d); mha_reference to 1.0
    _close(out, mha_reference(q, k, v, causal=True, scale=d ** -0.5), 5e-2)


# ------------------------------------------------------ causal softmax
def test_causal_softmax(tpu_backend):
    from apex_tpu.kernels.causal_softmax import (causal_softmax,
                                                 causal_softmax_reference)

    x = jax.random.normal(jax.random.PRNGKey(10), (4, 256, 256),
                          jnp.float32) * 3.0
    _close(jax.jit(lambda x: causal_softmax(x, 0.5))(x),
           causal_softmax_reference(x, 0.5), 1e-5)
    gk = jax.jit(jax.grad(lambda x: jnp.sum(jnp.sin(
        causal_softmax(x) * 3))))(x)
    gr = jax.grad(lambda x: jnp.sum(jnp.sin(
        causal_softmax_reference(x) * 3)))(x)
    _close(gk, gr, 1e-4)


# ------------------------------------------------- tuned block overrides
def test_tuned_override_lowers_and_matches(tpu_backend):
    """A bench_kernels --sweep override (non-default block) must lower on
    silicon and keep oracle parity — the 'only ever slower, never broken'
    contract behind APEX_TPU_TUNED."""
    from apex_tpu.kernels import vmem
    from apex_tpu.kernels.layer_norm import layer_norm, layer_norm_reference

    prev = vmem.overrides().get("layer_norm.block_rows")
    try:
        vmem.set_override("layer_norm.block_rows", 32)
        x = jax.random.normal(jax.random.PRNGKey(20), (512, 1024))
        w, b = jnp.ones((1024,)) * 1.1, jnp.zeros((1024,)) + 0.1
        _close(jax.jit(layer_norm)(x, w, b),
               layer_norm_reference(x, w, b), 1e-5)
    finally:
        # restore only OUR key — an APEX_TPU_TUNED registry loaded for
        # the whole gate run must survive this test
        if prev is None:
            vmem.remove_override("layer_norm.block_rows")
        else:
            vmem.set_override("layer_norm.block_rows", prev)


# ------------------------------------------------------ masked softmax
def test_masked_softmax(tpu_backend):
    """N8's arbitrary-mask kernel (round 3): compiled Mosaic lowering vs
    the fp32 oracle, incl. the [b, 1, sq, sk] head-broadcast mask."""
    from apex_tpu.kernels.masked_softmax import (masked_softmax,
                                                 masked_softmax_reference)

    b, h, sq, sk = 2, 4, 128, 256
    x = jax.random.normal(jax.random.PRNGKey(11), (b, h, sq, sk),
                          jnp.float32) * 3.0
    m = jax.random.bernoulli(jax.random.PRNGKey(12), 0.3,
                             (b, 1, sq, sk)).at[..., 0].set(False)
    _close(jax.jit(lambda x: masked_softmax(x, m, 0.5))(x),
           masked_softmax_reference(x, m, 0.5), 1e-5)
    gk = jax.jit(jax.grad(lambda x: jnp.sum(jnp.sin(
        masked_softmax(x, m) * 3))))(x)
    gr = jax.grad(lambda x: jnp.sum(jnp.sin(
        masked_softmax_reference(x, m) * 3)))(x)
    _close(gk, gr, 1e-4)


# ---------------------------------------------------------- group norm
@pytest.mark.parametrize("act", [None, "silu"])
def test_group_norm_fwd_bwd(tpu_backend, act):
    from apex_tpu.kernels.group_norm import (group_norm_nhwc,
                                             group_norm_reference)

    x = jax.random.normal(jax.random.PRNGKey(11), (2, 16, 16, 256),
                          jnp.float32) * 2.0
    g = jax.random.normal(jax.random.PRNGKey(12), (256,)) + 1.0
    b = jax.random.normal(jax.random.PRNGKey(13), (256,))

    out = jax.jit(lambda x: group_norm_nhwc(x, 16, g, b, act=act))(x)
    ref = group_norm_reference(x, 16, g, b, act=act)
    _close(out, ref, 1e-4, atol=1e-4)

    gk = jax.jit(jax.grad(lambda x, g, b: jnp.sum(jnp.sin(
        group_norm_nhwc(x, 16, g, b, act=act) * 2)), argnums=(0, 1, 2)))(
        x, g, b)
    gr = jax.grad(lambda x, g, b: jnp.sum(jnp.sin(
        group_norm_reference(x, 16, g, b, act=act) * 2)),
        argnums=(0, 1, 2))(x, g, b)
    for a, r in zip(gk, gr):
        _close(a, r, 1e-3, atol=1e-3)


def test_fp16_inputs_take_the_xla_fallback(tpu_backend):
    """TPU Mosaic has no fp16: every public fused op must detect float16
    operands and route to its jnp fallback (where XLA upconverts) instead
    of crashing the compile — found by the on-silicon scaler soak.
    bf16 stays on the Pallas path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.kernels import fused_scale, layer_norm, rms_norm
    from apex_tpu.kernels.xentropy import softmax_cross_entropy_loss
    from apex_tpu.kernels.flash_attention import flash_attention
    from apex_tpu.kernels.group_norm import group_norm_nhwc

    x16 = jnp.ones((8, 256), jnp.float16)
    g = jnp.ones((256,), jnp.float32)
    b = jnp.zeros((256,), jnp.float32)
    assert layer_norm(x16, g, b).dtype == jnp.float16
    assert rms_norm(x16, g).dtype == jnp.float16
    out, found = fused_scale(jnp.ones((300,), jnp.float16), 2.0)
    assert not bool(found) and float(out[0]) == 2.0
    lg = jnp.ones((8, 128), jnp.float16)
    assert np.isfinite(float(softmax_cross_entropy_loss(
        lg, jnp.zeros((8,), jnp.int32)).mean()))
    q = jnp.ones((1, 2, 128, 64), jnp.float16)
    assert jnp.all(jnp.isfinite(jnp.asarray(
        flash_attention(q, q, q, causal=True), jnp.float32)))
    xg = jnp.ones((2, 4, 4, 128), jnp.float16)
    y = group_norm_nhwc(xg, 4, jnp.ones((128,)), jnp.zeros((128,)))
    assert jnp.all(jnp.isfinite(jnp.asarray(y, jnp.float32)))
    # grads flow through the fallbacks too
    dx = jax.grad(lambda x: jnp.sum(jnp.asarray(
        layer_norm(x, g, b), jnp.float32)))(x16)
    assert dx.dtype == jnp.float16


# ------------------------------------------- serving attention (PR 21)
def _serving_case(kind, kv, seed=0):
    """Random operands at GPT-2 head geometry (12 x 64) over a 128-page
    layout: ``(kernel_fn, reference_fn, operands, kernel_name)``. int8
    pools carry per-head dequant scales; page tables are a random
    permutation of the pool so a wrong gather cannot pass."""
    import importlib

    rng = np.random.default_rng(seed)
    B, h, d, page, max_pages, C = 4, 12, 64, 128, 5, 256
    L = page * max_pages
    paged = kind.startswith("paged")
    decode = kind.endswith("decode")
    rows = B if decode else 1
    q_shape = (rows, h, d) if decode else (rows, h, C, d)
    q = jnp.asarray(rng.standard_normal(q_shape), jnp.bfloat16)
    kv_shape = (rows * max_pages + 1, h, page, d) if paged \
        else (rows, h, L, d)
    if kv == "int8":
        k, v = (jnp.asarray(rng.integers(-127, 128, kv_shape), jnp.int8)
                for _ in range(2))
        scales = dict(
            k_scale=jnp.asarray(rng.uniform(0.01, 0.03, h), jnp.float32),
            v_scale=jnp.asarray(rng.uniform(0.01, 0.03, h), jnp.float32))
    else:
        k, v = (jnp.asarray(rng.standard_normal(kv_shape), jnp.bfloat16)
                for _ in range(2))
        scales = {}
    if decode:
        # lengths: empty slot, one token, mid-page, page boundary
        pos = jnp.asarray([0, 1, 300, L][:rows], jnp.int32)
    else:
        pos = jnp.asarray([page], jnp.int32)     # chunk starts on page 1
    ops = [q, k, v]
    if paged:
        table = rng.permutation(np.arange(1, rows * max_pages + 1))
        ops.append(jnp.asarray(table.reshape(rows, max_pages), jnp.int32))
    ops.append(pos)
    # by module path: the package re-exports same-named FUNCTIONS
    mod = importlib.import_module(
        "apex_tpu.kernels."
        + ("decode_attention" if decode else "prefill_attention"))
    kernel = getattr(mod, f"{kind}_attention")
    reference = getattr(mod, f"{kind}_attention_reference")
    scale = 1.0 / d ** 0.5
    return (lambda *a: kernel(*a, **scales),
            lambda *a: reference(*a, scale=scale, **scales),
            ops, f"{kind}_attention")


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["paged_decode", "prefill",
                                  "paged_prefill"])
def test_serving_attention_matches_reference(tpu_backend, kind, kv):
    """The three serving attention kernels, compiled by Mosaic and RUN,
    against their gather/jnp oracles — and really the kernel: the
    compiled program must hold the ``tpu_custom_call`` (at these aligned
    shapes a silent give-way to the reference would compare the oracle
    with itself)."""
    from apex_tpu.utils.chip import kernel_calls

    kernel, reference, ops, name = _serving_case(kind, kv)
    compiled = jax.jit(kernel).lower(*ops).compile()
    assert kernel_calls(compiled.as_text()).get(name), \
        f"{name} gave way to its reference on {jax.default_backend()}"
    got = compiled(*ops)
    want = reference(*ops)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.all(jnp.isfinite(jnp.asarray(got, jnp.float32))))
    _close(got, want, 2e-2)


# (rows, query heads, K/V heads, head_dim, table pages, pool dtype): the
# served models' head geometries, and the int8 tier
_WRITE_CASES = {
    "mha_20x64_bf16": (24, 20, 20, 64, 8, "bf16"),
    "gqa_8q2kv_x128_bf16": (32, 8, 2, 128, 16, "bf16"),
    "gqa_16q2kv_x256_bf16": (16, 16, 2, 256, 16, "bf16"),
    "mha_12x64_int8": (8, 12, 12, 64, 5, "int8"),
}


@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_paged_decode_writes_the_token_it_attends(tpu_backend, case):
    """The decode program's call - the rows' new K/V handed to the
    kernel, which edits each row's last page in VMEM and copies it back
    while it multiplies - against what it replaced, the XLA page write
    in front of the read-only kernel: the attention output and the whole
    pool, every layer of it, bit for bit. Compiled and RUN: the order of
    the kernel's DMAs is the one thing the interpreter cannot show.
    Lengths cover a fresh page (lane 0), a page's last lane, mid-page,
    one token, a full table and rows of length 0 in between."""
    from apex_tpu.kernels.decode_attention import (_pool_write_tokens,
                                                   paged_decode_attention)
    from apex_tpu.utils.chip import kernel_calls

    rows, h, h_kv, d, table, kv = _WRITE_CASES[case]
    rng = np.random.default_rng(3)
    layers, page = 3, 128
    pool = rows * table + 1
    shape = (layers, pool, h_kv, d, page)
    if kv == "int8":
        store = jnp.int8
        draw = lambda s: jnp.asarray(rng.integers(-127, 128, s), store)  # noqa: E731,E501
        scales = dict(
            k_scale=jnp.asarray(rng.uniform(0.01, 0.03, h_kv), jnp.float32),
            v_scale=jnp.asarray(rng.uniform(0.01, 0.03, h_kv), jnp.float32))
    else:
        store = jnp.bfloat16
        draw = lambda s: jnp.asarray(rng.standard_normal(s), store)  # noqa: E731,E501
        scales = {}
    kp, vp, q = draw(shape), draw(shape), jnp.asarray(
        rng.standard_normal((rows, h, d)), jnp.bfloat16)
    new_k, new_v = draw((rows, h_kv, d)), draw((rows, h_kv, d))
    L = table * page
    lengths = rng.integers(1, L + 1, size=rows)
    lengths[:8] = [1, 0, 129, 128, 0, L, 2 * page + 1, 300]
    pt = rng.permutation(np.arange(1, pool)).reshape(rows, table)
    pt[np.arange(table)[None, :] >= -(-lengths[:, None] // page)] = 0
    pos = np.maximum(lengths - 1, 0)
    ids = np.where(lengths > 0, pt[np.arange(rows), pos // page], pool)
    pt, lens, ids, off = (jnp.asarray(t, jnp.int32)
                          for t in (pt, lengths, ids, pos % page))

    def writes(q, kp, vp):
        outs = []
        for layer in range(layers):
            out, kp, vp = paged_decode_attention(
                q, kp, vp, pt, lens, new_k=new_k, new_v=new_v, layer=layer,
                **scales)
            outs.append(out)
        return jnp.stack(outs), kp, vp

    def write_then_read(q, kp, vp):
        outs = []
        for layer in range(layers):
            kp = _pool_write_tokens(kp, layer, ids, off, new_k)
            vp = _pool_write_tokens(vp, layer, ids, off, new_v)
            outs.append(paged_decode_attention(q, kp, vp, pt, lens,
                                               layer=layer, **scales))
        return jnp.stack(outs), kp, vp

    compiled = jax.jit(writes, donate_argnums=(1, 2)).lower(
        q, kp, vp).compile()
    assert kernel_calls(compiled.as_text()) == \
        {"paged_decode_attention": layers}
    want = jax.jit(write_then_read)(q, kp, vp)
    got = compiled(q, kp + 0, vp + 0)           # copies: these are donated
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        bits = {1: np.uint8, 2: np.uint16}[g.itemsize]
        assert g.shape == w.shape and (g.view(bits) == w.view(bits)).all()
    # and the new columns are where the rows' lengths say
    live = np.flatnonzero(lengths)
    k_got = np.asarray(got[1])
    bits = {1: np.uint8, 2: np.uint16}[k_got.itemsize]
    cols = k_got[:, np.asarray(ids)[live], :, :, np.asarray(off)[live]]
    assert (cols.view(bits)                     # [live rows, layers, h, d]
            == np.asarray(new_k)[live][:, None].view(bits)).all()
