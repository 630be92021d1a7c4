"""Grouped-query heads in the three serving attention kernels against
their repeat-the-heads oracles, the grouped GEMM against the one-hot dense
form (uneven and empty groups, a held subset), and the drop-nothing expert
layer's shares against the uncut reference's expert sublayer - small sizes
on the CPU, Pallas in interpret mode. float32 throughout: the tolerances
(2e-6 attention, 1e-4 products of 256-384 terms) are summation order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.kernels.decode_attention import (_pool_write_tokens,
                                               decode_attention_reference,
                                               gather_pages,
                                               paged_decode_attention)
from apex_tpu.kernels.grouped_gemm import (group_ranges, grouped_gemm,
                                           grouped_gemm_reference)
from apex_tpu.kernels.prefill_attention import (paged_prefill_attention,
                                                prefill_attention,
                                                prefill_attention_reference)
from apex_tpu.transformer.moe import dropless_top1_experts
from benchmarks.lib import reference_zaya as rz

pytestmark = pytest.mark.serving

CFG = {"hidden_size": 128, "num_hidden_layers": 1,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "num_experts": 16, "moe_intermediate_size": 128,
       "router_hidden_size": 16, "vocab_size": 256, "cca_time0": 2,
       "cca_time1": 2}


# ------------------------------------------------------- grouped attention
def _pool_case(h, h_kv, d, seed=0):
    B, pl_, P, L = 3, 128, 9, 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, h, d))
    kp = jax.random.normal(ks[1], (L, P, h_kv, d, pl_))
    vp = jax.random.normal(ks[2], (L, P, h_kv, d, pl_))
    pt = jnp.asarray(np.array([[1, 2, 0], [3, 4, 5], [6, 0, 0]], np.int32))
    qc = jax.random.normal(ks[3], (B, h, 128, d))
    return q, qc, kp, vp, pt


@pytest.mark.parametrize("h,h_kv,d", [(4, 2, 16), (8, 2, 128), (4, 4, 64),
                                      (6, 1, 64), (8, 2, 64), (2, 2, 128)])
def test_grouped_heads_match_the_repeated_heads_oracle(h, h_kv, d):
    q, qc, kp, vp, pt = _pool_case(h, h_kv, d)
    G, scale = h // h_kv, 1.0 / np.sqrt(d)
    k, v = gather_pages(kp, pt, 1), gather_pages(vp, pt, 1)
    kr, vr = jnp.repeat(k, G, 1), jnp.repeat(v, G, 1)
    lens = jnp.asarray([200, 300, 7], jnp.int32)
    want = decode_attention_reference(q, kr, vr, lens, scale=scale)
    got = paged_decode_attention(q, kp, vp, pt, lens, layer=1)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    offs = jnp.asarray([0, 128, 0], jnp.int32)
    want = prefill_attention_reference(qc, kr, vr, offs, scale=scale)
    got = paged_prefill_attention(qc, kp, vp, pt, offs, layer=1)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    got = prefill_attention(qc, k, v, offs)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6


@pytest.mark.parametrize("lens", [
    [128, 129, 384],      # on a page boundary, one past it, a full table
    [1, 384, 0],          # one token beside a full table, an empty row
    [256, 257, 1],        # live pages 2, 3, 1 at two pages a step
], ids=["boundary", "one_and_full_and_empty", "ragged_steps"])
@pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
@pytest.mark.parametrize("h,h_kv,d", [(8, 2, 128), (4, 4, 64), (16, 2, 256)])
def test_paged_decode_walk_under_grouped_heads(h, h_kv, d, lens, write):
    """The decode kernel's steps of several pages, live pages only, at
    the expert models' head geometries (8q/2kv x 128, 16q/2kv x 256) and
    at plain heads: float32, so the tolerance is summation order. The
    sentinel page is NaN. ``write``: the call is handed the rows' new
    K/V too - its output and its pool must equal, bit for bit, the XLA
    write (``_pool_write_tokens``) followed by the read-only call."""
    from apex_tpu.kernels import vmem
    q, _, kp, vp, pt = _pool_case(h, h_kv, d, seed=3)
    G, scale = h // h_kv, 1.0 / np.sqrt(d)
    lens = jnp.asarray(lens, jnp.int32)
    pt = jnp.asarray(np.array([[1, 2, 7], [3, 4, 5], [6, 8, 2]], np.int32))
    live = -(-np.asarray(lens) // 128)
    pt = jnp.where(np.arange(3)[None, :] < live[:, None], pt, 0)
    if write:
        nk, nv = jax.random.normal(jax.random.PRNGKey(9), (2, 3, h_kv, d))
        pos = np.maximum(np.asarray(lens) - 1, 0)
        # a row of length 0 names a page past the pool: dropped
        ids = jnp.where(lens > 0, pt[np.arange(3), pos // 128], kp.shape[1])
        k_in, v_in = kp.at[:, 0].set(jnp.nan), vp.at[:, 0].set(jnp.nan)
        kp = _pool_write_tokens(kp, 1, ids, jnp.asarray(pos % 128), nk)
        vp = _pool_write_tokens(vp, 1, ids, jnp.asarray(pos % 128), nv)
    k, v = gather_pages(kp, pt, 1), gather_pages(vp, pt, 1)
    want = decode_attention_reference(q, jnp.repeat(k, G, 1),
                                      jnp.repeat(v, G, 1), lens, scale=scale)
    kp, vp = kp.at[:, 0].set(jnp.nan), vp.at[:, 0].set(jnp.nan)
    vmem.set_override("decode.paged_step_bytes", 2 * h_kv * d * 128 * 4)
    try:
        got = jax.jit(lambda *a: paged_decode_attention(*a, layer=1))(
            q, kp, vp, pt, lens)
        if write:
            out, k_got, v_got = jax.jit(
                lambda q, k, v, pt, lens, nk, nv: paged_decode_attention(
                    q, k, v, pt, lens, new_k=nk, new_v=nv, layer=1))(
                q, k_in, v_in, pt, lens, nk, nv)
            assert np.array_equal(np.asarray(out), np.asarray(got))
            for t_got, t_want in ((k_got, kp), (v_got, vp)):
                assert np.array_equal(np.asarray(t_got), np.asarray(t_want),
                                      equal_nan=True)
    finally:
        vmem.remove_override("decode.paged_step_bytes")
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    assert bool(jnp.all(got[np.asarray(lens) == 0] == 0))


def test_query_heads_that_do_not_divide_are_refused_by_name():
    q, qc, kp, vp, pt = _pool_case(4, 2, 16)
    lens = jnp.asarray([5, 5, 5], jnp.int32)
    with pytest.raises(ValueError, match="paged_decode_attention.*multiple"):
        paged_decode_attention(q[:, :3], kp, vp, pt, lens, layer=0)
    with pytest.raises(ValueError, match="paged_prefill_attention.*multiple"):
        paged_prefill_attention(qc[:, :3], kp, vp, pt, lens, layer=0)


# ------------------------------------------------------------ grouped GEMM
@pytest.mark.parametrize("M,picks", [
    (40, [0, 1, 3, 4]),          # uneven, expert 2 empty
    (300, [0, 1, 3, 4]),         # three row tiles
    (16, [4]),                   # every token on one expert
    (1, [2]),                    # one token
])
def test_grouped_gemm_matches_the_one_hot_dense_form(M, picks):
    K, N, E = 256, 384, 5
    ks = jax.random.split(jax.random.PRNGKey(M), 2)
    x = jax.random.normal(ks[0], (M, K))
    w = jax.random.normal(ks[1], (E, K, N))
    choice = jnp.sort(jnp.asarray(
        np.random.default_rng(M).choice(picks, M), jnp.int32))
    sizes, st, en = group_ranges(choice, E)
    assert int(sizes.sum()) == M
    want = jnp.stack([x[i] @ w[choice[i]] for i in range(M)])
    for bk in (128, 256):
        got = grouped_gemm(x, w, st, en, block_k=bk, block_n=128)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    assert float(jnp.max(jnp.abs(
        grouped_gemm_reference(x, w, st, en) - want))) < 1e-4
    # a share: only experts 3 and 1 held here, in that order
    _, st, en = group_ranges(choice, E, held=(3, 1))
    got = grouped_gemm(x, w[jnp.asarray([3, 1])], st, en, block_n=128)
    mine = ((choice == 3) | (choice == 1))[:, None]
    assert float(jnp.max(jnp.abs(got - jnp.where(mine, want, 0)))) < 1e-4


def test_the_expert_layers_shares_add_up_to_the_uncut_reference():
    """16 experts, two chips holding 0-7 and 8-15: each gives its part of
    the layer's result, nothing is dropped at any imbalance (expert 5
    gets no token, expert 9 half of them), and the parts add up to what
    the uncut reference gives for the whole sublayer."""
    cfg = CFG
    E, H, F, T = 16, 128, 128, 50
    lp = rz.seeded_weights(cfg, 7, jnp.float32,
                           balance_tokens=0)["layers"][0]
    rng = np.random.default_rng(0)
    choice = np.where(rng.random(T) < 0.5, 9, rng.integers(0, E, T))
    choice[choice == 5] = 6
    choice = jnp.asarray(choice, jnp.int32)
    u = jax.random.normal(jax.random.PRNGKey(1), (T, H))
    p = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (T, E)), -1)
    gate = jnp.take_along_axis(p, choice[:, None], 1)[:, 0]
    with jax.default_matmul_precision("highest"):
        whole = rz.experts(u, p, choice, lp, cfg)
        parts, counts = [], None
        for held in (tuple(range(8)), tuple(range(8, 16))):
            idx = jnp.asarray(held)
            y, counts = dropless_top1_experts(
                u, gate, choice, lp["experts/w_gate_up"][idx],
                lp["experts/w_down"][idx], num_experts=E, experts_held=held)
            ref_part = rz.experts(u, p, choice, lp, cfg, held=held)
            assert float(jnp.max(jnp.abs(y - ref_part))) < 1e-4
            parts.append(y)
    assert float(jnp.max(jnp.abs(parts[0] + parts[1] - whole))) < 1e-4
    assert int(counts.sum()) == T and int(counts[5]) == 0
    assert int(counts[9]) >= T // 3
    # every token's row is some expert's product, none zeroed: dropped
    # tokens would read 0 here
    assert float(jnp.min(jnp.max(jnp.abs(parts[0] + parts[1]), -1))) > 0
