"""Blockwise (flash) attention Pallas kernels with custom VJP.

TPU-native equivalent of the reference's fused attention extensions:
- ``fast_multihead_attn`` (apex/contrib/csrc/multihead_attn/*.cu —
  self_multihead_attn_forward/backward: strided-batched QKV GEMMs + fused
  softmax) and
- ``fmhalib`` (apex/contrib/csrc/fmha/fmha_api.cpp — varlen packed
  flash-MHA for seqlen ≤ 512).

Design (SURVEY §6 long-context note): the kernel is blockwise over KV with
an online-softmax running (m, l) state, so a later ring-attention/context-
parallel extension only has to rotate KV blocks between chips (ppermute)
around the same inner kernel. Numerics follow the reference kernels: bf16/
half I/O in bf16 (fp16 operands take the jnp fallback on hardware —
Mosaic has no fp16), all accumulation in fp32, logsumexp saved for backward.

Layout: [batch, heads, seq, head_dim] (q, k, v). ``segment_ids`` gives the
varlen/packed-sequence masking of fmhalib (tokens attend only within their
segment). Unaligned shapes fall back to the jnp reference path, which XLA
fuses acceptably — the Pallas path is the transformer hot path
(seq % block == 0).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.kernels import vmem
from apex_tpu.kernels import mosaic_dtype_ok

__all__ = ["flash_attention", "mha_reference", "attn_chunk_fwd",
           "attn_chunk_bwd"]

_NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


# --------------------------------------------------------------- jnp reference
def mha_reference(q, k, v, *, causal: bool = False, scale: float = 1.0,
                  segment_ids: Optional[jnp.ndarray] = None,
                  mask: Optional[jnp.ndarray] = None,
                  bias: Optional[jnp.ndarray] = None,
                  dropout_rate: float = 0.0,
                  dropout_seed=None):
    """fp32-math reference (the oracle the reference's tests use a torch
    softmax composition for). ``bias`` is ADDITIVE on the scaled logits
    (apex's additive-mask MHA variants), broadcastable to [b, h, sq, sk].
    ``dropout_rate``/``dropout_seed``: inverted dropout on the softmax
    probabilities (the reference's fused softmax+dropout, N11) — the
    fallback stream (jax.random) differs from the Pallas kernel's hardware
    PRNG, like the reference's python vs fused impls differ."""
    out_dtype = q.dtype
    q32, k32, v32 = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q32, k32) * scale
    if bias is not None:
        s = s + jnp.asarray(bias, jnp.float32)
    sq, sk = s.shape[-2], s.shape[-1]
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, _NEG_INF)
    if segment_ids is not None:
        seg_mask = segment_ids[:, None, :, None] == \
            segment_ids[:, None, None, :]
        s = jnp.where(seg_mask, s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        key = jax.random.PRNGKey(jnp.asarray(dropout_seed, jnp.int32))
        keep = jax.random.bernoulli(key, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.asarray(jnp.einsum("bhqk,bhkd->bhqd", p, v32), out_dtype)


def _mix_seed(seed, b, qi, ki):
    """Murmur-style avalanche of (user seed, bh index, q-block, k-block)
    into one PRNG seed. A linear combination would collide systematically —
    seed=step with step+1 at block index i-1 reuses step's block-i mask, and
    nearby seeds shift rather than change the mask field; the wrap-multiply
    + xorshift mixing decorrelates all four inputs."""
    x = jnp.asarray(seed, jnp.uint32)
    for v, c in ((b, 0x9E3779B1), (qi, 0x85EBCA77), (ki, 0xC2B2AE3D)):
        x = (x ^ jnp.asarray(v, jnp.uint32)) * jnp.uint32(c)
        x = x ^ (x >> 16)
    return x.astype(jnp.int32)


def _keep_mask(seed_ref, b, qi, ki, block_q, block_k, rate):
    """Deterministic per-(bh, q-block, k-block) dropout keep-mask from the
    hardware PRNG. The seed formula is shared by the forward and BOTH
    backward kernels, so backward replays the exact forward mask (the
    reference kernels replay their philox state the same way, N11)."""
    pltpu.prng_seed(_mix_seed(seed_ref[0], b, qi, ki))
    bits = pltpu.bitcast(
        pltpu.prng_random_bits((block_q, block_k)), jnp.uint32)
    thresh = min(int(rate * 4294967296.0), 4294967295)
    return bits >= jnp.uint32(thresh)


# -------------------------------------------------------------- forward kernel
def _fwd_kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref, bias_ref, seed_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref, *, scale, causal,
                block_q, block_k, have_segs, have_bias, dropout_rate):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: whole block above the diagonal → skip
    run = True
    if causal:
        run = (qi * block_q + block_q - 1) >= (ki * block_k)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if have_bias:
            s = s + bias_ref[0].astype(jnp.float32)

        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if have_segs:
            segq = segq_ref[0, 0, pl.ds(qi * block_q, block_q)]   # [bq]
            segk = segk_ref[0, 0, pl.ds(ki * block_k, block_k)]   # [bk]
            s = jnp.where(segq[:, None] == segk[None, :], s, _NEG_INF)

        m_prev = m_ref[:, :1]                     # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                    # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)           # [bq, 1]
        # l accumulates UNDROPPED p (the softmax normalizer is exact);
        # dropout zeroes entries only in the PV accumulation
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        p_acc = p
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref, pl.program_id(0), qi, ki,
                              block_q, block_k, dropout_rate)
            p_acc = jnp.where(keep, p, 0.0)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p_acc, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        denom = l_safe * (1.0 - dropout_rate)   # inverted-dropout scaling
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(l_safe)
        lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = lse[:, 0]


# ------------------------------------------------------------- backward kernels
def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     segq_ref, segk_ref, bias_ref, seed_ref, dk_ref, dv_ref,
                     dk_acc, dv_acc, *, scale, causal, block_q, block_k,
                     have_segs, have_bias, dropout_rate):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (qi * block_q + block_q - 1) >= (ki * block_k)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if have_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if have_segs:
            segq = segq_ref[0, 0, pl.ds(qi * block_q, block_q)]
            segk = segk_ref[0, 0, pl.ds(ki * block_k, block_k)]
            s = jnp.where(segq[:, None] == segk[None, :], s, _NEG_INF)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
        p = jnp.exp(s - lse[:, None])                 # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # replay the forward's mask: same seed formula, (qi, ki) order
            keep = _keep_mask(seed_ref, pl.program_id(0), qi, ki,
                              block_q, block_k, dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_d = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            p_d = p
        dv_acc[:] += jax.lax.dot_general(
            p_d, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   segq_ref, segk_ref, bias_ref, seed_ref, dq_ref, *rest,
                   scale, causal, block_q, block_k, have_segs, have_bias,
                   emit_dlog, dropout_rate):
    # rest = (dlog_ref, dq_acc) when emit_dlog else (dq_acc,)
    if emit_dlog:
        dlog_ref, dq_acc = rest
    else:
        (dq_acc,) = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = (qi * block_q + block_q - 1) >= (ki * block_k)

    if emit_dlog and causal:
        # each (qi, ki) grid step owns its dlog block; skipped blocks must
        # still be defined
        @pl.when(jnp.logical_not(run))
        def _zero_dlog():
            dlog_ref[0] = jnp.zeros_like(dlog_ref[0])

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if have_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if have_segs:
            segq = segq_ref[0, 0, pl.ds(qi * block_q, block_q)]
            segk = segk_ref[0, 0, pl.ds(ki * block_k, block_k)]
            s = jnp.where(segq[:, None] == segk[None, :], s, _NEG_INF)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref, pl.program_id(0), qi, ki,
                              block_q, block_k, dropout_rate)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        dlogits = p * (dp - delta[:, None])       # d loss / d (scaled+bias)
        if emit_dlog:
            dlog_ref[0] = dlogits.astype(dlog_ref.dtype)
        ds = dlogits * scale
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dbias_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  segq_ref, segk_ref, bias_ref, seed_ref, dbias_ref, *,
                  scale, causal, block_q, block_k, have_segs, n_inner,
                  dropout_rate, bh_of):
    """Reduced bias cotangent for BROADCAST bias classes: grid is
    (B*, nq, nk, R) with the broadcast-reduced dim R innermost, so the
    (class, i, j) output block stays resident in VMEM across the R steps
    and dlogits accumulates in place — HBM only ever sees the final
    [B*, sq, sk], never the [b*h, sq, sk] intermediate."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    run = True
    if causal:
        run = (qi * block_q + block_q - 1) >= (ki * block_k)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if have_segs:
            segq = segq_ref[0, 0, pl.ds(qi * block_q, block_q)]
            segk = segk_ref[0, 0, pl.ds(ki * block_k, block_k)]
            s = jnp.where(segq[:, None] == segk[None, :], s, _NEG_INF)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            bh_idx = bh_of(pl.program_id(0), pl.program_id(3))
            keep = _keep_mask(seed_ref, bh_idx, qi, ki,
                              block_q, block_k, dropout_rate)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        dbias_ref[0] += (p * (dp - delta[:, None])).astype(dbias_ref.dtype)


# ------------------------------------------------------------------- dispatch
def _flatten(q):
    b, h, s, d = q.shape
    return q.reshape(b * h, s, d)


def _seg_flat(segment_ids, h):
    # [b, s] -> [b*h, s]
    return jnp.repeat(segment_ids, h, axis=0)


def _has_vma(x):
    """True when ``x`` is varying over shard_map manual axes. Pallas
    interpret mode (the CPU test path) cannot lower such inputs — its
    internal dynamic_slice grid indexing mixes unvaried loop constants with
    varying operands and trips check_vma — so dispatch falls back to the
    jnp reference there. Real-TPU Mosaic lowering is unaffected."""
    try:
        return bool(jax.typeof(x).vma)
    except (AttributeError, TypeError):
        return False


def _match_vma(x, like):
    """Cast a freshly-created constant to the varying-manual-axes of ``like``
    so it can mix with per-shard data inside shard_map(check_vma=True)."""
    try:
        vma = jax.typeof(like).vma
        cur = jax.typeof(x).vma
        missing = tuple(sorted(set(vma) - set(cur)))
        if missing:
            return jax.lax.pcast(x, missing, to="varying")
    except (AttributeError, TypeError):
        pass
    return x


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the vma (varying-manual-axes) of ``like``,
    so pallas_call outputs type-check inside shard_map(check_vma=True) —
    the ring/Ulysses context-parallel wrappers call these kernels there."""
    try:
        vma = jax.typeof(like).vma
        if vma:
            return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    except (AttributeError, TypeError):
        pass
    return jax.ShapeDtypeStruct(shape, dtype)


def _resolve_blocks(block_q, block_k):
    """Default/tuned block sizes, clamped to the Pallas tile alignments
    (_pallas_ok: bq sublane-multiple 8, bk lane-multiple 128 — a tuned
    file must never drop the kernel to the quadratic-memory fallback)."""
    if block_q is None:
        block_q = vmem.get_override("flash.block_q", DEFAULT_BLOCK_Q,
                                    multiple=8)
    if block_k is None:
        block_k = vmem.get_override("flash.block_k", DEFAULT_BLOCK_K,
                                    multiple=128)
    return block_q, block_k


def _resolve_bwd_blocks(bq, bk, sq, sk, dropout_rate):
    """Backward-specific tuned blocks (``flash.bwd_block_q``/``_k``),
    defaulting to the forward's resolved values.

    Only consulted when dropout is OFF: the dropout keep-mask is seeded
    per (bh, q-block, k-block) FORWARD block, so a backward running on a
    different geometry could not replay it. The bwd kernels' working set
    differs from the forward's (dk/dv accumulators + dlog tiles), so its
    optimum need not match — measured on v5e the bwd prefers a smaller
    k-block than the forward's 1024 (BASELINE.md round-5 kernel tier)."""
    if dropout_rate > 0.0:
        return bq, bk
    bq2 = vmem.get_override("flash.bwd_block_q", bq, multiple=8)
    bk2 = vmem.get_override("flash.bwd_block_k", bk, multiple=128)
    return _fit_block(bq2, sq, 8), _fit_block(bk2, sk, 128)


def _fit_block(b, s, multiple):
    """Shrink a (possibly tuned) block to the LARGEST aligned divisor of
    the sequence that is <= b. A big tuned block (e.g. block_q=1024 from
    the v5e sweep) must degrade to a smaller Pallas block at shapes it
    doesn't divide — never drop the call to the quadratic-memory
    fallback, which is what _pallas_ok would otherwise do.

    Divisor scan, not repeated halving: halving a non-divisor like 768
    at s=1024 bottoms out at 8 (every halving step misses 512), and
    near-degenerate blocks are both slow and fragile in Mosaic; the
    scan finds 512. When s has NO aligned divisor >= multiple (e.g.
    s=250 at multiple=128) the floor `multiple` itself is returned even
    though it does not divide s — callers must keep the _pallas_ok gate,
    which rejects that case into the jnp fallback. Trace-time only,
    <= b/multiple iterations."""
    b = min(b, s)
    b -= b % multiple
    while b > multiple and s % b:
        b -= multiple
    return max(multiple, b)


def _pallas_ok(sq, sk, d, bq, bk):
    # bk is the lane dim of the [bq, bk] score tile → multiple of 128;
    # bq is the sublane dim → multiple of 8.
    return (sq % bq == 0 and sk % bk == 0 and d % 8 == 0
            and bq % 8 == 0 and bk % 128 == 0)


def _validate_bias(bias, b, h, sq, sk):
    """Shared bias validation for BOTH dispatch paths (Pallas and the jnp
    fallback must agree on what is accepted, or a model validated at
    unaligned shapes would crash once shapes become block-aligned)."""
    if bias is None:
        return
    if getattr(bias, "ndim", None) != 4 or bias.shape[2:] != (sq, sk) \
            or bias.shape[0] not in (1, b) or bias.shape[1] not in (1, h):
        raise ValueError(
            f"flash_attention: bias shape {getattr(bias, 'shape', None)} "
            f"not broadcastable to {(b, h, sq, sk)} (rank 4; leading dims "
            "may be 1; the [sq, sk] plane must be full)")


def _canon_bias(bias, bh, h, sq, sk):
    """Canonicalize an additive logits bias broadcastable to [b, h, sq, sk]
    into (bias3 [B*, sq, sk], index fn flat-bh-index → B*-index, have_bias,
    broadcast class).

    Only the leading two dims may broadcast (the [sq, sk] plane is always
    full — a [*, 1, sk] padding mask should be broadcast by the caller,
    which costs sq× memory but keeps the kernel's block map static)."""
    if bias is None:
        return None, (lambda b: 0), False, "none"
    b = bh // h
    _validate_bias(bias, b, h, sq, sk)
    bb, bhh = bias.shape[0], bias.shape[1]
    if bb == 1 and bhh == 1:
        return bias.reshape(1, sq, sk), (lambda i: 0), True, "one"
    if bb == 1:
        return bias.reshape(h, sq, sk), (lambda i: i % h), True, "head"
    if bhh == 1:
        return bias.reshape(b, sq, sk), (lambda i: i // h), True, "batch"
    return bias.reshape(bh, sq, sk), (lambda i: i), True, "full"


def _seed_operand(seed, like):
    """SMEM (1,) int32 seed operand (zeros when dropout is off)."""
    if seed is None:
        arr = jnp.zeros((1,), jnp.int32)
    else:
        arr = jnp.asarray(seed, jnp.int32).reshape(1)
    return _match_vma(arr, like)


_SEED_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _fwd_pallas(q3, k3, v3, segq, segk, scale, causal, bq, bk, interpret,
                bias=None, h=None, dropout_rate=0.0, dropout_seed=None):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    have_segs = segq is not None
    if not have_segs:
        segq = _match_vma(jnp.zeros((bh, sq), jnp.int32), q3)
        segk = _match_vma(jnp.zeros((bh, sk), jnp.int32), q3)
    segq = segq.reshape(bh, 1, sq)
    segk = segk.reshape(bh, 1, sk)
    bias3, bmap, have_bias, _ = _canon_bias(bias, bh, h or 1, sq, sk)
    if not have_bias:
        bias3 = _match_vma(jnp.zeros((1, bq, bk), jnp.float32), q3)
        bias_spec = pl.BlockSpec((1, bq, bk), lambda b, i, j: (0, 0, 0))
    else:
        bias_spec = pl.BlockSpec((1, bq, bk),
                                 lambda b, i, j: (bmap(b), i, j))
    seed1 = _seed_operand(dropout_seed, q3)
    grid = (bh, sq // bq, sk // bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, have_segs=have_segs,
                               have_bias=have_bias,
                               dropout_rate=dropout_rate)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, sq), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, sk), lambda b, i, j: (b, 0, 0)),
            bias_spec,
            _SEED_SPEC,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, sq), lambda b, i, j: (b, 0, 0)),
        ],
        out_shape=[
            _sds((bh, sq, d), q3.dtype, q3),
            _sds((bh, 1, sq), jnp.float32, q3),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret, name="flash_attention_fwd",
    )(q3, k3, v3, segq, segk, bias3, seed1)
    return o, lse


def _bwd_pallas(q3, k3, v3, do3, lse, delta, segq, segk, scale, causal, bq, bk,
                interpret, out_dtype=None, bias=None, h=None,
                dropout_rate=0.0, dropout_seed=None):
    """delta: [bh, 1, sq] fp32 = sum(do * o, -1); lse: [bh, 1, sq] fp32.

    ``out_dtype`` overrides the gradient dtypes (default: match inputs);
    ring attention passes fp32 so cross-chunk accumulation stays exact while
    the kernels still stream bf16 inputs (they upcast per-tile internally).

    With ``bias``, additionally returns dlogits [bh, sq, sk] fp32 (the bias
    cotangent before broadcast-reduction) — an O(s²) buffer, same footprint
    the unfused backward pays; bias-free calls allocate nothing extra.
    """
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    have_segs = segq is not None
    if not have_segs:
        segq = _match_vma(jnp.zeros((bh, sq), jnp.int32), q3)
        segk = _match_vma(jnp.zeros((bh, sk), jnp.int32), q3)
    segq = segq.reshape(bh, 1, sq)
    segk = segk.reshape(bh, 1, sk)
    bias3, bmap, have_bias, bclass = _canon_bias(bias, bh, h or 1, sq, sk)
    if not have_bias:
        bias3 = _match_vma(jnp.zeros((1, bq, bk), jnp.float32), q3)
        bias_spec_ji = pl.BlockSpec((1, bq, bk), lambda b, j, i: (0, 0, 0))
        bias_spec_ij = pl.BlockSpec((1, bq, bk), lambda b, i, j: (0, 0, 0))
    else:
        bias_spec_ji = pl.BlockSpec((1, bq, bk),
                                    lambda b, j, i: (bmap(b), i, j))
        bias_spec_ij = pl.BlockSpec((1, bq, bk),
                                    lambda b, i, j: (bmap(b), i, j))
    # full-rank bias: dlogits IS dbias, emit it straight from the dq kernel;
    # broadcast classes: a separate reduced pass (below) so HBM never holds
    # the [bh, sq, sk] intermediate
    emit_dlog = have_bias and bclass == "full"
    seed1 = _seed_operand(dropout_seed, q3)

    dkdv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, have_segs=have_segs,
                          have_bias=have_bias, dropout_rate=dropout_rate),
        grid=(bh, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),   # do
            pl.BlockSpec((1, 1, sq), lambda b, j, i: (b, 0, 0)),   # lse
            pl.BlockSpec((1, 1, sq), lambda b, j, i: (b, 0, 0)),   # delta
            pl.BlockSpec((1, 1, sq), lambda b, j, i: (b, 0, 0)),   # segq
            pl.BlockSpec((1, 1, sk), lambda b, j, i: (b, 0, 0)),   # segk
            bias_spec_ji,
            _SEED_SPEC,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _sds((bh, sk, d), out_dtype or k3.dtype, q3),
            _sds((bh, sk, d), out_dtype or v3.dtype, q3),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret, name="flash_attention_bwd_dkv",
    )(q3, k3, v3, do3, lse, delta, segq, segk, bias3, seed1)

    dq_out_specs = [pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))]
    dq_out_shape = [_sds((bh, sq, d), out_dtype or q3.dtype, q3)]
    if emit_dlog:
        dq_out_specs.append(
            pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, j)))
        dq_out_shape.append(_sds((bh, sq, sk), jnp.float32, q3))
    dq_res = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, have_segs=have_segs,
                          have_bias=have_bias, emit_dlog=emit_dlog,
                          dropout_rate=dropout_rate),
        grid=(bh, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # do
            pl.BlockSpec((1, 1, sq), lambda b, i, j: (b, 0, 0)),   # lse
            pl.BlockSpec((1, 1, sq), lambda b, i, j: (b, 0, 0)),   # delta
            pl.BlockSpec((1, 1, sq), lambda b, i, j: (b, 0, 0)),   # segq
            pl.BlockSpec((1, 1, sk), lambda b, i, j: (b, 0, 0)),   # segk
            bias_spec_ij,
            _SEED_SPEC,
        ],
        out_specs=dq_out_specs,
        out_shape=dq_out_shape,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret, name="flash_attention_bwd_dq",
    )(q3, k3, v3, do3, lse, delta, segq, segk, bias3, seed1)
    dq = dq_res[0]
    dlog = dq_res[1] if emit_dlog else None

    if have_bias and not emit_dlog:
        # broadcast classes: one extra recompute pass whose output is the
        # REDUCED cotangent [B*, sq, sk] — bh/B* × less HBM than emitting
        # full dlogits and summing outside
        h_ = h or 1
        b_ = bh // h_
        if bclass == "one":
            B, R = 1, bh
            bexpr = lambda c, r: r                            # noqa: E731
        elif bclass == "head":
            B, R = h_, b_
            bexpr = lambda c, r: r * h_ + c                   # noqa: E731
        else:                                                 # "batch"
            B, R = b_, h_
            bexpr = lambda c, r: c * h_ + r                   # noqa: E731
        dlog = pl.pallas_call(
            functools.partial(_dbias_kernel, scale=scale, causal=causal,
                              block_q=bq, block_k=bk, have_segs=have_segs,
                              n_inner=R, dropout_rate=dropout_rate,
                              bh_of=bexpr),
            grid=(B, sq // bq, sk // bk, R),
            in_specs=[
                pl.BlockSpec((1, bq, d),
                             lambda c, i, j, r: (bexpr(c, r), i, 0)),  # q
                pl.BlockSpec((1, bk, d),
                             lambda c, i, j, r: (bexpr(c, r), j, 0)),  # k
                pl.BlockSpec((1, bk, d),
                             lambda c, i, j, r: (bexpr(c, r), j, 0)),  # v
                pl.BlockSpec((1, bq, d),
                             lambda c, i, j, r: (bexpr(c, r), i, 0)),  # do
                pl.BlockSpec((1, 1, sq),
                             lambda c, i, j, r: (bexpr(c, r), 0, 0)),  # lse
                pl.BlockSpec((1, 1, sq),
                             lambda c, i, j, r: (bexpr(c, r), 0, 0)),  # delta
                pl.BlockSpec((1, 1, sq),
                             lambda c, i, j, r: (bexpr(c, r), 0, 0)),  # segq
                pl.BlockSpec((1, 1, sk),
                             lambda c, i, j, r: (bexpr(c, r), 0, 0)),  # segk
                pl.BlockSpec((1, bq, bk),
                             lambda c, i, j, r: (c, i, j)),            # bias
                _SEED_SPEC,
            ],
            out_specs=[pl.BlockSpec((1, bq, bk),
                                    lambda c, i, j, r: (c, i, j))],
            out_shape=[_sds((B, sq, sk), jnp.float32, q3)],
            interpret=interpret, name="flash_attention_bwd_dbias",
        )(q3, k3, v3, do3, lse, delta, segq, segk, bias3, seed1)[0]

    return dq, dkdv[0], dkdv[1], dlog


# ------------------------------------------------- chunk API (ring attention)
def _ref_chunk_keep(dropout_seed, shape, dropout_rate):
    """Fallback-path keep mask: regenerated identically in chunk fwd and
    bwd from the (deterministic) per-chunk-pair seed."""
    key = jax.random.PRNGKey(jnp.asarray(dropout_seed, jnp.int32))
    return jax.random.bernoulli(key, 1.0 - dropout_rate, shape)


def _ref_chunk_fwd(q3, k3, v3, scale, causal, dropout_rate=0.0,
                   dropout_seed=None):
    """jnp chunk forward returning (o fp32-normalized, lse fp32)."""
    q32, k32, v32 = (jnp.asarray(t, jnp.float32) for t in (q3, k3, v3))
    s = jnp.einsum("bqd,bkd->bqk", q32, k32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)          # normalizer stays UNDROPPED
    l_safe = jnp.where(l == 0.0, 1.0, l)
    p_acc = p
    denom = l_safe[..., None]
    if dropout_rate > 0.0:
        keep = _ref_chunk_keep(dropout_seed, p.shape, dropout_rate)
        p_acc = jnp.where(keep, p, 0.0)
        denom = denom * (1.0 - dropout_rate)
    o = jnp.einsum("bqk,bkd->bqd", p_acc, v32) / denom
    lse = m + jnp.log(l_safe)
    return o, lse


def _ref_chunk_bwd(q3, k3, v3, do3, lse, delta, scale, causal,
                   dropout_rate=0.0, dropout_seed=None):
    """jnp chunk backward given fwd residuals (lse [bh,s], delta=sum(do*o))."""
    q32, k32, v32 = (jnp.asarray(t, jnp.float32) for t in (q3, k3, v3))
    do32 = jnp.asarray(do3, jnp.float32)
    s = jnp.einsum("bqd,bkd->bqk", q32, k32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, _NEG_INF)
    p = jnp.exp(s - lse[..., None])
    dp = jnp.einsum("bqd,bkd->bqk", do32, v32)
    p_d = p
    if dropout_rate > 0.0:
        keep = _ref_chunk_keep(dropout_seed, p.shape, dropout_rate)
        inv = 1.0 / (1.0 - dropout_rate)
        p_d = jnp.where(keep, p * inv, 0.0)
        dp = jnp.where(keep, dp * inv, 0.0)
    dv = jnp.einsum("bqk,bqd->bkd", p_d, do32)
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, k32)
    dk = jnp.einsum("bqk,bqd->bkd", ds, q32)
    return dq, dk, dv


def attn_chunk_fwd(q3, k3, v3, *, scale, causal,
                   block_q=None, block_k=None,
                   dropout_rate=0.0, dropout_seed=None,
                   interpret=False):
    """One attention block: [bh, sq, d] x [bh, sk, d] -> (o fp32, lse fp32).

    The building block ring attention rotates KV around (SURVEY §6: the
    kernel is blockwise over KV precisely so context parallelism can reuse
    it). Output is softmax-normalized *within the chunk*; ``lse`` lets the
    caller re-weight when combining chunks (o, lse) -> global softmax.

    ``dropout_rate``/``dropout_seed``: fused softmax dropout; the caller
    must pass a seed unique per (ring step, chunk pair) — ring attention
    derives it via _mix_seed — and the SAME seed to attn_chunk_bwd so the
    mask replays.
    """
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    sq, sk, d = q3.shape[1], k3.shape[1], q3.shape[2]
    block_q, block_k = _resolve_blocks(block_q, block_k)
    bq, bk = _fit_block(block_q, sq, 8), _fit_block(block_k, sk, 128)
    if jax.default_backend() == "cpu":
        interpret = True
    if not _pallas_ok(sq, sk, d, bq, bk) or (interpret and _has_vma(q3)) \
            or (dropout_rate > 0.0 and interpret) \
            or (not interpret and not mosaic_dtype_ok(q3, k3, v3)):
        return _ref_chunk_fwd(q3, k3, v3, scale, causal, dropout_rate,
                              dropout_seed)
    o3, lse = _fwd_pallas(q3, k3, v3, None, None, scale, causal, bq, bk,
                          interpret, dropout_rate=dropout_rate,
                          dropout_seed=dropout_seed)
    return jnp.asarray(o3, jnp.float32), lse[:, 0, :]


def attn_chunk_bwd(q3, k3, v3, do3, lse, delta, *, scale, causal,
                   block_q=None, block_k=None,
                   dropout_rate=0.0, dropout_seed=None,
                   interpret=False):
    """Chunk backward given residuals; returns fp32 (dq, dk, dv)."""
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    sq, sk, d = q3.shape[1], k3.shape[1], q3.shape[2]
    blocks_explicit = block_q is not None or block_k is not None
    block_q, block_k = _resolve_blocks(block_q, block_k)
    bq, bk = _fit_block(block_q, sq, 8), _fit_block(block_k, sk, 128)
    if not blocks_explicit:
        # explicit caller blocks win; only tuned/default geometry may
        # take the backward-specific knobs
        bq, bk = _resolve_bwd_blocks(bq, bk, sq, sk, dropout_rate)
    if jax.default_backend() == "cpu":
        interpret = True
    if not _pallas_ok(sq, sk, d, bq, bk) or (interpret and _has_vma(q3)) \
            or (dropout_rate > 0.0 and interpret) \
            or (not interpret and not mosaic_dtype_ok(q3, k3, v3, do3)):
        return _ref_chunk_bwd(q3, k3, v3, do3, lse, delta, scale, causal,
                              dropout_rate, dropout_seed)
    # _bwd_pallas recomputes p from lse and reads delta directly; o3 itself
    # is not needed once delta is in hand, so pass delta through. Inputs keep
    # their storage dtype (the kernels upcast per-tile); only the outputs are
    # forced fp32 for exact cross-chunk accumulation in the ring.
    bh = q3.shape[0]
    lse3 = lse.reshape(bh, 1, sq)
    dq, dk, dv, _ = _bwd_pallas(q3, k3, v3, do3, lse3,
                                delta.reshape(bh, 1, sq), None, None,
                                scale, causal, bq, bk, interpret,
                                out_dtype=jnp.float32,
                                dropout_rate=dropout_rate,
                                dropout_seed=dropout_seed)
    return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, bias, segment_ids, dropout_seed, causal, scale, block_q,
           block_k, interpret, dropout_rate, blocks_explicit):
    out, _ = _flash_fwd(q, k, v, bias, segment_ids, dropout_seed, causal,
                        scale, block_q, block_k, interpret, dropout_rate,
                        blocks_explicit)
    return out


def _flash_fwd(q, k, v, bias, segment_ids, dropout_seed, causal, scale,
               block_q, block_k, interpret, dropout_rate,
               blocks_explicit=False):
    b, h, sq, d = q.shape
    q3, k3, v3 = _flatten(q), _flatten(k), _flatten(v)
    segq = segk = None
    if segment_ids is not None:
        segq = _seg_flat(segment_ids, h)
        segk = segq
    o3, lse = _fwd_pallas(q3, k3, v3, segq, segk, scale, causal, block_q,
                          block_k, interpret, bias=bias, h=h,
                          dropout_rate=dropout_rate,
                          dropout_seed=dropout_seed)
    out = o3.reshape(b, h, sq, d)
    return out, (q3, k3, v3, o3, lse, segq, segk, bias, dropout_seed, b, h)


def _flash_bwd(causal, scale, block_q, block_k, interpret, dropout_rate,
               blocks_explicit, res, g):
    q3, k3, v3, o3, lse, segq, segk, bias, dropout_seed, b, h = res
    do3 = _flatten(g)
    bh, sq = q3.shape[0], q3.shape[1]
    if not blocks_explicit:
        # explicit caller blocks win for BOTH passes; only tuned/default
        # geometry may take the backward-specific knobs
        block_q, block_k = _resolve_bwd_blocks(block_q, block_k, sq,
                                               k3.shape[1], dropout_rate)
    delta = jnp.sum(jnp.asarray(do3, jnp.float32) *
                    jnp.asarray(o3, jnp.float32), axis=-1,
                    keepdims=True).reshape(bh, 1, sq)
    dq3, dk3, dv3, dlog = _bwd_pallas(q3, k3, v3, do3, lse, delta, segq,
                                      segk, scale, causal, block_q, block_k,
                                      interpret, bias=bias, h=h,
                                      dropout_rate=dropout_rate,
                                      dropout_seed=dropout_seed)
    sq, d = q3.shape[1], q3.shape[2]
    sk = k3.shape[1]
    dq = dq3.reshape(b, h, sq, d)
    dk = dk3.reshape(b, h, sk, d)
    dv = dv3.reshape(b, h, sk, d)
    dbias = None
    if bias is not None:
        # dlog arrives already reduced to the bias's broadcast class
        # ([B*, sq, sk] with B* = prod of bias's leading dims)
        dbias = dlog.reshape(bias.shape).astype(bias.dtype)
    return dq, dk, dv, dbias, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    segment_ids: Optional[jnp.ndarray] = None,
                    bias: Optional[jnp.ndarray] = None,
                    dropout_rate: float = 0.0,
                    dropout_seed=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Fused attention. q,k,v: [batch, heads, seq, head_dim].

    ``segment_ids``: [batch, seq] int — varlen packing (fmhalib parity);
    tokens attend only within equal segment ids. ``scale`` defaults to
    1/sqrt(head_dim) (the reference kernels bake the same default).

    ``bias``: ADDITIVE logits bias of shape [b|1, h|1, sq, sk] (the apex
    additive-mask MHA variants / evoformer pair bias), applied after the
    q·k scale. Differentiable; the bias cotangent costs one O(s²) fp32
    buffer in backward (the same footprint unfused attention pays) — the
    bias-free path allocates nothing extra.

    ``dropout_rate``/``dropout_seed``: fused softmax-probability dropout
    (reference: fast_multihead_attn's fused softmax+dropout with philox
    replay, N11). The mask is generated in-kernel from the hardware PRNG,
    seeded per (batch·head, q-block, k-block) from ``dropout_seed`` (an
    int32 scalar — vary it per training step; inside shard_map also fold
    the shard's ``lax.axis_index`` into it, or every shard draws the same
    mask field), and REPLAYED exactly in backward. On the CPU/interpret
    fallback the mask comes from jax.random instead (same semantics,
    different stream — matching how the reference's python and fused impls
    differ). Hardware replay is covered by tests/tpu/ (self-skipping on
    the CPU CI backend).
    """
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    sq, sk = q.shape[2], k.shape[2]
    # validated on EVERY path: the jnp fallback must reject exactly what the
    # Pallas path rejects, or aligned shapes would crash where unaligned ran
    _validate_bias(bias, q.shape[0], q.shape[1], sq, sk)
    # explicitness MUST be read before _resolve_blocks overwrites the
    # Nones — computed after, the flag is always True and the bwd knobs
    # are dead (caught by code review + the gating test)
    blocks_explicit = block_q is not None or block_k is not None
    block_q, block_k = _resolve_blocks(block_q, block_k)
    bq = _fit_block(block_q, sq, 8)
    bk = _fit_block(block_k, sk, 128)
    if jax.default_backend() == "cpu":
        interpret = True  # pallas-TPU lowering needs a TPU; CPU interprets
    if not _pallas_ok(sq, sk, d, bq, bk) or (interpret and _has_vma(q)) \
            or (dropout_rate > 0.0 and interpret) \
            or (not interpret and not mosaic_dtype_ok(q, k, v, bias)):
        # interpret mode has no pltpu PRNG lowering → jnp dropout fallback
        return mha_reference(q, k, v, causal=causal, scale=scale,
                             segment_ids=segment_ids, bias=bias,
                             dropout_rate=dropout_rate,
                             dropout_seed=dropout_seed)
    return _flash(q, k, v, bias, segment_ids, dropout_seed, causal, scale,
                  bq, bk, interpret, dropout_rate, blocks_explicit)
