"""The gated delta rule — the recurrent token mixer of a linear-attention
layer (Gated DeltaNet; :class:`apex_tpu.models.qwen3_next.Qwen3NextLM`).

Per value head a matrix ``S [dk, dv]`` (key x value, float32) is the
whole memory of a sequence. A token with query ``q``, key ``k`` (both
``[dk]``, L2-normalised by the caller), value ``v [dv]``, log-decay ``g <=
0`` and write strength ``beta`` in (0, 1) does::

    S <- exp(g) S;  r = S^T k;  S <- S + k (beta (v - r))^T;  o = S^T q

Two programs use it, and each has a kernel here:

- :func:`gated_delta_step` (``name="gated_delta_step"``) — ONE token for
  every slot of the decode batch. The state block of ALL slots and
  layers ``[layers, slots, heads, dk, dv]`` is an aliased operand: the
  kernel reads each slot's heads, updates them and writes them back
  where they lie, and a row that does not decode (``active`` false)
  is handed back as it was — no select over the block, no copy of it.
  Memory-bound by construction: the state crosses HBM twice a token.
- :func:`gated_delta_chunk` (``name="gated_delta_chunk"``) — a chunk of
  ``T`` prompt tokens of ONE slot, from the state the slot holds (zeros
  where ``fresh``) to the state its last token leaves, in the chunked
  (WY / UT-transform) form over sub-chunks of ``C = 64``: within a
  sub-chunk the ``C`` rank-one writes are the solution ``U`` of a
  unit-lower-triangular system ``(I + A) U = beta (V - e^gc K S_0)``,
  ``A[i, j] = beta_i e^(gc_i - gc_j) k_i . k_j`` below the diagonal
  (``gc`` the running sum of ``g``), solved by forward substitution; the
  outputs are ``e^gc Q S_0 + (e^(gc_i - gc_j) q_i . k_j, j <= i) U`` and
  the state moves by ``e^gc_C S_0 + (e^(gc_C - gc) K)^T U``. A padded
  position has ``g = 0`` and ``beta = 0``: it writes nothing and decays
  nothing, so the state a padded chunk leaves is its last VALID
  position's.

**A decay per key channel** (Kimi delta attention;
:class:`apex_tpu.models.ling.LingLM`): ``g`` may be a vector ``[dk]`` a
head instead of a scalar, ``S <- diag(exp(g)) S``. The decay's shape is
the operand's: ``g [.., H]`` is the scalar rule, ``g [.., H, dk]`` the
per-channel one, in the oracles and in both entry points.

- The STEP kernel is one body for both: it is handed columns ``k``, a
  query column, ``a beta k`` and ``a`` a head (``a = exp(g)``), and a
  scalar ``a`` is a constant column. Per channel the query column is ``a
  q`` (``o = S^T (a q) + (k . q) u``; the scalar form multiplies ``a``
  after, as it always did, so its results are the same bits). It runs
  under the name ``kda_step`` so that a trace tells the two apart.
- The CHUNK form no longer factors: ``A[i, j] = beta_i sum_c k_i[c] k_j[c]
  e^(gc_i[c] - gc_j[c])`` has the decays INSIDE the product, ``(k_i
  e^(gc_i - r)) . (k_j e^(r - gc_j))`` for a reference row ``r``. One
  ``r`` a sub-chunk would need ``e^(+5 x 64)`` at a log-decay of -5 a
  token; so a sub-chunk's 64 rows are taken in blocks of ``KDA_BLOCK`` =
  16, ``r`` the block's MIDDLE row: the rows' factors and the block's own
  columns' lie within ``e^(+-8 |g|)`` (``e^(+-40)`` at -5, finite down to
  about -10 a token), columns before the block have ``r - gc_j <= 0``
  (underflow to 0 is the true value's), columns after it are masked and
  their exponent is capped. :func:`kda_chunk` (``name="kda_chunk"``) is a
  sibling kernel, not a mode of ``gated_delta_chunk``: four small products
  a sub-chunk where the scalar rule has one, and ``exp`` over ``[rows,
  dk]`` where it has ``[rows, rows]`` - the scalar cells keep the kernel
  they were measured with.

``layer`` is an operand (scalar prefetch), so every linear layer of a
model shares one traced and lowered kernel. Matrix products are float32
at ``Precision.HIGHEST``: the state is float32 and ``A`` is a matrix of
differences. :func:`gated_delta_recurrence` (a ``lax.scan`` over time) is
the oracle; :func:`gated_delta_step_reference` and
:func:`gated_delta_chunk_reference` are the jnp forms of the two kernels,
their fallback where the tiling does not take the shape (``dv`` not a
multiple of 128, ``dk`` of 8, ``T`` of ``C``) and, batched, the plain
forward's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta_step", "gated_delta_chunk", "gated_delta_recurrence",
           "gated_delta_step_reference", "gated_delta_chunk_reference",
           "kda_chunk"]

STEP_KERNEL = "gated_delta_step"
CHUNK_KERNEL = "gated_delta_chunk"
KDA_STEP_KERNEL = "kda_step"
KDA_CHUNK_KERNEL = "kda_chunk"
SUB = 64                    # the chunked form's sub-chunk
KDA_BLOCK = 16              # rows of a sub-chunk that share a reference row
KDA_EXP_CAP = 80.0          # exponent cap of the (masked) later columns
STEP_BYTES = 2 << 20        # state a grid step of the step kernel takes
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ---------------------------------------------------------------- oracles

def gated_delta_recurrence(q, k, v, g, beta, s0):
    """The recurrence itself, token by token: ``q, k [B, T, H, dk]``,
    ``v [B, T, H, dv]``, ``beta [B, T, H]``, ``g [B, T, H]`` (a decay a
    head) or ``[B, T, H, dk]`` (a decay a key channel), ``s0 [B, H, dk,
    dv]`` -> ``(o [B, T, H, dv], s_T)``, all float32."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = _decay(gt, S) * S
        r = jnp.einsum("bhkv,bhk->bhv", S, kt, precision=HI)
        S = S + kt[..., None] * (bt[..., None] * (vt - r))[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=HI)
    xs = tuple(jnp.moveaxis(jnp.asarray(t, F32), 1, 0)
               for t in (q, k, v, g, beta))
    sT, o = jax.lax.scan(step, jnp.asarray(s0, F32), xs)
    return jnp.moveaxis(o, 0, 1), sT


def _decay(g, S):
    """``exp(g)`` shaped to scale the rows of ``S [.., dk, dv]``: ``g [..]``
    a head or ``[.., dk]`` a key channel."""
    return jnp.exp(g).reshape(g.shape + (1,) * (S.ndim - g.ndim))


def _kda_products(Q, K, gc, sub, block=KDA_BLOCK):
    """``(sum_c K_i K_j e^(gc_i - gc_j), the same with Q_i)`` ``[.., sub,
    sub]`` for ``j <= i`` (garbage, finite, above the diagonal) from ``Q,
    K, gc [.., sub, dk]``, row block by row block around the block's
    middle row (module docstring)."""
    A, P = [], []
    for lo in range(0, sub, block):
        hi = min(lo + block, sub)
        r = gc[..., (lo + hi) // 2:(lo + hi) // 2 + 1, :]
        rowf = jnp.exp(gc[..., lo:hi, :] - r)
        Kc = K * jnp.exp(jnp.minimum(r - gc, KDA_EXP_CAP))
        A.append(jnp.einsum("...ik,...jk->...ij", K[..., lo:hi, :] * rowf,
                            Kc, precision=HI))
        P.append(jnp.einsum("...ik,...jk->...ij", Q[..., lo:hi, :] * rowf,
                            Kc, precision=HI))
    return jnp.concatenate(A, -2), jnp.concatenate(P, -2)


def gated_delta_chunk_reference(q, k, v, g, beta, s0, sub: int = SUB):
    """The chunked form in plain jnp, batched (module docstring): same
    operands and results as :func:`gated_delta_recurrence` (``g`` a head
    or a key channel); ``T`` is padded to a multiple of ``sub`` with
    positions that write nothing."""
    B, T, H, dk = q.shape
    per_channel = g.ndim == 4
    pad = -T % sub
    if pad:
        z = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))  # noqa: E731,E501
        q, k, v, g, beta = z(q), z(k), z(v), z(g), z(beta)
    n = (T + pad) // sub
    # [n, B, H, sub, ...]
    blk = lambda t: jnp.moveaxis(                                # noqa: E731
        jnp.asarray(t, F32).reshape((B, n, sub) + t.shape[2:]), (1, 3), (0, 2))
    ii = jnp.arange(sub)[:, None]
    jj = jnp.arange(sub)[None, :]

    def one_kda(S, x):
        Q, K, V, G, Bt = x                     # G [B, H, sub, dk]
        gc = jnp.cumsum(G, -2)
        eg = jnp.exp(gc)
        kk, qk = _kda_products(Q, K, gc, sub)
        A = jnp.where(ii > jj, Bt[..., None] * kk, 0.0)
        rhs = Bt[..., None] * (V - jnp.einsum("bhik,bhkv->bhiv", K * eg, S,
                                              precision=HI))
        U = jax.scipy.linalg.solve_triangular(
            A + jnp.eye(sub, dtype=F32), rhs, lower=True, unit_diagonal=True)
        O = jnp.einsum("bhik,bhkv->bhiv", Q * eg, S, precision=HI) \
            + jnp.einsum("bhij,bhjv->bhiv", jnp.where(ii >= jj, qk, 0.0), U,
                         precision=HI)
        gl = gc[..., -1:, :]
        S = jnp.exp(gl[..., 0, :])[..., None] * S \
            + jnp.einsum("bhik,bhiv->bhkv", K * jnp.exp(gl - gc), U,
                         precision=HI)
        return S, O

    def one(S, x):
        Q, K, V, G, Bt = x                     # [B, H, sub, d], [B, H, sub]
        gc = jnp.cumsum(G, -1)
        E = jnp.exp(jnp.minimum(gc[..., :, None] - gc[..., None, :], 0.0))
        kk = jnp.einsum("bhik,bhjk->bhij", K, K, precision=HI)
        A = jnp.where(ii > jj, Bt[..., None] * E * kk, 0.0)
        KS = jnp.einsum("bhik,bhkv->bhiv", K, S, precision=HI)
        QS = jnp.einsum("bhik,bhkv->bhiv", Q, S, precision=HI)
        rhs = Bt[..., None] * (V - jnp.exp(gc)[..., None] * KS)
        U = jax.scipy.linalg.solve_triangular(
            A + jnp.eye(sub, dtype=F32), rhs, lower=True, unit_diagonal=True)
        P = jnp.where(ii >= jj, E * jnp.einsum("bhik,bhjk->bhij", Q, K,
                                               precision=HI), 0.0)
        O = jnp.exp(gc)[..., None] * QS \
            + jnp.einsum("bhij,bhjv->bhiv", P, U, precision=HI)
        gl = gc[..., -1:]
        Kd = K * jnp.exp(gl - gc)[..., None]
        S = jnp.exp(gl)[..., None] * S \
            + jnp.einsum("bhik,bhiv->bhkv", Kd, U, precision=HI)
        return S, O

    sT, o = jax.lax.scan(one_kda if per_channel else one,
                         jnp.asarray(s0, F32),
                         (blk(q), blk(k), blk(v), blk(g), blk(beta)))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, T + pad, H, -1)
    return o[:, :T], sT


def gated_delta_step_reference(state, layer, q, k, v, g, beta, active):
    """:func:`gated_delta_step` in jnp: a select over the layer's block
    (the small-shape fallback and the oracle, never the chip's path)."""
    S = _decay(jnp.asarray(g, F32), state[layer]) * state[layer]
    r = jnp.einsum("bhkv,bhk->bhv", S, k, precision=HI)
    S = S + k[..., None] * (beta[..., None] * (v - r))[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S, q, precision=HI)
    keep = jnp.asarray(active, bool)[:, None, None, None]
    return o, state.at[layer].set(jnp.where(keep, S, state[layer]))


# ------------------------------------------------------------ step kernel

def _step_kernel(layer_ref, act_ref, s_ref, c_ref, bv_ref, so_ref, sq_ref,
                 u_ref, *, hb):
    b = pl.program_id(0)

    @pl.when(act_ref[b] != 0)
    def _update():
        c = c_ref[...]                                   # [dk, 4 hb]
        for j in range(hb):
            S = s_ref[j]                                 # [dk, dv]
            col = lambda n: c[:, n * hb + j:n * hb + j + 1]   # noqa: E731
            kc, qc, kab, ac = col(0), col(1), col(2), col(3)
            # r = a beta S^T k and S^T q, of the state as it was: sums
            # over the sublanes (keys), the value axis in the lanes
            u = bv_ref[j:j + 1, :] - jnp.sum(S * kab, axis=0, keepdims=True)
            sq_ref[j:j + 1, :] = jnp.sum(S * qc, axis=0, keepdims=True)
            u_ref[j:j + 1, :] = u
            so_ref[j] = S * ac + kc * u

    @pl.when(act_ref[b] == 0)
    def _keep():
        so_ref[...] = s_ref[...]
        sq_ref[...] = jnp.zeros_like(sq_ref)
        u_ref[...] = jnp.zeros_like(u_ref)


def _step_heads(H, dk, dv):
    """Heads of a slot a grid step takes: as many as STEP_BYTES of state
    allow (the block is double-buffered in and out), a divisor of ``H``
    that is ``H`` or a multiple of 8."""
    ok = [h for h in range(1, H + 1)
          if H % h == 0 and (h == H or h % 8 == 0)]
    fit = [h for h in ok if h * dk * dv * 4 <= STEP_BYTES]
    return max(fit) if fit else min(ok)


def gated_delta_step(state, layer, q, k, v, g, beta, active, *,
                     interpret: bool = False):
    """One token a slot (module docstring). ``state [layers, slots, H, dk,
    dv]`` float32, updated IN PLACE (aliased) for the rows where
    ``active [slots]``; ``layer`` int (an operand); ``q, k [slots, H,
    dk]``, ``v [slots, H, dv]``, ``beta [slots, H]``, ``g [slots, H]`` or
    (a decay a key channel: the same kernel body, named ``kda_step``)
    ``[slots, H, dk]``. Returns ``(o [slots, H, dv] float32, state)``."""
    Ls, B, H, dk, dv = state.shape
    if jax.default_backend() == "cpu":
        interpret = True
    q, k, v, g, beta = (jnp.asarray(t, F32) for t in (q, k, v, g, beta))
    if dv % 128 or dk % 8 or state.dtype != F32:
        return gated_delta_step_reference(state, layer, q, k, v, g, beta,
                                          active)
    hb = _step_heads(H, dk, dv)
    nb = H // hb
    per_channel = g.ndim == 3
    a = jnp.exp(g)                                     # [B, H] | [B, H, dk]
    # what the kernel broadcasts along the lanes, as COLUMNS: k, the
    # query, a beta k and a, head by head within a block of hb heads
    if per_channel:
        cols = jnp.stack([k, a * q, a * beta[..., None] * k, a], 1)
    else:
        cols = jnp.stack([k, q, (a * beta)[..., None] * k,
                          jnp.broadcast_to(a[..., None], k.shape)], 1)
    cols = cols.reshape(B, 4, nb, hb, dk).transpose(0, 2, 4, 1, 3) \
        .reshape(B, nb, dk, 4 * hb)
    bv = beta[..., None] * v
    kernel = functools.partial(_step_kernel, hb=hb)
    rows = pl.BlockSpec((None, hb, dv), lambda b, h, lr, ar: (b, h, 0))
    block = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda b, h, lr, ar: (lr[0], b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, nb),
        in_specs=[block,
                  pl.BlockSpec((None, None, dk, 4 * hb),
                               lambda b, h, lr, ar: (b, h, 0, 0)),
                  rows],
        out_specs=[block, rows, rows])
    state, sq, u = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((B, H, dv), F32),
                   jax.ShapeDtypeStruct((B, H, dv), F32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=8 * hb * dk * dv * 4 + (16 << 20)),
        interpret=interpret,
        name=KDA_STEP_KERNEL if per_channel else STEP_KERNEL,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(active, jnp.int32), state, cols, bv)
    # o = S'^T q = a S^T q + (k . q) u; per channel the kernel's query
    # column was a q already
    if not per_channel:
        sq = a[..., None] * sq
    o = sq + jnp.sum(k * q, -1, keepdims=True) * u
    return o, state


# ----------------------------------------------------------- chunk kernel

def _chunk_kernel(meta_ref, s_ref, q_ref, k_ref, v_ref, c_ref, r_ref,
                  so_ref, o_ref, *, hb, n_sub, sub):
    fresh = meta_ref[2] != 0
    ii = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    nt = (((1,), (1,)), ((), ()))                     # x @ y^T
    tn = (((0,), (0,)), ((), ()))                     # x^T @ y
    dot = functools.partial(jax.lax.dot_general, precision=HI,
                            preferred_element_type=F32)

    def head(j, _):
        S = jnp.where(fresh, 0.0, s_ref[j])                  # [dk, dv]
        for s in range(n_sub):
            rows = pl.ds(s * sub, sub)
            Q, K, V = q_ref[j, rows, :], k_ref[j, rows, :], v_ref[j, rows, :]
            cc = c_ref[j, rows, :]                           # [sub, 8]
            gcol, bcol, ecol = cc[:, 0:1], cc[:, 1:2], cc[:, 2:3]
            grow = r_ref[j, s, 0:1, :sub]                    # [1, sub]
            E = jnp.exp(jnp.minimum(gcol - grow, 0.0))       # [sub, sub]
            A = jnp.where(ii > jj, bcol * E * dot(K, K, nt), 0.0)
            U = bcol * (V - ecol * dot(K, S, (((1,), (0,)), ((), ()))))
            # (I + A) U = rhs by forward substitution, a column a step:
            # row t is final once the rows before it have been taken out
            for t in range(sub - 1):
                U = U - A[:, t:t + 1] * U[t:t + 1, :]
            P = jnp.where(ii >= jj, E * dot(Q, K, nt), 0.0)
            o_ref[j, rows, :] = ecol * dot(Q, S, (((1,), (0,)), ((), ()))) \
                + dot(P, U, (((1,), (0,)), ((), ())))
            glast = gcol[sub - 1:sub, :]                     # [1, 1]
            # (e^gc_C comes as a row: Mosaic broadcasts one way at a time)
            S = r_ref[j, s, 1:2, :] * S \
                + dot(K * jnp.exp(glast - gcol), U, tn)
        so_ref[j] = S
        return _

    jax.lax.fori_loop(0, hb, head, None)


def gated_delta_chunk(state, layer, slot, fresh, q, k, v, g, beta, *,
                      interpret: bool = False):
    """``T`` tokens of one slot (module docstring). ``state [layers,
    slots, H, dk, dv]`` float32, slot ``slot``'s heads of ``layer``
    updated IN PLACE (aliased), read as zeros where ``fresh``; ``q, k [T,
    H, dk]``, ``v [T, H, dv]``, ``beta [T, H]``, ``g [T, H]`` or (a decay
    a key channel: :func:`kda_chunk`) ``[T, H, dk]``. Returns ``(o [T, H,
    dv] float32, state)``."""
    Ls, B, H, dk, dv = state.shape
    T = q.shape[0]
    if jax.default_backend() == "cpu":
        interpret = True
    q, k, v, g, beta = (jnp.asarray(t, F32) for t in (q, k, v, g, beta))
    aligned = not (dv % 128 or dk % 8 or T % SUB or state.dtype != F32)
    if g.ndim == 3 and aligned and dk % 128 == 0:
        return kda_chunk(state, layer, slot, fresh, q, k, v, g, beta,
                         interpret=interpret)
    if not aligned or g.ndim == 3:
        s0 = jnp.where(fresh, 0.0, state[layer, slot])[None]
        o, sT = gated_delta_chunk_reference(q[None], k[None], v[None],
                                            g[None], beta[None], s0)
        return o[0], state.at[layer, slot].set(sT[0])
    n_sub = T // SUB
    hb = 8 if H % 8 == 0 else H
    heads_first = lambda t: jnp.moveaxis(t, 1, 0)            # noqa: E731
    gc = jnp.cumsum(g.T.reshape(H, n_sub, SUB), -1)          # [H, n, sub]
    # what the kernel broadcasts along the lanes, as COLUMNS: gc, beta,
    # e^gc
    zeros = jnp.zeros((H, T, 5), F32)
    cols = jnp.concatenate([gc.reshape(H, T, 1), beta.T[..., None],
                            jnp.exp(gc).reshape(H, T, 1), zeros], -1)
    # ... and along the sublanes, as ROWS: gc, and e^gc_C over dv lanes
    rows = jnp.zeros((H, n_sub, 8, dv), F32).at[:, :, 0, :SUB].set(gc) \
        .at[:, :, 1, :].set(jnp.exp(gc[:, :, -1:]))
    kernel = functools.partial(_chunk_kernel, hb=hb, n_sub=n_sub, sub=SUB)
    seq = lambda d: pl.BlockSpec((hb, T, d), lambda h, m: (h, 0, 0))  # noqa: E731,E501
    block = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda h, m: (m[0], m[1], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(H // hb,),
        in_specs=[block, seq(dk), seq(dk), seq(dv), seq(8),
                  pl.BlockSpec((hb, n_sub, 8, dv),
                               lambda h, m: (h, 0, 0, 0))],
        out_specs=[block, seq(dv)])
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(slot, jnp.int32),
                      jnp.asarray(fresh, jnp.int32)])
    state, o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((H, T, dv), F32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=48 << 20),
        interpret=interpret, name=CHUNK_KERNEL,
    )(meta, state, heads_first(q), heads_first(k), heads_first(v), cols,
      rows)
    return jnp.moveaxis(o, 0, 1), state


# ------------------------------------------- chunk kernel, decay a channel

def _kda_chunk_kernel(meta_ref, s_ref, q_ref, k_ref, v_ref, g_ref, c_ref,
                      d_ref, so_ref, o_ref, *, hb, n_sub, sub, block):
    fresh = meta_ref[2] != 0
    ii = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    nn = (((1,), (0,)), ((), ()))                     # x @ y
    nt = (((1,), (1,)), ((), ()))                     # x @ y^T
    tn = (((0,), (0,)), ((), ()))                     # x^T @ y
    dot = functools.partial(jax.lax.dot_general, precision=HI,
                            preferred_element_type=F32)

    def head(j, _):
        S = jnp.where(fresh, 0.0, s_ref[j])                  # [dk, dv]
        dec = d_ref[j]                                       # [dk, n_sub]
        for s in range(n_sub):
            rows = pl.ds(s * sub, sub)
            Q, K, V = q_ref[j, rows, :], k_ref[j, rows, :], v_ref[j, rows, :]
            GC = g_ref[j, rows, :]                           # [sub, dk]
            bcol = c_ref[j, rows, :][:, 0:1]                 # [sub, 1]
            EG = jnp.exp(GC)
            # the decays inside the products, a block of rows around its
            # middle row at a time (module docstring)
            A, P = [], []
            for lo in range(0, sub, block):
                mid = lo + block // 2
                r = GC[mid:mid + 1, :]                       # [1, dk]
                rowf = jnp.exp(GC[lo:lo + block, :] - r)
                Kc = K * jnp.exp(jnp.minimum(r - GC, KDA_EXP_CAP))
                A.append(dot(K[lo:lo + block, :] * rowf, Kc, nt))
                P.append(dot(Q[lo:lo + block, :] * rowf, Kc, nt))
            A = jnp.where(ii > jj, bcol * jnp.concatenate(A, 0), 0.0)
            P = jnp.where(ii >= jj, jnp.concatenate(P, 0), 0.0)
            U = bcol * (V - dot(K * EG, S, nn))
            # (I + A) U = rhs by forward substitution, a column a step
            for t in range(sub - 1):
                U = U - A[:, t:t + 1] * U[t:t + 1, :]
            o_ref[j, rows, :] = dot(Q * EG, S, nn) + dot(P, U, nn)
            glast = GC[sub - 1:sub, :]                       # [1, dk]
            S = dec[:, s:s + 1] * S + dot(K * jnp.exp(glast - GC), U, tn)
        so_ref[j] = S
        return _

    jax.lax.fori_loop(0, hb, head, None)


def kda_chunk(state, layer, slot, fresh, q, k, v, g, beta, *,
              interpret: bool = False):
    """:func:`gated_delta_chunk` with a decay a key channel, ``g [T, H,
    dk]`` (module docstring); the other operands and the results as
    there. ``dk`` a multiple of 128, ``T`` of ``SUB``."""
    Ls, B, H, dk, dv = state.shape
    T = q.shape[0]
    n_sub = T // SUB
    # q, k, v, gc and o of hb heads, double-buffered, within ~24 MB
    hb = max(h for h in (8, 4, 2, 1) if H % h == 0 and (h * T <= 4096
                                                        or h == 1))
    heads_first = lambda t: jnp.moveaxis(t, 1, 0)            # noqa: E731
    gc = jnp.cumsum(heads_first(g).reshape(H, n_sub, SUB, dk), 2)
    # beta as a COLUMN, and e^gc_C of each sub-chunk as columns over dk
    cols = jnp.concatenate([beta.T[..., None], jnp.zeros((H, T, 7), F32)],
                           -1)
    dec = jnp.exp(gc[:, :, -1, :]).transpose(0, 2, 1)        # [H, dk, n]
    kernel = functools.partial(_kda_chunk_kernel, hb=hb, n_sub=n_sub,
                               sub=SUB, block=KDA_BLOCK)
    seq = lambda d: pl.BlockSpec((hb, T, d), lambda h, m: (h, 0, 0))  # noqa: E731,E501
    block = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda h, m: (m[0], m[1], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(H // hb,),
        in_specs=[block, seq(dk), seq(dk), seq(dv), seq(dk), seq(8),
                  pl.BlockSpec((hb, dk, n_sub), lambda h, m: (h, 0, 0))],
        out_specs=[block, seq(dv)])
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(slot, jnp.int32),
                      jnp.asarray(fresh, jnp.int32)])
    state, o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((H, T, dv), F32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=48 << 20),
        interpret=interpret, name=KDA_CHUNK_KERNEL,
    )(meta, state, heads_first(q), heads_first(k), heads_first(v),
      gc.reshape(H, T, dk), cols, dec)
    return jnp.moveaxis(o, 0, 1), state
