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
           "gated_delta_step_reference", "gated_delta_chunk_reference"]

STEP_KERNEL = "gated_delta_step"
CHUNK_KERNEL = "gated_delta_chunk"
SUB = 64                    # the chunked form's sub-chunk
STEP_BYTES = 2 << 20        # state a grid step of the step kernel takes
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ---------------------------------------------------------------- oracles

def gated_delta_recurrence(q, k, v, g, beta, s0):
    """The recurrence itself, token by token: ``q, k [B, T, H, dk]``,
    ``v [B, T, H, dv]``, ``g, beta [B, T, H]``, ``s0 [B, H, dk, dv]`` ->
    ``(o [B, T, H, dv], s_T)``, all float32."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None, None] * S
        r = jnp.einsum("bhkv,bhk->bhv", S, kt, precision=HI)
        S = S + kt[..., None] * (bt[..., None] * (vt - r))[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=HI)
    xs = tuple(jnp.moveaxis(jnp.asarray(t, F32), 1, 0)
               for t in (q, k, v, g, beta))
    sT, o = jax.lax.scan(step, jnp.asarray(s0, F32), xs)
    return jnp.moveaxis(o, 0, 1), sT


def gated_delta_chunk_reference(q, k, v, g, beta, s0, sub: int = SUB):
    """The chunked form in plain jnp, batched (module docstring): same
    operands and results as :func:`gated_delta_recurrence`; ``T`` is
    padded to a multiple of ``sub`` with positions that write nothing."""
    B, T, H, dk = q.shape
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

    sT, o = jax.lax.scan(one, jnp.asarray(s0, F32),
                         (blk(q), blk(k), blk(v), blk(g), blk(beta)))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, T + pad, H, -1)
    return o[:, :T], sT


def gated_delta_step_reference(state, layer, q, k, v, g, beta, active):
    """:func:`gated_delta_step` in jnp: a select over the layer's block
    (the small-shape fallback and the oracle, never the chip's path)."""
    S = jnp.exp(jnp.asarray(g, F32))[..., None, None] * state[layer]
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
    dk]``, ``v [slots, H, dv]``, ``g, beta [slots, H]``. Returns ``(o
    [slots, H, dv] float32, state)``."""
    Ls, B, H, dk, dv = state.shape
    if jax.default_backend() == "cpu":
        interpret = True
    q, k, v, g, beta = (jnp.asarray(t, F32) for t in (q, k, v, g, beta))
    if dv % 128 or dk % 8 or state.dtype != F32:
        return gated_delta_step_reference(state, layer, q, k, v, g, beta,
                                          active)
    hb = _step_heads(H, dk, dv)
    nb = H // hb
    a = jnp.exp(g)                                           # [B, H]
    # what the kernel broadcasts along the lanes, as COLUMNS: k, q,
    # a beta k and a, head by head within a block of hb heads
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
        interpret=interpret, name=STEP_KERNEL,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(active, jnp.int32), state, cols, bv)
    # o = S'^T q = a S^T q + (k . q) u
    o = a[..., None] * sq + jnp.sum(k * q, -1, keepdims=True) * u
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
    H, dk]``, ``v [T, H, dv]``, ``g, beta [T, H]``. Returns ``(o [T, H,
    dv] float32, state)``."""
    Ls, B, H, dk, dv = state.shape
    T = q.shape[0]
    if jax.default_backend() == "cpu":
        interpret = True
    q, k, v, g, beta = (jnp.asarray(t, F32) for t in (q, k, v, g, beta))
    if dv % 128 or dk % 8 or T % SUB or state.dtype != F32:
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
