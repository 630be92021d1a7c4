"""The pieces the served decoder models share — :class:`~apex_tpu.models
.zaya.ZayaLM`, :class:`~apex_tpu.models.qwen3_next.Qwen3NextLM` and
:class:`~apex_tpu.models.ling.LingLM` are built from these and from the
kernels, so that a fourth model is its equations and not a fourth copy of
a rotary, a norm, a short convolution and an expert block.

Everything here is a plain function of arrays (or a two-line parameter
holder): float32 inside where the models' contract says so (norms,
rotary, convolution sums, router, logits), the compute dtype ``cdt`` at
the matrix products.
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.transformer_lm import _pool_write_pages

__all__ = ["f32", "einsum32", "shift", "rotary", "positions_of",
           "last_valid", "rms", "short_conv", "gated_mlp", "held_experts",
           "paged_attend", "chunk_pages", "Leaves", "Groups"]

f32 = lambda t: jnp.asarray(t, jnp.float32)                     # noqa: E731


def einsum32(spec, a, b):
    """``einsum`` of half operands accumulated (and returned) in float32:
    the MXU's own form. The CPU backend's dot takes no bf16 x bf16 ->
    f32, so there the operands are widened first (the same products,
    exact in float32, the same sums)."""
    if jax.default_backend() == "cpu":
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def shift(x, prev):
    """``x_{t-1}`` along axis 1 of ``x [B, S, ...]``, the row before the
    first taken from ``prev [B, ...]``."""
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def rotary(x, pos, theta, rot):
    """Half-split rotary on the first ``rot`` of the last axis of ``x [B,
    S, heads, d]`` (float32) at absolute positions ``pos [B, S]``."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def positions_of(positions, B, S):
    """Absolute positions ``[B, S]``: ``positions[b] + s``, from 0 where
    ``positions`` is None."""
    if positions is None:
        return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return jnp.asarray(positions, jnp.int32)[:, None] \
        + jnp.arange(S, dtype=jnp.int32)[None]


def last_valid(x, n_valid):
    """Row ``n_valid[b] - 1`` of ``x [B, S, ...]`` -> ``[B, ...]``."""
    if n_valid is None:
        return x[:, -1]
    idx = jnp.clip(jnp.asarray(n_valid, jnp.int32) - 1, 0, x.shape[1] - 1)
    return jax.vmap(lambda row, i: jax.lax.dynamic_index_in_dim(
        row, i, keepdims=False))(x, idx)


def rms(x, w, eps, centred=False):
    """RMSNorm over the last axis in float32, times ``w`` or (zero-centred
    gain) ``1 + w``."""
    x = f32(x)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * ((1.0 + f32(w)) if centred else f32(w))


def short_conv(x, tail, w, mask=None):
    """Depthwise causal convolution over time with its tail: ``x [B, S,
    C]``, ``tail [B, K - 1, C]`` the inputs of the ``K - 1`` positions
    before the first, ``w [C, K]`` (tap ``K - 1`` on the current
    position); ``mask [B, S]`` the positions that are real tokens (None:
    all). Returns ``(the sums [B, S, C] float32, the tail the last REAL
    position leaves [B, K - 1, C])``."""
    B, S, _ = x.shape
    K = w.shape[1]
    xs = jnp.concatenate([jnp.asarray(tail, x.dtype), x], 1)
    w = f32(w)
    c = sum(w[:, j] * f32(xs[:, j:j + S]) for j in range(K))
    n = jnp.full((B,), S, jnp.int32) if mask is None \
        else jnp.sum(mask, 1).astype(jnp.int32)
    new_tail = jax.vmap(lambda row, i: jax.lax.dynamic_slice_in_dim(
        row, i, K - 1, axis=0))(xs, n)
    return c, new_tail


def gated_mlp(flat, w_gate_up, w_down, cdt):
    """SwiGLU over ``flat [T, H]``: ``(silu(u Wg) * (u Wu)) Wd`` with the
    gate and up projections fused ``[H, 2 F]``; float32 out."""
    F = w_down.shape[0]
    gu = jnp.dot(flat, jnp.asarray(w_gate_up, cdt))
    h = jax.nn.silu(f32(gu[:, :F])) * f32(gu[:, F:])
    return einsum32("tf,fh->th", jnp.asarray(h, cdt),
                    jnp.asarray(w_down, cdt))


def held_experts(flat, weights, choice, ep, cdt, *, num_experts,
                 experts_held, valid, block_rows=None):
    """The routed sum over the experts this chip HOLDS
    (:func:`~apex_tpu.transformer.moe.dropless_topk_experts`, in row
    blocks of ``block_rows`` where given) of ``flat [T, H]`` under
    ``choice, weights [T, k]``, and the tokens routed to each of ALL
    ``num_experts`` over the ``valid [T]`` tokens ``[E]`` int32."""
    from apex_tpu.transformer.moe import (TOPK_BLOCK_ROWS,
                                          dropless_topk_experts)

    y = dropless_topk_experts(
        flat, weights, choice, jnp.asarray(ep["w_gate_up"], cdt),
        jnp.asarray(ep["w_down"], cdt), num_experts=num_experts,
        experts_held=experts_held, out_dtype=jnp.float32,
        block_rows=block_rows or TOPK_BLOCK_ROWS)
    counts = jnp.zeros((num_experts,), jnp.int32).at[
        choice.reshape(-1)].add(jnp.repeat(
            valid.reshape(-1).astype(jnp.int32), choice.shape[1]))
    return y, counts


def paged_attend(q, k, v, cache, positions, layer, scale):
    """Causal attention of ``q [B, nq, S, d]`` over ``k, v [B, nk, S, d]``
    (``nq // nk`` query heads a K/V head). With the paged ``cache =
    (k_pool, v_pool, page_table)`` the new K/V are written IN PLACE into
    pool layer ``layer`` at ``positions [B]`` and attention reads the pool
    through the table (one token: written by the decode kernel itself,
    into the row's last page as it holds it; a chunk: whole pages,
    scattered in front of the chunk kernel); without, the sequence
    attends itself. Returns
    ``(ctx [B, nq, S, d], (k_pool, v_pool) | None)``."""
    B, _, S, _ = q.shape
    if cache is not None:
        from apex_tpu.kernels.decode_attention import \
            paged_decode_attention
        from apex_tpu.kernels.prefill_attention import \
            paged_prefill_attention
        k_pool, v_pool, page_table = cache
        page_len = k_pool.shape[4]
        L = page_table.shape[1] * page_len
        p0 = jnp.clip(jnp.asarray(positions, jnp.int32), 0, L - S)
        if S == 1:
            ctx, k_pool, v_pool = paged_decode_attention(
                q[:, :, 0], k_pool, v_pool, page_table, p0 + 1,
                new_k=jnp.asarray(k[:, :, 0], k_pool.dtype),
                new_v=jnp.asarray(v[:, :, 0], v_pool.dtype),
                scale=scale, layer=layer)
            ctx = ctx[:, :, None]
        else:
            pages = chunk_pages(page_table, p0, S, page_len)
            k_pool = _pool_write_pages(
                k_pool, layer, pages, jnp.asarray(k, k_pool.dtype))
            v_pool = _pool_write_pages(
                v_pool, layer, pages, jnp.asarray(v, v_pool.dtype))
            ctx = paged_prefill_attention(
                q, k_pool, v_pool, page_table, p0, scale=scale,
                layer=layer)
        aux = (k_pool, v_pool)
    else:
        from apex_tpu.kernels.prefill_attention import \
            prefill_attention
        ctx = prefill_attention(q, k, v,
                                jnp.zeros((B,), jnp.int32),
                                scale=scale)
        aux = None
    return ctx, aux


def chunk_pages(page_table, p0, S, page_len):
    """The pool pages ``[B, S // page_len]`` an aligned chunk of ``S``
    positions from ``p0 [B]`` fills."""
    if S % page_len:
        raise ValueError(f"paged chunk prefill needs S ({S}) to be a "
                         f"multiple of page_len ({page_len})")
    idx = (p0 // page_len)[:, None] + jnp.arange(
        S // page_len, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(page_table, idx, axis=1)


_INITS = {"ones": nn.initializers.ones, "zeros": nn.initializers.zeros,
          "lecun": nn.initializers.lecun_normal(),
          "normal02": nn.initializers.normal(0.02)}


class Leaves(nn.Module):
    """The parameters of one named group, as a dict: ``spec`` is
    ``((leaf, shape, init), ...)``."""

    spec: Tuple
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self):
        return {leaf: self.param(leaf, _INITS[init], shape, self.param_dtype)
                for leaf, shape, init in self.spec}


class Groups(nn.Module):
    """One layer's groups: ``{module: {leaf: array}}``."""

    spec: Tuple
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self):
        return {mod: Leaves(leaves, self.param_dtype, name=mod)()
                for mod, leaves in self.spec}
