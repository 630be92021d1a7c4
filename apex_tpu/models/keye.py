"""Keye-VL-2.0-style causal LM (Kwai; the language model of
``Keye-VL-2.0-30B-A3B``) - the serving tier's fifth architecture:
grouped-query attention that reads, for each token, only the positions a
LEARNED INDEXER picks (DeepSeek-Sparse-Attention), over a wide top-k
expert layer.

- **The lightning indexer** on every layer: ``qI = rot(u W_qI)`` (``nh``
  heads of ``di``, rotary on the first half of a head), ONE key a position
  ``kI = rot(LayerNorm(u W_kI))`` shared by the heads, weights ``w = u W_w
  / sqrt(nh)``; the score of position ``s`` for token ``t`` is ``sum_j
  w_j ReLU(qI_j . kI_s / sqrt(di))`` over ``s <= t``, and the token keeps
  the ``index_topk`` best (ties to the lower position; all of them below
  ``index_topk`` positions). The index keys live in a paged cache of their
  own beside K/V (the index page kind of :class:`~apex_tpu.serving
  .kv_cache.CacheSpec`), addressed by the same page ids.
- **Sparse grouped-query attention**: ``num_heads`` query heads of
  ``head_dim`` over ``num_kv_heads`` K/V heads, per-head RMS norms on q
  and k, rotary over the whole head (the text's three ``mrope`` position
  ids are equal, so ``mrope_section`` is 1-D rotary), softmax over the
  token's kept positions only - one selection for all heads of a token.
  K/V pages are held in the CONTIGUOUS-ROW layout (``[page_len, kv_heads
  * head_dim]``): a kept position is one row.
- **A drop-nothing top-k expert layer** on every layer, no shared expert:
  float32 softmax router over ALL ``num_experts``, the
  ``experts_per_token`` largest renormalised, the routed sum over the
  experts this chip HOLDS (:func:`~apex_tpu.models.lm_layers
  .held_experts`).
- Plain RMSNorm ``w``, plain residuals, an UNTIED head; no vision tower
  (prompts are token ids) and no multi-token prediction.

What the engine holds is :meth:`KeyeLM.cache_spec`. The equations, and
which details the published configuration does not fix ("assumed"), are
in ``benchmarks/lib/reference_keye.py``, the float32 reference this module
is tested against. Compute is bfloat16 (``inference_dtype``) with float32
norms, rotary, indexer weights and scores, router, softmax and logits.

Serving modes follow :class:`~apex_tpu.models.qwen3_next.Qwen3NextLM`'s
operands with the index pool as a fourth cache entry: ``cache=(k_pool,
v_pool, page_table, index_pool)``, ``positions``, ``state`` (no blocks)
and ``addr`` [+ ``n_valid`` | ``valid``] -> ``(logits, (k_pool, v_pool,
blocks, tokens_per_expert, index_pool))``. A DECODE step writes each row's
K/V row and index key, scores the row's live index pages
(:func:`~apex_tpu.kernels.dsa.dsa_index`), selects
(:func:`~apex_tpu.kernels.dsa.dsa_select`), gathers the kept positions'
K/V rows alone and attends them (:func:`~apex_tpu.kernels.dsa
.dsa_attend`); an aligned CHUNK writes whole pages, scores and selects
for every query and attends the live pages once under the selection as a
mask (:func:`~apex_tpu.kernels.dsa.dsa_attend_chunk`). The plain forward
returns logits ``[B, S, V]``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.lm_layers import (Groups, Leaves, chunk_pages, einsum32,
                                       f32, held_experts, last_valid,
                                       positions_of, rms, rotary)

__all__ = ["KeyeLM"]


def _layer_norm(x, w, b, eps):
    x = f32(x)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(w) + f32(b)


class KeyeLM(nn.Module):
    """The model; see the module docstring. Sizes default to
    Keye-VL-2.0-30B-A3B's."""

    vocab_size: int = 151936
    hidden: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    num_experts: int = 128
    experts_per_token: int = 8
    expert_width: int = 768
    rms_eps: float = 1e-6
    max_seq_len: int = 262144
    # the experts whose weights this chip holds, as a tuple of ids in the
    # order they are stacked in the parameters (None: all of them); the
    # router always runs over all `num_experts`
    experts_held: Optional[Tuple[int, ...]] = None
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    inference_dtype: Optional[Any] = None

    model_kind = "KeyeVL2"

    def __post_init__(self):
        super().__post_init__()
        if self.num_heads % self.num_kv_heads or self.index_dim % 4:
            raise ValueError("KeyeLM: query heads must be a multiple of "
                             "K/V heads, and an index head's rotary half "
                             "a whole number of pairs")

    def cache_spec(self) -> dict:
        """What the serving engine holds for this model
        (:class:`~apex_tpu.serving.kv_cache.CacheSpec`): every layer K/V
        pages in the contiguous-row layout and an index-key page of
        ``index_dim`` beside them, no per-slot state, a ``[layers,
        num_experts]`` counter of tokens routed."""
        return {"page_layers": self.num_layers,
                "kv_heads": self.num_kv_heads, "head_dim": self.head_dim,
                "row_pages": True, "index_dim": self.index_dim,
                "index_topk": self.index_topk,
                "counter_layers": self.num_layers,
                "num_experts": self.num_experts}

    # ---------------------------------------------------------- sublayers
    def _projections(self, u, lp, cdt, pos):
        """``u [B, S, H]`` -> ``q [B, S, nq, d]``, ``k, v [B, S, nk, d]``
        (compute dtype), the indexer's ``qI [B, S, nh, di]``, ``kI [B, S,
        di]`` (compute dtype) and ``w [B, S, nh]`` float32."""
        B, S, _ = u.shape
        nq, nk, d = self.num_heads, self.num_kv_heads, self.head_dim
        nh, di = self.index_heads, self.index_dim
        ap, ip = lp["attn"], lp["indexer"]
        with jax.named_scope("attn.proj"):
            q = jnp.dot(u, jnp.asarray(ap["wq"], cdt)).reshape(B, S, nq, d)
            k = jnp.dot(u, jnp.asarray(ap["wk"], cdt)).reshape(B, S, nk, d)
            v = jnp.dot(u, jnp.asarray(ap["wv"], cdt)).reshape(B, S, nk, d)
            q = rotary(rms(q, ap["q_norm"], self.rms_eps), pos,
                       self.rope_theta, d)
            k = rotary(rms(k, ap["k_norm"], self.rms_eps), pos,
                       self.rope_theta, d)
        with jax.named_scope("dsa.index"):
            qi = jnp.dot(u, jnp.asarray(ip["wq"], cdt)).reshape(B, S, nh, di)
            qi = rotary(f32(qi), pos, self.rope_theta, di // 2)
            ki = _layer_norm(jnp.dot(u, jnp.asarray(ip["wk"], cdt)),
                             ip["k_norm"], ip["k_bias"], self.rms_eps)
            ki = rotary(ki[:, :, None], pos, self.rope_theta, di // 2)[:, :, 0]
            w = einsum32("bsh,hn->bsn", u, jnp.asarray(ip["ww"], cdt)) \
                / np.sqrt(nh)
        return (jnp.asarray(q, cdt), jnp.asarray(k, cdt), v,
                jnp.asarray(qi, cdt), jnp.asarray(ki, cdt), w)

    def _sparse_attention(self, q, k, v, qi, ki, w, *, layer, cache,
                          positions):
        """Write this layer's K/V rows and index keys into the pools,
        select each token's positions and attend them: ``(ctx [B, S, nq,
        d] float32, the pools, the selection as a mask [B, S, span] for
        whoever asks)``."""
        from apex_tpu.kernels import dsa

        k_pool, v_pool, pt, ix_pool = cache
        B, S = q.shape[:2]
        nk, d = self.num_kv_heads, self.head_dim
        page_len = k_pool.shape[2]
        span = pt.shape[1] * page_len
        p0 = jnp.clip(jnp.asarray(positions, jnp.int32), 0, span - S)
        k, v = k.reshape(B, S, nk * d), v.reshape(B, S, nk * d)   # rows
        if S == 1:
            page = jnp.take_along_axis(pt, (p0 // page_len)[:, None],
                                       axis=1)[:, 0]
            off = p0 % page_len
            k_pool = k_pool.at[layer, page, off].set(
                k[:, 0].astype(k_pool.dtype))
            v_pool = v_pool.at[layer, page, off].set(
                v[:, 0].astype(v_pool.dtype))
            ix_pool = ix_pool.at[layer, page, :, off].set(
                ki[:, 0].astype(ix_pool.dtype))
        else:
            pages = chunk_pages(pt, p0, S, page_len)
            n = pages.shape[1]
            k_pool = k_pool.at[layer, pages].set(
                k.reshape(B, n, page_len, nk * d).astype(k_pool.dtype))
            v_pool = v_pool.at[layer, pages].set(
                v.reshape(B, n, page_len, nk * d).astype(v_pool.dtype))
            ix_pool = ix_pool.at[layer, pages].set(
                jnp.swapaxes(ki.reshape(B, n, page_len, -1), 2, 3).astype(
                    ix_pool.dtype))
        with jax.named_scope("dsa.index"):
            scores = dsa.dsa_index(qi, w, ix_pool, pt, p0, layer=layer,
                                   scale=1.0 / np.sqrt(self.index_dim))
        pos = p0[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        with jax.named_scope("dsa.select"):
            thr, cut = dsa.dsa_select(scores.reshape(B * S, span),
                                      pos.reshape(-1), self.index_topk)
            thr, cut = thr.reshape(B, S), cut.reshape(B, S)
        with jax.named_scope("dsa.attend"):
            if S == 1:
                idx, n_sel = dsa.selected_positions(
                    scores[:, 0], thr[:, 0], cut[:, 0], p0, self.index_topk)
                ks = dsa.gather_selected(k_pool, pt, idx, layer)
                vs = dsa.gather_selected(v_pool, pt, idx, layer)
                ctx = dsa.dsa_attend(q[:, 0], ks, vs, n_sel,
                                     scale=1.0 / np.sqrt(d))[:, None]
            else:
                ctx = dsa.dsa_attend_chunk(
                    q, k_pool, v_pool, pt, p0, scores, thr, cut,
                    layer=layer, scale=1.0 / np.sqrt(d))
        mask = dsa.selected_mask(scores, thr, cut, pos)
        return ctx, (k_pool, v_pool, ix_pool), mask

    def _dense_sparse_attention(self, q, k, v, qi, ki, w):
        """The same mathematics over one whole sequence without a cache
        (the plain forward)."""
        from apex_tpu.kernels import dsa

        B, S, nq, d = q.shape
        nk = self.num_kv_heads
        s = jnp.einsum("bqhd,bsd->bqhs", f32(qi), f32(ki)) \
            / np.sqrt(self.index_dim)
        scores = jnp.einsum("bqhs,bqh->bqs", jnp.maximum(s, 0.0), w)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        scores = jnp.where(jnp.arange(S)[None, None] <= pos[..., None],
                           scores, -jnp.inf)
        thr, cut = dsa.select_reference(scores.reshape(B * S, S),
                                        pos.reshape(-1), self.index_topk)
        keep = dsa.selected_mask(scores, thr.reshape(B, S),
                                 cut.reshape(B, S), pos)
        qg = f32(q).reshape(B, S, nk, nq // nk, d)
        att = jnp.einsum("bqgrd,bsgd->bqgrs", qg, f32(k)) / np.sqrt(d)
        att = jnp.where(keep[:, :, None, None], att, -jnp.inf)
        ctx = jnp.einsum("bqgrs,bsgd->bqgrd", jax.nn.softmax(att, -1),
                         f32(v))
        return ctx.reshape(B, S, nq, d), keep

    def _experts(self, u, lp, cdt, valid):
        """``u [B, S, H]`` -> ``(y [B, S, H] float32, each token's experts
        [B, S, k], tokens per expert [E] int32 over ``valid [B, S]``)``."""
        B, S, H = u.shape
        kk = self.experts_per_token
        flat = u.reshape(B * S, H)
        with jax.named_scope("moe.router"):
            p = jax.nn.softmax(jnp.dot(f32(flat), f32(lp["router"]["w"])),
                               -1)
            top, choice = jax.lax.top_k(p, kk)
            weights = top / jnp.sum(top, -1, keepdims=True)
            choice = choice.astype(jnp.int32)
        y, counts = held_experts(
            flat, weights, choice, lp["experts"], cdt,
            num_experts=self.num_experts, experts_held=self.experts_held,
            valid=valid)
        return y.reshape(B, S, H), choice.reshape(B, S, kk), counts

    # -------------------------------------------------------------- model
    def _layer_spec(self):
        H, E, F = self.hidden, self.num_experts, self.expert_width
        G = E if self.experts_held is None else len(self.experts_held)
        nq, nk, d = self.num_heads, self.num_kv_heads, self.head_dim
        nh, di = self.index_heads, self.index_dim
        return (
            ("attn_norm", (("scale", (H,), "ones"),)),
            ("attn", (("wq", (H, nq * d), "lecun"),
                      ("wk", (H, nk * d), "lecun"),
                      ("wv", (H, nk * d), "lecun"),
                      ("q_norm", (d,), "ones"),
                      ("k_norm", (d,), "ones"),
                      ("wo", (nq * d, H), "lecun"))),
            ("indexer", (("wq", (H, nh * di), "lecun"),
                         ("wk", (H, di), "lecun"),
                         ("k_norm", (di,), "ones"),
                         ("k_bias", (di,), "zeros"),
                         ("ww", (H, nh), "lecun"))),
            ("moe_norm", (("scale", (H,), "ones"),)),
            ("router", (("w", (H, E), "lecun"),)),
            ("experts", (("w_gate_up", (G, H, 2 * F), "lecun"),
                         ("w_down", (G, F, H), "lecun"))),
        )

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, cache=None,
                 positions=None, state=None, addr=None, n_valid=None,
                 valid=None):
        if train:
            raise NotImplementedError(
                "KeyeLM is a serving model: neither the selection nor the "
                "drop-nothing expert layer has a backward here")
        if cache is not None and len(cache) != 4:
            raise NotImplementedError(
                "KeyeLM: the paged cache (k_pool, v_pool, page_table, "
                "index_pool) only")
        del addr                        # no per-slot state
        from apex_tpu.amp.autocast import resolve_dtype
        cdt = resolve_dtype(self.dtype, "linear", jnp.float32)
        if self.inference_dtype is not None:
            cdt = self.inference_dtype
        B, S = tokens.shape
        emb = Leaves((("embedding", (self.vocab_size, self.hidden),
                        "normal02"),), self.param_dtype,
                      name="wte")()["embedding"]
        x = jnp.asarray(emb[tokens], cdt)
        if valid is None:
            valid = jnp.ones((B, S), bool) if n_valid is None else (
                jnp.arange(S, dtype=jnp.int32)[None]
                < jnp.asarray(n_valid, jnp.int32)[:, None])
        pos = positions_of(positions, B, S)
        spec = self._layer_spec()
        pools = None if cache is None else (cache[0], cache[1], cache[3])
        counts = []
        for i in range(self.num_layers):
            lp = Groups(spec, self.param_dtype, name=f"layer_{i}")()
            u = jnp.asarray(rms(x, lp["attn_norm"]["scale"], self.rms_eps),
                            cdt)
            q, k, v, qi, ki, w = self._projections(u, lp, cdt, pos)
            if pools is None:
                ctx, keep = self._dense_sparse_attention(q, k, v, qi, ki, w)
            else:
                ctx, pools, keep = self._sparse_attention(
                    q, k, v, qi, ki, w, layer=i,
                    cache=(pools[0], pools[1], cache[2], pools[2]),
                    positions=positions)
            # each token's kept positions, for whoever asks (the tests)
            self.sow("intermediates", "dsa_selection", keep)
            with jax.named_scope("attn.out"):
                out = jnp.dot(jnp.asarray(ctx.reshape(B, S, -1), cdt),
                              jnp.asarray(lp["attn"]["wo"], cdt))
            x = jnp.asarray(f32(x) + f32(out), cdt)
            u = jnp.asarray(rms(x, lp["moe_norm"]["scale"], self.rms_eps),
                            cdt)
            y, choice, cnt = self._experts(u, lp, cdt, valid)
            self.sow("intermediates", "expert_choice", choice)
            counts.append(cnt)
            x = jnp.asarray(f32(x) + y, cdt)
        norm_f = Leaves((("scale", (self.hidden,), "ones"),),
                         self.param_dtype, name="norm_f")()["scale"]
        head = Leaves((("kernel", (self.hidden, self.vocab_size),
                         "lecun"),), self.param_dtype,
                       name="head")()["kernel"]
        if n_valid is not None:
            x = last_valid(x, n_valid)[:, None]             # [B, 1, H]
        x = jnp.asarray(rms(x, norm_f, self.rms_eps), cdt)
        logits = einsum32("bsh,hv->bsv", x, jnp.asarray(head, cdt))
        if pools is None:
            return logits
        return logits, (pools[0], pools[1], {} if state is None else state,
                        jnp.stack(counts), pools[2])
