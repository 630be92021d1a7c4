"""Ling-3.0-flash-style causal LM (inclusionAI; the language model of
``Ling-3.0-flash-VL``) - the serving tier's fourth architecture: a linear
mixer with a decay PER KEY CHANNEL, a full mixer whose cache is one LATENT
row a token, and a router limited to groups.

- **Kimi delta attention (KDA)** on five layers of six (layer ``l`` is
  latent attention where ``(l + 1) % layer_group_size == 0``): ``q, k, v =
  silu(conv(W u))`` through a depthwise causal convolution of kernel
  ``conv_kernel`` (:func:`~apex_tpu.models.lm_layers.short_conv`), as many
  key as value heads, ``q, k`` L2-normalised a head; log-decay ``g =
  kda_lower_bound * sigmoid(exp(A_log) (W_f u + dt_bias))`` a head AND key
  channel, write strength ``beta = sigmoid(W_b u)`` a head; a float32
  matrix ``S [dk, dv]`` a head read and written by the gated delta rule
  with ``S <- diag(exp(g)) S`` (:mod:`apex_tpu.kernels.gated_delta`:
  ``kda_step``, ``kda_chunk``); the output RMS-normed a head, times one
  sigmoid gate A HEAD, projected. Such a layer holds NO pages: a slot
  keeps ``S`` (``recurrent`` block) and the convolution's last inputs
  (``conv`` block).
- **Multi-head latent attention (MLA)** on every sixth layer, without a
  query latent: ``[c~ | k~_r] = W_kva u``, ``c = rms(c~)``, ``k_r`` rotated
  (one for all heads); per head ``[q_n | q_r] = W_q u`` (``q_n`` RMS-normed,
  ``q_r`` rotated), ``[k_n | v] = W_kvb c``, scores ``(q_n . k_n + q_r .
  k_r) / sqrt(d_n + d_r)``. The cache holds ``[c | k_r]`` - ONE row a
  token, key and value both for all heads (the latent page kind of
  :class:`~apex_tpu.serving.kv_cache.CacheSpec`) - and the served path is
  ABSORBED: ``q~ = W_kvb,k^T q_n`` scores against ``c`` itself, the values
  are ``sum p c`` taken through ``W_kvb,v`` afterwards
  (:func:`~apex_tpu.kernels.decode_attention.mla_decode_attention`, which
  also writes the token's row; :func:`~apex_tpu.kernels.prefill_attention
  .mla_prefill_attention`). The plain forward expands ``k_n, v`` instead:
  the same numbers by another order of products. One sigmoid gate a head,
  as KDA.
- **The expert block**: float32 sigmoid router over ALL ``num_experts``
  with a selection bias and groups
  (:func:`~apex_tpu.transformer.moe.group_limited_sigmoid_topk`), the
  routed sum over the experts this chip HOLDS, plus one shared expert
  every chip computes whole. The first ``first_dense`` layers have a
  dense SwiGLU MLP and no experts (and no row of the routed-token
  counter).
- Plain RMSNorm ``w``, plain residuals, an UNTIED head. No clamp in the
  expert SwiGLU (the published limit lists are 0 for the layers the
  benchmark keeps; ``build_lm`` refuses a kept layer with one), no vision
  tower and no multi-token-prediction module: prompts are token ids.

What the engine holds is :meth:`LingLM.cache_spec`. The equations, and
which of their details the published configuration does not fix
("assumed"), are in ``benchmarks/lib/reference_ling3.py``, the float32
reference this module is tested against. Compute is bfloat16
(``inference_dtype``) with float32 norms, convolution sums, decay,
recurrent state, rotary, router, softmax and logits. Serving modes and
operands are :class:`~apex_tpu.models.qwen3_next.Qwen3NextLM`'s.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.lm_layers import (Groups, Leaves, chunk_pages, einsum32,
                                       f32, gated_mlp, held_experts,
                                       last_valid, positions_of, rms, rotary,
                                       short_conv)
from apex_tpu.models.transformer_lm import _pool_write_pages

__all__ = ["LingLM"]

# Rows of (token, expert) pairs the expert layer takes at a time; every
# block streams the held experts once. A chunk of 1,024 tokens sends about
# 1024 x 8 x 128 / 512 = 2,048 rows (sd 40 under a balanced router) to the
# experts held: three blocks of 768 hold them with six sigma to spare,
# where the layer's default of 512 sits on the edge of 4 and 5.
EXPERT_BLOCK_ROWS = 768


class LingLM(nn.Module):
    """The model; see the module docstring. Sizes default to
    Ling-3.0-flash's."""

    vocab_size: int = 157184
    hidden: int = 2560
    num_layers: int = 42
    layer_group_size: int = 6
    first_dense: int = 2
    num_heads: int = 32
    head_dim: int = 128                 # KDA: dk = dv
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    dense_width: int = 6144
    num_experts: int = 512
    experts_per_token: int = 8
    expert_width: int = 768
    shared_width: int = 768
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    rms_eps: float = 1e-6
    max_seq_len: int = 131072
    # the experts whose weights this chip holds, as a tuple of ids in the
    # order they are stacked in the parameters (None: all of them); the
    # router always runs over all `num_experts`
    experts_held: Optional[Tuple[int, ...]] = None
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    inference_dtype: Optional[Any] = None

    model_kind = "ling_v3"

    def __post_init__(self):
        super().__post_init__()
        if self.num_experts % self.n_group \
                or not 0 < self.topk_group <= self.n_group:
            raise ValueError("LingLM: the experts lie in n_group equal "
                             "groups of which topk_group are kept")

    # ----------------------------------------------------------- geometry
    def is_latent(self, layer: int) -> bool:
        return (layer + 1) % self.layer_group_size == 0

    @property
    def page_layers(self) -> int:
        return self.num_layers // self.layer_group_size

    @property
    def conv_channels(self) -> int:
        return 3 * self.num_heads * self.head_dim

    @property
    def latent_row(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    def cache_spec(self) -> dict:
        """What the serving engine holds for this model
        (:class:`~apex_tpu.serving.kv_cache.CacheSpec`): the latent layers
        pages of ONE row ``[kv_lora_rank | qk_rope_dim]`` a token whose
        first ``kv_lora_rank`` columns are the value too; the KDA layers a
        float32 ``recurrent`` matrix a head and the convolution's last
        inputs; a counter row for each layer that has experts."""
        n_kda = self.num_layers - self.page_layers
        return {"page_layers": self.page_layers, "kv_heads": 1,
                "head_dim": self.latent_row, "value_dim": self.kv_lora_rank,
                "state": [("recurrent", n_kda,
                           (self.num_heads, self.head_dim, self.head_dim),
                           jnp.float32),
                          ("conv", n_kda, (self.conv_kernel - 1,
                                           self.conv_channels), None)],
                "counter_layers": self.num_layers - self.first_dense,
                "num_experts": self.num_experts}

    # ---------------------------------------------------------- sublayers
    def _kda(self, u, lp, cdt, *, index, rec, tail, addr, mask):
        """``u [B, S, H]`` (normed, compute dtype) -> ``(out [B, S, H],
        recurrent block (or the state the batch's rows leave), conv tail
        ``[B, K - 1, C]``)``; operands as
        :meth:`Qwen3NextLM._linear_attention`."""
        from apex_tpu.kernels import gated_delta as gd

        B, S, _ = u.shape
        nh, d = self.num_heads, self.head_dim
        W = nh * d
        with jax.named_scope("kda.proj"):
            x = jnp.dot(u, jnp.asarray(lp["w_qkv"], cdt))
            # the decay and the head-wise strengths in float32
            f = einsum32("bsh,hn->bsn", u, jnp.asarray(lp["w_f"], cdt))
            bg = einsum32("bsh,hn->bsn", u, jnp.asarray(lp["w_bg"], cdt))
            beta = jax.nn.sigmoid(bg[..., :nh])
            gate = jax.nn.sigmoid(bg[..., nh:])
            g = self.kda_lower_bound * jax.nn.sigmoid(
                jnp.exp(f32(lp["a_log"]))[:, None]
                * (f.reshape(B, S, nh, d) + f32(lp["dt_bias"]).reshape(nh, d)))
            if mask is not None:                    # padding writes nothing
                beta = jnp.where(mask[..., None], beta, 0.0)
                g = jnp.where(mask[..., None, None], g, 0.0)
        with jax.named_scope("kda.conv"):
            c, new_tail = short_conv(x, tail, lp["conv_w"], mask)
            c = jax.nn.silu(c)
            q, k, v = (c[..., i * W:(i + 1) * W].reshape(B, S, nh, d)
                       for i in range(3))
            unit = lambda t: t * jax.lax.rsqrt(                 # noqa: E731
                jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
            q, k = unit(q) * (1.0 / np.sqrt(d)), unit(k)
        if rec is None:
            with jax.named_scope("kda.chunk"):
                o, rec = gd.gated_delta_chunk_reference(
                    q, k, v, g, beta, jnp.zeros((B, nh, d, d)))
        elif S == 1:
            with jax.named_scope("kda.step"):
                o, rec = gd.gated_delta_step(
                    rec, index, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                    beta[:, 0], addr.active)
                o = o[:, None]
        else:
            if B != 1:
                raise ValueError("a chunk of the linear-attention state "
                                 "is one slot's: batch 1")
            with jax.named_scope("kda.chunk"):
                o, rec = gd.gated_delta_chunk(
                    rec, index, addr.slot, addr.fresh, q[0], k[0], v[0],
                    g[0], beta[0])
                o = o[None]
        with jax.named_scope("kda.gate"):
            y = rms(o, lp["norm"], self.rms_eps) * gate[..., None]
            out = jnp.dot(jnp.asarray(y.reshape(B, S, W), cdt),
                          jnp.asarray(lp["w_o"], cdt))
        return out, rec, new_tail

    def _mla(self, u, lp, cdt, *, index, cache, positions):
        """``u [B, S, H]`` -> ``(out [B, S, H], latent pool | None)``.
        ``cache = (pool, page_table)``."""
        B, S, _ = u.shape
        nh, dn, dr = self.num_heads, self.qk_nope_dim, self.qk_rope_dim
        dv, r = self.v_head_dim, self.kv_lora_rank
        scale = 1.0 / np.sqrt(dn + dr)
        with jax.named_scope("mla.proj"):
            q = jnp.dot(u, jnp.asarray(lp["w_q"], cdt)).reshape(
                B, S, nh, dn + dr)
            kva = einsum32("bsh,hn->bsn", u, jnp.asarray(lp["w_kva"], cdt))
            gate = jax.nn.sigmoid(einsum32(
                "bsh,hn->bsn", u, jnp.asarray(lp["w_g"], cdt)))
            pos = positions_of(positions, B, S)
            q_n = jnp.asarray(rms(q[..., :dn], lp["q_norm"], self.rms_eps),
                              cdt)
            q_r = rotary(f32(q[..., dn:]), pos, self.rope_theta, dr)
            c = rms(kva[..., :r], lp["kv_norm"], self.rms_eps)
            k_r = rotary(kva[..., None, r:], pos, self.rope_theta, dr)[:, :, 0]
            row = jnp.asarray(jnp.concatenate([c, k_r], -1), cdt)
            w_kvb = jnp.asarray(lp["w_kvb"], cdt).reshape(r, nh, dn + dv)
        if cache is None:
            # the plain forward: keys and values expanded, a head at a time
            with jax.named_scope("mla.attn"):
                kv = einsum32("bsr,rhd->bshd", row[..., :r], w_kvb)
                k_n, v = (jnp.asarray(t, cdt)
                          for t in (kv[..., :dn], kv[..., dn:]))
                s = (einsum32("bqhd,bkhd->bhqk", q_n, k_n)
                     + einsum32("bqhd,bkd->bhqk", jnp.asarray(q_r, cdt),
                                row[..., r:])) * scale
                s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
                o = einsum32("bhqk,bkhd->bqhd",
                             jnp.asarray(jax.nn.softmax(s, -1), cdt), v)
            pool = None
        else:
            from apex_tpu.kernels.decode_attention import \
                mla_decode_attention
            from apex_tpu.kernels.prefill_attention import \
                mla_prefill_attention
            pool, page_table = cache
            page_len = pool.shape[4]
            L = page_table.shape[1] * page_len
            p0 = jnp.clip(jnp.asarray(positions, jnp.int32), 0, L - S)
            with jax.named_scope("mla.absorb"):
                q_abs = einsum32("bshd,rhd->bshr", q_n, w_kvb[..., :dn])
                qq = jnp.asarray(jnp.concatenate([q_abs, q_r], -1),
                                 pool.dtype)
            with jax.named_scope("mla.attn"):
                row = jnp.asarray(row, pool.dtype)
                if S == 1:
                    lat, pool = mla_decode_attention(
                        qq[:, 0], pool, page_table, p0 + 1, value_dim=r,
                        new_row=row[:, 0], scale=scale, layer=index)
                    lat = lat[:, None]
                else:
                    pool = _pool_write_pages(
                        pool, index, chunk_pages(page_table, p0, S, page_len),
                        row[:, None])
                    lat = mla_prefill_attention(
                        qq, pool, page_table, p0, value_dim=r, scale=scale,
                        layer=index)
            with jax.named_scope("mla.absorb"):
                o = einsum32("bshr,rhd->bshd", jnp.asarray(lat, cdt),
                             w_kvb[..., dn:])
        with jax.named_scope("mla.gate"):
            ctx = o * gate[..., None]
            out = jnp.dot(jnp.asarray(ctx.reshape(B, S, nh * dv), cdt),
                          jnp.asarray(lp["w_o"], cdt))
        return out, pool

    def _experts(self, u, lp, cdt, valid):
        """``u [B, S, H]`` (normed, compute dtype) -> ``(y [B, S, H]
        float32, each token's experts [B, S, k], tokens per expert [E]
        int32 over the ``valid [B, S]`` tokens)``."""
        from apex_tpu.transformer.moe import group_limited_sigmoid_topk

        B, S, H = u.shape
        flat = u.reshape(B * S, H)
        with jax.named_scope("moe.router"):
            logits = jnp.dot(f32(flat), f32(lp["router"]["w"]),
                             precision=jax.lax.Precision.HIGHEST)
            weights, choice = group_limited_sigmoid_topk(
                logits, lp["router"]["bias"], k=self.experts_per_token,
                n_group=self.n_group, topk_group=self.topk_group,
                scale=self.routed_scaling)
        y, counts = held_experts(
            flat, weights, choice, lp["experts"], cdt,
            num_experts=self.num_experts, experts_held=self.experts_held,
            valid=valid, block_rows=EXPERT_BLOCK_ROWS)
        with jax.named_scope("moe.shared"):
            y = y + gated_mlp(flat, lp["shared"]["w_gate_up"],
                              lp["shared"]["w_down"], cdt)
        return y.reshape(B, S, H), \
            choice.reshape(B, S, self.experts_per_token), counts

    # -------------------------------------------------------------- model
    def _layer_spec(self, layer: int):
        """``((module, ((leaf, shape, init), ...)), ...)`` of layer
        ``layer``: the reference's ``layer_shapes`` as parameter paths."""
        H, nh, d = self.hidden, self.num_heads, self.head_dim
        if self.is_latent(layer):
            dn, dr, dv, r = self.qk_nope_dim, self.qk_rope_dim, \
                self.v_head_dim, self.kv_lora_rank
            mixer = ("mla", (("w_q", (H, nh * (dn + dr)), "lecun"),
                             ("w_kva", (H, r + dr), "lecun"),
                             ("kv_norm", (r,), "ones"),
                             ("q_norm", (dn,), "ones"),
                             ("w_kvb", (r, nh * (dn + dv)), "lecun"),
                             ("w_g", (H, nh), "lecun"),
                             ("w_o", (nh * dv, H), "lecun")))
        else:
            W = nh * d
            mixer = ("kda", (("w_qkv", (H, 3 * W), "lecun"),
                             ("w_f", (H, W), "lecun"),
                             ("w_bg", (H, 2 * nh), "lecun"),
                             ("conv_w", (3 * W, self.conv_kernel), "ones"),
                             ("a_log", (nh,), "zeros"),
                             ("dt_bias", (W,), "zeros"),
                             ("norm", (d,), "ones"),
                             ("w_o", (W, H), "lecun")))
        if layer < self.first_dense:
            Fd = self.dense_width
            mlp = (("mlp", (("w_gate_up", (H, 2 * Fd), "lecun"),
                            ("w_down", (Fd, H), "lecun"))),)
        else:
            E, F, Fs = self.num_experts, self.expert_width, self.shared_width
            G = E if self.experts_held is None else len(self.experts_held)
            mlp = (("router", (("w", (H, E), "lecun"),
                               ("bias", (E,), "zeros"))),
                   ("experts", (("w_gate_up", (G, H, 2 * F), "lecun"),
                                ("w_down", (G, F, H), "lecun"))),
                   ("shared", (("w_gate_up", (H, 2 * Fs), "lecun"),
                               ("w_down", (Fs, H), "lecun"))))
        return (("attn_norm", (("scale", (H,), "ones"),)), mixer,
                ("mlp_norm", (("scale", (H,), "ones"),))) + mlp

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, cache=None,
                 positions=None, state=None, addr=None, n_valid=None,
                 valid=None):
        if train:
            raise NotImplementedError(
                "LingLM is a serving model: neither the recurrence nor "
                "the drop-nothing expert layer has a backward here")
        if cache is not None and (len(cache) != 3 or state is None):
            raise NotImplementedError(
                "LingLM: the paged cache (latent pool, empty V pool, "
                "page_table) with the engine's state blocks only")
        from apex_tpu.amp.autocast import resolve_dtype
        cdt = resolve_dtype(self.dtype, "linear", jnp.float32)
        if self.inference_dtype is not None:
            cdt = self.inference_dtype
        B, S = tokens.shape
        emb = Leaves((("embedding", (self.vocab_size, self.hidden),
                       "normal02"),), self.param_dtype,
                     name="wte")()["embedding"]
        x = jnp.asarray(emb[tokens], cdt)
        mask = None if n_valid is None else (
            jnp.arange(S, dtype=jnp.int32)[None]
            < jnp.asarray(n_valid, jnp.int32)[:, None])
        if valid is None:
            valid = jnp.ones((B, S), bool) if mask is None else mask
        serving = state is not None
        rec = state["recurrent"] if serving else None
        n_kda = self.num_layers - self.page_layers
        tails = addr.read(state["conv"]) if serving else jnp.zeros(
            (n_kda, B, self.conv_kernel - 1, self.conv_channels), cdt)
        new_tails, counts = [], []
        pool = None if cache is None else cache[0]
        for i in range(self.num_layers):
            lp = Groups(self._layer_spec(i), self.param_dtype,
                        name=f"layer_{i}")()
            u = jnp.asarray(rms(x, lp["attn_norm"]["scale"], self.rms_eps),
                            cdt)
            if self.is_latent(i):
                out, pool = self._mla(
                    u, lp["mla"], cdt, index=i // self.layer_group_size,
                    cache=None if pool is None else (pool, cache[2]),
                    positions=positions)
            else:
                li = i - i // self.layer_group_size
                out, new_rec, tail = self._kda(
                    u, lp["kda"], cdt, index=li, rec=rec, tail=tails[li],
                    addr=addr, mask=mask)
                if serving:
                    rec = new_rec
                new_tails.append(tail)
            x = jnp.asarray(f32(x) + f32(out), cdt)
            u = jnp.asarray(rms(x, lp["mlp_norm"]["scale"], self.rms_eps),
                            cdt)
            if i < self.first_dense:
                with jax.named_scope("mlp.dense"):
                    y = gated_mlp(u.reshape(B * S, -1),
                                  lp["mlp"]["w_gate_up"],
                                  lp["mlp"]["w_down"], cdt).reshape(x.shape)
            else:
                y, choice, cnt = self._experts(u, lp, cdt, valid)
                # each token's experts, layer by layer, for whoever asks
                # (``mutable=["intermediates"]``: the tests)
                self.sow("intermediates", "expert_choice", choice)
                counts.append(cnt)
            x = jnp.asarray(f32(x) + y, cdt)
        norm_f = Leaves((("scale", (self.hidden,), "ones"),),
                        self.param_dtype, name="norm_f")()["scale"]
        head = Leaves((("kernel", (self.hidden, self.vocab_size),
                        "lecun"),), self.param_dtype,
                      name="head")()["kernel"]
        if n_valid is not None:
            x = last_valid(x, n_valid)[:, None]              # [B, 1, H]
        x = jnp.asarray(rms(x, norm_f, self.rms_eps), cdt)
        logits = einsum32("bsh,hv->bsv", x, jnp.asarray(head, cdt))
        if cache is None:
            return logits
        blocks = {"recurrent": rec,
                  "conv": addr.write(state["conv"], jnp.stack(new_tails))}
        return logits, (pool, cache[1], blocks, jnp.stack(counts))
