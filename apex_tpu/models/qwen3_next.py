"""Qwen3-Next-style causal LM (Qwen) — the serving tier's third
architecture: two KINDS of token mixer in one model and a wide, shallow
expert layer.

- **Gated delta-rule linear attention** on three layers of four (layer
  ``l`` is full attention where ``(l + 1) % full_attention_interval ==
  0``): one fused projection gives ``q~, k~, v~`` and an output gate
  ``z``, a second the per-head write strength and decay; ``q~, k~, v~``
  pass a depthwise causal convolution of kernel ``conv_kernel`` over time
  and a SiLU; ``q, k`` are L2-normalised per head and each key head
  serves ``lin_value_heads // lin_key_heads`` value heads; a float32
  matrix ``S [dk, dv]`` per value head is read and written by the gated
  delta rule (:mod:`apex_tpu.kernels.gated_delta`); the output is
  RMS-normed per head, gated by ``silu(z)`` and projected. Such a layer
  holds NO pages: a slot keeps ``S`` (``recurrent`` block) and the
  convolution's last ``conv_kernel - 1`` inputs (``conv`` block).
- **Gated full attention** on every fourth layer: ``num_kv_heads`` K/V
  heads of ``head_dim`` 256 each serving 8 query heads, per-head RMS
  norms on q and k, rotary on the first quarter of a head, and an
  elementwise ``sigmoid`` gate (projected beside the query) on the
  attention output. Such a layer holds pages and no state.
- **A drop-nothing top-k expert layer with a shared expert**: float32
  softmax router over ALL ``num_experts``, the ``experts_per_token``
  largest with their probabilities renormalised, the routed sum over the
  experts this chip HOLDS (:func:`~apex_tpu.transformer.moe
  .dropless_topk_experts`), plus a sigmoid-gated shared expert that every
  chip computes whole.
- Zero-centred RMSNorm ``(1 + w)``, plain residuals, an UNTIED head.

What the engine holds for it is stated per kind of layer by
:meth:`Qwen3NextLM.cache_spec`. The equations, and which of their details
the published configuration does not fix ("assumed"), are in
``benchmarks/lib/reference_qwen3next.py``, the float32 reference this
module is tested against. Compute is bfloat16 (``inference_dtype``) with
float32 norms, convolution sums, decay, recurrent state, rotary, router,
softmax and logits.

Serving modes are :class:`~apex_tpu.models.zaya.ZayaLM`'s: the paged
decode step and the aligned chunk take ``cache=(k_pool, v_pool,
page_table)``, ``positions``, ``state`` (the engine's blocks, whole) and
``addr`` (:class:`~apex_tpu.serving.kv_cache.SlotAddr`) [+ ``n_valid``]
and return ``(logits, (k_pool, v_pool, blocks, tokens_per_expert))``; the
plain forward returns logits ``[B, S, V]``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.lm_layers import (Groups, Leaves, einsum32, f32 as _f32,
                                       gated_mlp, held_experts, last_valid,
                                       paged_attend, positions_of, rms,
                                       rotary, short_conv)

__all__ = ["Qwen3NextLM"]


def _rms(x, w, eps, centred=True):
    """The model's norms: zero-centred ``(1 + w)`` (sublayer, final and
    q/k norms) unless told plain."""
    return rms(x, w, eps, centred)


class Qwen3NextLM(nn.Module):
    """The model; see the module docstring. Sizes default to
    Qwen3-Next-80B-A3B's."""

    vocab_size: int = 151936
    hidden: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    lin_key_heads: int = 16
    lin_value_heads: int = 32
    lin_key_dim: int = 128
    lin_value_dim: int = 128
    conv_kernel: int = 4
    num_experts: int = 512
    experts_per_token: int = 10
    expert_width: int = 512
    shared_width: int = 512
    rms_eps: float = 1e-6
    max_seq_len: int = 262144
    # the experts whose weights this chip holds, as a tuple of ids in the
    # order they are stacked in the parameters (None: all of them); the
    # router always runs over all `num_experts`
    experts_held: Optional[Tuple[int, ...]] = None
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    inference_dtype: Optional[Any] = None

    model_kind = "qwen3_next"

    def __post_init__(self):
        super().__post_init__()
        if self.lin_value_heads % self.lin_key_heads \
                or self.num_heads % self.num_kv_heads:
            raise ValueError("Qwen3NextLM: value heads must be a multiple "
                             "of key heads, query heads of K/V heads")

    # ----------------------------------------------------------- geometry
    def is_full(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @property
    def page_layers(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def conv_channels(self) -> int:
        return 2 * self.lin_key_heads * self.lin_key_dim \
            + self.lin_value_heads * self.lin_value_dim

    def cache_spec(self) -> dict:
        """What the serving engine holds for this model
        (:class:`~apex_tpu.serving.kv_cache.CacheSpec`): the full-attention
        layers pages, the linear layers a float32 ``recurrent`` matrix a
        value head and the convolution's last inputs (``conv``, the
        engine's half dtype), and no layer both."""
        n_lin = self.num_layers - self.page_layers
        return {"page_layers": self.page_layers,
                "kv_heads": self.num_kv_heads, "head_dim": self.head_dim,
                "state": [("recurrent", n_lin,
                           (self.lin_value_heads, self.lin_key_dim,
                            self.lin_value_dim), jnp.float32),
                          ("conv", n_lin, (self.conv_kernel - 1,
                                           self.conv_channels), None)],
                "counter_layers": self.num_layers,
                "num_experts": self.num_experts}

    # ---------------------------------------------------------- sublayers
    def _linear_attention(self, u, lp, cdt, *, index, rec, tail, addr,
                          mask):
        """``u [B, S, H]`` (normed, compute dtype) -> ``(out [B, S, H],
        recurrent block (or the state the batch's rows leave), conv tail
        ``[B, K - 1, C]``)``. ``rec`` is the engine's whole block (with
        ``addr``) or None (a sequence from zeros); ``mask [B, S]`` the
        positions that are real tokens (None: all)."""
        from apex_tpu.kernels import gated_delta as gd

        B, S, _ = u.shape
        nk, nv = self.lin_key_heads, self.lin_value_heads
        dk, dv = self.lin_key_dim, self.lin_value_dim
        kw, vw = nk * dk, nv * dv
        with jax.named_scope("gdn.proj"):
            qkvz = jnp.dot(u, jnp.asarray(lp["w_qkvz"], cdt))
            x, z = qkvz[..., :2 * kw + vw], qkvz[..., 2 * kw + vw:]
            ba = einsum32("bsh,hn->bsn", u, jnp.asarray(lp["w_ba"], cdt))
            beta = jax.nn.sigmoid(ba[..., :nv])
            g = -jnp.exp(_f32(lp["a_log"])) * jax.nn.softplus(
                ba[..., nv:] + _f32(lp["dt_bias"]))
            if mask is not None:                    # padding writes nothing
                beta = jnp.where(mask[..., None], beta, 0.0)
                g = jnp.where(mask[..., None], g, 0.0)
        with jax.named_scope("gdn.conv"):
            # depthwise, causal; the tail is the last K - 1 REAL inputs
            c, new_tail = short_conv(x, tail, lp["conv_w"], mask)
            c = jax.nn.silu(c)
            q = c[..., :kw].reshape(B, S, nk, dk)
            k = c[..., kw:2 * kw].reshape(B, S, nk, dk)
            v = c[..., 2 * kw:].reshape(B, S, nv, dv)
            unit = lambda t: t * jax.lax.rsqrt(                 # noqa: E731
                jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
            q = jnp.repeat(unit(q) * (1.0 / np.sqrt(dk)), nv // nk, axis=2)
            k = jnp.repeat(unit(k), nv // nk, axis=2)
        if rec is None:
            with jax.named_scope("gdn.chunk"):
                o, rec = gd.gated_delta_chunk_reference(
                    q, k, v, g, beta, jnp.zeros((B, nv, dk, dv)))
        elif S == 1:
            with jax.named_scope("gdn.step"):
                o, rec = gd.gated_delta_step(
                    rec, index, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                    beta[:, 0], addr.active)
                o = o[:, None]
        else:
            if B != 1:
                raise ValueError("a chunk of the linear-attention state "
                                 "is one slot's: batch 1")
            with jax.named_scope("gdn.chunk"):
                o, rec = gd.gated_delta_chunk(
                    rec, index, addr.slot, addr.fresh, q[0], k[0], v[0],
                    g[0], beta[0])
                o = o[None]
        with jax.named_scope("gdn.gate"):
            y = _rms(o, lp["norm"], self.rms_eps, centred=False) \
                * jax.nn.silu(_f32(z).reshape(B, S, nv, dv))
            out = jnp.dot(jnp.asarray(y.reshape(B, S, vw), cdt),
                          jnp.asarray(lp["w_out"], cdt))
        return out, rec, new_tail

    def _full_attention(self, u, lp, cdt, *, index, cache, positions):
        """``u [B, S, H]`` -> ``(out [B, S, H], pools | None)``."""
        B, S, _ = u.shape
        nq, nk, d = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope("attn.proj"):
            qg = jnp.dot(u, jnp.asarray(lp["wq"], cdt)).reshape(
                B, S, nq, 2 * d)
            q, gate = qg[..., :d], qg[..., d:]
            k = jnp.dot(u, jnp.asarray(lp["wk"], cdt)).reshape(B, S, nk, d)
            v = jnp.dot(u, jnp.asarray(lp["wv"], cdt)).reshape(B, S, nk, d)
            q = _rms(q, lp["q_norm"], self.rms_eps)
            k = _rms(k, lp["k_norm"], self.rms_eps)
            pos = positions_of(positions, B, S)
            rot = int(d * self.partial_rotary_factor)
            q = jnp.asarray(rotary(q, pos, self.rope_theta, rot), cdt)
            k = jnp.asarray(rotary(k, pos, self.rope_theta, rot), cdt)
            q, k, v = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))
        with jax.named_scope("attn.attn"):
            ctx, aux = paged_attend(q, k, v, cache, positions, index,
                               1.0 / np.sqrt(d))
        with jax.named_scope("attn.gate"):
            ctx = _f32(jnp.moveaxis(ctx, 1, 2)) * jax.nn.sigmoid(_f32(gate))
            out = jnp.dot(jnp.asarray(ctx.reshape(B, S, nq * d), cdt),
                          jnp.asarray(lp["wo"], cdt))
        return out, aux

    def _experts(self, u, lp, cdt, valid):
        """``u [B, S, H]`` (normed, compute dtype) -> ``(y [B, S, H]
        float32, each token's experts [B, S, k], tokens per expert [E]
        int32 over the ``valid [B, S]`` tokens)``."""
        B, S, H = u.shape
        kk = self.experts_per_token
        flat = u.reshape(B * S, H)
        with jax.named_scope("moe.router"):
            p = jax.nn.softmax(jnp.dot(_f32(flat), _f32(lp["router"]["w"])),
                               -1)                              # [T, E]
            top, choice = jax.lax.top_k(p, kk)
            weights = top / jnp.sum(top, -1, keepdims=True)
            choice = choice.astype(jnp.int32)
        y, counts = held_experts(
            flat, weights, choice, lp["experts"], cdt,
            num_experts=self.num_experts, experts_held=self.experts_held,
            valid=valid)
        with jax.named_scope("moe.shared"):
            sp = lp["shared"]
            ys = gated_mlp(flat, sp["w_gate_up"], sp["w_down"], cdt)
            gate = jax.nn.sigmoid(einsum32(
                "th,h->t", flat, jnp.asarray(sp["w_gate"], cdt)))
            y = y + gate[:, None] * ys
        return y.reshape(B, S, H), choice.reshape(B, S, kk), counts

    # -------------------------------------------------------------- model
    def _layer_spec(self, full: bool):
        """``((module, ((leaf, shape, init), ...)), ...)`` of one layer
        of a kind: the reference's ``layer_shapes`` as parameter paths."""
        H, E, F, Fs = self.hidden, self.num_experts, self.expert_width, \
            self.shared_width
        G = E if self.experts_held is None else len(self.experts_held)
        if full:
            nq, nk, d = self.num_heads, self.num_kv_heads, self.head_dim
            mixer = ("attn", (("wq", (H, nq * 2 * d), "lecun"),
                              ("wk", (H, nk * d), "lecun"),
                              ("wv", (H, nk * d), "lecun"),
                              ("q_norm", (d,), "zeros"),
                              ("k_norm", (d,), "zeros"),
                              ("wo", (nq * d, H), "lecun")))
        else:
            nv, dv = self.lin_value_heads, self.lin_value_dim
            C = self.conv_channels
            mixer = ("gdn", (("w_qkvz", (H, C + nv * dv), "lecun"),
                             ("w_ba", (H, 2 * nv), "lecun"),
                             ("conv_w", (C, self.conv_kernel), "ones"),
                             ("a_log", (nv,), "zeros"),
                             ("dt_bias", (nv,), "zeros"),
                             ("norm", (dv,), "ones"),
                             ("w_out", (nv * dv, H), "lecun")))
        return (
            ("attn_norm", (("scale", (H,), "zeros"),)),
            mixer,
            ("moe_norm", (("scale", (H,), "zeros"),)),
            ("router", (("w", (H, E), "lecun"),)),
            ("experts", (("w_gate_up", (G, H, 2 * F), "lecun"),
                         ("w_down", (G, F, H), "lecun"))),
            ("shared", (("w_gate_up", (H, 2 * Fs), "lecun"),
                        ("w_down", (Fs, H), "lecun"),
                        ("w_gate", (H,), "normal02"))),
        )

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, cache=None,
                 positions=None, state=None, addr=None, n_valid=None,
                 valid=None):
        if train:
            raise NotImplementedError(
                "Qwen3NextLM is a serving model: neither the recurrence "
                "nor the drop-nothing expert layer has a backward here")
        if cache is not None and (len(cache) != 3 or state is None):
            raise NotImplementedError(
                "Qwen3NextLM: the paged cache (k_pool, v_pool, page_table) "
                "with the engine's state blocks only")
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
        # the convolution tails are a small block: the batch's rows are
        # read once and written once (SlotAddr), like ZayaLM's
        tails = addr.read(state["conv"]) if serving else jnp.zeros(
            (self.num_layers - self.page_layers, B, self.conv_kernel - 1,
             self.conv_channels), cdt)
        new_tails, counts = [], []
        pools = None if cache is None else (cache[0], cache[1])
        specs = {full: self._layer_spec(full) for full in (False, True)}
        for i in range(self.num_layers):
            full = self.is_full(i)
            lp = Groups(specs[full], self.param_dtype, name=f"layer_{i}")()
            u = jnp.asarray(_rms(x, lp["attn_norm"]["scale"], self.rms_eps),
                            cdt)
            if full:
                out, aux = self._full_attention(
                    u, lp["attn"], cdt,
                    index=i // self.full_attention_interval,
                    cache=None if pools is None else pools + (cache[2],),
                    positions=positions)
                if pools is not None:
                    pools = aux
            else:
                li = i - i // self.full_attention_interval
                out, new_rec, tail = self._linear_attention(
                    u, lp["gdn"], cdt, index=li, rec=rec, tail=tails[li],
                    addr=addr, mask=mask)
                if serving:
                    rec = new_rec
                new_tails.append(tail)
            x = jnp.asarray(_f32(x) + _f32(out), cdt)
            u = jnp.asarray(_rms(x, lp["moe_norm"]["scale"], self.rms_eps),
                            cdt)
            y, choice, cnt = self._experts(u, lp, cdt, valid)
            # each token's experts, layer by layer, for whoever asks
            # (``mutable=["intermediates"]``: the tests; a no-op otherwise)
            self.sow("intermediates", "expert_choice", choice)
            counts.append(cnt)
            x = jnp.asarray(_f32(x) + y, cdt)
        norm_f = Leaves((("scale", (self.hidden,), "zeros"),),
                         self.param_dtype, name="norm_f")()["scale"]
        head = Leaves((("kernel", (self.hidden, self.vocab_size),
                         "lecun"),), self.param_dtype,
                       name="head")()["kernel"]
        if n_valid is not None:
            x = last_valid(x, n_valid)[:, None]             # [B, 1, H]
        x = jnp.asarray(_rms(x, norm_f, self.rms_eps), cdt)
        # the head is its own matrix; float32 logits from a half product
        logits = einsum32("bsh,hv->bsv", x, jnp.asarray(head, cdt))
        if pools is None:
            return logits
        blocks = {"recurrent": rec,
                  "conv": addr.write(state["conv"], jnp.stack(new_tails))}
        return logits, pools + (blocks, jnp.stack(counts))
