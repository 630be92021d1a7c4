"""ZAYA1-style causal LM (Zyphra) — the serving tier's second architecture.

Every layer is an attention sublayer and an expert sublayer, neither of
which :mod:`apex_tpu.models.transformer_lm` can express:

- **Compressed convolutional attention with grouped heads** (CCA, the
  CCGQA form of arXiv:2510.04476): queries and keys are projected into a
  latent of ``num_heads + num_kv_heads`` heads, mixed over TIME by two
  causal convolutions of kernel 2 (one depthwise, one grouped by head),
  corrected by the q-k mean of the unconvolved values, L2-normalised per
  head (keys times a learned temperature), rotated over the first
  ``partial_rotary_factor`` of each head by absolute position, and
  attended with ``num_heads // num_kv_heads`` query heads per K/V head.
  K/V head 0 carries the current token's values, head 1 the previous
  token's (the value shift). Kernels of width 2 and a shift of 1 mean a
  token's step needs three vectors of the token before it: the latent
  ``z``, the first convolution's output ``c1`` and the second value
  projection ``u Wv2`` — :attr:`ZayaLM.slot_state_width` values per layer
  that the serving engine keeps PER SLOT beside the paged K/V
  (:class:`~apex_tpu.serving.kv_cache.SlotState`).
- **A drop-nothing top-1 expert layer**: an MLP router over a
  low-rank router state that is averaged over depth (each layer adds
  ``gamma`` times the layer before's), float32 softmax, a balancing bias
  that takes part in the choice only, and
  :func:`~apex_tpu.transformer.moe.dropless_top1_experts` over the
  experts this chip holds (``experts_held``).
- **Scaled residuals**: ``x <- (x + b_r) * s_r + (f(rmsnorm(x)) + b_h) *
  s_h`` around each sublayer.

The equations, and which of their details the published configuration
does not fix ("assumed"), are in ``benchmarks/lib/reference_zaya.py``, the
float32 reference this module is tested against. Compute is bfloat16
(``inference_dtype``) with float32 norms, convolutions' sums,
normalisation, rotary, router, softmax and logits.

Serving modes (``serving.Engine`` drives them; all with ``train=False``):

- **paged decode / aligned chunk**: ``cache=(k_pool, v_pool, page_table)``
  + ``positions`` + ``state`` ``[layers, B, W]`` (what each row's
  previous token left; zeros for a row that starts) [+ ``n_valid``
  ``[B]``, the rows' count of real tokens in a padded chunk]. Returns
  ``(logits, (k_pool, v_pool, state', tokens_per_expert))`` — the pools
  written in place as :class:`~apex_tpu.models.transformer_lm
  .SelfAttention` does, ``state'`` what position ``n_valid - 1`` leaves,
  logits of THAT position only (``[B, 1, V]``) when ``n_valid`` is given.
- plain forward: logits ``[B, S, V]``.

Rotary positions are absolute in every mode: ``positions[b] + s``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.kernels.layer_norm import rms_norm_reference
from apex_tpu.models.lm_layers import (Groups, Leaves, einsum32, last_valid,
                                       paged_attend, positions_of, rotary,
                                       shift)

__all__ = ["ZayaLM"]


class ZayaLM(nn.Module):
    """The model; see the module docstring. Sizes default to ZAYA1-8B's."""

    vocab_size: int = 262272
    hidden: int = 2048
    num_layers: int = 40
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    num_experts: int = 16
    expert_width: int = 2048
    router_width: int = 256
    cca_time0: int = 2
    cca_time1: int = 2
    rope_theta: float = 5e6
    partial_rotary_factor: float = 0.5
    rms_eps: float = 1e-5
    max_seq_len: int = 131072
    # the experts whose weights this chip holds, as a tuple of ids in the
    # order they are stacked in the parameters (None: all of them); the
    # router always runs over all `num_experts`
    experts_held: Optional[Tuple[int, ...]] = None
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    inference_dtype: Optional[Any] = None

    model_kind = "zaya"

    def __post_init__(self):
        super().__post_init__()
        if self.cca_time0 != 2 or self.cca_time1 != 2:
            raise NotImplementedError(
                "ZayaLM: convolutions of kernel 2 only (one previous "
                f"position of state a slot); got cca_time0="
                f"{self.cca_time0}, cca_time1={self.cca_time1}")
        if self.num_kv_heads != 2 or self.num_heads % self.num_kv_heads:
            raise NotImplementedError(
                "ZayaLM: the value shift is defined for two K/V heads "
                f"(current and previous token); got {self.num_kv_heads}")

    @property
    def latent_heads(self) -> int:
        return self.num_heads + self.num_kv_heads

    @property
    def slot_state_width(self) -> int:
        """Values a slot keeps per layer between steps: ``z`` and ``c1``
        of the last position (``latent_heads * head_dim`` each) and its
        ``u Wv2`` (``head_dim``)."""
        return 2 * self.latent_heads * self.head_dim + self.head_dim

    def cache_spec(self) -> dict:
        """What the serving engine holds for this model
        (:class:`~apex_tpu.serving.kv_cache.CacheSpec`): every layer
        pages of ``num_kv_heads`` x ``head_dim`` and one ``rows`` block
        of :attr:`slot_state_width` values in the engine's half dtype."""
        return {"page_layers": self.num_layers,
                "kv_heads": self.num_kv_heads, "head_dim": self.head_dim,
                "state": [("rows", self.num_layers,
                           (self.slot_state_width,), None)],
                "counter_layers": self.num_layers,
                "num_experts": self.num_experts}

    # ------------------------------------------------------------ sublayers
    def _attention(self, u, lp, cdt, *, layer, cache, positions, prev,
                   n_valid):
        """``u [B, S, H]`` (normed, compute dtype) -> ``(out [B, S, H],
        cache aux, state row [B, W])``."""
        B, S, _ = u.shape
        nq, nk, d = self.num_heads, self.num_kv_heads, self.head_dim
        nz, G = nq + nk, nq // nk
        zw = nz * d
        with jax.named_scope("cca.conv"):
            mm = lambda w: jnp.dot(u, jnp.asarray(w, cdt))      # noqa: E731
            qt, kt = mm(lp["wq"]), mm(lp["wk"])
            v1, v2 = mm(lp["wv1"]), mm(lp["wv2"])               # [B, S, d]
            z = jnp.concatenate([qt, kt], -1)                   # [B, S, zw]
            z_prev, c1_prev = prev[:, :zw], prev[:, zw:2 * zw]
            v2_prev = prev[:, 2 * zw:]
            f32 = lambda t: jnp.asarray(t, jnp.float32)         # noqa: E731
            a = f32(lp["conv0_w"])                              # [zw, 2]
            c1 = jnp.asarray(
                a[:, 0] * f32(shift(z, z_prev)) + a[:, 1] * f32(z)
                + f32(lp["conv0_b"]), cdt)
            w1 = jnp.asarray(lp["conv1_w"], cdt)                # [nz, 2, d, d]
            heads = lambda t: t.reshape(B, S, nz, d)            # noqa: E731
            c2 = einsum32("bshd,hde->bshe", heads(shift(c1, c1_prev)),
                           w1[:, 0]) \
                + einsum32("bshd,hde->bshe", heads(c1), w1[:, 1]) \
                + f32(lp["conv1_b"])
            qh = f32(qt).reshape(B, S, nq, d)
            kh = f32(kt).reshape(B, S, nk, d)
            m_q = 0.5 * (qh + jnp.repeat(kh, G, axis=2))
            m_k = m_q.reshape(B, S, nk, G, d).mean(3)
            q, k = c2[:, :, :nq] + m_q, c2[:, :, nq:] + m_k
            norm = lambda t: t * (np.sqrt(d) * jax.lax.rsqrt(   # noqa: E731
                jnp.sum(jnp.square(t), -1, keepdims=True)))
            q = norm(q)
            k = norm(k) * jnp.exp(f32(lp["tau"]))[None, None, :, None]
            pos = positions_of(positions, B, S)
            rot = int(d * self.partial_rotary_factor)
            q = jnp.asarray(rotary(q, pos, self.rope_theta, rot), cdt)
            k = jnp.asarray(rotary(k, pos, self.rope_theta, rot), cdt)
            v = jnp.stack([v1, shift(v2, v2_prev)], 2)         # [B, S, 2, d]
            q, k, v = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))
            row = jnp.concatenate([last_valid(z, n_valid),
                                   last_valid(c1, n_valid),
                                   last_valid(v2, n_valid)], -1)
        scale = 1.0 / np.sqrt(d)
        with jax.named_scope("cca.attn"):
            ctx, aux = paged_attend(q, k, v, cache, positions, layer, scale)
            ctx = jnp.moveaxis(ctx, 1, 2).reshape(B, S, nq * d)
            out = jnp.dot(jnp.asarray(ctx, cdt), jnp.asarray(lp["wo"], cdt))
        return out, aux, row

    def _experts(self, u, r_prev, rp, ep, cdt, valid):
        """``u [B, S, H]`` (normed, compute dtype), the layer before's
        router state ``r_prev [B, S, R]`` -> ``(y [B, S, H], router state,
        each token's expert [B, S], tokens per expert [E] int32 over the
        ``valid [B, S]`` tokens)``."""
        from apex_tpu.transformer.moe import dropless_top1_experts

        B, S, H = u.shape
        f32 = lambda t: jnp.asarray(t, jnp.float32)             # noqa: E731
        with jax.named_scope("moe.router"):
            r = jnp.dot(f32(u), f32(rp["wd"])) + f32(rp["bd"]) \
                + f32(rp["gamma"]) * r_prev
            h = jax.nn.gelu(jnp.dot(r, f32(rp["w1"])) + f32(rp["b1"]),
                            approximate=False)
            h = jax.nn.gelu(jnp.dot(h, f32(rp["w2"])) + f32(rp["b2"]),
                            approximate=False)
            p = jax.nn.softmax(jnp.dot(h, f32(rp["w3"])), -1)   # [B, S, E]
            choice = jnp.argmax(p + f32(rp["bias_c"]), -1).astype(jnp.int32)
            gate = jnp.take_along_axis(p, choice[..., None], -1)[..., 0]
        y, _ = dropless_top1_experts(
            u.reshape(B * S, H), gate.reshape(-1), choice.reshape(-1),
            jnp.asarray(ep["w_gate_up"], cdt), jnp.asarray(ep["w_down"], cdt),
            num_experts=self.num_experts, experts_held=self.experts_held,
            out_dtype=jnp.float32)
        counts = jnp.zeros((self.num_experts,), jnp.int32).at[
            choice.reshape(-1)].add(valid.reshape(-1).astype(jnp.int32))
        return y.reshape(B, S, H), r, choice, counts

    # ---------------------------------------------------------------- model
    def _layer_spec(self):
        """``((module, ((leaf, shape, init), ...)), ...)`` of one layer:
        the reference's ``layer_shapes`` as parameter paths."""
        H, d, nq, nk = self.hidden, self.head_dim, self.num_heads, \
            self.num_kv_heads
        nz, R, E, F = nq + nk, self.router_width, self.num_experts, \
            self.expert_width
        G = E if self.experts_held is None else len(self.experts_held)
        res = (("s_r", (H,), "ones"), ("b_r", (H,), "zeros"),
               ("s_h", (H,), "ones"), ("b_h", (H,), "zeros"))
        return (
            ("attn_norm", (("scale", (H,), "ones"),)),
            ("attn", (("wq", (H, nq * d), "lecun"),
                      ("wk", (H, nk * d), "lecun"),
                      ("wv1", (H, d), "lecun"), ("wv2", (H, d), "lecun"),
                      ("wo", (nq * d, H), "lecun"),
                      ("conv0_w", (nz * d, 2), "ones"),
                      ("conv0_b", (nz * d,), "zeros"),
                      ("conv1_w", (nz, 2, d, d), "lecun"),
                      ("conv1_b", (nz, d), "zeros"), ("tau", (nk,), "zeros"))),
            ("attn_res", res),
            ("moe_norm", (("scale", (H,), "ones"),)),
            ("router", (("wd", (H, R), "lecun"), ("bd", (R,), "zeros"),
                        ("gamma", (R,), "zeros"), ("w1", (R, R), "lecun"),
                        ("b1", (R,), "zeros"), ("w2", (R, R), "lecun"),
                        ("b2", (R,), "zeros"), ("w3", (R, E), "lecun"),
                        ("bias_c", (E,), "zeros"))),
            ("experts", (("w_gate_up", (G, H, 2 * F), "lecun"),
                         ("w_down", (G, F, H), "lecun"))),
            ("moe_res", res),
        )

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, cache=None,
                 positions=None, state=None, addr=None, n_valid=None,
                 valid=None):
        if train:
            raise NotImplementedError(
                "ZayaLM is a serving model: the expert layer's training "
                "path (capacity, balancing loss, expert-parallel exchange) "
                "is transformer.moe.MoEMLP's and is not wired to it")
        if cache is not None and len(cache) != 3:
            raise NotImplementedError(
                "ZayaLM: the paged cache (k_pool, v_pool, page_table) only")
        from apex_tpu.amp.autocast import resolve_dtype
        cdt = resolve_dtype(self.dtype, "linear", jnp.float32)
        if self.inference_dtype is not None:
            cdt = self.inference_dtype
        B, S = tokens.shape
        W = self.slot_state_width
        emb = Leaves((("embedding", (self.vocab_size, self.hidden),
                         "normal02"),), self.param_dtype,
                       name="wte")()["embedding"]
        layer_spec = self._layer_spec()
        x = jnp.asarray(emb[tokens], cdt)
        # the serving engine hands the state blocks whole with their
        # addressing (kv_cache.SlotAddr); a caller without slots hands
        # the batch's own rows, or nothing (a sequence starts from zeros)
        blocks = state if isinstance(state, dict) else None
        if blocks is not None:
            state = addr.read(blocks["rows"])
        elif state is None:
            state = jnp.zeros((self.num_layers, B, W), cdt)
        if valid is None:
            valid = jnp.ones((B, S), bool) if n_valid is None else (
                jnp.arange(S, dtype=jnp.int32)[None]
                < jnp.asarray(n_valid, jnp.int32)[:, None])
        r = jnp.zeros((B, S, self.router_width), jnp.float32)
        rows, counts = [], []
        pools = None if cache is None else (cache[0], cache[1])

        def residual(x, fx, rp):
            f32 = lambda t: jnp.asarray(t, jnp.float32)         # noqa: E731
            return jnp.asarray(
                (f32(x) + f32(rp["b_r"])) * f32(rp["s_r"])
                + (f32(fx) + f32(rp["b_h"])) * f32(rp["s_h"]), cdt)

        for i in range(self.num_layers):
            lp = Groups(layer_spec, self.param_dtype, name=f"layer_{i}")()
            # float32 inside, back in the compute dtype
            u = rms_norm_reference(x, lp["attn_norm"]["scale"], self.rms_eps)
            out, aux, row = self._attention(
                u, lp["attn"], cdt, layer=i,
                cache=None if pools is None else pools + (cache[2],),
                positions=positions, prev=jnp.asarray(state[i], cdt),
                n_valid=n_valid)
            if pools is not None:
                pools = aux
            rows.append(row)
            x = residual(x, out, lp["attn_res"])
            u = rms_norm_reference(x, lp["moe_norm"]["scale"], self.rms_eps)
            y, r, choice, cnt = self._experts(u, r, lp["router"],
                                              lp["experts"], cdt, valid)
            # each token's expert, layer by layer, for whoever asks
            # (``mutable=["intermediates"]``: the tests; a no-op otherwise)
            self.sow("intermediates", "expert_choice", choice)
            counts.append(cnt)
            x = residual(x, y, lp["moe_res"])
        norm_f = Leaves((("scale", (self.hidden,), "ones"),),
                         self.param_dtype, name="norm_f")()["scale"]
        if n_valid is not None:
            x = last_valid(x, n_valid)[:, None]             # [B, 1, H]
        x = rms_norm_reference(x, norm_f, self.rms_eps)
        # tied head, float32 logits: a bf16 x bf16 product accumulated in
        # float32 (the embedding is never widened whole)
        logits = einsum32("bsh,vh->bsv", x, jnp.asarray(emb, cdt))
        new_state = jnp.stack(rows).astype(state.dtype)      # [L, B, W]
        if blocks is not None:
            new_state = {"rows": addr.write(blocks["rows"], new_state)}
        counts = jnp.stack(counts)                           # [L, E]
        if pools is not None:
            return logits, pools + (new_state, counts)
        return logits
