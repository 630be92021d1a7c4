"""GPT-style causal transformer LM — the framework's config-3 workload.

The reference has no LM of its own; its transformer pieces (FusedLayerNorm,
fused softmax/xentropy kernels, FusedAdam) are exercised by external Megatron
recipes (BASELINE.json config 3: "FusedLayerNorm + FusedAdam transformer LM
(WikiText-2)"). This model is the standalone equivalent, assembled entirely
from the framework's own fused tiers:

- pre-LN blocks with :class:`apex_tpu.normalization.FusedLayerNorm`
- attention via :func:`apex_tpu.kernels.flash_attention.flash_attention`
  (Pallas, causal tile-skip — replaces N8/N11's fused softmax+MHA kernels)
- MLP via :func:`apex_tpu.fused_dense.fused_dense_gelu_dense_function`'s
  fp32-epilogue GELU semantics
- LM loss via :mod:`apex_tpu.kernels.xentropy` in the recipes.

TPU-first choices: bf16 compute with fp32 params (amp O2 shape), weights kept
as flax Dense kernels (MXU-layout friendly), embedding output scaled and tied
to the LM head (standard GPT weight tying — one less HBM-resident vocab
matrix).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.kernels.decode_attention import _pool_write_tokens
from apex_tpu.kernels.flash_attention import flash_attention
from apex_tpu.normalization import FusedLayerNorm

__all__ = ["TransformerLM", "TransformerBlock", "create_lm"]


def _lora_term(x, pair, alpha, adapter_ids, out_dtype):
    """The gathered multi-tenant LoRA epilogue term for one GEMM site:
    ``(x @ A[ids]) @ B[ids] * alpha[ids]`` — the serving engine's
    stacked-adapter residual (:mod:`apex_tpu.serving.lora`).

    ``pair`` is the site's arena slice ``(A [rows, in, rank],
    B [rows, rank, out])`` and ``adapter_ids [B]`` names each batch
    row's arena row — a TRACED operand, so heterogeneous adapters ride
    one compiled program and the adapter id is data, never a trace
    key. Math runs in fp32 (the epilogue-accumulator convention every
    fused tier here shares) and the result is cast to the base GEMM's
    output dtype. Arena row 0 is all-zero with ``alpha[0] == 0``: a
    base (adapter-free) row's term is exactly ``+0.0`` per element,
    which fp32/bf16 addition leaves value-identical — the
    ``fault_bias`` pin, reapplied."""
    a, b = pair
    ids = jnp.asarray(adapter_ids, jnp.int32)
    h = jnp.einsum("bsh,bhr->bsr", jnp.asarray(x, jnp.float32),
                   jnp.asarray(a, jnp.float32)[ids])
    t = jnp.einsum("bsr,bro->bso", h,
                   jnp.asarray(b, jnp.float32)[ids])
    t = t * jnp.asarray(alpha, jnp.float32)[ids][:, None, None]
    return jnp.asarray(t, out_dtype)


def _dense_factory(weight_quant: bool, dense_dtype, param_dtype):
    """The one Dense-site constructor both block modules share: plain
    ``nn.Dense`` on the default path (kept verbatim — the bitwise
    baseline), ``QuantDense`` (int8 kernel, per-output-channel scale
    in the epilogue) when the engine enabled weight quantization —
    same param paths either way."""
    if weight_quant:
        from apex_tpu.serving.weight_quant import QuantDense

        def _dense(features, name):
            return QuantDense(features, dtype=dense_dtype,
                              param_dtype=param_dtype, name=name)
    else:
        def _dense(features, name):
            return nn.Dense(features, dtype=dense_dtype,
                            param_dtype=param_dtype, name=name)
    return _dense


def _pool_write_pages(pool, layer, page_ids, new):
    """Write whole pages into the stacked paged pool, in place:
    ``new`` ``[B, h, n * page_len, d]`` (storage dtype) fills pages
    ``page_ids`` ``[B, n]`` of ``layer``, each stored ``[h, d,
    page_len]``."""
    B, h, S, d = new.shape
    n = page_ids.shape[1]
    new = new.reshape(B, h, n, S // n, d).transpose(0, 2, 1, 4, 3)
    return pool.at[layer, page_ids].set(new)          # [B, n, h, d, pl]


class SelfAttention(nn.Module):
    """Causal MHA with these modes sharing one set of weights:

    - **train/eval** (default): full-sequence flash attention.
    - ``return_kv=True``: same forward, additionally returning this
      layer's ``(k, v)`` ``[B, h, S, d]``. Its one use is the int8 KV
      tier's calibration forward
      (:meth:`apex_tpu.serving.kv_quant.KVQuantConfig.resolve_scales`).
    - **decode** (``cache=(k_pool, v_pool, page_table)`` + ``positions``
      + the static ``layer``, S == 1): the token's K/V is written into
      the pool at ``positions[b]`` and attention runs against the cached
      prefix, length-masked with fp32 accumulation - ONE call,
      :func:`apex_tpu.kernels.decode_attention.paged_decode_attention`
      handed the new K/V: its kernel edits the row's last page in the
      VMEM it fetched it into and copies that page back, so the program
      has no gather, select or scatter of pages in front of it.
    - **chunked prefill** (same ``cache``, S > 1): S consecutive prompt
      tokens starting at cache position ``positions[b]`` — their K/V is
      written at ``[positions[b], positions[b] + S)`` and each attends
      the cached prefix up to and including itself (write-then-attend,
      shifted-causal,
      :func:`apex_tpu.kernels.prefill_attention.paged_prefill_attention`).

      Both run over the
      serving engine's paged pool, which arrives WHOLE — the stacked
      ``[layers, num_pages, h, d, page_len]`` K and V that every layer
      shares — and is WRITTEN IN PLACE: K/V land in the pool itself at
      ``(layer, page_table[b, pos // page_len], :, :, pos % page_len)``
      and attention reads the pool itself through the table via the
      ``paged_*`` kernels, the layer one more block index of
      their page DMA. No layer is sliced out of the pool and nothing
      pool-shaped is stacked, transposed or copied, so the buffer the
      engine donates is the buffer it gets back. The returned aux is
      the UPDATED POOL pair (pages are shared across rows), not per-row
      caches; chunk writes must be page-aligned and whole-page (the
      engine enforces ``chunk_len % page_len == 0``).
    - **unaligned append** (``unaligned_append=True``, ``S > 1``):
      the speculative-verify write shape — a SMALL block of S draft
      tokens landing at an arbitrary (non-page-aligned) cache offset
      mid-generation, where the whole-page chunk write cannot apply.
      Each of the S positions scatters individually by page id (the
      decode write, unrolled over the static S), then the same
      shifted-causal paged prefill attention runs. The pages written
      are always the slot's own: generation positions sit past any
      copy-on-write share, so unaligned writes can never touch a
      shared page.

    ``inference_dtype`` is the decode path's storage/compute dtype: when
    set, Q/K/V leave the qkv GEMM in that dtype (normally the amp half —
    pure-bf16 decode needs no fp32 master weights anywhere); when None
    the training-policy ``dense_dtype`` governs, as before.

    - **quantized cache** (``kv_scales=(k_scale, v_scale)``, each
      ``[heads]`` fp32 for this layer — the serving engine's
      ``kv_quant`` int8 storage tier): every cache WRITE above
      quantizes the fresh K/V symmetrically per head
      (:mod:`apex_tpu.serving.kv_quant`) before storing, and every
      attention READ passes the scales into the kernels, which
      dequantize in-kernel (int8 block load → scale multiply → the
      unchanged online-softmax fp32 math). ``kv_scales=None`` (the
      default) leaves every mode byte-identical to the bf16 tier.

    **Tensor parallelism** (``tp_axis``/``tp_size``, set by
    ``serving.Engine(mesh=...)`` and meaningful only inside a
    ``shard_map`` over that axis): the module becomes ONE SHARD of a
    Megatron-style split — the qkv projection is column-parallel over
    ``num_heads // tp_size`` local heads, attention (cached or not)
    runs entirely over the local heads (the KV cache/pool arrives
    heads-sharded, so nothing here crosses ICI), and the row-parallel
    output projection's partial sum is ``psum``-reduced over
    ``tp_axis``. The projection BIAS is added per shard inside the
    Dense and the param sharder value-scales it by ``1/tp_size``
    (:mod:`apex_tpu.serving.sharding`), so the psum restores it exactly
    once. ``tp_size=1`` (the default) leaves every shape and op
    untouched.

    **Quantized weights** (``weight_quant=True``, set by
    ``serving.Engine(weight_quant=...)``): the qkv and proj GEMMs run
    over int8 kernels through
    :class:`~apex_tpu.serving.weight_quant.QuantDense` — the
    per-output-channel fp32 scale multiplies the accumulator in the
    epilogue, so dequantized weights never materialise. The default
    (False) keeps ``nn.Dense`` on the trace path verbatim.
    """

    hidden: int
    num_heads: int
    dropout: float = 0.0
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    inference_dtype: Optional[Any] = None
    tp_axis: Optional[str] = None
    tp_size: int = 1
    weight_quant: bool = False

    @nn.compact
    def __call__(self, x, train: bool, cache=None, positions=None,
                 return_kv: bool = False, unaligned_append: bool = False,
                 kv_scales=None, lora=None, adapter_ids=None,
                 layer: Optional[int] = None):
        # dtype=None → O1 engine: GEMMs are FP16_FUNCS 'linear'
        from apex_tpu.amp.autocast import resolve_dtype
        dense_dtype = resolve_dtype(self.dtype, "linear", jnp.float32)
        if self.inference_dtype is not None and not train:
            dense_dtype = self.inference_dtype
        _dense = _dense_factory(self.weight_quant, dense_dtype,
                                self.param_dtype)
        B, S, H = x.shape
        d = self.hidden // self.num_heads
        # tensor-parallel shard: this module computes heads // tp local
        # heads over the full (replicated) residual stream; the param
        # sharder hands it the matching qkv/proj kernel slices
        heads = self.num_heads // self.tp_size
        qkv = _dense(3 * heads * d, "qkv")(x)
        if lora is not None:
            # column-parallel site: x and A replicated, B output-split
            # (the arena stores qkv's B head-group-permuted, so this
            # shard's slice lands on its own columns)
            qkv = qkv + _lora_term(x, lora["qkv"], lora["alpha"],
                                   adapter_ids, qkv.dtype)
        # one transpose to [3, B, h, S, d], then three views — no
        # throwaway generator re-indexing qkv[:, :, i] three times
        qkv = qkv.reshape(B, S, 3, heads, d).transpose(2, 0, 3, 1, 4)
        q, k, v = qkv[0], qkv[1], qkv[2]             # [B, h, S, d]
        # quantized-cache tier: per-head dequant scales for this layer
        # ([heads] fp32 each; None = the byte-identical bf16 tier).
        # _store is the ONE write-site cast every cache mode below
        # shares: a plain dtype cast on the bf16 tier, symmetric int8
        # quantization on the quant tier (heads at `axis`).
        ks = vs = None
        if kv_scales is not None:
            ks, vs = kv_scales

        def _store(new, ref_dtype, scale, axis):
            if scale is None:
                return jnp.asarray(new, ref_dtype)
            from apex_tpu.serving.kv_quant import quantize
            return quantize(new, scale, axis=axis)

        if cache is not None:
            # (k_pool, v_pool, page_table) — pool
            # [layers, num_pages, h, d, page_len] shared across rows
            # AND layers, table [B, max_pages] int32 mapping logical
            # blocks to pages. Writes land in the pool itself at
            # (layer, page id); attention reads it through the table
            # with the layer as one more block index — the pool is
            # never sliced per layer (the serving engine's block-
            # table refactor, written in place).
            k_cache, v_cache, page_table = cache
            page_len = k_cache.shape[4]
            L = page_table.shape[1] * page_len
            # clip is a traced-value safety net only: an out-of-range
            # offset would RELOCATE the S-wide write over earlier cache
            # rows, so callers must bound positions host-side (the
            # serving engine validates offset + chunk_len <= max_len)
            pos = jnp.clip(jnp.asarray(positions, jnp.int32), 0, L - S)
            if S == 1:
                from apex_tpu.kernels.decode_attention import \
                    paged_decode_attention

                # write-then-attend, both in the one call: the token's
                # K/V goes to position pos of its row - the row's last
                # live page, which the kernel holds in VMEM anyway and
                # copies back edited - and the token sees it. Inactive
                # slots' tables point at the sentinel page, so their
                # (discarded) write can never corrupt a live row; a
                # live slot's write page is uniquely owned (shared
                # pages are always full — copy-on-write by
                # construction).
                ctx, k_cache, v_cache = paged_decode_attention(
                    q[:, :, 0], k_cache, v_cache, page_table, pos + 1,
                    new_k=_store(k[:, :, 0], k_cache.dtype, ks, 1),
                    new_v=_store(v[:, :, 0], v_cache.dtype, vs, 1),
                    k_scale=ks, v_scale=vs, layer=layer)
            else:
                from apex_tpu.kernels.prefill_attention import \
                    paged_prefill_attention

                if unaligned_append:
                    # speculative verify: S is small (draft_len + 1)
                    # and the offset is an arbitrary mid-generation
                    # position — write each position individually
                    # (the decode write, unrolled over the static S)
                    for s in range(S):
                        p = pos + s                             # [B]
                        page_ids = jnp.take_along_axis(
                            page_table, (p // page_len)[:, None],
                            axis=1)[:, 0]
                        off = p % page_len
                        k_cache = _pool_write_tokens(
                            k_cache, layer, page_ids, off,
                            _store(k[:, :, s], k_cache.dtype, ks, 1))
                        v_cache = _pool_write_tokens(
                            v_cache, layer, page_ids, off,
                            _store(v[:, :, s], v_cache.dtype, vs, 1))
                    ctx = paged_prefill_attention(q, k_cache, v_cache,
                                                  page_table, pos,
                                                  k_scale=ks,
                                                  v_scale=vs,
                                                  layer=layer)
                else:
                    # chunk writes must cover whole pages: the serving
                    # engine pins chunk_len % page_len == 0 and page-
                    # aligned offsets, so the chunk's S positions are
                    # exactly S // page_len freshly-allocated pages
                    if S % page_len:
                        raise ValueError(
                            f"paged chunk prefill needs S ({S}) to be "
                            f"a multiple of page_len ({page_len})")
                    npg = S // page_len
                    idx = (pos // page_len)[:, None] + jnp.arange(
                        npg, dtype=jnp.int32)[None, :]
                    chunk_pages = jnp.take_along_axis(page_table, idx,
                                                      axis=1)  # [B, npg]
                    k_cache = _pool_write_pages(
                        k_cache, layer, chunk_pages,
                        _store(k, k_cache.dtype, ks, 1))
                    v_cache = _pool_write_pages(
                        v_cache, layer, chunk_pages,
                        _store(v, v_cache.dtype, vs, 1))
                    ctx = paged_prefill_attention(q, k_cache, v_cache,
                                                  page_table, pos,
                                                  k_scale=ks,
                                                  v_scale=vs,
                                                  layer=layer)
            out = jnp.moveaxis(ctx.reshape(B, heads, S, d),
                               1, 2).reshape(B, S, heads * d)
        else:
            out = flash_attention(q, k, v, causal=True)  # [B, h, S, d]
            out = jnp.moveaxis(out, 1, 2).reshape(B, S, heads * d)
        ctx_in = out
        out = _dense(self.hidden, "proj")(ctx_in)
        if lora is not None:
            # row-parallel site: A input-split to match the local
            # heads' context, B replicated — the term is a partial sum
            # the psum below restores, zero new collectives
            out = out + _lora_term(ctx_in, lora["proj"], lora["alpha"],
                                   adapter_ids, out.dtype)
        if self.tp_size > 1:
            # row-parallel reduce: each shard's proj saw only its heads'
            # context, so the outputs are partial sums; the Dense added
            # the 1/tp-scaled bias per shard (sharding.shard_params), so
            # this one psum yields x @ W + b exactly — the first of the
            # block's two canonical TP all-reduces
            out = jax.lax.psum(out, self.tp_axis)
        if self.dropout > 0.0:
            out = nn.Dropout(rate=self.dropout, deterministic=not train)(out)
        if cache is not None:
            return out, (k_cache, v_cache)
        if return_kv:
            return out, (k, v)
        return out


class TransformerBlock(nn.Module):
    """Pre-LN block: x + attn(LN(x)); x + mlp(LN(x)).

    ``cache``/``positions``/``return_kv``/``layer`` thread straight
    through to :class:`SelfAttention` (see its docstring for the modes);
    with ``cache`` or ``return_kv`` on, the block returns ``(x, aux)``
    where aux is the whole pool, written in place at ``layer``
    (``cache``) or this layer's ``(k, v)`` (``return_kv``).
    """

    hidden: int
    num_heads: int
    mlp_ratio: int = 4
    dropout: float = 0.0
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    inference_dtype: Optional[Any] = None
    tp_axis: Optional[str] = None
    tp_size: int = 1
    weight_quant: bool = False

    @nn.compact
    def __call__(self, x, train: bool, cache=None, positions=None,
                 return_kv: bool = False, unaligned_append: bool = False,
                 kv_scales=None, lora=None, adapter_ids=None,
                 layer: Optional[int] = None):
        # FusedLayerNorm resolves 'layer_norm' (FP32) itself from the raw
        # self.dtype; the Dense sites resolve 'linear' (FP16) here
        from apex_tpu.amp.autocast import resolve_dtype
        dense_dtype = resolve_dtype(self.dtype, "linear", jnp.float32)
        if self.inference_dtype is not None and not train:
            dense_dtype = self.inference_dtype
        _dense = _dense_factory(self.weight_quant, dense_dtype,
                                self.param_dtype)
        h = FusedLayerNorm(normalized_shape=self.hidden, dtype=self.dtype,
                           name="ln_attn")(x)
        aux = None
        attn_out = SelfAttention(self.hidden, self.num_heads, self.dropout,
                                 self.dtype, self.param_dtype,
                                 self.inference_dtype,
                                 self.tp_axis, self.tp_size,
                                 weight_quant=self.weight_quant,
                                 name="attn")(h, train=train, cache=cache,
                                              positions=positions,
                                              return_kv=return_kv,
                                              unaligned_append=
                                              unaligned_append,
                                              kv_scales=kv_scales,
                                              lora=lora,
                                              adapter_ids=adapter_ids,
                                              layer=layer)
        if cache is not None or return_kv:
            attn_out, aux = attn_out
        x = x + attn_out
        h = FusedLayerNorm(normalized_shape=self.hidden, dtype=self.dtype,
                           name="ln_mlp")(x)
        # tensor-parallel shard: column-parallel up-projection (this
        # shard's inner/tp slice), row-parallel down-projection psummed
        # below — the MLP half of the Megatron split
        inner = self.mlp_ratio * self.hidden // self.tp_size
        mlp_in_x = h
        h = _dense(inner, "mlp_in")(mlp_in_x)
        if lora is not None:
            # column-parallel site: B output-split (contiguous — the
            # mlp_in kernel's own split), A replicated
            h = h + _lora_term(mlp_in_x, lora["mlp_in"], lora["alpha"],
                               adapter_ids, h.dtype)
        # tanh-approximation GELU (GPT-2's own formulation) on the fp32
        # accumulator. tanh fuses into the GEMM epilogue on TPU; exact
        # erf priced at +250 us per MLP f+b at the gpt2 shape on v5e
        # (the VPU erf is NOT epilogue-fusable). The apex-parity
        # fused_dense API keeps exact erf; the models use the variant
        # their original papers trained with.
        h = nn.gelu(jnp.asarray(h, jnp.float32), approximate=True)
        mlp_out_x = jnp.asarray(h, dense_dtype)
        h = _dense(self.hidden, "mlp_out")(mlp_out_x)
        if lora is not None:
            # row-parallel site: A input-split to match this shard's
            # inner slice, B replicated — psummed below
            h = h + _lora_term(mlp_out_x, lora["mlp_out"],
                               lora["alpha"], adapter_ids, h.dtype)
        if self.tp_size > 1:
            # row-parallel reduce (the block's second TP all-reduce);
            # mlp_out's bias is 1/tp-scaled per shard, restored here
            h = jax.lax.psum(h, self.tp_axis)
        if self.dropout > 0.0:
            h = nn.Dropout(rate=self.dropout, deterministic=not train)(h)
        if aux is not None:
            return x + h, aux
        return x + h


class TransformerLM(nn.Module):
    """Causal LM: tied-embedding GPT with pre-LN blocks + final FusedLayerNorm.

    ``__call__(tokens[B, S], train) -> logits[B, S, vocab]`` (logits fp32 —
    loss math never runs in half, matching amp's FP32_FUNCS policy for
    softmax/loss: apex/amp/lists/functional_overrides.py).

    Inference modes (the ``apex_tpu.serving`` engine's compiled
    programs — see :class:`SelfAttention`):

    - **decode**: ``__call__(tokens[B, 1], train=False,
      cache=(k_pool, v_pool, page_table), positions=lengths) ->
      (logits, (k_pool', v_pool'))`` — the
      single new token per batch row is embedded at ``positions[b]``,
      its K/V written into the pool, and attention runs length-masked
      against the cached prefix.
    - **chunked prefill**: same signature with ``tokens[B, C]`` (C > 1)
      — C consecutive prompt tokens per row, embedded at ``positions[b]
      + s``, K/V written to cache ``[positions[b], positions[b] + C)``,
      shifted-causal attention over the cached prefix (the engine's
      chunk-prefill program; one chunk per decode heartbeat).

      In both the stacked pools
      ``[layers, num_pages, h, d, page_len]`` are ONE value carried
      through the layer loop: each block writes its K/V into them in
      place and its kernel reads them in place (see
      :class:`SelfAttention`), and they are returned as they are —
      nothing is sliced per layer or restacked, so a donated pool is
      updated where it lives.
    - **speculative verify**: chunked prefill with
      ``unaligned_append=True`` — a ``[B, K+1]`` draft block landing at
      an arbitrary mid-generation offset, written by per-position
      scatters (see :class:`SelfAttention`).
    - ``return_kv=True`` (no cache): the plain forward, additionally
      returning ``(k, v)`` stacked per layer ``[layers, B, h, S, d]``.
      Its one use is the int8 KV tier's calibration forward
      (:meth:`apex_tpu.serving.kv_quant.KVQuantConfig.resolve_scales`).

    ``inference_dtype`` (normally the amp half dtype) pins the
    eval-mode GEMM/cache dtype independently of the training policy, so
    a pure-bf16 serving engine needs no fp32 master weights.
    """

    vocab_size: int
    hidden: int = 512
    num_layers: int = 6
    num_heads: int = 8
    max_seq_len: int = 1024
    mlp_ratio: int = 4
    dropout: float = 0.0
    # activation checkpointing per block (the reference gets this from
    # apex/transformer/tensor_parallel/random.py — checkpoint; on TPU it is
    # jax.checkpoint trading recompute for HBM, the standard long-context
    # memory lever)
    remat: bool = False
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    inference_dtype: Optional[Any] = None
    # tensor parallelism (serving.Engine(mesh=...); meaningful only
    # inside a shard_map over tp_axis): every block becomes one
    # Megatron-style shard (local heads, split MLP, 2 psums/block) and
    # the tied LM head returns VOCAB-LOCAL logits — each shard matmuls
    # its vocab/tp slice of the replicated embedding; the caller (the
    # engine's compiled program) all-gathers only the sampled rows.
    tp_axis: Optional[str] = None
    tp_size: int = 1
    # quantized serving weights (serving.Engine(weight_quant=...); the
    # engine provides int8 kernels + per-output-channel fp32 scales in
    # the params tree): every block GEMM runs through QuantDense and
    # the tied embedding/head through QuantEmbed — dequant is the
    # epilogue scale multiply, never a materialised weight matrix.
    # Serving-only: int8 kernels cannot train.
    weight_quant: bool = False

    def cache_spec(self) -> dict:
        """What the serving engine holds for this model
        (:class:`~apex_tpu.serving.kv_cache.CacheSpec`): every layer
        pages of all its heads, and no per-slot state."""
        return {"page_layers": self.num_layers, "kv_heads": self.num_heads,
                "head_dim": self.hidden // self.num_heads}

    @nn.compact
    def __call__(self, tokens, *, train: bool = True,
                 features_only: bool = False, cache=None, positions=None,
                 return_kv: bool = False, unaligned_append: bool = False,
                 kv_scales=None, lora=None, adapter_ids=None):
        from apex_tpu.amp.autocast import resolve_dtype
        dense_dtype = resolve_dtype(self.dtype, "linear", jnp.float32)
        if self.inference_dtype is not None and not train:
            dense_dtype = self.inference_dtype
        if cache is not None and return_kv:
            raise ValueError("cache (decode) and return_kv (calibration) "
                             "are exclusive modes")
        if self.weight_quant and train:
            raise ValueError(
                "weight_quant is a serving-only mode: int8 kernels "
                "cannot train — keep the bf16/fp32 model for training "
                "and let serving.Engine(weight_quant=...) quantize")
        if self.tp_size > 1 and (self.num_heads % self.tp_size
                                 or self.vocab_size % self.tp_size):
            raise ValueError(
                f"tp_size={self.tp_size} must divide num_heads="
                f"{self.num_heads} and vocab_size={self.vocab_size}")
        B, S = tokens.shape
        if self.weight_quant:
            from apex_tpu.serving.weight_quant import QuantEmbed
            embed = QuantEmbed(self.vocab_size, self.hidden,
                               dtype=dense_dtype,
                               param_dtype=self.param_dtype, name="wte")
        else:
            embed = nn.Embed(self.vocab_size, self.hidden,
                             param_dtype=self.param_dtype, name="wte")
        pos = self.param("wpe", nn.initializers.normal(stddev=0.02),
                         (self.max_seq_len, self.hidden), self.param_dtype)
        if cache is not None:
            # decode/chunk: token s of row b lives at positions[b] + s
            ppos = jnp.clip(jnp.asarray(positions, jnp.int32)[:, None]
                            + jnp.arange(S, dtype=jnp.int32)[None, :],
                            0, self.max_seq_len - 1)          # [B, S]
            x = jnp.asarray(embed(tokens) + pos[ppos], dense_dtype)
        else:
            x = jnp.asarray(embed(tokens) + pos[:S][None], dense_dtype)
        if self.dropout > 0.0:
            x = nn.Dropout(rate=self.dropout, deterministic=not train)(x)
        block_cls = TransformerBlock
        if self.remat and cache is None and not return_kv:
            block_cls = nn.remat(TransformerBlock, static_argnums=(2,))
        kv_out = ([], [])
        if cache is not None:
            k_pool, v_pool, page_table = cache
        for i in range(self.num_layers):
            block = block_cls(self.hidden, self.num_heads, self.mlp_ratio,
                              self.dropout, self.dtype, self.param_dtype,
                              self.inference_dtype, self.tp_axis,
                              self.tp_size,
                              weight_quant=self.weight_quant,
                              name=f"block_{i}")
            # quantized cache: this layer's per-head scale pair
            # ([layers, heads] engine arrays sliced at i)
            layer_scales = None if kv_scales is None else \
                (kv_scales[0][i], kv_scales[1][i])
            # multi-tenant LoRA: this layer's slice of the stacked
            # adapter arena ([layers, rows, ...] engine arrays sliced
            # at i; alpha is layer-free) — serving modes only, like
            # kv_scales
            layer_lora = None if lora is None else {
                "qkv": (lora["qkv_a"][i], lora["qkv_b"][i]),
                "proj": (lora["proj_a"][i], lora["proj_b"][i]),
                "mlp_in": (lora["mlp_in_a"][i], lora["mlp_in_b"][i]),
                "mlp_out": (lora["mlp_out_a"][i],
                            lora["mlp_out_b"][i]),
                "alpha": lora["alpha"],
            }
            if cache is not None:
                # paged pools [layers, P, h, d, page_len] + one shared
                # [B, max_pages] page table: the WHOLE pools go in with
                # the layer to write and read, and come back updated in
                # place (see SelfAttention) — never sliced, never
                # restacked
                x, (k_pool, v_pool) = block(
                    x, train, cache=(k_pool, v_pool, page_table),
                    positions=positions,
                    unaligned_append=unaligned_append,
                    kv_scales=layer_scales, lora=layer_lora,
                    adapter_ids=adapter_ids, layer=i)
            elif return_kv:
                x, (lk, lv) = block(x, train, return_kv=True)
                kv_out[0].append(lk)
                kv_out[1].append(lv)
            else:
                x = block(x, train)
        x = FusedLayerNorm(normalized_shape=self.hidden, dtype=self.dtype,
                           name="ln_f")(x)
        if features_only:
            # pre-head hidden states [B, S, H] for callers fusing the
            # tied head into the loss (kernels/lm_head_loss.py — the
            # head weight is params["wte"]["embedding"], vocab-major)
            return x
        # tied LM head; logits in fp32. Quantized weights: the head's
        # output channels ARE the vocab rows, so the per-row embedding
        # scales multiply the logits accumulator in the epilogue —
        # sliced by the SAME dynamic_slice as the vocab-parallel matrix
        if self.tp_size > 1:
            # vocab-parallel head: each shard matmuls its vocab/tp slice
            # of the replicated embedding (cutting the largest GEMM in a
            # decode step by tp) and returns VOCAB-LOCAL logits — the
            # engine all-gathers only the rows it actually samples
            vl = self.vocab_size // self.tp_size
            idx = jax.lax.axis_index(self.tp_axis)
            head = jax.lax.dynamic_slice_in_dim(
                jnp.asarray(embed.embedding, jnp.float32), idx * vl, vl,
                axis=0)                                     # [V/tp, H]
            logits = jnp.dot(jnp.asarray(x, jnp.float32), head.T)
            if self.weight_quant:
                logits = logits * jax.lax.dynamic_slice_in_dim(
                    embed.embedding_scale, idx * vl, vl, axis=0)
        else:
            logits = jnp.dot(jnp.asarray(x, jnp.float32),
                             jnp.asarray(embed.embedding, jnp.float32).T)
            if self.weight_quant:
                logits = logits * embed.embedding_scale
        if cache is not None:
            return logits, (k_pool, v_pool)
        if return_kv:
            return logits, (jnp.stack(kv_out[0]), jnp.stack(kv_out[1]))
        return logits


_LM_SIZES = {
    # (hidden, layers, heads) — "small" is the WikiText-2 recipe default
    "tiny": (128, 2, 4),
    "small": (512, 6, 8),
    "medium": (1024, 12, 16),
    "gpt2": (768, 12, 12),
}


def create_lm(size: str = "small", vocab_size: int = 32768,
              max_seq_len: int = 1024, dropout: float = 0.0,
              remat: bool = False, dtype: Optional[Any] = None,
              param_dtype: Any = jnp.float32,
              inference_dtype: Optional[Any] = None) -> TransformerLM:
    if size not in _LM_SIZES:
        raise ValueError(f"unknown LM size {size!r}; one of {sorted(_LM_SIZES)}")
    hidden, layers, heads = _LM_SIZES[size]
    return TransformerLM(vocab_size=vocab_size, hidden=hidden,
                         num_layers=layers, num_heads=heads,
                         max_seq_len=max_seq_len, dropout=dropout,
                         remat=remat, dtype=dtype, param_dtype=param_dtype,
                         inference_dtype=inference_dtype)
