"""Quantized KV-cache storage: int8 pools with per-``[layer, head]`` scales.

Serving capacity is HBM-bound and the KV pool is the dominant resident
allocation, so halving its bytes doubles resident prefixes, COW-shared
pages and concurrent slots on the same silicon. This module is the
storage-dtype tier the amp cast policies (:mod:`apex_tpu.amp.policy`
O0-O3) stop short of: where a policy picks the COMPUTE half dtype
(bf16), :class:`KVQuantConfig` picks the cache STORAGE dtype (int8)
independently — K/V leave the qkv GEMM in the compute half, are
quantized at the write site, and are dequantized INSIDE the attention
kernels (int8 block load → per-head scale multiply → the existing
online-softmax fp32 math), so quantized K/V never materialise in bf16
outside VMEM.

Scale layout — the design's load-bearing choice::

    k_scale, v_scale : fp32 [layers, heads]

- **per-head, not per-page/per-token**: a scale is a property of the
  (layer, head) DISTRIBUTION, frozen at engine construction from a
  calibration absmax. Storage stays a pure pytree of two int8 arrays
  plus two tiny fp32 arrays; no scale metadata rides the pages.
- **copy-on-write sharing stays free**: a prefix hit shares quantized
  pages by refcount bump exactly as in bf16 — because scales are not
  per-page, a shared page needs no scale copy and a donor and borrower
  read identical bytes through identical scales.
- **speculative rollback stays length arithmetic**: the rejected tail's
  quantized K/V sits past the committed length, unreachable and
  overwritten write-then-attend, with no scale state to unwind.
- **tensor parallelism shards scales with the pool**: ``[layers,
  heads]`` splits along the heads axis next to ``[layers, num_pages,
  heads/tp, head_dim, page_len]`` — each shard quantizes and
  dequantizes its own heads with its own scale slice, collective-free.

Numerics: symmetric linear quantization to ``[-127, 127]`` (qmax
:data:`QMAX`), ``scale = absmax * margin / 127``. The round-trip error
per element is bounded by ``scale / 2`` for inputs inside the
calibrated range (clipped beyond it — the ``margin`` headroom exists
because decode-time K/V can modestly exceed a prompt-sample absmax).
Greedy serving accuracy is therefore a TOLERANCE claim, not a bitwise
one: the quantized engine is measured as a token-match-rate against
the bf16 oracle, while
``kv_quant=None`` remains the default and the bitwise baseline.

Calibration: per-``[layer, head]`` absmax either given explicitly
(``calibration_absmax`` — a scalar, a ``[layers, heads]`` array, or a
``(k, v)`` pair of either) or measured by one eager ``return_kv``
forward over a deterministic token sample (``calibration_tokens`` /
seeded random ints). An absmax of 0 or a non-finite absmax would
produce degenerate scales — dequantizing everything to 0 or NaN — so
:meth:`KVQuantConfig.resolve_scales` raises at ENGINE CONSTRUCTION,
never letting a degenerate scale surface later as NaN output.

The numeric core — grid, scale resolution, degenerate-absmax guard —
lives in :mod:`apex_tpu.serving.quant_common`, shared with the weight
tier (:mod:`apex_tpu.serving.weight_quant`); ``QMAX`` / ``quantize`` /
``dequantize`` / ``expand_scale`` are re-exported here unchanged, so
every pre-refactor import keeps working.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from .quant_common import (QMAX, check_absmax, dequantize, expand_scale,
                           quantize, scale_from_absmax)

__all__ = ["KVQuantConfig", "QMAX", "quantize", "dequantize",
           "expand_scale"]


def _as_layer_head(value, layers: int, heads: int, what: str):
    arr = np.asarray(value, np.float32)
    if arr.ndim == 0:
        arr = np.full((layers, heads), float(arr), np.float32)
    if arr.shape != (layers, heads):
        raise ValueError(
            f"{what} calibration absmax must be a scalar or a "
            f"[layers={layers}, heads={heads}] array, got {arr.shape}")
    return arr


# eq=False: calibration_absmax may hold arrays and calibration_tokens a
# list, so a generated __eq__ would raise on array truthiness and the
# paired __hash__ would make the config unhashable — identity semantics
# keep the frozen config usable as a dict key / set member / static arg
@dataclasses.dataclass(frozen=True, eq=False)
class KVQuantConfig:
    """Storage-dtype tier for the serving KV cache (``Engine(kv_quant=
    KVQuantConfig())``): int8 K/V with per-``[layer, head]`` fp32
    scales.

    Parameters
    ----------
    dtype:
        Cache storage dtype. Only ``int8`` is implemented (the bf16
        default lives at ``kv_quant=None``, not here).
    scale_granularity:
        Only ``"head"`` (one scale per ``[layer, head]``) is
        implemented — the granularity at which copy-on-write page
        sharing needs no scale copy and tensor parallelism shards
        scales with the pool.
    calibration_absmax:
        Explicit per-``[layer, head]`` absolute-maximum calibration: a
        scalar, a ``[layers, heads]`` array, or a ``(k, v)`` pair of
        either. ``None`` (default) calibrates by running one eager
        ``return_kv`` forward over ``calibration_tokens`` (or a seeded
        random sample) and taking per-``[layer, head]`` absmax of the
        returned K/V. Zero or non-finite values are rejected LOUDLY at
        engine construction (degenerate scales), never deferred to NaN
        output.
    calibration_tokens:
        Token sample for auto-calibration (e.g. a representative
        system prompt); ``None`` draws ``calibration_len`` seeded
        random ids. Ignored when ``calibration_absmax`` is given.
    calibration_len / calibration_seed:
        Size and seed of the random fallback sample.
    margin:
        Headroom factor on the calibrated absmax (``scale = absmax *
        margin / 127``): decode-time K/V can modestly exceed a
        prompt-sample absmax, and a clipped outlier costs more accuracy
        than one coarser quantization step. The 1.25 default covers the
        decode drift measured on the shared-prefix bench stream (absmax
        up to ~1.12x the prompt-sample calibration); pushing it far
        higher trades the clipping it prevents for rounding error
        everywhere (the grid coarsens with the scale), which flips
        near-tie argmaxes just as surely as clipping does.
    """

    dtype: Any = jnp.int8
    scale_granularity: str = "head"
    calibration_absmax: Optional[Union[float, Any, Tuple]] = None
    calibration_tokens: Optional[Sequence[int]] = None
    calibration_len: int = 32
    calibration_seed: int = 0
    margin: float = 1.25

    def __post_init__(self):
        if jnp.dtype(self.dtype) != jnp.int8:
            raise ValueError(
                f"KVQuantConfig supports int8 storage only, got "
                f"{jnp.dtype(self.dtype).name} (bf16 storage is the "
                f"kv_quant=None default, not a quant config)")
        if self.scale_granularity != "head":
            raise ValueError(
                f"KVQuantConfig supports scale_granularity='head' "
                f"(one scale per [layer, head]), got "
                f"{self.scale_granularity!r}")
        if not (np.isfinite(self.margin) and self.margin > 0):
            raise ValueError(f"margin must be finite and > 0, got "
                             f"{self.margin}")
        if self.calibration_len < 1:
            raise ValueError("calibration_len must be >= 1")

    # ----------------------------------------------------------- scales
    def _calibrate(self, model, params, layers: int, heads: int):
        """Measure per-[layer, head] absmax from one eager return_kv
        forward over the calibration sample (the serving prefill path's
        own K/V, so the measured range is the stored range)."""
        vocab = int(model.vocab_size)
        max_len = int(getattr(model, "max_seq_len", self.calibration_len))
        if self.calibration_tokens is not None:
            toks = np.asarray(self.calibration_tokens, np.int32)
            if toks.ndim != 1 or toks.size < 1:
                raise ValueError("calibration_tokens must be a non-"
                                 "empty 1-D token sequence")
            toks = toks[:max_len]
        else:
            rng = np.random.default_rng(self.calibration_seed)
            n = min(self.calibration_len, max_len)
            toks = rng.integers(1, vocab, size=n).astype(np.int32)
        _, (k, v) = model.apply({"params": params}, toks[None, :],
                                train=False, return_kv=True)
        # [layers, 1, heads, S, d] -> absmax over (batch, pos, dim)
        k_absmax = np.asarray(jnp.max(jnp.abs(jnp.asarray(k, jnp.float32)),
                                      axis=(1, 3, 4)))
        v_absmax = np.asarray(jnp.max(jnp.abs(jnp.asarray(v, jnp.float32)),
                                      axis=(1, 3, 4)))
        if k_absmax.shape != (layers, heads):
            raise ValueError(
                f"calibration forward returned K/V for "
                f"{k_absmax.shape} (layers, heads); engine expected "
                f"({layers}, {heads})")
        return k_absmax, v_absmax

    def resolve_scales(self, model, params, *, layers: int, heads: int):
        """The per-``[layer, head]`` fp32 scale pair ``(k_scale,
        v_scale)`` the engine stores alongside its cache pytree.

        Raises :class:`ValueError` at (engine) construction when any
        calibration absmax is zero or non-finite — a zero absmax would
        make ``quantize`` divide by ~0 and ``dequantize`` return 0
        everywhere, a non-finite one would poison every attended token;
        both must fail HERE, loudly, not later as NaN output."""
        if self.calibration_absmax is not None:
            cal = self.calibration_absmax
            if isinstance(cal, tuple) and len(cal) == 2:
                k_absmax = _as_layer_head(cal[0], layers, heads, "K")
                v_absmax = _as_layer_head(cal[1], layers, heads, "V")
            else:
                k_absmax = _as_layer_head(cal, layers, heads, "K")
                v_absmax = k_absmax.copy()
        else:
            k_absmax, v_absmax = self._calibrate(model, params, layers,
                                                 heads)
        for name, absmax in (("K", k_absmax), ("V", v_absmax)):
            check_absmax(
                absmax,
                describe=lambda lh, n=name: (
                    f"{n} calibration absmax at [layer={lh[0]}, "
                    f"head={lh[1]}]"),
                hint="fix the calibration sample or pass an explicit "
                     "positive calibration_absmax")
        k_scale = scale_from_absmax(k_absmax, self.margin)
        v_scale = scale_from_absmax(v_absmax, self.margin)
        return jnp.asarray(k_scale), jnp.asarray(v_scale)
