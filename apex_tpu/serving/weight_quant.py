"""Quantized serving weights: int8 GEMM kernels with per-output-channel
fp32 scales, dequantized in the matmul epilogue.

The amp cast policies (:mod:`apex_tpu.amp.policy` O0-O3) pick the
COMPUTE half dtype and PR 10's :class:`~apex_tpu.serving.KVQuantConfig`
picked the cache STORAGE dtype; this module extends the same machinery
to the third HBM-resident population — the serving weights. The big
GEMM kernels of every transformer block (fused qkv, attention output
projection, MLP up/down) plus the tied vocab head (the ``wte``
embedding, doubling as the LM head matrix) are stored as int8 with one
fp32 scale per OUTPUT CHANNEL, and the scale multiplies the GEMM's
accumulator in the epilogue — exactly where PR 10 folds KV scales into
the attention kernels' block loads — so dequantized weights never
materialise and the engine's compiled-program set is unchanged (the
trace-count pins hold; quantization is a params property, not a new
executable). Together with int8 KV this roughly doubles model-size
headroom per chip on top of the KV tier's 2x concurrency.

Scale layout — per output channel, the design's load-bearing choice:

- **epilogue fold is exact algebra**: with one scale per output channel
  ``j``, ``sum_i x_i * (Wq_ij * s_j) == (sum_i x_i * Wq_ij) * s_j`` —
  the multiply commutes out of the contraction, so dequant rides the
  accumulator for free (per-input-channel or per-block scales would
  not commute and would force a materialised dequant or a custom
  kernel);
- **tensor parallelism shards scales with their weights** under the
  PR 9 partition-rule table: column-parallel kernels (qkv, mlp_in)
  split on the output axis, so their scale vectors split the same way
  (the fused qkv layout is head-group PERMUTED before splitting —
  scales ride the same permutation, so every local channel keeps its
  own scale and tp=1 stays bitwise vs unsharded); row-parallel kernels
  (proj, mlp_out) split on the INPUT axis, so their per-output scales
  replicate, and ``psum(partial_shard * s + b/tp) == s * sum(partials)
  + b`` — scaling each shard's partial sum before the reduce is exact
  because the scale is constant across shards;
- **the tied head quantizes per vocab row**: the head GEMM's output
  channels are vocab entries, so the embedding gets one scale per row —
  the embedding LOOKUP dequantizes its row by the same scale (one
  gathered multiply), and the vocab-parallel head slices scale and
  matrix together with the same ``dynamic_slice``.

Calibration needs no forward pass: unlike K/V (activations whose range
must be sampled), weights are static — the per-channel absmax read off
the checkpoint IS the range, so ``margin`` is not headroom here:
values below 1.0 clip the weight tails (measured as a match-rate
collapse) and values at or above 1.0 differ only by grid pitch, with
the 1.2 default pinned by the bench stream (see
:class:`WeightQuantConfig`). The loud-failure contract is PR
10's, shared through :mod:`apex_tpu.serving.quant_common`: an all-zero
or non-finite output channel raises at ENGINE CONSTRUCTION with the
parameter path and channel named, never surfacing later as NaN logits.

Accuracy is the PR 10 contract one tier over: greedy serving under
``Engine(weight_quant=WeightQuantConfig())`` is a token-match-rate
claim vs the bf16 oracle,
while ``weight_quant=None`` stays the default and the bitwise baseline
— none of this module is on its trace path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .quant_common import (QMAX, check_absmax, quantize_host,
                           scale_from_absmax)

__all__ = ["WeightQuantConfig", "QuantDense", "QuantEmbed",
           "param_bytes", "param_count", "quant_scale_absmax"]

# the serving GEMM kernels the tier quantizes, as (path-suffix, channel
# axis) pairs over the TransformerLM tree: Dense kernels are
# [in, out] (channel axis -1); the tied embedding is [vocab, hidden]
# and its head-GEMM output channels are the VOCAB ROWS (axis 0)
_DENSE_SITES = ("attn/qkv", "attn/proj", "mlp_in", "mlp_out")
_SCALE_LEAVES = ("kernel_scale", "embedding_scale")


@dataclasses.dataclass(frozen=True)
class WeightQuantConfig:
    """Storage tier for the serving weights (``Engine(weight_quant=
    WeightQuantConfig())``): int8 GEMM kernels with per-output-channel
    fp32 scales, dequantized in the matmul epilogue.

    Parameters
    ----------
    dtype:
        Weight storage dtype. Only ``int8`` is implemented (the bf16
        default lives at ``weight_quant=None``, not here).
    granularity:
        Only ``"channel"`` (one scale per output channel) is
        implemented — the granularity at which the epilogue fold is
        exact algebra and tensor parallelism shards scales with their
        weights (see the module docstring).
    margin:
        Factor on the per-channel absmax (``scale = absmax * margin /
        QMAX``). Weights are static, so unlike the KV tier no headroom
        is NEEDED — but the setting still matters at both ends:
        margins below 1.0 CLIP the per-channel weight tails and
        collapse the match rate (measured: 0.94 -> 0.47 on the bench
        stream at 0.85), while margins above 1.0 trade a slightly
        coarser grid for nothing systematic — at tiny-model scale the
        near-tie argmaxes make that range noise-dominated, and the
        1.2 default is the value the bench smoke stream pinned at
        token-match-rate 1.0 (both the weights-only and the
        weights+KV combined tier), per the PR 10 tune-then-pin
        contract. Recalibrate on your own stream when the dashboard
        match rate matters more than the pin.
    """

    dtype: Any = jnp.int8
    granularity: str = "channel"
    margin: float = 1.2

    def __post_init__(self):
        if jnp.dtype(self.dtype) != jnp.int8:
            raise ValueError(
                f"WeightQuantConfig supports int8 storage only, got "
                f"{jnp.dtype(self.dtype).name} (bf16 weights are the "
                f"weight_quant=None default, not a quant config)")
        if self.granularity != "channel":
            raise ValueError(
                f"WeightQuantConfig supports granularity='channel' "
                f"(one scale per output channel — the granularity the "
                f"epilogue fold is exact at), got "
                f"{self.granularity!r}")
        if not (np.isfinite(self.margin) and self.margin > 0):
            raise ValueError(f"margin must be finite and > 0, got "
                             f"{self.margin}")

    # ------------------------------------------------------- quantization
    def _quantize_leaf(self, leaf, path: str, axis: int):
        """One kernel/embedding leaf -> ``(int8 codes, fp32 [out]
        scale)`` with the loud per-channel absmax guard. ``axis`` is
        the output-channel axis. Everything runs on HOST copies
        (:func:`~apex_tpu.serving.quant_common.quantize_host`) — no
        full-size leaf transits a device, and the fp32 round-trip
        keeps ml_dtypes halves off numpy ufunc paths (the sharding
        module's own discipline)."""
        w = np.asarray(leaf, np.float32)
        reduce_axes = tuple(a for a in range(w.ndim)
                            if a != axis % w.ndim)
        absmax = check_absmax(
            np.max(np.abs(w), axis=reduce_axes),
            describe=lambda idx: (
                f"weight absmax of {path} output channel {idx[0]}"),
            hint="an all-zero or non-finite output channel cannot be "
                 "per-channel quantized; fix the checkpoint or serve "
                 "this model with weight_quant=None")
        scale = scale_from_absmax(absmax, self.margin)
        q = quantize_host(w, scale, axis=axis % w.ndim)
        return jnp.asarray(q), jnp.asarray(scale)

    def quantize_params(self, params):
        """The quantized parameter tree the engine serves from: every
        targeted GEMM kernel (``attn/qkv``, ``attn/proj``, ``mlp_in``,
        ``mlp_out`` — per-module ``kernel`` leaves) becomes int8 with a
        sibling fp32 ``kernel_scale`` [out] leaf, the tied ``wte``
        embedding becomes int8 with a per-vocab-row ``embedding_scale``
        leaf, and everything else (biases, LayerNorms, ``wpe``) rides
        through untouched in its policy-cast dtype. Raises loudly when
        the tree holds NO quantizable site (a model this tier does not
        understand must not silently serve unquantized) or when any
        output channel's absmax is degenerate."""
        from collections.abc import Mapping

        sites = []

        def _walk(node, prefix):
            if not isinstance(node, Mapping):
                return node
            out = {}
            for name, child in node.items():
                path = f"{prefix}/{name}" if prefix else str(name)
                if name == "kernel" and not isinstance(child, dict) \
                        and prefix.endswith(_DENSE_SITES):
                    q, s = self._quantize_leaf(child, path, axis=-1)
                    out["kernel"] = q
                    out["kernel_scale"] = s
                    sites.append(path)
                elif name == "embedding" \
                        and not isinstance(child, dict) \
                        and prefix.endswith("wte"):
                    q, s = self._quantize_leaf(child, path, axis=0)
                    out["embedding"] = q
                    out["embedding_scale"] = s
                    sites.append(path)
                else:
                    out[name] = _walk(child, path)
            return out

        quantized = _walk(dict(params), "")
        if not sites:
            raise ValueError(
                "weight_quant found no quantizable GEMM kernels in the "
                "parameter tree (expected attn/qkv, attn/proj, mlp_in, "
                "mlp_out kernels and/or a wte embedding — the "
                "TransformerLM serving contract); refusing to serve "
                "silently unquantized")
        return quantized


# ------------------------------------------------------ serving modules
# The flax modules the quantized serving branch of TransformerLM swaps
# in for nn.Dense / nn.Embed. They read the SAME parameter paths
# (<site>/kernel, <site>/bias, wte/embedding) plus the scale leaves
# quantize_params added, so the partition-rule table and every
# checkpoint/sharding tool keep one tree shape to reason about. Used at
# apply time only (the engine provides quantized params); their inits
# exist to satisfy flax's shape validation and are never serving state.
class QuantDense(nn.Module):
    """Dense over an int8 ``kernel`` with the fp32 per-output-channel
    ``kernel_scale`` multiplied onto the accumulator in the epilogue:
    ``y = (x @ Wq) * s + b``. The dot runs in ``dtype`` (the engine's
    inference half — int8 codes cast losslessly: every value in
    [-127, 127] is exact in bf16), the epilogue in fp32 (the same
    fp32-epilogue idiom as the MLP GELU), and the output returns to
    ``dtype`` so downstream dataflow matches ``nn.Dense``'s."""

    features: int
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features),
                            self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,), self.param_dtype)
        scale = self.param("kernel_scale", nn.initializers.ones_init(),
                           (self.features,), jnp.float32)
        dtype = self.dtype or jnp.float32
        # the dot reads dtype-width operands (int8 codes cast
        # losslessly) but KEEPS its accumulator fp32 into the epilogue
        # — the MXU's own semantics, and one fewer rounding than
        # dot-to-bf16 then rescale — where the per-channel scale and
        # the bias apply before the single cast back to dtype
        acc = jax.lax.dot_general(
            jnp.asarray(x, dtype), jnp.asarray(kernel, dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        y = acc * jnp.asarray(scale, jnp.float32) \
            + jnp.asarray(bias, jnp.float32)
        return jnp.asarray(y, dtype)


class QuantEmbed(nn.Module):
    """Embedding over an int8 ``embedding`` with per-vocab-row fp32
    ``embedding_scale``: a lookup gathers its row's codes AND scale
    (one extra [B, S] gather + multiply, dequantized in fp32 then cast
    to ``dtype`` — the serving half, so the residual stream's entry
    width matches the ``nn.Embed`` path it swaps in for), and the
    tied-head GEMM's caller reads ``embedding`` / ``embedding_scale``
    directly to fold the row scales onto the logits accumulator (vocab
    rows ARE the head GEMM's output channels)."""

    num_embeddings: int
    features: int
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32

    def setup(self):
        self.embedding = self.param(
            "embedding", nn.initializers.normal(stddev=0.02),
            (self.num_embeddings, self.features), self.param_dtype)
        self.embedding_scale = self.param(
            "embedding_scale", nn.initializers.ones_init(),
            (self.num_embeddings,), jnp.float32)

    def __call__(self, tokens):
        rows = jnp.take(jnp.asarray(self.embedding, jnp.float32),
                        tokens, axis=0)
        rows = rows * jnp.take(self.embedding_scale, tokens)[..., None]
        return jnp.asarray(rows, self.dtype or jnp.float32)


# ------------------------------------------------------- accounting
def param_bytes(params) -> int:
    """Total bytes of a parameter tree — the numerator of the
    ``serving.wq.bytes_per_param`` gauge and the bench leg's
    weight-bytes-reduction claim (global bytes under a mesh: a sharded
    leaf reports its full logical size)."""
    return int(sum(leaf.nbytes
                   for leaf in jax.tree_util.tree_leaves(params)))


def param_count(params) -> int:
    """Total WEIGHT elements of a parameter tree, scale leaves
    excluded — the denominator of ``serving.wq.bytes_per_param``:
    scales are overhead the gauge must charge to the weights they
    dequantize, not dilute away as extra 'parameters'."""
    n = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        if name not in _SCALE_LEAVES:
            n += int(np.prod(np.shape(leaf)) or 1)
    return n


def quant_scale_absmax(params) -> float:
    """The largest absolute weight the calibrated scales can represent
    (``max(scale) * QMAX`` over every scale leaf) — the
    ``serving.wq.quant_scale_absmax`` gauge. Weights are static, so
    unlike the KV tier's drift signal this is a pure provenance number:
    it changes only when the checkpoint (or margin) does, and a
    dashboard step in it flags a silent weight swap."""
    worst = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in _SCALE_LEAVES:
            worst = max(worst, float(jnp.max(leaf)))
    return worst * QMAX
