"""Tensor-parallel sharding for the serving engine's compiled programs.

Everything a single-chip ``serving.Engine`` compiles — paged decode,
chunk prefill, monolithic prefill, speculative verify — is capped by
one chip's HBM and FLOPs. This module supplies the three pieces that
let ``Engine(mesh=...)`` serve the SAME programs Megatron-style over a
tensor-parallel mesh axis:

1. **a partition-rule table** over the :class:`~apex_tpu.models
   .transformer_lm.TransformerLM` parameter pytree
   (:func:`partition_rules` + :func:`match_partition_rules`, the
   ``match_partition_rules`` idiom from the pjit exemplars): attention
   qkv and the MLP up-projection are COLUMN-parallel (output features
   split over the ``tp`` axis), the attention output projection and the
   MLP down-projection are ROW-parallel (input features split),
   embeddings / positional table / LayerNorms replicated;
2. **a parameter sharder** (:func:`shard_params`) that places the cast
   param tree on the mesh per those rules — including the two host-side
   transforms a plain even split cannot express:

   - the fused qkv kernel's output axis is laid out ``(3, heads, d)``,
     so a contiguous split would hand shard 0 all of Q plus half of K
     — :func:`shard_params` PERMUTES it to ``(tp, 3, heads/tp, d)``
     first, so the even split per the rule gives every shard its own
     heads' Q, K **and** V in the exact ``(3, local_heads, d)`` layout
     the per-shard module expects;
   - ROW-parallel biases are value-scaled by ``1/tp``: the module adds
     the bias inside its Dense on every shard and the post-GEMM
     ``psum`` sums the shards, so ``psum(x @ W_t + b/tp) = x @ W + b``
     exactly once (``1/tp`` is an exponent shift for power-of-two tp —
     exact in bf16/fp32; tp=1 is the identity);

3. **cache/pool specs** (:func:`cache_pspec`): the paged KV pool is
   sharded along the HEADS axis — ``[layers, num_pages, heads/tp,
   head_dim, page_len]`` per shard — so every attention gather, page
   scatter and per-page kernel step is shard-local. Attention NEVER
   crosses ICI: each shard runs the unchanged paged kernels over fewer
   heads (the grid over ``batch x heads`` simply has fewer rows), and
   page tables / lengths / tokens / sampling scalars stay replicated
   host state.

The collective inventory this buys (:func:`expected_collectives`, the
HLO pin in ``tests/L0/test_sharding.py``):

- **2 psums per transformer block** — after the row-parallel attention
  projection and after the row-parallel MLP down-projection (the two
  canonical Megatron all-reduces; residual stream replicated);
- **1 all-gather at the logits** — the tied LM head is computed
  vocab-parallel (each shard matmuls its ``vocab/tp`` slice of the
  replicated embedding, cutting the head GEMM — the largest single
  matmul in a decode step — by ``tp``) and only the ROWS BEING SAMPLED
  are gathered back to the full vocabulary (``[rows, vocab/tp]`` →
  ``[rows, vocab]``), so greedy/temperature/top-k sampling and the
  fused non-finite guard run on full rows exactly as on one chip.

``Engine(mesh=None)`` remains the verbatim single-chip baseline (none
of this module is on that path); a ``tp=1`` mesh runs the sharded
programs over one device — identity collectives, bitwise-pinned against
``mesh=None`` on a greedy stream.
"""

from __future__ import annotations

import re
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

__all__ = ["partition_rules", "match_partition_rules", "shard_params",
           "cache_pspec", "scale_pspec", "shard_cache", "zeros_sharded",
           "expected_collectives", "tp_axis_of", "validate_tp_geometry"]

# host-side transforms a plain even split cannot express, keyed by the
# SAME regexes the rule table uses (see shard_params). kernel_scale is
# the weight-quant tier's per-output-channel dequant vector: it lives
# on the qkv OUTPUT axis, so it rides the same head-group permutation
# as the kernel and bias — every local channel keeps its own scale,
# which is what makes tp=1 bitwise vs the unsharded quantized engine.
_QKV_RE = re.compile(r"attn/qkv/(kernel|bias|kernel_scale)$")
_ROW_BIAS_RE = re.compile(r"(attn/proj|mlp_out)/bias$")


def partition_rules(axis: str = "tp") -> Tuple[Tuple[str, PartitionSpec],
                                               ...]:
    """The TransformerLM partition-rule table: ``(regex, PartitionSpec)``
    pairs matched first-wins against ``/``-joined parameter paths
    (``block_0/attn/qkv/kernel``). Column-parallel output splits for
    qkv and the MLP up-projection, row-parallel input splits for the
    output projections, everything else replicated (embeddings stay
    replicated so the lookup is collective-free; the logits are sliced
    vocab-parallel *in-program* instead — see the module docstring)."""
    P = PartitionSpec
    # kernel_scale leaves are the weight-quant tier's per-output-channel
    # dequant vectors: column-parallel kernels split on the OUTPUT axis,
    # so their scales split with them (qkv's additionally head-group
    # permuted — see _QKV_RE); row-parallel kernels split on the INPUT
    # axis, so their per-output scales replicate (the scale is constant
    # across shards, which is exactly why scaling each partial sum
    # before the psum is exact). wte's embedding_scale replicates with
    # the embedding via the catch-all; the vocab-parallel head slices
    # matrix and scale together in-program.
    return (
        (r"attn/qkv/kernel$", P(None, axis)),   # column-parallel (heads)
        (r"attn/qkv/bias$", P(axis)),
        (r"attn/qkv/kernel_scale$", P(axis)),
        (r"attn/proj/kernel$", P(axis, None)),  # row-parallel
        (r"attn/proj/bias$", P()),              # replicated, scaled 1/tp
        (r"attn/proj/kernel_scale$", P()),      # replicated (row-par.)
        (r"mlp_in/kernel$", P(None, axis)),     # column-parallel
        (r"mlp_in/bias$", P(axis)),
        (r"mlp_in/kernel_scale$", P(axis)),
        (r"mlp_out/kernel$", P(axis, None)),    # row-parallel
        (r"mlp_out/bias$", P()),                # replicated, scaled 1/tp
        (r"mlp_out/kernel_scale$", P()),        # replicated (row-par.)
        (r".*", P()),   # wte(+scale)/wpe/LayerNorms/ln_f: replicated
    )


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def match_partition_rules(rules, params):
    """A pytree of :class:`PartitionSpec` mirroring ``params``: each
    leaf gets the spec of the first rule whose regex ``re.search``-es
    its ``/``-joined path (the ``match_partition_rules`` idiom). Scalar
    leaves are always replicated; a leaf no rule matches is an error —
    an unsharded new parameter must be CHOSEN, not defaulted silently
    (the catch-all ``.*`` rule in :func:`partition_rules` is that
    choice, made visibly)."""

    def _spec(path, leaf):
        name = _leaf_name(path)
        if np.ndim(leaf) == 0 or np.size(leaf) == 1:
            return PartitionSpec()
        for rule, ps in rules:
            if re.search(rule, name) is not None:
                return ps
        raise ValueError(f"no partition rule matches param {name!r}")

    return jax.tree_util.tree_map_with_path(_spec, params)


def tp_axis_of(mesh) -> str:
    """The mesh's tensor-parallel axis name. Serving meshes are 1-D —
    the KV pool shards over exactly one axis (heads), so a 2-D mesh is
    a configuration error named loudly here."""
    names = tuple(mesh.axis_names)
    if len(names) != 1:
        raise ValueError(
            f"serving needs a 1-D tensor-parallel mesh, got axes "
            f"{names}: shard the engine over one axis (heads/MLP) and "
            "scale further with replica engines")
    return names[0]


def validate_tp_geometry(tp: int, *, num_heads: int, hidden: int,
                         mlp_ratio: int, vocab_size: int) -> None:
    """The divisibility contract a tensor-parallel engine needs:
    heads (the KV pool's shard axis and attention's work unit), the MLP
    inner width (column/row splits) and the vocabulary (the in-program
    logits slice) must all split evenly over ``tp``. Rejected at
    construction — a ragged shard would otherwise surface as a shape
    error deep inside the first traced program."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if num_heads % tp:
        raise ValueError(
            f"num_heads {num_heads} is not divisible by tp={tp}: the "
            "KV pool shards along the heads axis, so every shard must "
            "own a whole number of heads")
    if (mlp_ratio * hidden) % tp:
        raise ValueError(
            f"MLP inner width {mlp_ratio * hidden} is not divisible by "
            f"tp={tp} (column/row-parallel MLP split)")
    if vocab_size % tp:
        raise ValueError(
            f"vocab_size {vocab_size} is not divisible by tp={tp}: the "
            "tied LM head computes a vocab/tp logits slice per shard")


def _group_qkv_kernel(kernel, tp: int, num_heads: int):
    """Permute a fused qkv kernel ``[in, 3*heads*d]`` (output laid out
    ``(3, heads, d)``) so a contiguous even split over the output axis
    hands shard ``t`` its own heads' Q, K and V in ``(3, heads/tp, d)``
    order — the exact layout the per-shard module's
    ``reshape(B, S, 3, local_heads, d)`` expects."""
    three_h = kernel.shape[-1]
    d = three_h // (3 * num_heads)
    hl = num_heads // tp
    lead = kernel.shape[:-1]
    k = kernel.reshape(*lead, 3, tp, hl, d)
    # (..., 3, tp, hl, d) -> (..., tp, 3, hl, d): shard-major
    k = np.moveaxis(k, -4, -3)
    return np.ascontiguousarray(k).reshape(*lead, three_h)


def shard_params(params, mesh, *, num_heads: int, axis: str = None,
                 rules=None):
    """Place a (policy-cast) TransformerLM param tree on ``mesh`` per
    the partition-rule table: qkv leaves are head-group permuted first
    (see :func:`_group_qkv_kernel`), row-parallel biases are value-
    scaled by ``1/tp`` (the per-shard Dense adds the scaled bias and
    the post-GEMM psum restores it exactly once), then every leaf is
    ``device_put`` with its rule's :class:`NamedSharding`. ``tp=1``
    leaves every value bitwise untouched (permutation and scaling are
    identities).

    The transforms run on HOST copies: each leaf is pulled to numpy,
    permuted/scaled there, and ``device_put`` straight into its sharded
    layout — so no device ever holds a transient full-size permuted
    copy of the weights (the caller's original arrays are the caller's;
    at real model sizes pass host-resident params)."""
    if axis is None:
        axis = tp_axis_of(mesh)
    if rules is None:
        rules = partition_rules(axis)
    tp = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    specs = match_partition_rules(rules, params)

    def _place(path, leaf, spec):
        name = _leaf_name(path)
        arr = np.asarray(leaf)
        if _QKV_RE.search(name):
            arr = _group_qkv_kernel(arr, tp, num_heads)
        elif _ROW_BIAS_RE.search(name) and tp > 1:
            # exact for power-of-two tp (exponent shift); the fp32
            # round-trip keeps ml_dtypes halves off numpy ufunc paths
            arr = (arr.astype(np.float32) / tp).astype(arr.dtype)
        return jax.device_put(arr, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(_place, params, specs)


def cache_pspec(axis: str = "tp") -> PartitionSpec:
    """The paged KV pool's partition spec: ``[layers, num_pages,
    heads/tp, head_dim, page_len]`` per shard — heads-axis sharding, so
    attention never crosses ICI (each shard's paged kernels run
    unchanged over fewer heads; page tables and lengths stay replicated
    host state)."""
    return PartitionSpec(None, None, axis, None, None)


def scale_pspec(axis: str = "tp") -> PartitionSpec:
    """The quantized-cache tier's scale spec: per-``[layer, head]``
    dequantization scales split along the SAME heads axis as the pool
    (``[layers, heads/tp]`` per shard), so every shard quantizes and
    dequantizes its own heads with its own slice — the int8 tier adds
    zero collectives, exactly like the pool sharding itself."""
    return PartitionSpec(None, axis)


def shard_cache(cache, mesh, axis: str = None):
    """Reshard an EXISTING :class:`~apex_tpu.serving.PagedKVCache` onto
    ``mesh`` with the heads-sharded pool spec. For a FRESH pool prefer
    :func:`zeros_sharded` — resharding an existing pool necessarily
    holds the full arrays somewhere first, which is exactly what a pool
    sized to aggregate HBM cannot afford."""
    if axis is None:
        axis = tp_axis_of(mesh)
    ns = NamedSharding(mesh, cache_pspec(axis))
    return cache.replace(k=jax.device_put(cache.k, ns),
                         v=jax.device_put(cache.v, ns))


def zeros_sharded(shape, dtype, mesh, spec: PartitionSpec):
    """Allocate a zeroed array DIRECTLY in its sharded layout: a jitted
    ``zeros`` with sharded ``out_shardings``, so XLA materialises each
    shard on its own device and NO chip ever holds the full array. This
    is what lets ``Engine(mesh=...)`` build a KV pool sized to
    AGGREGATE HBM — the whole point of sharding it — instead of OOMing
    device 0 on a transient full-size allocation at construction."""
    ns = NamedSharding(mesh, spec)
    with mesh:
        return jax.jit(lambda: jnp.zeros(shape, dtype),
                       out_shardings=ns)()


def expected_collectives(num_layers: int) -> dict:
    """The collective inventory of ONE sharded serving program (the
    scheduled-HLO pin): two all-reduces per transformer block (post-
    attention-projection and post-MLP-down-projection psums) and one
    all-gather at the logits (the sampled rows' ``vocab/tp`` slices
    rejoined). The embedding lookup is collective-free (replicated
    table) and the KV pool is heads-sharded, so attention itself adds
    nothing."""
    return {"all_reduce": 2 * int(num_layers), "all_gather": 1}
