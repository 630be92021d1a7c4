"""Transformer LM recipe — BASELINE.json config 3.

"FusedLayerNorm + FusedAdam transformer LM (WikiText-2)": a causal LM built
from the framework's fused tiers (apex_tpu.models.transformer_lm), trained
with apex_tpu.optimizers.fused_adam under an amp opt-level, LM loss via the
fused xentropy kernel. The reference has no in-repo LM recipe (it supplies
FusedAdam/FusedLayerNorm to external Megatron/DeepLearningExamples scripts);
this is the standalone equivalent, argument-shaped like examples/imagenet.

No network access: --synthetic generates token streams with a Zipfian
unigram distribution (WikiText-2-like vocab statistics); point --data at a
pre-tokenized .npy to train on real text.
"""

from __future__ import annotations

import os as _os
import sys as _sys

# run as a script from anywhere: put the repo root on sys.path (the reference
# relies on `pip install apex`; this repo is used in-tree)
_REPO_ROOT = _os.path.abspath(_os.path.join(_os.path.dirname(__file__),
                                            _os.pardir, _os.pardir))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp
from apex_tpu.kernels.xentropy import softmax_cross_entropy_loss
from apex_tpu.models.transformer_lm import create_lm
from apex_tpu.optimizers import fused_adam
from apex_tpu.telemetry import tracing
from apex_tpu.utils import chip


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu transformer LM recipe")
    p.add_argument("--data", default=None,
                   help="pre-tokenized int32 .npy (else synthetic)")
    p.add_argument("--size", default="small",
                   choices=["tiny", "small", "medium", "gpt2"])
    p.add_argument("--vocab-size", type=int, default=32768)
    p.add_argument("-b", "--batch-size", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--weight-decay", type=float, default=0.01)
    p.add_argument("--iters", type=int, default=50)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--loss-scale", default="dynamic")
    p.add_argument("--smoothing", type=float, default=0.0,
                   help="label smoothing (fused xentropy kernel)")
    p.add_argument("--fused-head", action="store_true",
                   help="fuse the tied LM head into the loss "
                        "(kernels/lm_head_loss.py): logits never hit HBM "
                        "and the head GEMMs run in the amp half dtype — "
                        "measured 1.4x faster at the GPT-2 tail shape with "
                        "the [B,S,V] logits residual gone. Single-chip, "
                        "or with --vocab-parallel under shard_map (the "
                        "op's axis_name mode fuses Megatron's CE "
                        "reductions into the sharded head GEMM); off by "
                        "default so the default trajectory stays the "
                        "parallel tiers' oracle")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--deterministic", action="store_true")
    p.add_argument("--remat", action="store_true",
                   help="activation checkpointing per block (memory lever)")
    p.add_argument("--accum-steps", type=int, default=1, metavar="N",
                   help="in-jit microbatch gradient accumulation "
                        "(amp.make_train_step accum_steps): the step "
                        "scans N microbatches of batch-size/N, paying "
                        "ONE unscale + optimizer + scaler update per "
                        "window — apex's delay_unscale recipe, compiled. "
                        "Single-chip path only: the parallel tiers' "
                        "1F1B/no-pipelining schedules already accumulate "
                        "over --microbatches")
    # ---- model-parallel tier (SURVEY P22-P24): dp x tp x pp over a
    # ('data','pipe','model') mesh; any value > 1 selects the parallel path
    p.add_argument("--data-parallel", type=int, default=1, metavar="DP",
                   help="data-parallel ranks (DDP grad psum)")
    p.add_argument("--tensor-parallel", type=int, default=1, metavar="TP",
                   help="Megatron TP: QKV/MLP column+row parallel")
    p.add_argument("--pipeline-parallel", type=int, default=1, metavar="PP",
                   help="pipeline stages, hand-scheduled 1F1B when > 1")
    p.add_argument("--virtual-pipeline", type=int, default=1, metavar="VPP",
                   help="virtual chunks per stage (interleaved 1F1B)")
    p.add_argument("--sequence-parallel", action="store_true",
                   help="Megatron SP: LN/residual activations sharded "
                        "along sequence over the TP group (needs tp>1)")
    p.add_argument("--vocab-parallel", action="store_true",
                   help="Megatron parallel LM head: the output projection "
                        "sharded over the vocab dim with "
                        "vocab_parallel_cross_entropy (needs tp>1; "
                        "exclusive with --sequence-parallel)")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO: shard optimizer state over the data axis "
                        "(contrib DistributedFusedAdam — mean-reduce-"
                        "scatter grads, shard-local update, all-gather "
                        "params; needs dp>1). Under --partitioning "
                        "gspmd the same sharding is ONE PartitionSpec "
                        "on the m/v superbuffers — XLA does the rest")
    p.add_argument("--opt-layout", default="tree",
                   choices=["tree", "flat"],
                   help="fused_adam state layout: per-leaf 'tree' "
                        "(default; XLA-fused update at the HBM roofline "
                        "— BASELINE.md round-5 kernel tier) or the "
                        "'flat' superbuffer (bitwise-identical; the "
                        "layout ZeRO shards, forced automatically under "
                        "gspmd --zero)")
    p.add_argument("--microbatches", type=int, default=None,
                   help="pipeline microbatches (default 2*pp)")
    p.add_argument("--partitioning", default="shard_map",
                   choices=["shard_map", "gspmd"],
                   help="how the mesh is driven: explicit shard_map "
                        "collectives (default), or 'gspmd' — plain "
                        "jax.jit over the SAME 1-device program with "
                        "NamedShardings built from the TP modules' "
                        "kernel_partition_spec(); XLA's SPMD partitioner "
                        "inserts the collectives (dp x tp, + --zero)")
    p.add_argument("--prof-device", type=int, default=0, metavar="N",
                   help="after training, time N extra steps on the "
                        "DEVICE lanes of a profiler capture and print "
                        "device tokens/s (the apex recipes' --prof, on "
                        "the round-5 device-time basis). Observation-"
                        "only: runs on a copy of the state; prints n/a "
                        "on backends with no device lanes")
    p.add_argument("--save", default=None, metavar="CKPT",
                   help="write the final train state (params, masters, "
                        "optimizer state incl. ZeRO shards, scaler) plus "
                        "the step count to this .npz")
    p.add_argument("--resume", default=None, metavar="CKPT",
                   help="restore a --save checkpoint and continue: with "
                        "--deterministic the resumed run reproduces the "
                        "uninterrupted trajectory exactly")
    p.add_argument("--layers", type=int, default=None,
                   help="override the size preset's layer count (parallel "
                        "path; must divide by pp*vpp)")
    p.add_argument("--telemetry", default=None, metavar="SPEC",
                   help="stream per-step telemetry (loss, grad norm, "
                        "scaler trajectory, step time) from inside the "
                        "jitted step: JSONL path, 'stdout', or 'null'; "
                        "summarize with python -m apex_tpu.telemetry "
                        "(sharded paths emit one record per rank)")
    # ---- serving tier (apex_tpu.serving): generate after training
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, serve N-token generations from "
                        "synthetic prompts through the apex_tpu.serving "
                        "engine (compiled KV-cache prefill + decode-step "
                        "programs, continuous batching) and print "
                        "tokens/s + time-to-first-token. Single-chip "
                        "path only")
    p.add_argument("--gen-prompts", type=int, default=8, metavar="K",
                   help="number of synthetic prompts to serve (their "
                        "lengths vary to exercise continuous batching)")
    p.add_argument("--gen-slots", type=int, default=4,
                   help="concurrent decode slots (batch width of the "
                        "compiled decode step)")
    p.add_argument("--gen-prompt-len", type=int, default=32,
                   help="prefill program capacity (prompts are sampled "
                        "at 1..this many tokens)")
    p.add_argument("--gen-temperature", type=float, default=0.0,
                   help="sampling temperature (0 = greedy)")
    p.add_argument("--gen-top-k", type=int, default=0,
                   help="top-k truncation for sampled decode (0 = off)")
    return p.parse_args(argv)


def synthetic_tokens(rng, batch, seq_len, vocab):
    """Zipf-ish unigram stream: token ranks follow 1/(r+10)."""
    ranks = jnp.arange(vocab, dtype=jnp.float32)
    logits = -jnp.log(ranks + 10.0)
    return jax.random.categorical(rng, logits, shape=(batch, seq_len + 1))


def load_token_stream(path, vocab_size, seq_len):
    """Load + validate a pre-tokenized flat .npy for --data. Out-of-vocab
    ids are rejected here because under jit the embedding gather would
    clamp them silently — wrong training, not a crash."""
    data = np.load(path)
    if not isinstance(data, np.ndarray):
        raise SystemExit(f"--data {path!r} is an archive (.npz?); "
                         "expected a flat .npy token stream")
    if data.ndim != 1:
        raise SystemExit(f"--data {path!r} must be a flat token stream; "
                         f"got shape {data.shape}")
    if not np.issubdtype(data.dtype, np.integer):
        raise SystemExit(f"--data {path!r} holds {data.dtype} values; "
                         "token streams must be integers (floats would "
                         "truncate silently)")
    if len(data) < seq_len + 2:
        raise SystemExit(f"--data holds {len(data)} tokens; need at least "
                         f"seq_len+2 = {seq_len + 2}")
    lo, hi = int(data.min()), int(data.max())
    if lo < 0 or hi >= vocab_size:
        raise SystemExit(f"--data token ids span [{lo}, {hi}]; "
                         f"--vocab-size is {vocab_size}")
    return data


def data_batch(data, rng, batch_size, seq_len):
    """Random [batch, seq_len+1] windows from the flat stream — the same
    sampler on the single-chip and model-parallel paths. Gathered in
    numpy and shipped as ONE host-to-device transfer; maxval is
    exclusive, so len-seq_len admits the last valid window start."""
    with tracing.phase("train.batch_draw"):
        with tracing.phase("train.rng_readback"):
            idx = np.asarray(jax.random.randint(rng, (batch_size,), 0,
                                                len(data) - seq_len))
        with tracing.phase("train.gather"):
            rows = np.stack([data[i:i + seq_len + 1] for i in idx])
        with tracing.phase("train.h2d"):
            return jnp.asarray(rows)


# --------------------------------------------------------------------------
# Model-parallel tier: Megatron-composed LM over a (data, pipe, model) mesh.
#
# Reference composition (SURVEY P22-P24, §4.5): Megatron trainers drive
# apex's ColumnParallelLinear/RowParallelLinear (TP) and the 1F1B pipeline
# schedules through a training loop with amp O2 master weights + the dynamic
# loss scaler. This is that loop, TPU-first: blocks pipelined with the
# hand-scheduled collective-permute 1F1B (activation memory flat in the
# microbatch count; in-flight bound in schedules.forward_backward_1f1b), QKV/MLP
# column+row-parallel over 'model', DDP as one grad psum over 'data',
# embedding/head replicated with grads completed via the 1F1B
# input-cotangent / loss-param hooks, all inside ONE jitted train step built
# by amp.make_train_step(grad_fn=...) — unscale -> found_inf -> skip/step ->
# master->model copy semantics identical to the single-chip path.
# --------------------------------------------------------------------------

def build_parallel_lm(args, policy):
    """Build (mesh, state, jit_step, n_params) for the dp x tp x pp LM.

    Returns a jitted ``step(state, tokens) -> (state, metrics)`` already
    shard_mapped over the mesh; ``tokens`` is the GLOBAL int32 batch
    ``[B, seq_len+1]``, sharded over 'data' by the step itself.
    """
    from jax.sharding import Mesh, PartitionSpec as P
    from apex_tpu.utils.compat import shard_map

    from apex_tpu import comm
    from apex_tpu.kernels.layer_norm import layer_norm
    from apex_tpu.models.transformer_lm import _LM_SIZES
    from apex_tpu.transformer import pipeline_parallel as pp_mod
    from apex_tpu.transformer.tensor_parallel.layers import (
        ColumnParallelLinear, RowParallelLinear)

    dp, tp = args.data_parallel, args.tensor_parallel
    pp, vpp = args.pipeline_parallel, args.virtual_pipeline
    gspmd = getattr(args, "partitioning", "shard_map") == "gspmd"
    hidden, layers, heads = _LM_SIZES[args.size]
    if args.layers:
        layers = args.layers
    L = pp * vpp
    if layers % L:
        raise SystemExit(f"--size {args.size} has {layers} layers; needs "
                         f"layers % (pp*vpp) == 0, got pp*vpp={L}")
    if vpp > 1 and pp == 1:
        raise SystemExit("--virtual-pipeline needs --pipeline-parallel > 1")
    if heads % tp:
        raise SystemExit(f"heads {heads} must divide by tp {tp}")
    if hidden % heads:
        raise SystemExit(f"hidden {hidden} must divide by heads {heads}")
    sp_on = bool(args.sequence_parallel)
    if sp_on and tp < 2:
        raise SystemExit("--sequence-parallel needs --tensor-parallel > 1")
    if sp_on and args.seq_len % tp:
        raise SystemExit(f"--seq-len {args.seq_len} must divide by tp {tp} "
                         "under --sequence-parallel")
    vp_on = bool(args.vocab_parallel)
    if vp_on and tp < 2:
        raise SystemExit("--vocab-parallel needs --tensor-parallel > 1")
    if vp_on and sp_on:
        raise SystemExit("--vocab-parallel and --sequence-parallel are "
                         "currently exclusive (the head's seq layouts "
                         "differ)")
    if vp_on and args.vocab_size % tp:
        raise SystemExit(f"--vocab-size {args.vocab_size} must divide by "
                         f"tp {tp} under --vocab-parallel")
    zero_on = bool(args.zero)
    if zero_on and dp < 2:
        raise SystemExit("--zero needs --data-parallel > 1")
    if gspmd and (pp > 1 or vpp > 1 or sp_on or vp_on):
        raise SystemExit(
            "--partitioning gspmd drives dp x tp (optionally --zero); "
            "pipeline/sequence/vocab-parallel run under the (default) "
            "shard_map path")
    # Under GSPMD the module MATH is the 1-device program (world 1, no
    # mappings.py collectives); tp lives only in the sharding specs.
    tpm = 1 if gspmd else tp
    per_stage = layers // L
    H, V, S = hidden, args.vocab_size, args.seq_len
    inner = 4 * H
    M = args.microbatches or 2 * pp
    B = args.batch_size
    if B % dp or (B // dp) % M:
        raise SystemExit(f"batch {B} must divide by dp*microbatches "
                         f"({dp}*{M})")
    n_dev = dp * pp * tp
    devices = comm.ensure_devices(n_dev)
    mesh = Mesh(np.array(devices[:n_dev]).reshape(dp, pp, tp),
                ("data", "pipe", "model"))

    h_local, d_head = heads // tpm, H // heads
    mdt = policy.model_dtype  # thread into the TP modules (ADVICE round-2)
    # Under SP the column linears all-gather the sequence (dim 0 — hence
    # the recipe's seq-first [s, mb, H] activation layout) and the row
    # linears reduce-scatter it back: the TP allreduce split into its two
    # halves around the seq-sharded LN/residual region (SURVEY §3.3 SP).
    col_qkv = ColumnParallelLinear(input_size=H, output_size=3 * H,
                                   use_bias=False, world_size=tpm, dtype=mdt,
                                   sequence_parallel_enabled=sp_on)
    row_proj = RowParallelLinear(input_size=H, output_size=H, use_bias=True,
                                 input_is_parallel=True, world_size=tpm,
                                 dtype=mdt,
                                 sequence_parallel_enabled=sp_on)
    col_mlp = ColumnParallelLinear(input_size=H, output_size=inner,
                                   use_bias=False, world_size=tpm, dtype=mdt,
                                   sequence_parallel_enabled=sp_on)
    row_mlp = RowParallelLinear(input_size=inner, output_size=H,
                                use_bias=True, input_is_parallel=True,
                                world_size=tpm, dtype=mdt,
                                sequence_parallel_enabled=sp_on)

    # ---- parameters. TP-sharded leaves ("col") carry an explicit model-
    # shard dim [L, tp, per_stage, ...] so the HOST holds the full weight
    # and shard_map hands each (pipe, model) rank its own block — the
    # functional analogue of the reference's _initialize_affine_weight_gpu
    # scatter (the full weight is drawn in canonical layout and split, so
    # the same seed yields the same MATH at every dp/tp/pp — testable
    # against the 1-device configuration). Replicated-per-stage leaves
    # ("rep") are [L, per_stage, ...].
    def init_params(rng):
        def nrm(k, shape, std):
            return (jax.random.normal(k, shape) * std).astype(jnp.float32)

        ks = iter(jax.random.split(rng, 8))
        # canonical full weights; head dim layout [3, heads, d_head]
        qkv_full = nrm(next(ks), (L, per_stage, H, 3, heads, d_head), 0.02)
        proj_full = nrm(next(ks), (L, per_stage, heads, d_head, H), 0.02)
        mlp_in_full = nrm(next(ks), (L, per_stage, H, inner), 0.02)
        mlp_out_full = nrm(next(ks), (L, per_stage, inner, H), 0.02)
        col = {
            # rank r owns heads [r*h_local, (r+1)*h_local)
            "qkv_k": jnp.stack(
                [qkv_full[:, :, :, :, r * h_local:(r + 1) * h_local]
                 .reshape(L, per_stage, H, 3 * H // tpm)
                 for r in range(tpm)], axis=1),
            "proj_k": jnp.stack(
                [proj_full[:, :, r * h_local:(r + 1) * h_local]
                 .reshape(L, per_stage, H // tpm, H)
                 for r in range(tpm)], axis=1),
            "mlp_in_k": jnp.stack(
                [mlp_in_full[..., r * (inner // tpm):(r + 1) * (inner // tpm)]
                 for r in range(tpm)], axis=1),
            "mlp_out_k": jnp.stack(
                [mlp_out_full[:, :, r * (inner // tpm):(r + 1) * (inner // tpm)]
                 for r in range(tpm)], axis=1),
        }
        rep = {
            "ln1_s": jnp.ones((L, per_stage, H)),
            "ln1_b": jnp.zeros((L, per_stage, H)),
            "ln2_s": jnp.ones((L, per_stage, H)),
            "ln2_b": jnp.zeros((L, per_stage, H)),
            "proj_b": jnp.zeros((L, per_stage, H)),
            "mlp_out_b": jnp.zeros((L, per_stage, H)),
        }
        emb = {"wte": nrm(next(ks), (V, H), 0.02),
               "wpe": nrm(next(ks), (S, H), 0.01)}
        head_full = nrm(next(ks), (H, V), 0.02)
        if vp_on:
            # Megatron parallel head: vocab columns split over tp; drawn
            # full-first so the math is tp-invariant like the col leaves
            head_k = jnp.stack(
                [head_full[:, r * (V // tp):(r + 1) * (V // tp)]
                 for r in range(tp)], axis=0)       # [tp, H, V/tp]
        else:
            head_k = head_full
        head = {"ln_s": jnp.ones((H,)), "ln_b": jnp.zeros((H,)),
                "kernel": head_k}
        return {"emb": emb, "stages": {"col": col, "rep": rep},
                "head": head}

    order = _stage_order(pp, vpp)

    def maybe_rep(p):
        # Under SP, LN/bias params act on seq-LOCAL activations, so each
        # model rank's grad is partial: identity-fwd/psum-bwd completes it
        # (Megatron's SP LN-grad allreduce; mappings.copy_to_...).
        if sp_on:
            from apex_tpu.transformer.tensor_parallel.mappings import (
                copy_to_tensor_model_parallel_region)
            return copy_to_tensor_model_parallel_region(p, "model")
        return p

    def block_fn(bp, x):
        # x: [s_local_or_s, mb, H] — seq-first (the SP shard dim is dim 0)
        mb = x.shape[1]
        cdt = x.dtype
        h = layer_norm(x.reshape(-1, H), maybe_rep(bp["rep"]["ln1_s"]),
                       maybe_rep(bp["rep"]["ln1_b"])
                       ).reshape(x.shape).astype(cdt)
        qkv = col_qkv.apply({"params": {"kernel": bp["col"]["qkv_k"]}}, h)
        s_full = qkv.shape[0]              # SP: seq gathered back to full
        qkv = qkv.reshape(s_full, mb, 3, h_local, d_head)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        att = jnp.einsum("qbhd,kbhd->bhqk", q, k)
        # N8 fused path: scale+causal-mask+softmax in one Pallas pass
        # (fp32 math, half I/O), jnp fallback on unaligned shapes
        from apex_tpu.transformer.functional.fused_softmax import (
            scaled_upper_triang_masked_softmax)
        att = scaled_upper_triang_masked_softmax(
            att, scale=float(1.0 / np.sqrt(d_head))).astype(cdt)
        ctx = jnp.einsum("bhqk,kbhd->qbhd", att, v).reshape(
            s_full, mb, h_local * d_head)
        x = x + row_proj.apply(
            {"params": {"kernel": bp["col"]["proj_k"],
                        "bias": maybe_rep(bp["rep"]["proj_b"])}},
            ctx).astype(cdt)
        h = layer_norm(x.reshape(-1, H), maybe_rep(bp["rep"]["ln2_s"]),
                       maybe_rep(bp["rep"]["ln2_b"])
                       ).reshape(x.shape).astype(cdt)
        h = col_mlp.apply({"params": {"kernel": bp["col"]["mlp_in_k"]}}, h)
        # tanh GELU, matching models/transformer_lm.py EXACTLY — this
        # block IS the single-chip model's math under TP sharding, and
        # the parallel-vs-oracle trajectory parity is asserted bitwise
        h = jax.nn.gelu(jnp.asarray(h, jnp.float32),
                        approximate=True).astype(cdt)
        h = row_mlp.apply({"params": {"kernel": bp["col"]["mlp_out_k"],
                                      "bias": maybe_rep(
                                          bp["rep"]["mlp_out_b"])}}, h)
        return (x + h.astype(cdt)).astype(cdt)

    def stage_fn(sp, x):
        for i in range(per_stage):
            bp = jax.tree_util.tree_map(lambda l: l[i], sp)
            x = block_fn(bp, x)
        return x

    def lm_loss(y, tgt, head):
        # y: [s_local_or_s, mb, H], tgt: [s_local_or_s, mb] (seq-first).
        # head params are used RAW (no maybe_rep): under SP every head
        # grad (LN and kernel alike) is seq-chunk-partial and the caller
        # psums the whole head tree over 'model' once — mixing in
        # copy_to's psum-bwd here would double-count the LN grads.
        hh = layer_norm(y.reshape(-1, H), head["ln_s"], head["ln_b"])
        if vp_on:
            if args.fused_head:
                # fused vocab-parallel tail (kernels/lm_head_loss.py
                # axis_name mode): the op emits copy_to's psum-bwd on
                # dx itself and fuses Megatron's CE reductions into the
                # chunked head GEMM — the [S*mb, V_loc] logits never
                # materialize. head["kernel"] is [H, V_loc]; the .T
                # view fuses into the chunk GEMMs' dimension numbers.
                from apex_tpu.kernels.lm_head_loss import lm_head_xentropy
                losses = lm_head_xentropy(
                    hh, head["kernel"].T, tgt.reshape(-1),
                    smoothing=args.smoothing, compute_dtype=y.dtype,
                    axis_name="model")
                return losses.mean()
            # Megatron parallel-LM-head rule (P23): the head input goes
            # through copy_to (identity fwd, psum bwd) so every vocab
            # shard back-props the FULL dL/dh; the local logits block
            # feeds the all-reduce-based parallel cross entropy. Head
            # grads come out complete per shard (kernel: its vocab
            # block; LN: identical on every rank) — no caller psum.
            from apex_tpu.transformer.tensor_parallel import (
                copy_to_tensor_model_parallel_region,
                vocab_parallel_cross_entropy)
            hh = copy_to_tensor_model_parallel_region(hh, "model")
            logits = jnp.dot(jnp.asarray(hh, y.dtype),
                             jnp.asarray(head["kernel"], y.dtype))
            losses = vocab_parallel_cross_entropy(
                logits, tgt.reshape(-1), label_smoothing=args.smoothing,
                axis_name="model")
            return losses.mean()
        logits = jnp.dot(jnp.asarray(hh, y.dtype),
                         jnp.asarray(head["kernel"], y.dtype))
        losses = softmax_cross_entropy_loss(
            jnp.asarray(logits, jnp.float32), tgt.reshape(-1),
            smoothing=args.smoothing)
        l = losses.mean()
        if sp_on:
            # each model rank sees a seq chunk; return local/tp so the
            # collective transposes make the optimized objective the
            # GLOBAL mean, and psum value-only so the reported loss is
            # the global mean too (testing.build_full_parallel_step's
            # mb_loss rule)
            l = l / tp
            l = l + jax.lax.stop_gradient(jax.lax.psum(l, "model") - l)
        return l

    cdtype = policy.compute_dtype
    s_loc = S // tp if sp_on else S

    def slice_wpe(wpe):
        """This rank's position-embedding rows under SP (full rows else)."""
        if sp_on:
            wpe = jax.lax.dynamic_slice_in_dim(
                wpe, jax.lax.axis_index("model") * s_loc, s_loc, axis=0)
        return wpe

    def _psum_model(tree):
        return jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, "model"), tree)

    def grad_fn(params, batch, loss_scale):
        tokens = batch                               # [B/dp, S+1] int32
        # seq-first streams: [M, S, mb]
        inp = tokens[:, :-1].reshape(M, -1, S).transpose(0, 2, 1)
        tgt = tokens[:, 1:].reshape(M, -1, S).transpose(0, 2, 1)
        if sp_on:
            # slice token ids (not embeddings) to the rank's chunk: the
            # lookup then costs 1/tp, and the vjp scatter only touches
            # local positions (psum over 'model' completes demb)
            mr = jax.lax.axis_index("model")
            tgt = jax.lax.dynamic_slice_in_dim(tgt, mr * s_loc, s_loc,
                                               axis=1)
            inp = jax.lax.dynamic_slice_in_dim(inp, mr * s_loc, s_loc,
                                               axis=1)

        def embed(ep):
            wpe = slice_wpe(jnp.asarray(ep["wpe"], cdtype))
            return jnp.asarray(ep["wte"], cdtype)[inp] \
                + wpe[None, :, None, :]        # [M, s_loc, mb, H]

        # strip the model-shard dim shard_map left on the col leaves
        sp_local = {"col": jax.tree_util.tree_map(lambda l: l[:, 0],
                                                  params["stages"]["col"]),
                    "rep": params["stages"]["rep"]}
        if vpp == 1:
            sp_local = jax.tree_util.tree_map(lambda l: l[0], sp_local)
        head_local = dict(params["head"])
        if vp_on:
            head_local["kernel"] = params["head"]["kernel"][0]

        def pack_head_grads(hg):
            if vp_on:
                hg = dict(hg)
                hg["kernel"] = hg["kernel"][None]
            return hg

        if pp == 1:
            # TP-only (no pipe axis): reference fwd_bwd_no_pipelining —
            # grad accumulation over the microbatch stream
            def mb_loss_fn(p3, mb_tokens, t3):
                # mb_tokens: [s_loc, mb] seq-first (pre-sliced under SP)
                wpe = slice_wpe(jnp.asarray(p3["emb"]["wpe"], cdtype))
                x = jnp.asarray(p3["emb"]["wte"], cdtype)[mb_tokens] \
                    + wpe[:, None, :]
                return lm_loss(stage_fn(p3["sp"], x), t3, p3["head"])

            loss, g3 = pp_mod.forward_backward_no_pipelining(
                mb_loss_fn,
                {"emb": params["emb"], "sp": sp_local,
                 "head": head_local},
                inp, tgt, accum_dtype=jnp.float32)
            g3 = jax.tree_util.tree_map(
                lambda g: g * jnp.asarray(loss_scale, g.dtype), g3)
            emb_g, head_g = g3["emb"], g3["head"]
            if sp_on:
                # per-rank seq chunks contribute partial emb/head grads
                emb_g, head_g = _psum_model(emb_g), _psum_model(head_g)
            sgrads = g3["sp"]
            if vpp == 1:
                sgrads = jax.tree_util.tree_map(lambda g: g[None], sgrads)
            return loss, {
                "emb": emb_g,
                "stages": {"col": jax.tree_util.tree_map(
                    lambda g: g[:, None], sgrads["col"]),
                    "rep": sgrads["rep"]},
                "head": pack_head_grads(head_g),
            }

        x_stream, emb_vjp = jax.vjp(embed, params["emb"])
        loss, sgrads, aux = pp_mod.forward_backward_1f1b(
            stage_fn, lm_loss, sp_local, x_stream, tgt,
            num_stages=pp, num_chunks=vpp, loss_scale=loss_scale,
            loss_params=head_local, return_input_cotangents=True)
        if vpp == 1:
            sgrads = jax.tree_util.tree_map(lambda g: g[None], sgrads)
        (demb,) = emb_vjp(jnp.asarray(aux["input_cotangents"],
                                      x_stream.dtype))
        head_g = aux["loss_param_grads"]
        if sp_on:
            demb, head_g = _psum_model(demb), _psum_model(head_g)
        return loss, {
            "emb": demb,
            "stages": {"col": jax.tree_util.tree_map(lambda g: g[:, None],
                                                     sgrads["col"]),
                       "rep": sgrads["rep"]},
            "head": pack_head_grads(head_g),
        }

    if zero_on and not gspmd:
        _inner_grad_fn = grad_fn

        def grad_fn(params, batch, loss_scale):  # noqa: F811
            loss, grads = _inner_grad_fn(params, batch, loss_scale)
            # the grad psum normally pmean's the reported loss inside
            # make_train_step; ZeRO hands grads over un-averaged, so the
            # metric needs the global-batch mean here
            return jax.lax.pmean(loss, "data"), grads

        # ZeRO (contrib DistributedFusedAdam): the transformation does its
        # own mean-reduce-scatter over 'data', updates its 1/dp state
        # shard, and all-gathers params — so grads are handed over
        # UN-averaged (grad_average_axis=None) and found_inf must sync
        # over 'data' explicitly (no grad psum carries the infs).
        from apex_tpu.contrib.optimizers import distributed_fused_adam
        optimizer = distributed_fused_adam(
            args.lr, weight_decay=args.weight_decay, adam_w_mode=True,
            axis_name="data", world_size=dp)
        grad_avg_axis = None
    else:
        # plain fused_adam — including gspmd --zero, where ZeRO-1 is a
        # sharding SPEC on the m/v superbuffers (_finish_gspmd), not a
        # different optimizer. That spec (P('data') on a 1-D buffer) is
        # what forces layout="flat" there; every other path defaults to
        # the per-leaf tree layout (round 5 — 4x less optimizer time).
        layout = "flat" if (zero_on and gspmd) else args.opt_layout
        optimizer = fused_adam(args.lr, weight_decay=args.weight_decay,
                               adam_w_mode=True, layout=layout)
        grad_avg_axis = "data" if dp > 1 else None
    # stage/col leaves are shard-local to pipe/model: their infs never ride
    # a grad psum, so found_inf must sync explicitly (make_train_step docs)
    sync = tuple(ax for ax, size in (("pipe", pp), ("model", tp))
                 if size > 1)
    if zero_on:
        sync = ("data",) + sync
    if gspmd:
        # one LOGICAL program: the loss is the global-batch mean and the
        # grads are its true gradients — XLA's SPMD partitioner inserts
        # the data-parallel reduction itself, and found_inf is a single
        # global value (no axis to sync over)
        grad_avg_axis, sync = None, ()
    init_fn, step_fn = amp.make_train_step(
        None, optimizer, policy, grad_fn=grad_fn,
        grad_average_axis=grad_avg_axis,
        overflow_sync_axes=sync or None,
        telemetry=bool(args.telemetry))

    params = init_params(jax.random.PRNGKey(args.seed))
    params["stages"] = jax.tree_util.tree_map(
        lambda l: l[order], params["stages"])

    def _keys(path):
        return [getattr(k, "key", getattr(k, "name", None)) for k in path]

    if gspmd:
        return _finish_gspmd(args, mesh, init_fn, step_fn, params, _keys,
                             H=H, V=V, inner=inner, tp=tp, zero=zero_on)

    def param_spec(path, _leaf):
        keys = _keys(path)
        if "col" in keys:
            return P("pipe", "model")
        if "stages" in keys:
            return P("pipe")
        if vp_on and "head" in keys and "kernel" in keys:
            return P("model")
        return P()

    pspec = jax.tree_util.tree_map_with_path(param_spec, params)

    # Per-rank local param shapes → the amp state (masters, scaler, and
    # fused_adam's FLAT m/v superbuffers) must be created INSIDE shard_map
    # so each rank's optimizer state covers exactly its own shards.
    def local_struct(path, l):
        keys = _keys(path)
        shape = list(l.shape)
        if "col" in keys:
            shape[0] //= pp
            shape[1] //= tp
        elif "stages" in keys:
            shape[0] //= pp
        elif vp_on and "head" in keys and "kernel" in keys:
            shape[0] //= tp
        return jax.ShapeDtypeStruct(tuple(shape), l.dtype)

    local_params = jax.tree_util.tree_map_with_path(local_struct, params)
    state_shapes = jax.eval_shape(init_fn, local_params)

    def state_spec(path, sds):
        keys = _keys(path)
        if "col" in keys:
            return P("pipe", "model")
        if "stages" in keys:
            return P("pipe")
        if vp_on and "head" in keys and "kernel" in keys:
            return P("model")
        if zero_on and ("m_shard" in keys or "v_shard" in keys):
            # ZeRO m/v shard (DistAdamState fields, matched by name):
            # rank-local over data AND (pipe, model)
            return P(("data", "pipe", "model"))
        if keys and keys[-1] in ("m", "v") and len(sds.shape) == 1:
            # flat superbuffer (FusedAdamState.m/.v, matched by field
            # name — ADVICE r3: a coincidental same-size 1-D leaf must
            # not be swept in): rank-local, stacked over the
            # (pipe, model) product on the global axis
            return P(("pipe", "model"))
        return P()

    sspec = jax.tree_util.tree_map_with_path(state_spec, state_shapes)
    sharded_init = jax.jit(shard_map(init_fn, mesh=mesh, in_specs=(pspec,),
                                     out_specs=sspec, check_vma=False))
    state = sharded_init(params)

    sharded = shard_map(step_fn, mesh=mesh,
                        in_specs=(sspec, P("data")),
                        out_specs=(sspec, P()), check_vma=False)
    jit_step = jax.jit(sharded, donate_argnums=(0,))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    return mesh, state, jit_step, n_params


def _finish_gspmd(args, mesh, init_fn, step_fn, params, _keys, *,
                  H, V, inner, tp, zero=False):
    """The GSPMD/pjit tier (SURVEY §3.3 TP row: "pjit with sharded weight
    specs — the mappings collapse into sharding constraints").

    The step is the SAME 1-device program build_parallel_lm composed (tp=1
    module math, no mappings.py collectives, no shard_map); the dp x tp
    distribution comes ENTIRELY from NamedShardings built from the TP
    modules' own ``kernel_partition_spec()``: column kernels P(None,
    'model'), row kernels P('model', None), the embedding table vocab-
    sharded P('model', None), the LM head as a vocab-column parallel
    linear, the batch P('data'). XLA's SPMD partitioner inserts the TP
    all-reduces and the DP grad reduction that the shard_map path spells
    out explicitly — trajectory parity between the two paths and the
    1-device oracle is asserted in tests/distributed/
    test_lm_gspmd.py. fp32 masters ride the same specs as their params.

    ``zero`` (--zero under gspmd) is ZeRO-1 the GSPMD way: the flat
    Adam m/v superbuffers get ``P('data')`` — one spec line, no
    collective code — so each device holds 1/dp of the optimizer state
    (GSPMD pads non-divisible lengths). The shard_map path implements
    the same semantics explicitly (contrib DistributedFusedAdam:
    psum_scatter → shard-local update → all_gather); without ``zero``
    the superbuffers stay replicated.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.transformer.tensor_parallel.layers import (
        ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)

    B, S = args.batch_size, args.seq_len
    # the specs come from the MODULES — these four instances are the
    # single source of truth for how each kernel class shards over tp
    spec_col = ColumnParallelLinear(
        input_size=H, output_size=3 * H,
        world_size=tp).kernel_partition_spec()        # P(None, 'model')
    spec_row = RowParallelLinear(
        input_size=inner, output_size=H,
        world_size=tp).kernel_partition_spec()        # P('model', None)
    spec_emb = VocabParallelEmbedding(
        num_embeddings=V, embedding_dim=H,
        world_size=tp).kernel_partition_spec()        # P('model', None)
    spec_head = ColumnParallelLinear(
        input_size=H, output_size=V,
        world_size=tp).kernel_partition_spec()        # vocab-column head

    matrix_spec = {"qkv_k": spec_col, "mlp_in_k": spec_col,
                   "proj_k": spec_row, "mlp_out_k": spec_row}

    def extend(spec, ndim):
        # col leaves are stacked [L=1, shard=1, layers, <matrix dims>]:
        # the module spec names the trailing matrix dims, leading stack
        # dims stay replicated
        return P(*([None] * (ndim - len(spec)) + list(spec)))

    def leaf_spec(path, leaf):
        keys = _keys(path)
        ndim = len(getattr(leaf, "shape", ()))
        if "col" in keys:
            return extend(matrix_spec[keys[-1]], ndim)
        if "wte" in keys:
            return spec_emb
        if "head" in keys and "kernel" in keys:
            return spec_head
        if zero and keys and keys[-1] in ("m", "v") and ndim == 1:
            # ZeRO-1 as a sharding spec: the flat Adam superbuffers
            # (FusedAdamState.m/.v, matched by field name like the
            # shard_map path's state_spec) live 1/dp per device
            return P("data")
        return P()

    state_shapes = jax.eval_shape(init_fn, params)
    state_sh = jax.tree_util.tree_map_with_path(
        lambda path, sds: NamedSharding(mesh, leaf_spec(path, sds)),
        state_shapes)
    batch_struct = jax.ShapeDtypeStruct((B, S + 1), jnp.int32)
    batch_sh = NamedSharding(mesh, P("data"))
    metrics_shapes = jax.eval_shape(step_fn, state_shapes, batch_struct)[1]
    metrics_sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), metrics_shapes)

    state = jax.jit(init_fn, out_shardings=state_sh)(params)
    jit_step = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, metrics_sh),
                       donate_argnums=(0,))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    return mesh, state, jit_step, n_params


def _stage_order(pp, vpp):
    """Rank-major pipe layout: global row r*vpp + c holds logical stage
    c*pp + r (the interleaved schedule's round-robin split). Shared by the
    scatter in build_parallel_lm and its inverse in canonicalize_params."""
    return np.asarray([c * pp + r for r in range(pp) for c in range(vpp)])


def canonicalize_params(params, *, pp, vpp, heads, vocab_parallel=False):
    """Invert build_parallel_lm's (pipe, model) scatter back to the
    canonical full-weight layout init_params drew from.

    The scatter is pure layout — rank-major stage permutation, explicit tp
    shard dim on the "col" leaves, vocab-column split on the parallel head
    — so two runs at different dp/tp/pp agree iff their canonicalized
    trees agree. This is the reference's cross-rank master-param
    consistency check (SURVEY §5 — amp_master_params/compare.py) in
    functional form: tests and the multichip dryrun compare WHOLE final
    param/master trees, not a loss scalar.
    """
    inv = np.argsort(_stage_order(pp, vpp))

    def unstage(l):
        # global row i holds logical stage order[i]; sort rows into
        # logical-stage order, then flatten [L, per_stage, ...] -> layers
        l = l[inv]
        return l.reshape((l.shape[0] * l.shape[1],) + l.shape[2:])

    col = params["stages"]["col"]
    qkv = col["qkv_k"][inv]            # [L, tp, per_stage, H, 3H/tp]
    Ld, tpd, per_stage, H = qkv.shape[:4]
    d_head = H // heads
    h_local = heads // tpd
    qkv_full = jnp.concatenate(
        [qkv[:, r].reshape(Ld, per_stage, H, 3, h_local, d_head)
         for r in range(tpd)], axis=4)
    proj = col["proj_k"][inv]          # [L, tp, per_stage, H/tp, H]
    proj_full = jnp.concatenate(
        [proj[:, r].reshape(Ld, per_stage, h_local, d_head, H)
         for r in range(tpd)], axis=2)
    mlp_in_full = jnp.concatenate(     # [L, tp, per_stage, H, inner/tp]
        [col["mlp_in_k"][inv][:, r] for r in range(tpd)], axis=-1)
    mlp_out_full = jnp.concatenate(    # [L, tp, per_stage, inner/tp, H]
        [col["mlp_out_k"][inv][:, r] for r in range(tpd)], axis=2)

    def layers_first(l):
        return l.reshape((Ld * per_stage,) + l.shape[2:])

    head = dict(params["head"])
    if vocab_parallel:                 # [tp, H, V/tp] -> [H, V]
        head["kernel"] = jnp.concatenate(
            [head["kernel"][r] for r in range(head["kernel"].shape[0])],
            axis=-1)
    return {
        "emb": params["emb"],
        "stages": {
            "qkv": layers_first(qkv_full),
            "proj": layers_first(proj_full),
            "mlp_in": layers_first(mlp_in_full),
            "mlp_out": layers_first(mlp_out_full),
            **{k: unstage(v) for k, v in params["stages"]["rep"].items()},
        },
        "head": head,
    }


def canonicalize_from_args(params, args):
    """canonicalize_params with the knobs read off the parsed recipe args."""
    from apex_tpu.models.transformer_lm import _LM_SIZES
    heads = _LM_SIZES[args.size][2]
    return canonicalize_params(params, pp=args.pipeline_parallel,
                               vpp=args.virtual_pipeline, heads=heads,
                               vocab_parallel=bool(args.vocab_parallel))


def assert_trees_close(got, want, rtol=2e-4, atol=5e-5):
    """Leaf-for-leaf allclose over whole pytrees, failing with the leaf's
    key path. Shared by the hermetic parity tests and the multichip
    dryrun so both certify the same canonicalized-tree agreement.

    atol is 5e-5, not 1e-5: parallel-vs-sequential reduction order is
    legitimate fp32 roundoff, and the tanh-GELU switch showed single
    elements (1 in 1e5) landing at ~2e-5 — reduction-order noise passed
    through the nonlinearity's curvature, not a parity bug."""
    jax.tree_util.tree_map_with_path(
        lambda path, a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path)),
        got, want)


def run_parallel(args, policy, on_step=None):
    """The dp x tp x pp training loop. ``on_step(it, metrics)``, when
    given, sees every step's metrics right after dispatch (a caller
    that wants per-step wall time blocks on them there)."""
    if args.iters < 1:
        raise SystemExit("--iters must be >= 1")
    if args.remat:
        raise SystemExit("--remat is not supported on the model-parallel "
                         "path (the 1F1B schedule already recomputes "
                         "in-backward); drop the flag")
    tele = _maybe_telemetry(args)   # sink must exist before the first step
    mesh, state, jit_step, n_params = build_parallel_lm(args, policy)
    print(f"=> LM {args.size} dp={args.data_parallel} "
          f"tp={args.tensor_parallel} pp={args.pipeline_parallel} "
          f"vpp={args.virtual_pipeline}"
          f"{' sp' if args.sequence_parallel else ''}"
          f"{' vocab-parallel' if args.vocab_parallel else ''}"
          f"{' zero' if args.zero else ''}"
          f"{' gspmd' if args.partitioning == 'gspmd' else ''}, "
          f"params: {n_params:,}")
    data = None
    if args.data:
        data = load_token_stream(args.data, args.vocab_size, args.seq_len)
    rng = jax.random.PRNGKey(args.seed)
    state, start_it, rng = _maybe_resume(args, state, rng)
    t0, toks, metrics = None, 0, None
    loss_history = []
    with mesh:
        for it in range(start_it, args.iters):
            with tracing.phase("train.turn", it=it):
                rng, sub = jax.random.split(rng)
                if args.deterministic:
                    sub = jax.random.PRNGKey(it)
                if data is not None:
                    batch = data_batch(data, sub, args.batch_size,
                                       args.seq_len)
                else:
                    batch = synthetic_tokens(sub, args.batch_size,
                                             args.seq_len,
                                             args.vocab_size)
                with tracing.phase("train.dispatch"):
                    state, metrics = jit_step(state, batch)
                loss_history.append(metrics["loss"])
                if on_step is not None:
                    with tracing.phase("train.on_step"):
                        on_step(it, metrics)
                if it == start_it + 2:
                    metrics["loss"].block_until_ready()
                    t0 = time.perf_counter()
                    toks = 0
                toks += args.batch_size * args.seq_len
                if it % 10 == 0 or it == args.iters - 1:
                    with tracing.phase("train.log"):
                        print(f"[{it}/{args.iters}] loss "
                              f"{float(metrics['loss']):.4f} loss_scale "
                              f"{float(metrics['loss_scale']):g}")
    jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
    if t0 is not None and args.iters - start_it > 3:
        dt = time.perf_counter() - t0
        print(f"throughput: "
              f"{(toks - args.batch_size * args.seq_len) / dt:,.0f} tokens/s")
    _maybe_prof_device(args, jit_step, state, batch)
    _maybe_save(args, state, rng)
    _finish_telemetry(tele)
    metrics = dict(metrics)
    metrics["final_state"] = state
    # one device-to-host transfer for the whole history
    metrics["loss_history"] = np.asarray(jnp.stack(loss_history),
                                         np.float32).tolist()
    return metrics


def _maybe_telemetry(args):
    """--telemetry SPEC: fresh default registry + sink (JSONL path,
    'stdout', 'null'); the step's in-jit emission lands there."""
    if not args.telemetry:
        return None
    from apex_tpu import telemetry
    return telemetry.start_run(args.telemetry)


def _finish_telemetry(tele):
    if tele is None:
        return
    jax.effects_barrier()      # flush in-flight step callbacks
    tele.emit_snapshot()       # final aggregate + comm-health line
    tele.close()


def _maybe_resume(args, state, rng):
    """--resume via the shared helper (jit re-shards the restored host
    arrays per the step's in_specs on entry, so the same call serves the
    single-chip and shard_mapped paths)."""
    if not args.resume:
        return state, 0, rng
    from apex_tpu.utils.checkpoint import resume_train_checkpoint
    return resume_train_checkpoint(args.resume, state, rng,
                                   step_limit=args.iters,
                                   limit_flag="--iters")


def _maybe_prof_device(args, jit_step, state, batch):
    """--prof-device N: print device tokens/s for N extra steps via
    pyprof.device_throughput_line (observation-only — copied state,
    never raises; see pyprof.step_device_throughput's docstring)."""
    from apex_tpu import pyprof

    line = pyprof.device_throughput_line(
        jit_step, state, batch, args.prof_device,
        args.batch_size * args.seq_len, "tokens/s")
    if line:
        print(line)


def _maybe_save(args, state, rng):
    if not args.save:
        return
    from apex_tpu.utils.checkpoint import save_train_checkpoint
    save_train_checkpoint(args.save, state, args.iters, rng)


def _maybe_generate(args, model, params, tele):
    """--generate N: serve synthetic variable-length prompts through the
    compiled KV-cache engine (apex_tpu.serving) with the just-trained
    params — the recipe's end-to-end inference leg. Returns the
    completed requests, the model and ``Engine`` geometry keywords they
    were served with, the Pallas kernels each serving program holds and
    the bytes it keeps beside its operands (for callers inspecting the
    outputs or checking them against the model's plain forward), or None
    without --generate."""
    if not args.generate:
        return None
    import numpy as _np

    from apex_tpu import serving

    plen = min(args.gen_prompt_len, args.seq_len - 1)
    max_len = min(args.seq_len, plen + args.generate)
    engine = serving.Engine(model, params, slots=args.gen_slots,
                            max_len=max_len, prefill_len=plen,
                            top_k=args.gen_top_k, registry=tele)
    sched = serving.Scheduler(engine, registry=tele,
                              max_queue=max(args.gen_prompts, 1))
    rng = _np.random.default_rng(args.seed)
    reqs = [serving.Request(
        prompt=rng.integers(1, args.vocab_size,
                            size=int(rng.integers(1, plen + 1))).tolist(),
        max_new_tokens=args.generate, temperature=args.gen_temperature)
        for _ in range(args.gen_prompts)]
    t0 = time.perf_counter()
    done = sched.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.output_tokens) for r in done)
    ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
    print(f"=> generate: {len(done)} requests, {toks} tokens in "
          f"{dt:.2f}s ({toks / dt:,.0f} tokens/s), "
          f"ttft p50 {sorted(ttfts)[len(ttfts) // 2] * 1e3:.1f} ms, "
          f"compiled programs: {engine.compiled_programs}")
    kernels = engine.program_kernels()
    print(f"=> serving programs (paged, page_len {engine.page_len}, "
          f"chunk_len {engine.chunk_len}): " + "; ".join(
              f"{name}: {chip.format_kernels(k)}"
              for name, k in kernels.items()))
    memory = engine.program_memory()
    pool_bytes = engine.cache.nbytes()
    print(f"=> serving programs beside a KV pool of "
          f"{pool_bytes / 2**20:.1f} MiB: " + "; ".join(
              f"{name}: temporaries {m['temp_bytes'] / 2**20:.1f} MiB, "
              f"{m['alias_bytes'] / 2**20:.1f} MiB updated in place"
              for name, m in memory.items()))
    preview = done[0]
    print(f"   sample [{preview.finish_reason}]: "
          f"{list(preview.prompt)[:8]}... -> "
          f"{preview.output_tokens[:16]}")
    return {"requests": done, "kernels": kernels, "seconds": dt,
            "memory": memory, "pool_bytes": pool_bytes,
            "model": model, "page_len": engine.page_len,
            "geometry": {"slots": engine.slots, "max_len": engine.max_len,
                         "prefill_len": engine.prefill_len,
                         "chunk_len": engine.chunk_len}}


def main(argv=None, on_step=None):
    """Train (and with --generate, serve). ``on_step(it, metrics)``,
    when given, sees every train step's metrics right after dispatch
    (a caller that wants per-step wall time blocks on them there)."""
    args = parse_args(argv)
    if args.iters < 1:
        raise SystemExit("--iters must be >= 1")
    if args.accum_steps < 1:
        raise SystemExit("--accum-steps must be >= 1")
    if args.generate and (args.gen_prompts < 1 or args.gen_slots < 1
                          or args.gen_prompt_len < 1):
        raise SystemExit("--generate needs --gen-prompts, --gen-slots and "
                         "--gen-prompt-len all >= 1")
    if args.batch_size % args.accum_steps:
        raise SystemExit(f"--batch-size {args.batch_size} must divide by "
                         f"--accum-steps {args.accum_steps}")
    policy = amp.resolve_policy(opt_level=args.opt_level,
                                loss_scale=args.loss_scale)
    print(policy.banner())
    if (args.data_parallel * args.tensor_parallel
            * args.pipeline_parallel * args.virtual_pipeline) > 1:
        if args.generate:
            raise SystemExit(
                "--generate runs on the single-chip path only (the "
                "serving engine consumes the flax param tree, not the "
                "parallel tiers' scattered stage layout); drop the "
                "parallelism flags or serve from a --save checkpoint")
        if args.accum_steps > 1:
            raise SystemExit(
                "--accum-steps composes with the single-chip path only: "
                "the parallel tiers drive amp via grad_fn (1F1B / "
                "no-pipelining schedules), which already accumulate over "
                "--microbatches — raise --microbatches there instead")
        if args.fused_head and not args.vocab_parallel:
            raise SystemExit("--fused-head under the parallel tiers "
                             "needs --vocab-parallel AND "
                             "--tensor-parallel >= 2 (the fused op's "
                             "axis_name mode shards the head over "
                             "'model'); without them the replicated-"
                             "head tail keeps the materialized loss")
        if args.fused_head and getattr(args, "partitioning",
                                       "shard_map") == "gspmd":
            raise SystemExit("--fused-head is shard_map-only under "
                             "parallelism (gspmd keeps the materialized "
                             "vocab-parallel loss)")
        return run_parallel(args, policy, on_step)
    if args.partitioning == "gspmd":
        raise SystemExit("--partitioning gspmd needs a mesh: pass "
                         "--data-parallel and/or --tensor-parallel > 1")

    model = create_lm(args.size, vocab_size=args.vocab_size,
                      max_seq_len=args.seq_len, remat=args.remat,
                      dtype=policy.model_dtype)
    rng = jax.random.PRNGKey(args.seed)
    sample = jnp.zeros((2, args.seq_len), jnp.int32)
    params = model.init(rng, sample, train=False)["params"]

    optimizer = fused_adam(args.lr, weight_decay=args.weight_decay,
                           adam_w_mode=True)

    if args.fused_head:
        from apex_tpu.amp.autocast import resolve_dtype
        from apex_tpu.kernels.lm_head_loss import lm_head_xentropy
        head_dtype = resolve_dtype(policy.model_dtype, "linear",
                                   jnp.float32)

        def loss_fn(p, batch):
            tokens = batch
            hidden = model.apply({"params": p}, tokens[:, :-1], train=True,
                                 features_only=True)
            losses = lm_head_xentropy(hidden, p["wte"]["embedding"],
                                      tokens[:, 1:],
                                      smoothing=args.smoothing,
                                      compute_dtype=head_dtype)
            return losses.mean()
    else:
        def loss_fn(p, batch):
            tokens = batch
            logits = model.apply({"params": p}, tokens[:, :-1], train=True)
            losses = softmax_cross_entropy_loss(logits, tokens[:, 1:],
                                                smoothing=args.smoothing)
            return losses.mean()

    tele = _maybe_telemetry(args)
    init_fn, step_fn = amp.make_train_step(loss_fn, optimizer, policy,
                                           telemetry=tele is not None,
                                           accum_steps=args.accum_steps)
    state = init_fn(params)
    jit_step = jax.jit(step_fn, donate_argnums=(0,))

    data = None
    if args.data:
        data = load_token_stream(args.data, args.vocab_size, args.seq_len)

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"=> LM {args.size}, params: {n_params:,}")

    state, start_it, rng = _maybe_resume(args, state, rng)
    t0 = None
    toks = 0
    metrics = None
    loss_history = []
    compiled = kernels = None
    for it in range(start_it, args.iters):
        # the turn's regions are ``with`` blocks in THIS frame, not a
        # helper: a caller's on_step may read main()'s locals (state,
        # batch, compiled) through the frame it was called from
        with tracing.phase("train.turn", it=it):
            rng, sub = jax.random.split(rng)
            if args.deterministic:
                sub = jax.random.PRNGKey(it)
            if data is not None:
                batch = data_batch(data, sub, args.batch_size,
                                   args.seq_len)
            else:
                batch = synthetic_tokens(sub, args.batch_size,
                                         args.seq_len, args.vocab_size)
            # [B, S+1] → [N, B/N, S+1]: the microbatch scan axis of
            # make_train_step(accum_steps=N); identity at N=1
            batch = amp.to_microbatches(batch, args.accum_steps)
            if compiled is None:
                # one ahead-of-time compile, so the recipe can say which
                # fused kernels the step really holds on this backend
                compiled, kernels, line = chip.compile_and_report(
                    "LM train step", jit_step, state, batch)
                print(line)
            with tracing.phase("train.dispatch"):
                state, metrics = compiled(state, batch)
            loss_history.append(metrics["loss"])
            if on_step is not None:
                with tracing.phase("train.on_step"):
                    on_step(it, metrics)
            if it == start_it + 4:
                metrics["loss"].block_until_ready()
                t0 = time.perf_counter()
                toks = 0
            toks += args.batch_size * args.seq_len
            if it % 10 == 0 or it == args.iters - 1:
                with tracing.phase("train.log"):
                    print(f"[{it}/{args.iters}] loss "
                          f"{float(metrics['loss']):.4f} "
                          f"loss_scale {float(metrics['loss_scale']):g}")
    jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
    if t0 is not None and args.iters - start_it > 5:
        dt = time.perf_counter() - t0
        print(f"throughput: "
              f"{(toks - args.batch_size * args.seq_len) / dt:,.0f} tokens/s")
    if metrics is None:
        _finish_telemetry(tele)
        return None
    _maybe_prof_device(args, compiled, state, batch)
    _maybe_save(args, state, rng)
    generated = _maybe_generate(args, model, state.params, tele)
    _finish_telemetry(tele)
    metrics = dict(metrics)
    metrics["final_state"] = state
    metrics["kernels"] = kernels
    metrics["generate"] = generated
    # one device-to-host transfer for the whole history
    metrics["loss_history"] = np.asarray(jnp.stack(loss_history),
                                         np.float32).tolist()
    return metrics


if __name__ == "__main__":
    print(f"=> compile cache: {chip.enable_compile_cache()}")
    main()
