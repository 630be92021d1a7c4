"""Long-context LM recipe — context-parallel ring attention end to end.

The reference has no long-context distribution (SURVEY §6: Megatron-SP is
its only sequence-scaling mechanism); this recipe shows the framework's
beyond-parity answer: a causal LM whose SEQUENCE is sharded over a
``context`` mesh axis, attention computed exactly with
:func:`apex_tpu.transformer.context_parallel.ring_attention` (KV rotating
around the ring via ppermute, zigzag layout balancing the causal work),
composed with amp mixed precision and the fused LN/xentropy kernels.

Every rank holds seq_len/ring_size tokens: the attention memory AND the
activation memory per chip stay flat as sequence length scales with the
ring — the point of context parallelism.

Run hermetically (8 virtual devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context/main_amp.py --ring 4 --seq-len 2048
"""

from __future__ import annotations

import os as _os
import sys as _sys

_REPO_ROOT = _os.path.abspath(_os.path.join(_os.path.dirname(__file__),
                                            _os.pardir, _os.pardir))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import flax.linen as nn

from apex_tpu import amp, comm
from apex_tpu.utils.compat import shard_map
from apex_tpu.kernels.xentropy import softmax_cross_entropy_loss
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.transformer.context_parallel import (ring_attention,
                                                   ulysses_attention,
                                                   zigzag_order)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="context-parallel LM recipe")
    p.add_argument("--ring", type=int, default=4,
                   help="context-axis size (ring width)")
    p.add_argument("--seq-len", type=int, default=2048,
                   help="GLOBAL sequence length (local = seq/ring)")
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("-b", "--batch-size", type=int, default=2)
    p.add_argument("--data-parallel", type=int, default=1, metavar="DP",
                   help="DDP over a 'data' axis composed OUTSIDE the "
                        "context ring (mesh [data, context]; grads "
                        "averaged over both axes)")
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--layout", default="zigzag",
                   choices=["zigzag", "contiguous"])
    p.add_argument("--attn", default="ring", choices=["ring", "ulysses"],
                   help="ring: KV rotates via ppermute; ulysses: "
                        "all-to-all head scatter (needs heads %% ring == 0)")
    p.add_argument("--data", default=None,
                   help="pre-tokenized int32 .npy token stream — the "
                        "fixed training batch becomes real long-context "
                        "windows instead of uniform noise")
    return p.parse_args(argv)


class RingBlock(nn.Module):
    """Pre-LN block whose attention runs over the context ring. Must be
    applied inside shard_map with the 'context' axis bound; x is the LOCAL
    sequence shard [B, s_local, H]."""

    hidden: int
    heads: int
    layout: str
    attn: str = "ring"
    # policy.model_dtype from the recipe: half under O2/O3, None under O1
    # (the autocast engine's per-op table decides), fp32 under O0.
    dtype: object = None

    @nn.compact
    def __call__(self, x):
        from apex_tpu.amp.autocast import resolve_dtype

        dtype = resolve_dtype(self.dtype, "linear", jnp.float32)
        B, S, H = x.shape
        d = self.hidden // self.heads
        h = FusedLayerNorm(normalized_shape=H, name="ln_attn")(x)
        qkv = nn.Dense(3 * H, dtype=dtype, name="qkv")(h)
        qkv = qkv.reshape(B, S, 3, self.heads, d)
        q, k, v = (jnp.moveaxis(qkv[:, :, i], 1, 2) for i in range(3))
        if self.attn == "ulysses":
            out = ulysses_attention(q, k, v, causal=True)
        else:
            out = ring_attention(q, k, v, causal=True, layout=self.layout)
        out = jnp.moveaxis(out, 1, 2).reshape(B, S, H)
        x = x + nn.Dense(H, dtype=dtype, name="proj")(out)
        h = FusedLayerNorm(normalized_shape=H, name="ln_mlp")(x)
        h = nn.Dense(4 * H, dtype=dtype, name="mlp_in")(h)
        h = nn.gelu(jnp.asarray(h, jnp.float32), approximate=True)
        h = nn.Dense(H, dtype=dtype, name="mlp_out")(
            jnp.asarray(h, dtype))
        return x + h


class RingLM(nn.Module):
    vocab: int
    hidden: int
    layers: int
    heads: int
    max_seq: int
    layout: str
    attn: str = "ring"
    dtype: object = None  # threaded into every RingBlock

    @nn.compact
    def __call__(self, tokens, positions):
        """tokens/positions: LOCAL shards [B, s_local] (positions carry the
        zigzag permutation so embeddings match the attention layout)."""
        wte = nn.Embed(self.vocab, self.hidden, name="wte")
        wpe = self.param("wpe", nn.initializers.normal(stddev=0.02),
                         (self.max_seq, self.hidden), jnp.float32)
        x = wte(tokens) + wpe[positions]
        for i in range(self.layers):
            x = RingBlock(self.hidden, self.heads, self.layout, self.attn,
                          dtype=self.dtype, name=f"block_{i}")(x)
        x = FusedLayerNorm(normalized_shape=self.hidden, name="ln_f")(x)
        return wte.attend(jnp.asarray(x, jnp.float32))


def main(argv=None):
    args = parse_args(argv)
    policy = amp.resolve_policy(opt_level=args.opt_level)
    dp = args.data_parallel
    if dp < 1:
        raise SystemExit(f"--data-parallel must be >= 1, got {dp}")
    if args.batch_size % dp:
        raise SystemExit(f"--batch-size {args.batch_size} must divide by "
                         f"--data-parallel {dp}")
    devices = comm.ensure_devices(dp * args.ring)
    mesh = Mesh(np.array(devices[:dp * args.ring]).reshape(dp, args.ring),
                ("data", "context"))
    comm.set_mesh(mesh)
    S, n = args.seq_len, args.ring
    if args.attn == "ulysses":
        # ulysses permutes heads, not the sequence: contiguous layout only
        args.layout = "contiguous"
        if args.heads % n:
            raise SystemExit(f"--attn ulysses needs heads % ring == 0 "
                             f"({args.heads} % {n})")
    chunk = 2 * n if args.layout == "zigzag" else n
    if S % chunk:
        raise SystemExit(f"--seq-len must divide by {chunk} "
                         f"({args.layout} chunks over a ring of {n})")
    model = RingLM(args.vocab, args.hidden, args.layers, args.heads,
                   max_seq=S, layout=args.layout, attn=args.attn,
                   dtype=policy.model_dtype)

    # zigzag layout: permute the GLOBAL sequence once on the host; each
    # rank then owns balanced front+back chunks of the causal triangle
    order = (np.asarray(zigzag_order(S, n)) if args.layout == "zigzag"
             else np.arange(S))
    positions = jnp.asarray(order)[None].repeat(args.batch_size, 0)

    rng = np.random.RandomState(0)
    if args.data:
        # real windows from a token stream (the LM recipe's validated
        # loader — out-of-vocab ids rejected, not clamped); targets are
        # the TRUE next tokens, though position S-1 stays masked below
        # so both data sources train the identical objective
        from examples.lm.main_amp import load_token_stream
        stream = load_token_stream(args.data, args.vocab, S)
        starts = rng.randint(0, len(stream) - S, size=args.batch_size)
        win = np.stack([stream[st:st + S + 1] for st in starts])
        tokens_global = win[:, :S].astype(np.int32)
        targets_global = win[:, 1:].astype(np.int32)
    else:
        tokens_global = rng.randint(
            0, args.vocab, size=(args.batch_size, S)).astype(np.int32)
        # next-token targets in GLOBAL order, permuted like the inputs
        targets_global = np.roll(tokens_global, -1, axis=1)
    tokens = jnp.asarray(tokens_global[:, order])
    targets = jnp.asarray(targets_global[:, order])

    def loss_fn(params, batch):
        toks, tgts, pos = batch
        logits = model.apply({"params": params}, toks, pos)
        losses = softmax_cross_entropy_loss(
            logits.reshape(-1, args.vocab), tgts.reshape(-1))
        # mask the final global position (no next token); its zigzag slot
        # lives wherever position == S-1. Per-rank valid counts are
        # UNEQUAL (one rank owns S-1), so normalize by the psum'd GLOBAL
        # count — a mean of per-rank means would over-weight that rank's
        # tokens. The ring-size factor makes grad_average_axis's pmean
        # recover exactly the global-mean gradient.
        valid = (pos.reshape(-1) != S - 1)
        local_sum = jnp.sum(jnp.where(valid, losses, 0.0))
        global_cnt = jax.lax.psum(jnp.sum(valid), "context")
        ring = jax.lax.psum(1, "context")
        return ring * local_sum / global_cnt

    from apex_tpu.optimizers.fused_adam import fused_adam

    # grad_average_axis: params are REPLICATED over the ring while each
    # rank's loss covers only its sequence shard — grads must be averaged
    # over the context axis (Megatron-SP's grad allreduce for sequence-
    # parallel regions) or every rank trains on a different objective
    # the average spans BOTH axes (make_train_step accepts axis tuples):
    # mean over per-data-shard means, each shard's mean already exact over
    # its ring (the reference DDP objective); at dp=1 the data axis has
    # size 1 and the extra pmean is the identity
    init_fn, step_fn = amp.make_train_step(
        loss_fn, fused_adam(args.lr), policy,
        grad_average_axis=("data", "context"))

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(), (P("data", "context"),
                                       P("data", "context"),
                                       P("data", "context"))),
                       out_specs=(P(), P()), check_vma=False)
    def sharded_step(state, batch):
        new_state, metrics = step_fn(state, batch)
        return new_state, metrics["loss"]

    # init under shard_map: ring_attention traces collectives, so the
    # context axis must be bound even at init (params come out identical
    # on every rank — same key, rank-independent shapes)
    s_local = S // n

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("data", "context"), P("data", "context")),
                       out_specs=P(), check_vma=False)
    def init_params(toks, pos):
        return model.init(jax.random.PRNGKey(0), toks, pos)["params"]

    params = init_params(tokens, positions)
    n_params = sum(np.prod(p.shape)
                   for p in jax.tree_util.tree_leaves(params))
    print(f"=> ring={n} dp={dp} layout={args.layout} global seq {S} "
          f"(local {s_local}), params {n_params:,}")
    state = jax.device_put(init_fn(params), NamedSharding(mesh, P()))
    sharding = NamedSharding(mesh, P("data", "context"))
    batch = tuple(jax.device_put(t, sharding)
                  for t in (tokens, targets, positions))

    jit_step = jax.jit(sharded_step)
    t0 = None
    for it in range(args.iters):
        state, loss = jit_step(state, batch)
        if it == 0:
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
        print(f"[{it}] loss {float(loss):.4f}")
    jax.block_until_ready(loss)
    if args.iters > 1:
        dt = time.perf_counter() - t0
        tok_s = args.batch_size * S * (args.iters - 1) / dt
        kind = args.attn if args.attn == "ulysses" else args.layout
        print(f"=> {tok_s:.0f} tokens/s ({kind} ring of {n})")
    return float(loss)


if __name__ == "__main__":
    from apex_tpu.utils.chip import enable_compile_cache

    print(f"=> compile cache: {enable_compile_cache()}")
    main()
