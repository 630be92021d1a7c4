"""Detectron-style SyncBN training shape (BASELINE config 5).

Reference context: the driver's config 5 is "SyncBatchNorm multi-chip
(Detectron-style Mask R-CNN)". The training characteristics that make that
workload exercise apex are: tiny per-chip batches (2 images) where
BatchNorm statistics are meaningless without cross-chip sync, a conv-heavy
FPN backbone, multi-scale feature maps, and amp+DDP composition. This
example reproduces exactly those characteristics — an FPN over a strided
conv backbone with SyncBatchNorm at every norm site, a dense per-pixel
head (the mask-head training shape), amp O0–O3, and DDP over a `data`
mesh axis — without dragging in box/ROI machinery that exercises nothing
apex-related.

Run (single chip):    python examples/detection/main_amp.py --iters 8
Hermetic multi-chip:  JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/detection/main_amp.py --data-parallel 8 --iters 4
"""

from __future__ import annotations

import argparse
import functools
import os as _os
import sys as _sys
import time
from typing import Any

# run as a script from anywhere: put the repo root on sys.path
_REPO_ROOT = _os.path.abspath(
    _os.path.join(_os.path.dirname(__file__), "..", ".."))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)

import jax
import jax.numpy as jnp
import numpy as np
import optax
import flax.linen as nn

from apex_tpu import amp
from apex_tpu.parallel import SyncBatchNorm
from apex_tpu.utils.compat import shard_map


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--opt-level", default="O2")
    p.add_argument("-b", "--batch-size", type=int, default=2,
                   help="per-chip batch (detection-typical: 2)")
    p.add_argument("--image-size", type=int, default=256)
    p.add_argument("--num-classes", type=int, default=21)
    p.add_argument("--iters", type=int, default=20,
                   help="training iterations (>= 1)")
    p.add_argument("--lr", type=float, default=0.02)
    p.add_argument("--data-parallel", type=int, default=1)
    p.add_argument("--no-sync-bn", action="store_true",
                   help="plain BatchNorm (shows why SyncBN matters at b=2)")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


class ConvStage(nn.Module):
    """Two 3x3 convs + norm + relu, downsampling by 2 (a bottleneck-stage
    stand-in: conv-heavy, norm at every site like Detectron backbones)."""

    features: int
    norm: Any

    @nn.compact
    def __call__(self, x, train: bool):
        x = nn.Conv(self.features, (3, 3), strides=(2, 2), use_bias=False,
                    dtype=x.dtype)(x)
        x = self.norm()(x, use_running_average=not train)
        x = nn.relu(x)
        y = nn.Conv(self.features, (3, 3), use_bias=False, dtype=x.dtype)(x)
        y = self.norm()(y, use_running_average=not train)
        return nn.relu(x + y)                    # residual


class FPNSegModel(nn.Module):
    """FPN backbone + dense per-pixel head (the mask-head training shape)."""

    num_classes: int
    norm: Any
    dtype: Any = jnp.float32
    widths: tuple = (32, 64, 128, 256)           # C2..C5
    fpn_width: int = 64

    @nn.compact
    def __call__(self, images, train: bool = True):
        x = jnp.asarray(images, self.dtype)
        feats = []
        for w in self.widths:
            x = ConvStage(w, self.norm)(x, train)
            feats.append(x)                       # strides 2, 4, 8, 16

        # FPN: lateral 1x1 + top-down upsample-add, smoothing 3x3
        laterals = [nn.Conv(self.fpn_width, (1, 1), dtype=self.dtype)(f)
                    for f in feats]
        p = laterals[-1]
        pyramid = [p]
        for lat in laterals[-2::-1]:
            b, h, w_, c = lat.shape
            p = jax.image.resize(p, (b, h, w_, c), "nearest") + lat
            pyramid.append(p)
        pyramid = [nn.Conv(self.fpn_width, (3, 3), dtype=self.dtype)(t)
                   for t in pyramid[::-1]]        # P2..P5 (fine→coarse)

        # dense head on the finest level (mask-head shape: convs + norm)
        h = pyramid[0]
        for _ in range(2):
            h = nn.Conv(self.fpn_width, (3, 3), use_bias=False,
                        dtype=self.dtype)(h)
            h = self.norm()(h, use_running_average=not train)
            h = nn.relu(h)
        logits = nn.Conv(self.num_classes, (1, 1), dtype=jnp.float32)(h)
        # upsample to input resolution (per-pixel supervision)
        b, hh, ww, c = logits.shape
        full = images.shape[1]
        return jax.image.resize(logits, (b, full, full, c), "nearest")


def main(argv=None):
    args = parse_args(argv)
    if args.iters < 1:
        raise SystemExit("--iters must be >= 1")
    if args.data_parallel > 1:
        from apex_tpu import comm as _comm
        _comm.ensure_devices(args.data_parallel)
    policy = amp.resolve_policy(opt_level=args.opt_level,
                                loss_scale="dynamic")
    print(policy.banner())

    axis_name = "data" if args.data_parallel > 1 else None
    bn_axis = None if args.no_sync_bn else axis_name
    norm = functools.partial(SyncBatchNorm, axis_name=bn_axis,
                             dtype=jnp.float32)

    model = FPNSegModel(num_classes=args.num_classes, norm=norm,
                        dtype=policy.model_dtype)
    rng = jax.random.PRNGKey(args.seed)
    sample = jnp.zeros((1, args.image_size, args.image_size, 3),
                       jnp.float32)
    variables = model.init(rng, sample, train=True)
    params = variables["params"]
    model_state = {k: v for k, v in variables.items() if k != "params"}

    def loss_fn(p, mstate, batch):
        images, labels = batch
        logits, updated = model.apply(
            {"params": p, **mstate}, images, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            jnp.asarray(logits, jnp.float32), labels).mean()
        return loss, updated

    optimizer = optax.sgd(args.lr, momentum=0.9)
    init_fn, step_fn = amp.make_train_step(
        loss_fn, optimizer, policy, with_model_state=True,
        grad_average_axis=axis_name)
    state = init_fn(params, model_state)

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"=> FPN-seg model, params: {n_params:,}, "
          f"sync_bn={'off' if args.no_sync_bn else 'on'}")

    if axis_name is not None:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from apex_tpu import comm
        mesh = comm.make_mesh({"data": args.data_parallel})
        state = jax.device_put(state, NamedSharding(mesh, P()))
        jit_step = jax.jit(shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), (P("data"), P("data"))),
            out_specs=P(), check_vma=False))
        global_batch = args.batch_size * args.data_parallel
        batch_sharding = (NamedSharding(mesh, P("data")),
                          NamedSharding(mesh, P("data")))
    else:
        jit_step = jax.jit(step_fn)
        global_batch = args.batch_size
        batch_sharding = None

    t0 = None
    for it in range(args.iters):
        key = jax.random.PRNGKey(1000 + it)
        images = jax.random.normal(
            key, (global_batch, args.image_size, args.image_size, 3),
            jnp.float32)
        labels = jax.random.randint(
            jax.random.fold_in(key, 1),
            (global_batch, args.image_size, args.image_size), 0,
            args.num_classes)
        batch = (images, labels)
        if batch_sharding is not None:
            batch = jax.device_put(batch, batch_sharding)
        state, metrics = jit_step(state, batch)
        if it == 1:
            metrics["loss"].block_until_ready()
            t0 = time.perf_counter()
            done = 0
        if it >= 2:
            done = it - 1
        if it % 5 == 0 or it == args.iters - 1:
            print(f"[{it}/{args.iters}] loss {float(metrics['loss']):.4f} "
                  f"loss_scale {float(state.scaler.loss_scale):.0f}")
    metrics["loss"].block_until_ready()
    if t0 is not None and done > 0:
        rate = done * global_batch / (time.perf_counter() - t0)
        print(f"=> {rate:.1f} img/s (global batch {global_batch})")


if __name__ == "__main__":
    from apex_tpu.utils.chip import enable_compile_cache

    print(f"=> compile cache: {enable_compile_cache()}")
    main()
