"""ImageNet recipe — the framework's canonical end-to-end example.

Mirrors the reference recipe (examples/imagenet/main_amp.py — main/train/
data_prefetcher/adjust_learning_rate/accuracy) argument-for-argument where it
makes sense on TPU:

- ``--arch``/``-b``/``--lr``/``--momentum``/``--weight-decay``/``--epochs``
- ``--opt-level O0..O3``, ``--loss-scale``, ``--keep-batchnorm-fp32``
- ``--sync_bn`` converts BatchNorm to SyncBatchNorm over the data axis
- ``--prof N`` profiles N iterations (jax.profiler trace instead of nvtx)
- ``--deterministic`` fixes seeds and data

TPU-first differences: no DistributedDataParallel wrapper object — data
parallelism is a mesh axis handed to amp.make_train_step(grad_average_axis=
"data") and batch sharding; no data_prefetcher side-stream — synthetic batches
are generated on device, and real input pipelines belong to grain/tf.data
outside this library's scope. Throughput is printed as img/s, the driver's
north-star unit.
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
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from apex_tpu import amp
from apex_tpu.models import create_model
from apex_tpu.utils import chip
from apex_tpu.utils.compat import shard_map


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu ImageNet recipe")
    p.add_argument("data", nargs="?", default=None,
                   help="dataset path (unused for --synthetic, the default)")
    p.add_argument("--arch", "-a", default="resnet18")
    p.add_argument("-b", "--batch-size", type=int, default=128)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--iters", type=int, default=50,
                   help="iterations per epoch for synthetic data")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--opt-level", default="O0")
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--keep-batchnorm-fp32", default=None)
    p.add_argument("--sync_bn", action="store_true")
    p.add_argument("--prof-device", type=int, default=0, metavar="N",
                   help="after training, time N extra steps on the "
                        "profiler's DEVICE lanes and print device img/s "
                        "(observation-only — runs on a copy of the "
                        "state; n/a without device lanes)")
    p.add_argument("--prof", type=int, default=0)
    p.add_argument("--accum-steps", type=int, default=1, metavar="N",
                   help="in-jit microbatch gradient accumulation "
                        "(amp.make_train_step accum_steps): each optimizer "
                        "step scans N microbatches of batch-size/N, paying "
                        "ONE grad allreduce + unscale + scaler update per "
                        "window — apex's delay_unscale recipe, compiled. "
                        "Composes with --data-parallel (the microbatch "
                        "rows shard over the data mesh)")
    p.add_argument("--telemetry", default=None, metavar="SPEC",
                   help="stream per-step telemetry (loss, grad norm, "
                        "scaler trajectory, step time) from inside the "
                        "jitted step: JSONL path, 'stdout', or 'null'; "
                        "summarize with python -m apex_tpu.telemetry")
    p.add_argument("--deterministic", action="store_true")
    p.add_argument("--resume", default=None,
                   help="checkpoint file (or dir: newest ckpt) to resume")
    p.add_argument("--checkpoint-dir", default=None,
                   help="save ckpt_{epoch}.npz here after each epoch")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--synthetic", action="store_true", default=True)
    p.add_argument("--host-data", action="store_true",
                   help="generate batches on host and feed them through "
                        "data_prefetcher (exercises the real-data "
                        "host->device path with copy/compute overlap)")
    p.add_argument("--data-parallel", type=int, default=1,
                   help="size of the data mesh axis (devices)")
    return p.parse_args(argv)


def build_policy(args):
    overrides = {}
    if args.loss_scale is not None:
        overrides["loss_scale"] = (
            args.loss_scale if args.loss_scale == "dynamic"
            else float(args.loss_scale))
    if args.keep_batchnorm_fp32 is not None:
        overrides["keep_batchnorm_fp32"] = args.keep_batchnorm_fp32
    return amp.resolve_policy(opt_level=args.opt_level, **overrides)


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def topk_hits(logits, labels, ks=(1, 5)):
    """Per-batch top-k hit counts via one lax.top_k(max(ks)) — shared by
    training metrics and validate()."""
    kmax = min(max(ks), logits.shape[-1])
    _, top = jax.lax.top_k(logits, kmax)
    return [jnp.sum(jnp.any(top[:, :min(k, kmax)] == labels[:, None],
                            axis=1))
            for k in ks]


def topk_accuracy(logits, labels, ks=(1, 5)):
    """examples/imagenet/main_amp.py — accuracy(output, target, topk)."""
    n = labels.shape[0]
    return [100.0 * h.astype(jnp.float32) / n
            for h in topk_hits(logits, labels, ks)]


def adjust_learning_rate(base_lr, epoch, steps_per_epoch):
    """Step schedule of the reference recipe: /10 at epochs 30, 60, 80."""
    def schedule(count):
        ep = count // steps_per_epoch
        factor = ((ep >= 30).astype(jnp.float32) + (ep >= 60) + (ep >= 80))
        return base_lr * (0.1 ** factor)
    return schedule


def make_loss_fn(model):
    def loss_fn(params, model_state, batch):
        images, labels = batch
        outputs, mutated = model.apply(
            {"params": params, **model_state}, images, train=True,
            mutable=list(model_state.keys()) or False)
        loss = cross_entropy(outputs, labels)
        return loss, (mutated, outputs)
    return loss_fn


def make_eval_step(model):
    """Eval step (reference: main_amp.py — validate's inner loop): frozen
    batch stats, per-batch (top1 hits, top5 hits, summed loss, count)."""

    def eval_step(params, model_state, batch):
        images, labels = batch
        logits = model.apply({"params": params, **model_state}, images,
                             train=False)
        logits = jnp.asarray(logits, jnp.float32)
        hit1, hit5 = topk_hits(logits, labels)
        loss = cross_entropy(logits, labels) * labels.shape[0]
        return hit1, hit5, loss, labels.shape[0]

    return eval_step


def validate(jit_eval, state, batches, epoch=None, quiet=False):
    """Reference: main_amp.py — validate(val_loader, model, criterion):
    full pass over the held-out set, prints and returns (prec1, prec5).
    """
    h1 = h5 = n = 0
    loss_sum = 0.0
    for batch in batches:
        b1, b5, bl, bn = jit_eval(state.params, state.model_state, batch)
        h1 += int(b1)
        h5 += int(b5)
        loss_sum += float(bl)
        n += int(bn)
    prec1 = 100.0 * h1 / max(n, 1)
    prec5 = 100.0 * h5 / max(n, 1)
    if not quiet:
        tag = f"Epoch {epoch} " if epoch is not None else ""
        print(f"{tag}* Prec@1 {prec1:.3f} Prec@5 {prec5:.3f} "
              f"val-loss {loss_sum / max(n, 1):.4f}")
    return prec1, prec5


# ImageNet channel statistics (the reference's data_prefetcher normalizes
# with these on the GPU: main_amp.py — data_prefetcher mean/std)
_MEAN = np.array([0.485, 0.456, 0.406], np.float32) * 255.0
_STD = np.array([0.229, 0.224, 0.225], np.float32) * 255.0


def load_file_dataset(path):
    """File-backed dataset: ``path`` is an .npz (keys train_images,
    train_labels[, val_images, val_labels]) or a directory containing
    train.npz / val.npz with keys images, labels. Images are NHWC; uint8
    images are normalized with the ImageNet statistics (the prefetcher's
    job in the reference), float images are used as-is."""

    def norm(images):
        images = np.asarray(images)
        if images.dtype == np.uint8:
            return ((images.astype(np.float32) - _MEAN) / _STD)
        return images.astype(np.float32)

    splits = {}
    if os.path.isdir(path):
        for split in ("train", "val"):
            f = os.path.join(path, f"{split}.npz")
            if os.path.exists(f):
                with np.load(f) as z:
                    splits[split] = (norm(z["images"]),
                                     np.asarray(z["labels"], np.int32))
    else:
        with np.load(path) as z:
            for split in ("train", "val"):
                if f"{split}_images" in z:
                    splits[split] = (norm(z[f"{split}_images"]),
                                     np.asarray(z[f"{split}_labels"],
                                                np.int32))
    if "train" not in splits:
        raise SystemExit(f"=> no train split found under {path!r}")
    return splits


def file_batches(images, labels, batch_size, seed=None, drop_last=True):
    """Shuffled (seeded) host batches over a file-backed split."""
    n = images.shape[0]
    idx = np.arange(n)
    if seed is not None:
        np.random.RandomState(seed).shuffle(idx)
    stop = (n // batch_size) * batch_size if drop_last else n
    for i in range(0, stop, batch_size):
        take = idx[i:i + batch_size]
        yield images[take], labels[take]


def synthetic_batch(rng, batch_size, image_size, num_classes):
    images = jax.random.normal(
        rng, (batch_size, image_size, image_size, 3), jnp.float32)
    labels = jax.random.randint(rng, (batch_size,), 0, num_classes)
    return images, labels


class data_prefetcher:
    """Reference: main_amp.py — class data_prefetcher (side CUDA stream that
    uploads + normalizes the NEXT batch while the current step computes).

    TPU version: ``jax.device_put`` dispatches asynchronously, so issuing the
    next batch's transfer BEFORE blocking on the current step gives the same
    copy/compute overlap without any stream management. Wraps any iterator
    of host (numpy) batches; used for the --host-data path (real-data I/O
    shape), while the default synthetic path generates on device."""

    def __init__(self, loader, sharding=None):
        self.loader = iter(loader)
        self.sharding = sharding
        self._preload()

    def _put(self, batch):
        if self.sharding is not None:
            return jax.device_put(batch, self.sharding)
        return jax.device_put(batch)

    def _preload(self):
        try:
            self.next_batch = self._put(next(self.loader))
        except StopIteration:
            self.next_batch = None

    def next(self):
        batch = self.next_batch
        if batch is not None:
            self._preload()   # issue next transfer before caller blocks
        return batch

    def __iter__(self):
        while True:
            batch = self.next()
            if batch is None:
                return
            yield batch


def main(argv=None, on_step=None):
    """Train and validate. ``on_step(it, metrics)``, when given, sees
    every train step's metrics right after dispatch (a caller that
    wants per-step wall time blocks on them there)."""
    args = parse_args(argv)
    if args.accum_steps < 1:
        raise SystemExit("--accum-steps must be >= 1")
    if args.batch_size % args.accum_steps:
        raise SystemExit(f"--batch-size {args.batch_size} must divide by "
                         f"--accum-steps {args.accum_steps}")
    if args.data_parallel > 1 and \
            (args.batch_size // args.accum_steps) % args.data_parallel:
        raise SystemExit(
            f"microbatch rows {args.batch_size // args.accum_steps} must "
            f"divide by --data-parallel {args.data_parallel}")
    policy = build_policy(args)
    print(policy.banner())

    norm_cls = None
    axis_name = None
    if args.data_parallel > 1:
        axis_name = "data"
    if args.sync_bn:
        from apex_tpu.parallel import SyncBatchNorm
        norm_cls = functools.partial(SyncBatchNorm, axis_name=axis_name)

    model = create_model(
        args.arch, num_classes=args.num_classes, dtype=policy.model_dtype,
        param_dtype=jnp.float32, norm_cls=norm_cls)

    rng = jax.random.PRNGKey(args.seed)
    sample = jnp.zeros((2, args.image_size, args.image_size, 3), jnp.float32)
    variables = model.init(rng, sample, train=True)
    model_state = {k: v for k, v in variables.items() if k != "params"}
    params = variables["params"]

    # dataset first: a file-backed dataset defines iters/epoch, which the
    # LR schedule's epoch-30/60/80 boundaries depend on (reference:
    # adjust_learning_rate is driven by the real loader length)
    dataset = load_file_dataset(args.data) if args.data else None
    if dataset is not None:
        n_train = dataset["train"][0].shape[0]
        args.iters = max(n_train // args.batch_size, 1)
        print(f"=> file dataset: {n_train} train images, "
              f"{args.iters} iters/epoch")

    steps_per_epoch = args.iters
    schedule = adjust_learning_rate(args.lr, 0, steps_per_epoch)
    optimizer = optax.chain(
        optax.add_decayed_weights(args.weight_decay),
        optax.sgd(schedule, momentum=args.momentum),
    )

    tele = None
    if args.telemetry:
        from apex_tpu import telemetry
        tele = telemetry.start_run(args.telemetry)

    init_fn, step_fn = amp.make_train_step(
        make_loss_fn(model), optimizer, policy, has_aux=True,
        with_model_state=True, grad_average_axis=axis_name,
        telemetry=tele is not None, accum_steps=args.accum_steps)
    state = init_fn(params, model_state)

    def to_microbatches(batch):
        """amp.to_microbatches bound to --accum-steps: the leading
        microbatch axis the step scans over (identity at N=1, so every
        data path below stays shape-stable)."""
        return amp.to_microbatches(batch, args.accum_steps)

    if axis_name is not None:
        from apex_tpu import comm
        mesh = comm.make_mesh({"data": args.data_parallel})
        from jax.sharding import NamedSharding, PartitionSpec as P
        # with accumulation the leading axis is the microbatch scan axis
        # (replicated); the data mesh shards the per-microbatch rows
        bspec = P("data") if args.accum_steps == 1 else P(None, "data")
        batch_sharding = (NamedSharding(mesh, bspec),
                          NamedSharding(mesh, bspec))
        replicated = NamedSharding(mesh, P())
        state = jax.device_put(state, replicated)
        jit_step = jax.jit(
            shard_map(
                step_fn, mesh=mesh,
                in_specs=(P(), (bspec, bspec)),
                out_specs=P(),
                check_vma=False))
    else:
        batch_sharding = None
        jit_step = jax.jit(step_fn)

    start_epoch = 0
    if args.resume:
        # reference: main_amp.py --resume (torch.load of model+optimizer+
        # epoch); here the whole AmpState round-trips through one file
        from apex_tpu.utils import latest_checkpoint, load_checkpoint
        path = args.resume
        if os.path.isdir(path):
            path = latest_checkpoint(path)
            if path is None:
                raise SystemExit(
                    f"=> no checkpoint found in {args.resume!r}")
        state, step, extra = load_checkpoint(path, state)
        start_epoch = extra.get("epoch", step)
        print(f"=> resumed from {path} (epoch {start_epoch})")

    print(f"=> model {args.arch}, params: "
          f"{sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params)):,}")

    ckpt = None
    if args.checkpoint_dir:
        from apex_tpu.utils import AsyncCheckpointer
        os.makedirs(args.checkpoint_dir, exist_ok=True)
        ckpt = AsyncCheckpointer()
    def host_batches(epoch_seed, n):
        hrng = np.random.RandomState(epoch_seed)
        for _ in range(n):
            yield (hrng.randn(args.batch_size, args.image_size,
                              args.image_size, 3).astype(np.float32),
                   hrng.randint(0, args.num_classes,
                                size=(args.batch_size,)).astype(np.int32))

    # validation: the file dataset's val split when present, otherwise a
    # FIXED held-out synthetic set so top-1 is still a measured number
    jit_eval = jax.jit(make_eval_step(model))
    if dataset is not None and "val" in dataset:
        def val_batches():
            return file_batches(*dataset["val"], args.batch_size,
                                drop_last=False)
    else:
        _val = [synthetic_batch(jax.random.PRNGKey(10_000 + i),
                                args.batch_size, args.image_size,
                                args.num_classes)
                for i in range(4)]

        def val_batches():
            return iter(_val)

    best_prec1 = 0.0
    last_batch = None          # for --prof-device after the loops
    compiled = None
    for epoch in range(start_epoch, args.epochs):
        t0 = None
        imgs = 0
        prefetcher = None
        if dataset is not None:
            # microbatch reshape happens on HOST, before the prefetcher's
            # device_put lays the batch out per batch_sharding
            prefetcher = data_prefetcher(
                map(to_microbatches,
                    file_batches(*dataset["train"], args.batch_size,
                                 seed=args.seed + epoch)),
                sharding=batch_sharding)
        elif args.host_data:
            prefetcher = data_prefetcher(
                map(to_microbatches,
                    host_batches(args.seed + epoch, args.iters)),
                sharding=batch_sharding)
        for it in range(args.iters):
            if prefetcher is not None:
                batch = prefetcher.next()
                if batch is None:
                    break
            else:
                rng, sub = jax.random.split(rng)
                if args.deterministic:
                    sub = jax.random.PRNGKey(it)
                batch = to_microbatches(
                    synthetic_batch(sub, args.batch_size,
                                    args.image_size, args.num_classes))
                if batch_sharding is not None:
                    batch = jax.device_put(batch, batch_sharding)
            if args.prof and it == 5:
                jax.profiler.start_trace("/tmp/apex_tpu_trace")
            last_batch = batch
            if compiled is None:
                # one ahead-of-time compile, so the recipe can say
                # what the step holds on this backend
                compiled, _, line = chip.compile_and_report(
                    f"{args.arch} train step", jit_step, state, batch)
                print(line)
            state, metrics = compiled(state, batch)
            if on_step is not None:
                on_step(it, metrics)
            if args.prof and it == 5 + args.prof:
                metrics["loss"].block_until_ready()
                jax.profiler.stop_trace()
            if it == 4:  # skip compile + warmup, like the reference's prof skip
                metrics["loss"].block_until_ready()
                t0 = time.perf_counter()
                imgs = 0
            imgs += args.batch_size
            if it % 10 == 0 or it == args.iters - 1:
                loss = float(metrics["loss"])
                scale = float(metrics["loss_scale"])
                print(f"Epoch {epoch} [{it}/{args.iters}] "
                      f"loss {loss:.4f} loss_scale {scale:g}")
        jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
        if t0 is not None and args.iters > 5:
            dt = time.perf_counter() - t0
            print(f"Epoch {epoch}: {(imgs - args.batch_size) / dt:.1f} img/s")
        # validation pass each epoch (reference: prec1 = validate(...);
        # best_prec1 tracked for the checkpoint's is_best flag)
        prec1, _ = validate(jit_eval, state, val_batches(), epoch=epoch)
        best_prec1 = max(best_prec1, prec1)
        if ckpt is not None:
            path = os.path.join(args.checkpoint_dir,
                                f"ckpt_{epoch + 1}.npz")
            ckpt.save(path, state, step=epoch + 1,
                      extra={"epoch": epoch + 1, "best_prec1": best_prec1})
            print(f"=> saved {path}")
    if ckpt is not None:
        ckpt.wait()
    if args.prof_device:
        # shared observation-only rendering (copied state, never raises).
        # A zero-iteration run (--epochs 0, or a resume already at the
        # epoch limit) never bound a batch — report n/a, don't crash.
        from apex_tpu import pyprof

        if last_batch is None:
            print("device throughput: n/a (no training step ran)")
        else:
            line = pyprof.device_throughput_line(
                compiled, state, last_batch, args.prof_device,
                args.batch_size, "img/s")
            if line:
                print(line)
    if tele is not None:
        jax.effects_barrier()      # flush in-flight step callbacks
        tele.emit_snapshot()       # final aggregate + comm-health line
        tele.close()
    print(f"=> best Prec@1 {best_prec1:.3f}")
    return state


if __name__ == "__main__":
    print(f"=> compile cache: {chip.enable_compile_cache()}")
    main()
