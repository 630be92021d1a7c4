"""BERT pretraining recipe — BASELINE.json config 4.

"BERT-large pretraining with FusedLAMB + amp O2": the apex-powered NVIDIA
DeepLearningExamples BERT recipe (run_pretraining.py — apex.optimizers.
FusedLAMB + amp + fused kernels), rebuilt standalone on the framework's own
tiers: apex_tpu.models.bert (flash-attention encoder, FusedLayerNorm),
apex_tpu.optimizers.fused_lamb (NVLAMB trust-ratio update), MLM+NSP loss via
the fused xentropy kernel, amp O2 master weights + dynamic loss scaling.

LAMB exists for exactly this workload: 64k-batch phase-1 pretraining (You et
al. 2019). The recipe keeps DeepLearningExamples' argument names
(--train_batch_size, --max_seq_length, --max_predictions_per_seq,
--warmup_proportion) and the poly-decay warmup schedule.

Data: ``--data shards.npz`` loads pre-tokenized examples carrying the
DeepLearningExamples hdf5-shard fields (input_ids, token_type_ids,
attention_mask, masked_lm_positions, masked_lm_ids,
next_sentence_labels); without it, synthetic batches with the same
schema (no network in this environment).
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
import optax

from apex_tpu import amp
from apex_tpu.kernels.xentropy import softmax_cross_entropy_loss
from apex_tpu.models.bert import BertForPreTraining, create_bert
from apex_tpu.optimizers import fused_lamb


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu BERT-LAMB pretraining")
    p.add_argument("--bert-model", default="tiny",
                   choices=["tiny", "base", "large"])
    p.add_argument("--train_batch_size", type=int, default=8)
    p.add_argument("--max_seq_length", type=int, default=128)
    p.add_argument("--max_predictions_per_seq", type=int, default=20)
    p.add_argument("--learning_rate", type=float, default=6e-3)
    p.add_argument("--warmup_proportion", type=float, default=0.2843)
    p.add_argument("--max_steps", type=int, default=30)
    p.add_argument("--prof-device", type=int, default=0, metavar="N",
                   help="after training, time N extra steps on the "
                        "profiler's DEVICE lanes and print device "
                        "sequences/s (observation-only — runs on a copy "
                        "of the state; n/a without device lanes)")
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--loss-scale", default="dynamic")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--data-parallel", type=int, default=1, metavar="N",
                   help="DDP over an N-way 'data' mesh axis (LAMB update "
                        "on psum-averaged grads — the reference's "
                        "multi-GPU BERT-LAMB shape)")
    p.add_argument("--data", default=None,
                   help="pre-tokenized .npz with the BERT input schema "
                        "(input_ids, token_type_ids, attention_mask, "
                        "masked_lm_positions, masked_lm_ids, "
                        "next_sentence_labels) — the DeepLearningExamples "
                        "hdf5 shards' fields; synthetic batches otherwise")
    p.add_argument("--max_position_embeddings", type=int, default=None,
                   help="position-table size (default: max_seq_length). "
                        "Set 512 in BOTH phases for the reference's "
                        "phase1(seq128)→phase2(seq512) workflow, or "
                        "--init-checkpoint cannot carry the weights over")
    p.add_argument("--total_steps", type=int, default=None,
                   help="length of the lr schedule (default: max_steps). "
                        "Set it to the FULL run length when saving an "
                        "interrupted run (--max_steps < --total_steps), "
                        "so the resumed run continues the same schedule "
                        "— DeepLearningExamples' max_steps vs "
                        "steps_this_run split")
    p.add_argument("--save", default=None, metavar="CKPT",
                   help="write the final train state + step to this .npz")
    p.add_argument("--resume", default=None, metavar="CKPT",
                   help="restore a --save checkpoint (full state) and "
                        "continue the same phase")
    p.add_argument("--accum-steps", type=int, default=1, metavar="N",
                   help="in-jit microbatch gradient accumulation "
                        "(amp.make_train_step accum_steps): each LAMB "
                        "step scans N microbatches of batch-size/N, "
                        "paying ONE grad allreduce + unscale + scaler "
                        "update per window — the reference recipe's "
                        "gradient_accumulation_steps, compiled. Composes "
                        "with --data-parallel")
    p.add_argument("--telemetry", default=None, metavar="SPEC",
                   help="stream per-step telemetry (loss, grad norm, "
                        "scaler trajectory, step time) from inside the "
                        "jitted step: JSONL path, 'stdout', or 'null'; "
                        "summarize with python -m apex_tpu.telemetry")
    p.add_argument("--init-checkpoint", default=None, metavar="CKPT",
                   help="DeepLearningExamples --init_checkpoint: load "
                        "ONLY the model params from a --save checkpoint; "
                        "masters re-derived, optimizer and schedule start "
                        "fresh (the phase1→phase2 handoff). Run both "
                        "phases with the same --bert-model, "
                        "--max_position_embeddings, and --opt-level")
    return p.parse_args(argv)


_DATA_KEYS = ("input_ids", "token_type_ids", "attention_mask",
              "masked_lm_positions", "masked_lm_ids",
              "next_sentence_labels")


def _check_id_range(name, arr, hi_exclusive, what):
    """One rule for every id field: out-of-range ids would be CLAMPED by
    XLA's gather under jit — silently wrong training, not a crash — so
    they are rejected at load."""
    lo, hi = int(arr.min()), int(arr.max())
    if lo < 0 or hi >= hi_exclusive:
        raise SystemExit(
            f"--data {name} span [{lo}, {hi}]; {what} (jit would clamp "
            "the gather silently)")


def load_pretokenized(path, seq_len, n_pred, vocab_size=None):
    """Load + validate a pre-tokenized .npz against the run's shapes and
    (when given) the model's vocab — every id class jit's gathers would
    otherwise clamp silently is rejected here."""
    with np.load(path) as z:
        missing = [k for k in _DATA_KEYS if k not in z]
        if missing:
            raise SystemExit(f"--data {path!r} is missing fields "
                             f"{missing}; need {list(_DATA_KEYS)}")
        data = {k: np.asarray(z[k]) for k in _DATA_KEYS}
    if data["input_ids"].shape[1] != seq_len:
        raise SystemExit(
            f"--data sequences are {data['input_ids'].shape[1]} long; "
            f"--max_seq_length is {seq_len}")
    if data["masked_lm_positions"].shape[1] != n_pred:
        raise SystemExit(
            f"--data has {data['masked_lm_positions'].shape[1]} "
            f"prediction slots; --max_predictions_per_seq is {n_pred}")
    counts = {k: len(v) for k, v in data.items()}
    if len(set(counts.values())) != 1:
        raise SystemExit(f"--data fields disagree on example count: "
                         f"{counts}")
    if len(data["input_ids"]) == 0:
        raise SystemExit(f"--data {path!r} holds zero examples")
    _check_id_range("masked_lm_positions", data["masked_lm_positions"],
                    seq_len, f"sequences are {seq_len} long")
    _check_id_range("token_type_ids", data["token_type_ids"], 2,
                    "BERT has 2 segment embeddings")
    _check_id_range("next_sentence_labels", data["next_sentence_labels"],
                    2, "NSP is binary")
    for k in ("input_ids", "masked_lm_ids"):
        if vocab_size is not None:
            _check_id_range(k, data[k], vocab_size,
                            f"the vocab is {vocab_size}")
        elif int(data[k].min()) < 0:   # negatives rejected regardless
            raise SystemExit(f"--data {k} holds negative ids (jit would "
                             "clamp the gather silently)")
    return data


def synthetic_bert_batch(rng, batch, seq_len, n_pred, vocab):
    ks = jax.random.split(rng, 5)
    input_ids = jax.random.randint(ks[0], (batch, seq_len), 0, vocab)
    lengths = jax.random.randint(ks[1], (batch,), seq_len // 2, seq_len + 1)
    attention_mask = (jnp.arange(seq_len)[None] < lengths[:, None]) \
        .astype(jnp.int32)
    token_type_ids = (jnp.arange(seq_len)[None] >=
                      (lengths // 2)[:, None]).astype(jnp.int32)
    masked_lm_positions = jax.random.randint(ks[2], (batch, n_pred), 0,
                                             seq_len // 2)
    masked_lm_ids = jax.random.randint(ks[3], (batch, n_pred), 1, vocab)
    next_sentence_labels = jax.random.randint(ks[4], (batch,), 0, 2)
    return (input_ids, token_type_ids, attention_mask, masked_lm_positions,
            masked_lm_ids, next_sentence_labels)


def make_schedule(lr, max_steps, warmup_proportion):
    """DeepLearningExamples PolyWarmUpScheduler: linear warmup, poly decay."""
    warmup = max(1, int(max_steps * warmup_proportion))
    return optax.join_schedules(
        [optax.linear_schedule(0.0, lr, warmup),
         optax.polynomial_schedule(lr, 0.0, power=1.0,
                                   transition_steps=max_steps - warmup)],
        [warmup])


def _phase_handoff_params(path, init_fn, params):
    """DeepLearningExamples phase1→phase2 handoff: carry the MODEL over
    (fp32 masters preferred), restart optimizer + schedule. The position
    table must be sized identically in both phases
    (--max_position_embeddings 512 there) or shapes won't match. Scoped
    in a helper so the restored phase-1 state (params + masters + both
    LAMB moments — ~4x model size) frees as soon as params are copied
    out."""
    from apex_tpu.utils.checkpoint import load_checkpoint
    # abstract template: shapes/dtypes for validation without
    # materializing a throwaway full train state
    restored, from_step, _ = load_checkpoint(
        path, jax.eval_shape(init_fn, params))
    src = amp.master_params(restored)
    out = jax.tree_util.tree_map(lambda m, p: jnp.asarray(m, p.dtype),
                                 src, params)
    print(f"=> initialized model from {path} "
          f"(phase handoff at step {from_step}; fresh optimizer)")
    return out


def main(argv=None):
    args = parse_args(argv)
    if args.max_steps < 1:
        raise SystemExit("--max_steps must be >= 1")
    if args.train_batch_size % max(args.data_parallel, 1):
        raise SystemExit(f"--train_batch_size {args.train_batch_size} "
                         f"must divide by --data-parallel "
                         f"{args.data_parallel}")
    if args.accum_steps < 1:
        raise SystemExit("--accum-steps must be >= 1")
    if args.train_batch_size % (args.accum_steps
                                * max(args.data_parallel, 1)):
        raise SystemExit(
            f"--train_batch_size {args.train_batch_size} must divide by "
            f"--accum-steps x --data-parallel "
            f"({args.accum_steps} x {max(args.data_parallel, 1)})")
    if args.resume and args.init_checkpoint:
        raise SystemExit("--resume (continue the phase) and "
                         "--init-checkpoint (fresh phase from saved "
                         "params) are exclusive")
    if args.data_parallel > 1:
        # before ANY arrays exist: ensure_devices may switch backends
        # (virtual CPU fallback) and refuses once state is live
        from apex_tpu import comm
        comm.ensure_devices(args.data_parallel)
    policy = amp.resolve_policy(opt_level=args.opt_level,
                                loss_scale=args.loss_scale)
    print(policy.banner())

    cfg = create_bert(args.bert_model,
                      max_position_embeddings=(
                          args.max_position_embeddings
                          or args.max_seq_length))
    if args.max_seq_length > cfg.max_position_embeddings:
        raise SystemExit(
            f"--max_seq_length {args.max_seq_length} exceeds the "
            f"position table ({cfg.max_position_embeddings}); raise "
            "--max_position_embeddings")
    model = BertForPreTraining(cfg, dtype=policy.model_dtype)
    rng = jax.random.PRNGKey(args.seed)
    b0 = synthetic_bert_batch(rng, 2, args.max_seq_length,
                              args.max_predictions_per_seq, cfg.vocab_size)
    params = model.init(rng, *b0[:4], train=False)["params"]

    if args.total_steps is not None and args.total_steps < args.max_steps:
        raise SystemExit(
            f"--total_steps {args.total_steps} < --max_steps "
            f"{args.max_steps}: the schedule would pin lr to 0 past "
            "total_steps (swapped flags?)")
    schedule = make_schedule(args.learning_rate,
                             args.total_steps or args.max_steps,
                             args.warmup_proportion)
    optimizer = fused_lamb(schedule, weight_decay=0.01)

    def loss_fn(p, batch):
        (input_ids, token_type_ids, attention_mask, mlm_pos, mlm_ids,
         nsp_labels, dropout_rng) = batch
        mlm_logits, nsp_logits = model.apply(
            {"params": p}, input_ids, token_type_ids, attention_mask,
            mlm_pos, train=True, rngs={"dropout": dropout_rng})
        # masked positions with id 0 are padding of the prediction slots
        # (DeepLearningExamples masks them out of the mean)
        mlm_losses = softmax_cross_entropy_loss(mlm_logits, mlm_ids)
        valid = (mlm_ids != 0).astype(jnp.float32)
        mlm_loss = jnp.sum(mlm_losses * valid) / jnp.maximum(
            jnp.sum(valid), 1.0)
        nsp_loss = softmax_cross_entropy_loss(nsp_logits, nsp_labels).mean()
        return mlm_loss + nsp_loss

    tele = None
    if args.telemetry:
        from apex_tpu import telemetry
        tele = telemetry.start_run(args.telemetry)

    dp = args.data_parallel
    init_fn, step_fn = amp.make_train_step(
        loss_fn, optimizer, policy,
        grad_average_axis="data" if dp > 1 else None,
        telemetry=tele is not None, accum_steps=args.accum_steps)

    def to_microbatches(batch):
        """amp.to_microbatches on the ARRAY leaves; the dropout key stays
        scalar — it is split into per-microbatch keys inside the step,
        after any per-rank fold."""
        if args.accum_steps == 1:
            return batch
        *arrays, drop = batch
        return amp.to_microbatches(tuple(arrays),
                                   args.accum_steps) + (drop,)
    start_it = 0
    if args.init_checkpoint:
        params = _phase_handoff_params(args.init_checkpoint, init_fn,
                                       params)
    state = init_fn(params)
    if args.resume:
        from apex_tpu.utils.checkpoint import resume_train_checkpoint
        state, start_it, rng = resume_train_checkpoint(
            args.resume, state, rng, step_limit=args.max_steps,
            limit_flag="--max_steps")
    if dp > 1:
        # reference shape: apex DDP over the batch + FusedLAMB — here one
        # grad psum over the 'data' axis (examples/imagenet's pattern);
        # the dropout rng is folded per-rank so masks differ across shards
        from apex_tpu.utils.compat import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu import comm

        devices = comm.ensure_devices(dp)
        mesh = Mesh(np.array(devices[:dp]), ("data",))

        def sharded_step(state, batch):
            *arrays, drop = batch
            drop = jax.random.fold_in(drop, jax.lax.axis_index("data"))
            if args.accum_steps > 1:
                drop = jax.random.split(drop, args.accum_steps)
            return step_fn(state, tuple(arrays) + (drop,))

        # with accumulation the leading axis is the microbatch scan axis
        # (replicated); the data mesh shards the per-microbatch rows
        bspec = P("data") if args.accum_steps == 1 else P(None, "data")
        jit_step = jax.jit(shard_map(
            sharded_step, mesh=mesh,
            in_specs=(P(), (bspec,) * 6 + (P(),)),
            out_specs=(P(), P()), check_vma=False),
            donate_argnums=(0,))
        ctx = mesh
    else:
        import contextlib
        if args.accum_steps > 1:
            def local_step(state, batch):
                *arrays, drop = batch
                drop = jax.random.split(drop, args.accum_steps)
                return step_fn(state, tuple(arrays) + (drop,))
            jit_step = jax.jit(local_step, donate_argnums=(0,))
        else:
            jit_step = jax.jit(step_fn, donate_argnums=(0,))
        ctx = contextlib.nullcontext()

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"=> BERT-{args.bert_model} dp={dp}, params: {n_params:,}")

    data = None
    if args.data:
        data = load_pretokenized(args.data, args.max_seq_length,
                                 args.max_predictions_per_seq,
                                 vocab_size=cfg.vocab_size)
        print(f"=> {len(data['input_ids'])} pre-tokenized examples "
              f"from {args.data}")

    t0 = None
    seqs = 0
    metrics = None
    loss_history = []
    with ctx:
        for it in range(start_it, args.max_steps):
            rng, sub = jax.random.split(rng)
            sub, drop = jax.random.split(sub)
            if data is not None:
                idx = np.asarray(jax.random.randint(
                    sub, (args.train_batch_size,), 0,
                    len(data["input_ids"])))
                batch = tuple(jnp.asarray(data[k][idx])
                              for k in _DATA_KEYS) + (drop,)
            else:
                batch = synthetic_bert_batch(sub, args.train_batch_size,
                                             args.max_seq_length,
                                             args.max_predictions_per_seq,
                                             cfg.vocab_size) + (drop,)
            batch = to_microbatches(batch)
            state, metrics = jit_step(state, batch)
            loss_history.append(metrics["loss"])
            if it == start_it + 4:
                metrics["loss"].block_until_ready()
                t0 = time.perf_counter()
                seqs = 0
            seqs += args.train_batch_size
            if it % 10 == 0 or it == args.max_steps - 1:
                print(f"[{it}/{args.max_steps}] loss "
                      f"{float(metrics['loss']):.4f} "
                      f"loss_scale {float(metrics['loss_scale']):g}")
    jax.tree_util.tree_leaves(state.params)[0].block_until_ready()
    if tele is not None:
        jax.effects_barrier()      # flush in-flight step callbacks
        tele.emit_snapshot()       # final aggregate + comm-health line
        tele.close()
    if t0 is not None and args.max_steps - start_it > 5:
        dt = time.perf_counter() - t0
        print(f"throughput: "
              f"{(seqs - args.train_batch_size) / dt:,.1f} sequences/s")
    if metrics is None:
        return None
    if args.prof_device:
        # shared observation-only rendering (copied state, never raises)
        from apex_tpu import pyprof

        line = pyprof.device_throughput_line(
            jit_step, state, batch, args.prof_device,
            args.train_batch_size, "sequences/s")
        if line:
            print(line)
    if args.save:
        from apex_tpu.utils.checkpoint import save_train_checkpoint
        save_train_checkpoint(args.save, state, args.max_steps, rng)
    metrics = dict(metrics)
    # one device-to-host transfer for the whole history, not one per step
    metrics["loss_history"] = np.asarray(jnp.stack(loss_history),
                                         np.float32).tolist()
    return metrics


if __name__ == "__main__":
    from apex_tpu.utils.chip import enable_compile_cache

    print(f"=> compile cache: {enable_compile_cache()}")
    main()
