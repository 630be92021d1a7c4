"""chip_smoke.py — does the system still start on the chip?

Drives the two things users of this repo run — the amp-O2 trainer and
the serving engine — once, through the recipes' own entry points, at
the published width of GPT-2 small (hidden 768, 12 layers, 12 heads,
head_dim 64, context 1024) and at ResNet-50 / batch 256 / 224 px, with
random weights made from ``--seed``. It is not a benchmark: no rate it
prints is a claim.

Default (one chip), each phase a few steps ending in
``block_until_ready``:

- *LM train + serve*: ``examples/lm/main_amp.py --size gpt2 --seq-len
  1024 --opt-level O2`` for a few steps, then the recipe's own
  ``--generate`` leg on the just-trained parameters (12 prompts of up
  to 512 tokens through 4 slots: chunk prefill, paged decode and slot
  reuse all happen, at page_len 128 where the Pallas paths are
  eligible). Checks: every loss finite; no step skipped by the loss
  scaler after the first; every request finished; every served token's
  logit, in the recipe model's plain float32 forward over prompt +
  output (same parameters, no cache), within 0.07 of that position's
  best; flash attention fwd/bwd, fused layer norm and fused
  cross-entropy are ``tpu_custom_call``s in the train step, paged
  decode / paged prefill in the decode / chunk programs.
- *ResNet-50 train*: ``examples/imagenet/main_amp.py -a resnet50 -b 256
  --image-size 224 --opt-level O2 --synthetic``. Loss finite.

``--chips 4`` runs ONLY the model-parallel recipe (``run_parallel`` at
GPT-2 width, ``--data-parallel 2 --tensor-parallel 2``, shard_map tier)
and the same seed on a 1x1 mesh as its reference: the loss trajectories
must agree, the mesh must be four distinct TPU devices, and each must
hold parameter bytes.

The one departure from the published configuration: vocabulary 50304
(GPT-2's 50257 padded to a multiple of 128, the usual padded GPT-2
vocabulary) — the fused cross-entropy kernel needs a lane-aligned vocab
and the recipe does not pad the head itself.

Exit code 0 and, as the LAST line of stdout, one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}`` only when
every phase passed on a TPU. Without an accelerator (or outside a
checkout of the repo) it exits non-zero before compiling anything and
prints no result line. One process; it starts no child.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

VOCAB = 50304           # 50257 padded to a multiple of 128 (see docstring)
LM_WIDTH = ["--size", "gpt2", "--seq-len", "1024",
            "--vocab-size", str(VOCAB), "--opt-level", "O2"]
# -b 16: 13.1 GiB of the chip's 16 by the compiler's own count (11.4
# temporaries + 1.6 state; -b 8 is 7.6, -b 24 is 15.4). The serve geometry
# makes max_len 512 + 128 = 640 = 5 pages of 128.
LM_ARGS = LM_WIDTH + ["-b", "16", "--iters", "6", "--generate", "128",
                      "--gen-prompts", "12", "--gen-slots", "4",
                      "--gen-prompt-len", "512"]
RESNET_ARGS = ["-a", "resnet50", "-b", "256", "--image-size", "224",
               "--opt-level", "O2", "--synthetic", "--iters", "6"]
PARALLEL_ARGS = LM_WIDTH + ["-b", "8", "--iters", "5", "--deterministic"]
# dp2 x tp2 against 1x1 under O2: the row-parallel GEMMs round their two
# partial sums to bf16 before the all-reduce, so the two trajectories are
# not bitwise equal. Observed on four v5e chips at GPT-2 width: 2.0e-5
# over five steps (PR 21); the bound leaves bf16 noise fifty times that.
PARALLEL_RTOL = 1e-3

TRAIN_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv", "layer_norm_fwd",
                 "layer_norm_bwd", "xentropy_fwd", "xentropy_bwd")
SERVE_KERNELS = {"decode": "paged_decode_attention",
                 "chunk": "paged_prefill_attention"}
# the serving cells' own rule (PERF.md section 2,
# served_logit_gap_widest): a served token's reference logit lies within
# this of its position's best
SERVED_LOGIT_GAP = 0.07


def load_recipe(name: str):
    """``examples/<name>/main_amp.py`` as a module."""
    return importlib.import_module(f"examples.{name}.main_amp")


class StepTimer:
    """The recipes' ``on_step`` hook: blocks on each step's loss and
    keeps its wall time, loss and the scaler's overflow verdict."""

    def __init__(self):
        self.marks = [time.perf_counter()]
        self.loss, self.found_inf = [], []

    def __call__(self, it, metrics):
        import jax

        jax.block_until_ready(metrics["loss"])
        self.marks.append(time.perf_counter())
        self.loss.append(float(metrics["loss"]))
        self.found_inf.append(bool(metrics["found_inf"]))

    def check(self, what: str) -> list:
        import math

        failures = []
        if not self.loss:
            failures.append(f"{what}: no step ran")
        if not all(math.isfinite(x) for x in self.loss):
            failures.append(f"{what}: non-finite loss in {self.loss}")
        if any(self.found_inf[1:]):
            failures.append(f"{what}: the loss scaler skipped a step "
                            f"after the first: {self.found_inf}")
        return failures

    def line(self, what: str) -> str:
        steps = [b - a for a, b in zip(self.marks, self.marks[1:])]
        later = ", ".join(f"{s:.3f}" for s in steps[1:])
        return (f"[{what}] to first step (build + compile + run) "
                f"{steps[0]:.1f}s; later steps [{later}] s; loss "
                f"{self.loss[0]:.4f} -> {self.loss[-1]:.4f}")


def peak_memory_line(what: str) -> str:
    import jax

    parts = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        parts.append(f"dev{d.id} peak "
                     f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB"
                     f" (in use {stats.get('bytes_in_use', 0) / 2**30:.2f})")
    return (f"[{what}] device memory, process-cumulative peak: "
            + "; ".join(parts))


def missing_kernels(what: str, have, need) -> list:
    return [f"{what}: kernel {k} is not in the compiled program "
            f"(it holds {dict(have or {})})"
            for k in need if not (have or {}).get(k)]


def pool_copies(memory, pool_bytes) -> list:
    """The paged pool is written in place: a serving program whose
    temporaries reach the pool's size is copying it (the compiler's
    count, ``Engine.program_memory()``, which the recipe prints beside
    the programs' kernels; it means something on a chip only —
    interpreted kernels carry their operands on the CPU). At
    this geometry the pool is 99 MB and a chunk's fp32 logits alone are
    52 MB, so the line drawn is the whole pool (a program that slices
    and restacks it reads two pools here) and not the tenth of it that
    a deployment's pool of gigabytes is held to."""
    import jax

    if jax.default_backend() != "tpu":
        return []
    return [f"lm serve {prog} program: {m['temp_bytes']} bytes of "
            f"temporaries beside a KV pool of {pool_bytes}: the pool is "
            f"being copied"
            for prog, m in memory.items()
            if m["temp_bytes"] >= pool_bytes]


def served_logit_gaps(model, params, sequences) -> list:
    """Teacher-force each ``(prompt, output)`` of ``sequences`` through
    ``model``'s plain forward - float32, no cache, no engine code - and
    return, per sequence, how far each served token's logit lies below
    the best logit of its position. Output token ``j`` is predicted at
    position ``len(prompt) - 1 + j`` of prompt + output; sequences are
    padded to the model's context so one program serves them all."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    plain = model.clone(dtype=jnp.float32, inference_dtype=None)

    @jax.jit
    def gaps(params, seq):          # params an operand, not a constant
        with jax.default_matmul_precision("highest"):
            logits = plain.apply({"params": params}, seq[None],
                                 train=False)[0]              # [S, V]
        nxt = jnp.roll(seq, -1)
        return jnp.max(logits, -1) - jnp.take_along_axis(
            logits, nxt[:, None], 1)[:, 0]

    out = []
    for prompt, output in sequences:
        n, m = len(prompt), len(output)
        seq = np.zeros(model.max_seq_len, np.int32)
        seq[:n + m] = list(prompt) + list(output)
        out.append(np.asarray(gaps(params, jnp.asarray(seq)))
                   [n - 1:n - 1 + m])
    return out


def served_token_failures(model, params, reqs) -> list:
    """The serve check's rule: every served token of every request
    within ``SERVED_LOGIT_GAP`` of its position's best."""
    gaps = served_logit_gaps(
        model, params, [(r.prompt, r.output_tokens) for r in reqs])
    widest = max(float(g.max()) for g in gaps)
    print(f"[lm serve] {sum(len(g) for g in gaps)} served tokens over "
          f"{len(gaps)} prompts against the plain forward: widest logit "
          f"gap {widest:.4f} (bound {SERVED_LOGIT_GAP})")
    return [f"lm serve: request {r.uid} token {int(g.argmax())} lies "
            f"{float(g.max()):.4f} below its position's best logit "
            f"(bound {SERVED_LOGIT_GAP})"
            for r, g in zip(reqs, gaps) if g.max() > SERVED_LOGIT_GAP]


def lm_phase(argv) -> list:
    """LM train + serve through the recipe; returns the failures."""
    from apex_tpu import serving

    lm = load_recipe("lm")
    timer = StepTimer()
    metrics = lm.main(argv, on_step=timer)
    print(timer.line("lm train"))
    failures = timer.check("lm train")
    failures += missing_kernels("lm train step", metrics["kernels"],
                                TRAIN_KERNELS)

    gen = metrics["generate"]
    reqs, geo = gen["requests"], gen["geometry"]
    args = lm.parse_args(argv)
    unfinished = [r.uid for r in reqs
                  if r.status != serving.RequestStatus.FINISHED
                  or len(r.output_tokens) != args.generate]
    if len(reqs) != args.gen_prompts or unfinished:
        failures.append(f"lm serve: {len(reqs)}/{args.gen_prompts} "
                        f"requests came back, unfinished: {unfinished}")
    chunks = [r.chunks for r in reqs]
    if max(chunks) < 2 or len(reqs) <= geo["slots"]:
        failures.append(f"lm serve: the stream did not exercise multi-"
                        f"chunk prefill and slot reuse (chunks {chunks},"
                        f" {geo['slots']} slots)")
    print(f"[lm serve] {len(reqs)} requests finished, "
          f"{sum(len(r.output_tokens) for r in reqs)} tokens in "
          f"{gen['seconds']:.2f}s (compile included); prefill chunks per "
          f"request {chunks}; page_len {gen['page_len']}, geometry {geo}")
    for prog, kernel in SERVE_KERNELS.items():
        failures += missing_kernels(f"lm serve {prog} program",
                                    gen["kernels"][prog], [kernel])
    failures += pool_copies(gen["memory"], gen["pool_bytes"])

    failures += served_token_failures(
        gen["model"], metrics["final_state"].params, reqs)
    print(peak_memory_line("lm"))
    return failures


def resnet_phase(argv) -> list:
    """ResNet-50 O2 training through the recipe; returns the failures."""
    timer = StepTimer()
    load_recipe("imagenet").main(argv, on_step=timer)
    print(timer.line("resnet train"))
    print(peak_memory_line("resnet"))
    return timer.check("resnet train")


def parallel_phase(argv, rtol: float) -> list:
    """dp2 x tp2 ``run_parallel`` against the same seed at 1x1."""
    import jax
    import numpy as np

    from apex_tpu import amp

    lm = load_recipe("lm")

    def run(dp, tp):
        args = lm.parse_args(argv + ["--data-parallel", str(dp),
                                     "--tensor-parallel", str(tp)])
        policy = amp.resolve_policy(opt_level=args.opt_level,
                                    loss_scale=args.loss_scale,
                                    verbose=False)
        timer = StepTimer()
        out = lm.run_parallel(args, policy, on_step=timer)
        print(timer.line(f"lm dp{dp} x tp{tp}"))
        return out, timer

    failures = []
    par, timer = run(2, 2)
    failures += timer.check("lm dp2 x tp2")
    leaves = jax.tree_util.tree_leaves(par["final_state"].params)
    held = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) \
                + shard.data.nbytes
    print("[lm dp2 x tp2] parameter bytes per device: " + "; ".join(
        f"{d.platform}:{d.id} {n / 2**20:.1f} MiB "
        f"(in use {(d.memory_stats() or {}).get('bytes_in_use', 0) / 2**20:.0f} MiB)"
        for d, n in sorted(held.items(), key=lambda kv: kv[0].id)))
    if len(held) != 4 or not all(n > 0 for n in held.values()):
        failures.append(f"lm dp2 x tp2: parameters live on {len(held)} "
                        f"device(s), expected 4 distinct ones")
    if any((d.memory_stats() or {}).get("bytes_in_use", 1) <= 0
           for d in held):
        failures.append("lm dp2 x tp2: a mesh device reports no bytes "
                        "in use")
    print(peak_memory_line("lm dp2 x tp2"))
    par_loss = par["loss_history"]
    del par, leaves
    gc.collect()

    one, timer = run(1, 1)
    failures += timer.check("lm 1 x 1")
    dev = np.abs(np.asarray(par_loss) - np.asarray(one["loss_history"])) \
        / np.abs(np.asarray(one["loss_history"]))
    print(f"[lm dp2 x tp2 vs 1 x 1] loss {par_loss} vs "
          f"{one['loss_history']}; max relative deviation "
          f"{dev.max():.2e} (bound {rtol:g})")
    if not (dev <= rtol).all():
        failures.append(f"lm dp2 x tp2: loss trajectory deviates from "
                        f"the 1 x 1 run by {dev.max():.2e} > {rtol:g}")
    return failures


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="1: the default phases on one chip; 4: only the "
                        "dp2 x tp2 recipe and its 1x1 reference")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    from apex_tpu.kernels import vmem
    from apex_tpu.utils import chip

    cache_from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    cache_dir = chip.enable_compile_cache()     # before the first compile
    device = chip.device_summary()
    if device["platform"] != "tpu":
        print(f"chip_smoke: no accelerator: jax reports "
              f"{device} — this script only passes on a TPU",
              file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} asked for, "
              f"{device['count']} attached", file=sys.stderr)
        return 2
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"device: {device}")
    print(f"compile cache: {cache_dir} ({n_cached} entries at start — "
          f"{'warm' if n_cached else 'cold'}; placed by "
          f"{'JAX_COMPILATION_CACHE_DIR' if cache_from_env else 'apex_tpu.utils.chip'})")
    tuned = vmem.packaged_path(device["kind"])
    vmem.get_override("decode.page_len", 0)     # triggers the lazy load
    print(f"tuned blocks: {tuned} "
          f"{'found' if os.path.isfile(tuned) else 'NOT FOUND'} for "
          f"device_kind {device['kind']!r}; "
          f"{len(vmem.overrides())} override(s) in force")
    print(f"departure from the published GPT-2 configuration: vocabulary "
          f"{VOCAB} (50257 padded to a multiple of 128)")

    seed = ["--seed", str(args.seed)]
    t0 = time.perf_counter()
    if args.chips == 4:
        failures = parallel_phase(PARALLEL_ARGS + seed, PARALLEL_RTOL)
    else:
        failures = lm_phase(LM_ARGS + seed)
        gc.collect()
        failures += resnet_phase(RESNET_ARGS + seed)
    print(f"total {time.perf_counter() - t0:.1f}s")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    print("all phases passed")
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
